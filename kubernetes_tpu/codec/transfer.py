"""Packed host->device transfer.

A pytree of small numpy leaves (PodBatch has ~60) costs one host->device
transfer PER LEAF when passed straight into a jitted call.  pack_tree
collapses the tree into at most three flat buffers (one per dtype kind:
float, int, bool) so the device pays one transfer each; unpack_tree
rebuilds the original tree *inside* the jitted program with static slices
(free: XLA folds them into the consumers).

Two further wire rules:
- callers jax.device_put the packed buffers explicitly (an async copy)
  instead of handing host arrays to the jitted call; and
- BYTES matter as much as transfers: the [B, ...] pair/mask tensors of
  controller-stamped batches repeat a handful of distinct rows, so
  pack_tree ships unique rows + an index and unpack_tree gathers the
  dense leaf back on device (~190MB -> ~2MB for a 2048-pod
  anti-affinity batch).

The reference has no analog (its scheduler state never leaves host RAM);
this is TPU-plumbing the same way protobuf wire-batching is etcd-plumbing.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.codec import faults
from kubernetes_tpu.codec.schema import (
    _FACTOR_MIN_BYTES,
    PairRows,
    _pow2,
    factor_row_limit,
)
from kubernetes_tpu.utils.trace import annotation

# ------------------------------------------------------ transfer accounting
#
# Every wire seam notes the bytes it moved (ISSUE 11): direction h2d/d2h
# plus the seam name, computed from HOST array nbytes — never a device
# sync, so the accounting is safe to leave always-on (the perf_smoke
# budget pins it inside the <2% observatory envelope).  Totals feed the
# ktpu_transfer_* counter families and the per-cycle deltas the
# scheduler annotates onto each cycle span / hands to the performance
# observatory (runtime/perfobs.py).

_XFER_LOCK = threading.Lock()
# (direction, seam) -> [bytes, calls]; plain ints under a lock — the
# fetch worker and the scheduling thread both note here
_XFER_TOTALS: "dict[Tuple[str, str], list]" = {}


def note_transfer(direction: str, seam: str, nbytes: int) -> None:
    """Account one transfer at a wire seam.  Zero-byte calls still count
    a call (an empty dirty set that reached the wire is signal)."""
    from kubernetes_tpu.utils import metrics as m

    nbytes = int(nbytes)
    with _XFER_LOCK:
        cell = _XFER_TOTALS.get((direction, seam))
        if cell is None:
            cell = _XFER_TOTALS[(direction, seam)] = [0, 0]
        cell[0] += nbytes
        cell[1] += 1
    m.TRANSFER_BYTES.inc(nbytes, direction=direction, seam=seam)
    m.TRANSFER_CALLS.inc(direction=direction, seam=seam)


def tree_nbytes(tree) -> int:
    """Sum of nbytes over the numpy/jax leaves of a pytree (None leaves
    and scalars without nbytes are free)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if nb:
            total += int(nb)
    return total


def note_transfer_tree(direction: str, seam: str, tree) -> None:
    note_transfer(direction, seam, tree_nbytes(tree))


def transfer_totals() -> "dict[str, dict]":
    """Snapshot of cumulative transfer accounting:
    {"h2d/snapshot_upload": {"bytes": B, "calls": C}, ...}.  Cheap (a
    handful of entries) — the scheduler snapshots it per cycle to
    compute the cycle's transfer delta."""
    with _XFER_LOCK:
        return {
            f"{d}/{s}": {"bytes": v[0], "calls": v[1]}
            for (d, s), v in _XFER_TOTALS.items()
        }


def transfer_delta(prev: "dict[str, dict]") -> "dict[str, dict]":
    """Non-zero per-seam deltas of transfer_totals() since `prev` (a
    previous transfer_totals() snapshot)."""
    out: dict = {}
    for key, cur in transfer_totals().items():
        p = prev.get(key, {"bytes": 0, "calls": 0})
        db, dc = cur["bytes"] - p["bytes"], cur["calls"] - p["calls"]
        if db or dc:
            out[key] = {"bytes": db, "calls": dc}
    return out


# ---------------------------------------------------------------- D2H fences
#
# Every device->host materialization the RUNTIME performs goes through the
# helpers below, which report each sync that actually BLOCKS the calling
# thread to the registered listeners.  Tests hook this (on_blocking_sync) to
# pin the per-cycle blocking-sync budget — the regression guard that keeps
# per-pod fetches from silently coming back (tests/test_host_sync_guard.py).
# Engine-INTERNAL syncs (the speculative CPU host-rounds loop) are a
# documented design choice and are not routed through here.

_SYNC_LISTENERS: List[Callable[[str], None]] = []


def on_blocking_sync(fn: Callable[[str], None]) -> Callable[[], None]:
    """Register a listener called with a tag on every blocking device sync
    performed through this module's fetch helpers.  Returns a remover."""
    _SYNC_LISTENERS.append(fn)

    def remove() -> None:
        try:
            _SYNC_LISTENERS.remove(fn)
        except ValueError:
            pass

    return remove


def _note_sync(tag: str) -> None:
    for fn in _SYNC_LISTENERS:
        fn(tag)


def _involved_device_ids(x):
    """frozenset of jax device ids a device array's sharding spans, or
    None when unknowable (host arrays, duck-typed handles, or — the
    common case — no fault injector installed).  The fault-injection
    seams report these so a shard-targeted arm (faults.FaultInjector
    device_index) faults exactly the computations that touch the dead
    device.  Computed ONLY while an injector is live: production runs
    keep faults.py's no-op contract (one module-global load per site)."""
    if faults.current_injector() is None:
        return None
    sh = getattr(x, "sharding", None)
    ds = getattr(sh, "device_set", None)
    if not ds:
        return None
    try:
        return frozenset(int(getattr(d, "id", -1)) for d in ds)
    except TypeError:
        return None


def host_fetch(x, tag: str = "fetch") -> np.ndarray:
    """The canonical BLOCKING device->host sync point: np.asarray with the
    fence listeners notified first.  Runtime code must fetch through this
    (or AsyncFetch) rather than raw np.asarray so sync counts stay
    observable."""
    _note_sync(tag)
    faults.check(faults.SITE_FETCH, devices=_involved_device_ids(x))
    with annotation(f"ktpu.{tag}"):
        out = faults.corrupt(faults.SITE_FETCH, np.asarray(x))
    note_transfer("d2h", tag, out.nbytes)
    return out


def upload_async(tree):
    """Async H2D: jax.device_put returns immediately (the copy overlaps
    host work); pair with ready_fence() when completion must be ordered
    before a dependent host step.  Exists mostly as the named seam — the
    point is that NO fence is needed on the hot path, because jit consumers
    order themselves on the transfer."""
    note_transfer_tree("h2d", "upload", tree)
    return jax.device_put(tree)


def ready_fence(tree, tag: str = "fence"):
    """Explicit blocking fence: waits until every leaf of `tree` is
    computed/transferred.  Counts as a blocking sync."""
    _note_sync(tag)
    faults.check(faults.SITE_FENCE)
    jax.block_until_ready(tree)
    return tree


class _FetchWorker:
    """One persistent daemon thread draining jobs in submission order
    (AsyncFetch's, or the telemetry hub's sample materialization) —
    per-cycle thread create/teardown was measurable under trickle arrival
    (hundreds of cycles/s), and a DAEMON thread (unlike a
    ThreadPoolExecutor's joined workers) cannot let a hung fetch block
    interpreter exit."""

    def __init__(self, name: str) -> None:
        import queue as _q

        self._jobs: Any = _q.SimpleQueue()
        self.thread = threading.Thread(
            target=self._drain, daemon=True, name=name
        )
        self.thread.start()

    def submit(self, fn: Callable[[], None]) -> None:
        self._jobs.put(fn)

    def _drain(self) -> None:
        while True:
            job = self._jobs.get()
            try:
                job()
            except BaseException:  # noqa: BLE001
                # A raising job must never kill the shared worker: every
                # fetch queued BEHIND it would hang forever at result().
                # AsyncFetch._run routes its own errors into the owning
                # handle; this guard covers jobs that fail before that
                # plumbing (or foreign submit() callers) — logged, since
                # such a caller has no other way to see the failure.
                import traceback

                traceback.print_exc()


_WORKERS: "dict[str, _FetchWorker]" = {}
_WORKERS_LOCK = threading.Lock()


def daemon_worker(name: str) -> _FetchWorker:
    """The process's worker thread `name`, started on first use (and
    again after a fork).  A job queued on one worker never waits behind
    another worker's jobs."""
    w = _WORKERS.get(name)
    if w is None or not w.thread.is_alive():  # first use, or post-fork
        with _WORKERS_LOCK:
            w = _WORKERS.get(name)
            if w is None or not w.thread.is_alive():
                w = _WORKERS[name] = _FetchWorker(name)
    return w


def _fetch_worker() -> _FetchWorker:
    return daemon_worker("ktpu-async-fetch")


class AsyncFetch:
    """Fetch-in-flight handle for a device result (the D2H half of the
    double-buffered commit pipeline).

    Starts the wire copy immediately — copy_to_host_async() enqueues the
    D2H DMA to fire the moment the producing computation finishes — and
    completes the materialization on the shared fetch worker, so the
    blocking device sync overlaps whatever the scheduling thread does
    next (dispatching batch k+1, running batch k-1's side-effect tail).

    result() is the ready-fence: it returns the host array, blocking only
    if the copy hasn't landed yet (and only that case is reported to the
    sync listeners); a device error re-raises HERE, so callers own the
    batch's recovery at the fence.

    The worker stamps four instants on the scheduler's clock
    (time.monotonic): `t_submit` (dispatch), `t_start` (the worker took
    the job), `t_ready` (block_until_ready returned: the computation is
    done) and `t_done` (the host copy landed).  Every duration is read
    from them: `seconds` (dispatch -> copy landed, the "fetch" phase,
    which may overlap other host phases), `execute_seconds` and
    `materialize_seconds` (the perf observatory's device split), and
    `fence_split` (what a fence wait on this handle was waiting for).
    The worker's own profiler range is `fetchworker.<tag>`: outside the
    `ktpu.` prefix, so a worker-thread range never names the
    scheduling thread's time."""

    def __init__(self, dev, tag: str = "fetch") -> None:
        self._dev = dev
        self._tag = tag
        # device ids this result's sharding spans (a mesh-replicated
        # winners buffer spans every mesh device): the fault seams below
        # report them so a lost shard faults this fetch attributably
        self._devices = _involved_device_ids(dev)
        if hasattr(dev, "copy_to_host_async"):
            dev.copy_to_host_async()
        self._done = threading.Event()
        self._out: Any = None
        self._err: Any = None
        self.t_submit = time.monotonic()
        self.t_start = self.t_ready = self.t_done = self.t_submit
        _fetch_worker().submit(self._run)

    def _run(self) -> None:
        self.t_start = time.monotonic()
        try:
            faults.check(faults.SITE_FETCH, devices=self._devices)
            with annotation(f"fetchworker.{self._tag}"):
                wait = getattr(self._dev, "block_until_ready", None)
                if wait is not None:
                    # ready fence BEFORE the materialize: splits "device
                    # still computing" from "host copying" (a failed
                    # computation raises here exactly as np.asarray would)
                    wait()
                self.t_ready = time.monotonic()
                self._out = faults.corrupt(
                    faults.SITE_FETCH, np.asarray(self._dev)
                )
            note_transfer("d2h", self._tag, self._out.nbytes)
        except BaseException as e:  # noqa: BLE001 — re-raised in result()
            self._err = e
        finally:
            self.t_done = time.monotonic()
            self.t_ready = max(self.t_ready, self.t_start)
            self._done.set()

    @property
    def seconds(self) -> float:
        return self.t_done - self.t_submit

    @property
    def execute_seconds(self) -> float:
        return self.t_ready - self.t_start

    @property
    def materialize_seconds(self) -> float:
        return self.t_done - self.t_ready

    def ready(self) -> bool:
        """Non-blocking fence probe: has the host copy landed?"""
        return self._done.is_set()

    def result(self) -> np.ndarray:
        """The ready-fence: host array, blocking (and reporting a blocking
        sync) only when the copy is still in flight.  Fence-site faults
        inject HERE — synchronously on the calling thread, where the
        scheduler's classified-retry wrapper owns recovery."""
        faults.check(faults.SITE_FENCE, devices=self._devices)
        if not self._done.is_set():
            _note_sync(self._tag)
            self._done.wait()
        if self._err is not None:
            raise self._err
        return self._out


def fence_split(fetch, t0: float, t1: float) -> Tuple[float, float, float]:
    """A fence wait [t0, t1] on `fetch`, split by what the fetch worker
    was doing: (queue, device, copy) = the overlap with [.., t_start)
    (the worker had not started this job), with [t_start, t_ready) (the
    result was not ready), and the rest (the D2H copy landing, and the
    host checks after it).  The three add up to t1 - t0.  A handle
    without stamps (a host-computed result) puts the whole wait in copy."""
    t_start = getattr(fetch, "t_start", None)
    if t_start is None or t1 <= t0:
        return 0.0, 0.0, t1 - t0
    queue = max(0.0, min(t1, t_start) - t0)
    device = max(0.0, min(t1, fetch.t_ready) - max(t0, t_start))
    return queue, device, (t1 - t0) - queue - device


_GROUPS = ("f", "i", "b")
_HOST_DTYPE = {"f": np.float32, "i": np.int32, "b": np.bool_}
_DEV_DTYPE = {"f": jnp.float32, "i": jnp.int32, "b": jnp.bool_}


def _group(dtype) -> str:
    k = np.dtype(dtype).kind
    if k == "f":
        return "f"
    if k in ("i", "u"):
        return "i"
    if k == "b":
        return "b"
    raise TypeError(f"unsupported leaf dtype {dtype!r}")


def factors_by_default() -> bool:
    """Whether pack_tree row-factors when not told: on every backend but
    the CPU's, where there is no transfer to save."""
    return jax.default_backend() != "cpu"


def _unique_rows(rows: np.ndarray, order, max_u: int):
    """Deduplicate rows[order] by content, in that order: (the distinct
    rows, i32 index of each order element into them), or None once more
    than max_u distinct rows appear (factoring can no longer win)."""
    seen: dict = {}
    uniq_rows: list = []
    idx = np.empty(len(order), np.int32)
    for i, r in enumerate(order):
        key = rows[r].tobytes()
        u = seen.get(key)
        if u is None:
            if len(uniq_rows) >= max_u:
                return None
            u = seen[key] = len(uniq_rows)
            uniq_rows.append(rows[r])
        idx[i] = u
    return uniq_rows, idx


def pack_tree(tree, factor: "bool | None" = None) -> Tuple[Tuple[np.ndarray, ...], Any]:
    """tree (numpy/scalar leaves) -> (buffers, meta).

    buffers: up to 3 flat numpy arrays (f32 / i32 / bool).  meta is hashable
    (treedef + per-leaf placement + factoring pattern) and is the jit-cache
    key for the matching unpack — batches of one workload (same shapes,
    same factoring bucket) share one compiled program.
    64-bit leaves are narrowed to 32-bit (the device schema is 32-bit).

    Large [B, ...] leaves (>= _FACTOR_MIN_BYTES) are shipped FACTORED:
    unique rows (pow2-padded, floor 32) plus an i32[B] row index;
    unpack_tree gathers the dense leaf back ON DEVICE: a 2048-pod
    anti-affinity batch carries ~150MB of dense pair tensors with ~20
    distinct rows.  factor=None follows factors_by_default() (off on
    the CPU backend); tests pass factor=True to force the path.

    A PairRows leaf (encode_pods' class-row form of a pair leaf) ships
    the same way without hashing its B dense rows: its few class rows are
    deduplicated by content in order of first use, which gives the uniq,
    idx and meta the dense leaf would give.  Where a leaf does not ship
    factored, it is expanded first, so the wire form never depends on
    which form the caller held.
    """
    if factor is None:
        factor = factors_by_default()
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    chunks = {g: [] for g in _GROUPS}
    offs = {g: 0 for g in _GROUPS}
    metas = []

    def _append(a, g, factored, shape):
        flat = np.ravel(a).astype(_HOST_DTYPE[g], copy=False)
        metas.append((g, offs[g], shape, factored))
        offs[g] += flat.size
        chunks[g].append(flat)

    def _append_factored(uniq_rows, idx, dtype, g, shape):
        U = max(32, _pow2(len(uniq_rows)))
        uniq = np.zeros((U,) + uniq_rows[0].shape, dtype)
        uniq[: len(uniq_rows)] = uniq_rows
        # factored leaf = two packed entries: uniq then idx
        _append(uniq, g, "uniq", (U,) + shape[1:])
        _append(idx, "i", "idx", (shape[0],))

    for leaf in leaves:
        if isinstance(leaf, PairRows):
            # pre-factored by the encoder: dedupe its few class rows by
            # content, in order of first use, which gives the very uniq
            # and idx the row hashing below gives for the dense leaf
            g = _group(leaf.dtype)
            if factor and leaf.nbytes >= _FACTOR_MIN_BYTES \
                    and leaf.shape[0] > 1:
                rows = leaf.rows.reshape(len(leaf.rows), -1)
                used, first = np.unique(leaf.index, return_index=True)
                order = used[np.argsort(first)]
                got = _unique_rows(rows, order, factor_row_limit(leaf.shape[0]))
                if got is not None:
                    uniq_rows, u = got
                    remap = np.zeros(len(rows), np.int32)
                    remap[order] = u
                    _append_factored(uniq_rows, remap[leaf.index],
                                     leaf.dtype, g, leaf.shape)
                    continue
            leaf = leaf.dense()
        a = np.asarray(leaf)
        g = _group(a.dtype)
        if factor and a.nbytes >= _FACTOR_MIN_BYTES and a.ndim >= 1 \
                and a.shape[0] > 1:
            B = a.shape[0]
            rows = a.reshape(B, -1)
            got = _unique_rows(rows, range(B), factor_row_limit(B))
            if got is not None:
                _append_factored(got[0], got[1], a.dtype, g, a.shape)
                continue
        _append(a, g, None, a.shape)
    bufs = tuple(
        np.concatenate(chunks[g]) if chunks[g] else np.zeros(0, _HOST_DTYPE[g])
        for g in _GROUPS
    )
    return bufs, (treedef, tuple(metas))


def unpack_tree(bufs, meta):
    """Rebuild the packed tree from device buffers (call inside jit).
    Factored leaves are re-densified with an on-device gather."""
    treedef, metas = meta
    by_group = dict(zip(_GROUPS, bufs))
    leaves = []
    pending_uniq = None  # (device uniq rows, dense row shape tail)
    for g, off, shape, factored in metas:
        size = int(np.prod(shape)) if shape else 1
        piece = by_group[g][off:off + size]
        arr = jnp.reshape(piece, shape).astype(_DEV_DTYPE[g])
        if factored == "uniq":
            pending_uniq = arr
            continue
        if factored == "idx":
            leaves.append(jnp.take(pending_uniq, arr, axis=0))
            pending_uniq = None
            continue
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _scatter_impl(dev, rows, vals):
    return dev.at[rows].set(vals)


_scatter_copy = jax.jit(_scatter_impl)
# donated variant: the resident buffer (arg 0) is consumed and its memory
# reused for the output — the per-cycle dirty-row refresh updates the
# snapshot IN PLACE instead of allocating + copying a whole tensor per
# scattered field (requested/nonzero move every cycle; at 50k nodes that
# is MBs per field per cycle of pure copy).  Sound because the sole
# caller (DeviceSnapshotCache.update) immediately replaces its _dev entry
# with the result, and PJRT sequences the donation behind any in-flight
# reader of the old buffer.
_scatter_donate = jax.jit(_scatter_impl, donate_argnums=(0,))


def _scatter_rows(dev, rows, vals):
    """Row scatter into a resident device buffer (duplicate indices carry
    identical values, so pad-by-repeat is safe).  XLA:CPU has no buffer
    donation — the copying variant keeps warning noise out of cpu runs."""
    faults.check(faults.SITE_SCATTER, devices=_involved_device_ids(dev))
    if jax.default_backend() == "cpu":
        return _scatter_copy(dev, rows, vals)
    return _scatter_donate(dev, rows, vals)


# one jitted scatter per (resident sharding, donation) pair — bounded by
# the handful of distinct field ranks a mesh-backed snapshot carries
_SCATTER_SHARDED: dict = {}


def _scatter_rows_sharded(dev, rows, vals, sharding):
    """Row scatter into a MESH-SHARDED resident buffer: out_shardings pins
    the output to the same NamedSharding the resident buffer carries, so
    XLA's SPMD partitioner routes each row update to the shard that owns
    the row (a shard drops updates outside its row block locally — the
    refreshed buffer never gathers to one chip and incremental upload
    stays O(dirty)).  Donation keeps the `_scatter_rows` semantics
    per shard on accelerator backends: each device recycles its own
    block's HBM for the output; XLA:CPU (the virtual test mesh) has no
    donation, so the copying variant serves it.

    Instrumented as the `scatter` fault seam: a fault here is raised
    inside the scheduler's classified launch wrapper, and — because the
    scatter lands on the shard that owns the rows — carries the device
    ids the delta touches, so the elastic ladder can attribute it to the
    failing shard instead of demoting the whole mesh.  The id set is
    only computed while an injector is live (the hot path pays one
    module-global load, faults.py's contract)."""
    if faults.current_injector() is not None:
        from kubernetes_tpu.parallel.mesh import mesh_device_ids

        faults.check(
            faults.SITE_SCATTER, devices=mesh_device_ids(sharding.mesh)
        )
    donate = jax.default_backend() != "cpu"
    key = (sharding, donate)
    fn = _SCATTER_SHARDED.get(key)
    if fn is None:
        fn = _SCATTER_SHARDED[key] = jax.jit(
            _scatter_impl,
            out_shardings=sharding,
            donate_argnums=(0,) if donate else (),
        )
    return fn(dev, rows, vals)


# fields whose leading axis is NOT the node-row axis, or which the encoder
# recomputes wholesale so their diffs are NOT confined to dirty rows
# (image_size rescales every row when the node count moves; group_counts
# can shift many rows when a spread selector registers) — never scattered
_NON_ROW_FIELDS = frozenset({"pair_topo_key", "image_size", "group_counts"})
# scatter only pays while the dirty set stays a small fraction of N
_SCATTER_MAX_FRAC = 4


class DeviceSnapshotCache:
    """Incremental cluster-snapshot upload (SURVEY's "device-resident state
    with delta scatter, not re-upload" requirement; the host-side analog is
    the generation-numbered incremental NodeInfo snapshot,
    internal/cache/cache.go:210-222).

    The scheduler takes a fresh host snapshot every cycle, but between
    cycles most cluster tensor fields are byte-identical — label/taint/
    topology tensors only move on node events, while requested/nonzero move
    on every commit.  update() skips any field whose host array is the
    SAME OBJECT as last time (the encoder's incremental snapshot reuses
    unchanged leaves by identity, making unchanged-field detection O(1));
    non-identical fields fall back to content comparison (memcmp) before
    re-uploading.  When the caller passes `dirty_rows` (the encoder's
    take_dirty_rows()), a changed row-indexed field uploads only those
    rows and scatters them into the resident device buffer instead of
    re-shipping the whole tensor — the dirty set is exactly the rows the
    incremental snapshot rewrote, so host arrays cannot differ elsewhere.

    Multi-chip sharding (mesh != None): every node-axis field uploads
    sharded over the mesh's `spec_axis` (parallel/mesh.py shard_cluster's
    classification — leading dim == the snapshot's node count; the
    cluster-wide pair_topo_key vector replicates), so NO single device
    ever holds the full node tensor, and the dirty-row scatter routes
    each row delta to the shard that owns the row
    (_scatter_rows_sharded).  mesh=None is today's single-chip behavior
    bit-for-bit.
    """

    def __init__(self, mesh=None, spec_axis=None) -> None:
        self._host: dict = {}   # field -> last-uploaded host array
        self._dev: dict = {}    # field -> resident device array
        self._mesh = mesh
        if mesh is not None and spec_axis is None:
            names = tuple(mesh.axis_names)
            spec_axis = names if len(names) > 1 else names[0]
        self._spec_axis = spec_axis

    @property
    def mesh(self):
        return self._mesh

    def _sharding_for(self, name: str, arr: np.ndarray, n_rows: int):
        """NamedSharding for one snapshot field (None = unsharded cache),
        classified by the ONE shared rule (parallel.mesh.node_axis_spec):
        node-axis fields split over spec_axis, everything else (and the
        cluster-wide pair_topo_key, whatever its length) replicates."""
        if self._mesh is None:
            return None
        from jax.sharding import NamedSharding

        from kubernetes_tpu.parallel.mesh import node_axis_spec

        return NamedSharding(
            self._mesh, node_axis_spec(name, arr, n_rows, self._spec_axis)
        )

    def resident(self, names: "tuple[str, ...]"):
        """Device-resident buffers for the named snapshot fields, or None
        when any is absent (before the first update(), or after a fault
        invalidate()).  The telemetry analytics side-launch
        (ops/analytics.py) reads the snapshot THROUGH this accessor so it
        reduces the buffers already on device — zero extra H2D traffic —
        and degrades to its host fallback exactly when the device state
        cannot be trusted."""
        out = []
        for n in names:
            dev = self._dev.get(n)
            if dev is None:
                return None
            out.append(dev)
        return tuple(out)

    def invalidate(self) -> None:
        """Drop every resident buffer: the next update() re-uploads the
        whole snapshot.  Called after a device fault — the wire state is
        unknown (an upload may have half-landed) and the encoder's
        dirty-row stream may have been consumed by the failed cycle, so
        the incremental invariant (_host == device contents) cannot be
        trusted until rebuilt from scratch."""
        self._host.clear()
        self._dev.clear()

    def update(self, cluster, dirty_rows=None):
        """Host ClusterTensors (or any flat dataclass of numpy arrays) ->
        same type with device-resident leaves, uploading only changes.
        dirty_rows: optional i32[] of node rows touched since the previous
        update (from SnapshotEncoder.take_dirty_rows(); None = unknown,
        full content comparison).

        Fault discipline: _host must only record arrays whose device copy
        actually landed — a raising upload leaves the already-committed
        fields coherent (host+dev move together) and the failed/remaining
        fields untouched, so a retry after a transient fault re-uploads
        exactly what is missing.  The whole-tensor path therefore stages
        its _host commits until after the batched device_put."""
        faults.check(faults.SITE_SNAPSHOT_UPDATE)
        changed = []
        staged: dict = {}
        rows_arr = None
        if dirty_rows is not None and len(dirty_rows) > 0:
            rows_arr = np.asarray(dirty_rows, np.int32)
        n_rows = getattr(cluster, "n_nodes", None)
        if n_rows is None:
            first = dataclasses.fields(cluster)[0]
            n_rows = np.asarray(getattr(cluster, first.name)).shape[0]
        if self._mesh is not None and n_rows % self._mesh.size:
            raise ValueError(
                f"snapshot node axis ({n_rows}) does not divide over the "
                f"{self._mesh.size}-device mesh (node arenas grow pow2 to "
                "2048 rows then in 512-multiples — use a pow2 mesh of at "
                "most 512 devices and no larger than the node axis)"
            )
        for f in dataclasses.fields(cluster):
            host = np.asarray(getattr(cluster, f.name))
            prev = self._host.get(f.name)
            if prev is host:
                continue  # identity: unchanged leaf reused by the encoder
            if (
                prev is not None
                and rows_arr is not None
                and f.name not in _NON_ROW_FIELDS
                and f.name in self._dev
                and prev.shape == host.shape
                and prev.dtype == host.dtype
                and host.ndim >= 1
                and len(rows_arr) <= host.shape[0] // _SCATTER_MAX_FRAC
            ):
                sub = host[rows_arr]
                if not np.array_equal(prev[rows_arr], sub):
                    # pad rows to a pow2 bucket (repeat the first row) so
                    # the scatter kernel compiles once per shape bucket
                    k = _pow2(len(rows_arr))
                    if k > len(rows_arr):
                        pad = k - len(rows_arr)
                        rows_p = np.concatenate(
                            [rows_arr, np.repeat(rows_arr[:1], pad)]
                        )
                        sub_p = np.concatenate(
                            [sub, np.repeat(sub[:1], pad, axis=0)]
                        )
                    else:
                        rows_p, sub_p = rows_arr, sub
                    # the delta that actually crosses the wire: the
                    # padded row-index vector + the padded row values
                    note_transfer(
                        "h2d", "dirty_scatter",
                        rows_p.nbytes + sub_p.nbytes,
                    )
                    if self._mesh is not None:
                        # rows/vals ship uncommitted (the compiler
                        # replicates the tiny delta); the scatter routes
                        # each row to its owning shard
                        self._dev[f.name] = _scatter_rows_sharded(
                            self._dev[f.name], rows_p, sub_p,
                            self._sharding_for(f.name, host, n_rows),
                        )
                    else:
                        dev_rows, dev_vals = jax.device_put((rows_p, sub_p))
                        self._dev[f.name] = _scatter_rows(
                            self._dev[f.name], dev_rows, dev_vals
                        )
                self._host[f.name] = host
                continue
            if (
                prev is None
                or prev.shape != host.shape
                or prev.dtype != host.dtype
                or not np.array_equal(prev, host)
            ):
                changed.append(f.name)
                staged[f.name] = host
            else:
                self._host[f.name] = host  # content-equal: no upload needed
        if changed:
            note_transfer(
                "h2d", "snapshot_upload",
                sum(staged[n].nbytes for n in changed),
            )
            with annotation("ktpu.snapshot_upload"):
                if self._mesh is not None:
                    uploaded = jax.device_put(
                        [staged[n] for n in changed],
                        [self._sharding_for(n, staged[n], n_rows)
                         for n in changed],
                    )
                else:
                    uploaded = jax.device_put([staged[n] for n in changed])
            self._dev.update(zip(changed, uploaded))
            self._host.update(staged)
        return type(cluster)(**self._dev)


# ------------------------------------------------------- snapshot deltas
# Host-side snapshot delta serialization for the decision ledger
# (runtime/ledger.py): the on-disk twin of DeviceSnapshotCache's
# incremental upload.  A recorded cycle stores only the rows of each
# field that moved since the previously RECORDED snapshot (the encoder's
# cow snapshot makes unchanged fields identity-equal, so most fields
# cost one pointer compare); replay folds the deltas back into a full
# ClusterTensors, bit-identical to what the cycle dispatched.


def _row_changed(prev: np.ndarray, cur: np.ndarray) -> np.ndarray:
    """intp[] rows (axis 0) where prev and cur differ, NaN-safe (NaN is
    a live value in label_nums — two NaNs count as equal)."""
    neq = prev != cur
    if prev.dtype.kind == "f":
        neq &= ~(np.isnan(prev) & np.isnan(cur))
    if neq.ndim > 1:
        neq = neq.reshape(neq.shape[0], -1).any(axis=1)
    return np.flatnonzero(neq)


def snapshot_delta(prev, cur) -> dict:
    """ClusterTensors pair -> {field: ("full", arr) | ("rows", idx, vals)}.
    prev=None (or a shape/dtype change, or a diff touching most rows)
    records the field whole; identity-equal fields are omitted entirely.
    Pure numpy — safe to run on the ledger's writer thread because the
    encoder's snapshot arrays are immutable by the dirty-row contract."""
    out: dict = {}
    for f in dataclasses.fields(cur):
        cur_a = np.asarray(getattr(cur, f.name))
        prev_a = (
            np.asarray(getattr(prev, f.name)) if prev is not None else None
        )
        if prev_a is cur_a:
            continue
        if (
            prev_a is None
            or prev_a.shape != cur_a.shape
            or prev_a.dtype != cur_a.dtype
            or cur_a.ndim == 0
        ):
            out[f.name] = ("full", cur_a)
            continue
        rows = _row_changed(prev_a, cur_a)
        if len(rows) == 0:
            continue
        if len(rows) > cur_a.shape[0] // 2:
            out[f.name] = ("full", cur_a)
        else:
            out[f.name] = ("rows", rows.astype(np.int64), cur_a[rows])
    return out


def apply_snapshot_delta(prev, delta: dict, cls=None):
    """Fold a snapshot_delta back onto `prev` (None for the first,
    necessarily-full record) -> a reconstructed snapshot of type `cls`
    (defaults to type(prev)).  Row patches copy-on-write, so the caller
    may keep every reconstructed snapshot alive (the replay harness
    does)."""
    if prev is None:
        missing = [
            f.name for f in dataclasses.fields(cls)
            if f.name not in delta
        ]
        if missing:
            raise ValueError(
                f"first ledger record is not a full snapshot: {missing}"
            )
        fields = {k: v[1] for k, v in delta.items()}
        return cls(**fields)
    cls = cls or type(prev)
    fields = {}
    for f in dataclasses.fields(prev):
        cur = np.asarray(getattr(prev, f.name))
        d = delta.get(f.name)
        if d is not None:
            if d[0] == "full":
                cur = d[1]
            else:
                cur = cur.copy()
                cur[d[1]] = d[2]
        fields[f.name] = cur
    return cls(**fields)
