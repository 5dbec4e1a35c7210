"""Optimistic cross-replica conflict reconciler + shared snapshot hub.

ISSUE 14 / ROADMAP item 3: N `Scheduler` replicas (threads in one
process) each pop a stable hash-shard of the PriorityQueue and dispatch
engine launches against the SAME resident device snapshot generation —
Omega-style optimistic concurrency.  Nothing is locked during the
device window; instead every cycle's winners pass through this module's
SEQUENCED commit check before they assume:

  * zero-conflict fast path: if the encoder generation at commit still
    equals the generation the cycle dispatched against, no other
    replica committed in between — the engine's feasibility verdicts
    are exact and the whole batch admits with ONE integer comparison
    (allocation-free, pinned by test).

  * conflict scan: otherwise the candidate winners + requested matrices
    run through one fused check (a jitted lax.scan over the batch, with
    a bit-identical numpy twin for degraded cycles): per conflicted
    node row, requests are prefix-admitted against the LIVE headroom
    (allocatable - committed requested), so two replicas spending the
    same node's headroom beyond allocatable admit exactly the sequenced
    winner and requeue only the losers — shed-exempt, back to their
    owner shard, so no popped pod is ever lost.

  * fairness: within one reconciliation the candidate order is the
    dominant-resource-fairness order — the pod whose namespace holds
    the SMALLEST dominant share of cluster capacity goes first (ties by
    batch sequence), extending APF's request fairness (PR 4) to
    placement fairness.  Per-namespace usage/quota columns live in the
    snapshot encoder (SnapshotEncoder.a_ns_usage / a_ns_quota); a
    finite quota is enforced by the same scan (quota losers park
    unschedulable rather than spin).

`SnapshotHub` is the shared-device-state half: one DeviceSnapshotCache
all replicas dispatch through, refreshed ATOMICALLY (cache lock held
across snapshot + take_dirty_rows + device scatter) so the single-
consumer dirty-row contract holds with N dispatchers, and every launch
is tagged with the generation it ran against (the fencing the fast
path compares).

The module also keeps the process-level replica registry serving
GET /debug/replicas — the explicit aggregate the per-scheduler
telemetry/perfobs/quality installs roll up into.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple

import numpy as np

from kubernetes_tpu.codec.schema import _pow2
from kubernetes_tpu.utils import klog
from kubernetes_tpu.utils import metrics as m

# same slack vocabulary as the invariant checker's capacity rule: the
# engines and the encoder accumulate requests in f32, so an exact
# comparison would fire on rounding dust
_EPS = 1e-3


def _lean_pod(pod) -> bool:
    """Can this pod's engine verdicts be trusted across a STALE
    generation fence?  Resources and node-static constraints (node
    selectors/affinity, taints) don't depend on other pods' placements
    — the admission scan re-checks the resource half against live
    truth.  Host ports, pod-(anti-)affinity, and volumes DO depend on
    what other pods committed since dispatch and have no vectorized
    re-check here, so a stale-fence winner carrying them must requeue
    and re-dispatch against fresh state instead of committing
    optimistically (spread counts are score-only — stale is suboptimal,
    never invalid)."""
    if pod.spec.volumes or pod.host_ports():
        return False
    a = pod.spec.affinity
    if a is not None and (
        a.pod_affinity is not None or a.pod_anti_affinity is not None
    ):
        return False
    return True


class SnapshotHub:
    """THE resident device snapshot N replicas share.

    refresh() is the only writer: under the cache lock it snapshots the
    encoder, takes the dirty-row stream (single consumer — replicas in
    hub mode must NOT take it themselves), scatters the delta into the
    one DeviceSnapshotCache, and records the generation.  Holding the
    cache lock across the scatter is what makes N dispatchers safe: a
    commit can never interleave between the snapshot and the upload, so
    the resident buffers always equal some exact host generation.
    JAX arrays are immutable (the CPU scatter path copies; donation is
    an accelerator-only in-place move the hub's serialized refresh
    keeps single-writer), so a launch enqueued against generation G
    keeps computing against G's buffers while the hub refreshes to G+1.
    """

    def __init__(self, cache, devcache):
        self.cache = cache
        self.dev = devcache
        self._lock = threading.Lock()  # guards dev + generation together
        self.generation = -1
        self.refreshes = 0
        self.refresh_hits = 0
        self._last = None  # (cluster, gen, dev) of the newest refresh

    def refresh(self):
        """Atomic host-snapshot -> device-scatter.  Returns
        (host ClusterTensors, generation, device ClusterTensors).
        Fast path: when NOTHING committed since the previous refresh
        (generation unchanged) the cached triple is returned as is —
        sibling replicas dispatching back-to-back against one
        generation pay one snapshot, not N."""
        with self.cache._lock:
            gen = self.cache.generation
            with self._lock:
                if gen == self.generation and self._last is not None:
                    self.refresh_hits += 1
                    return self._last
            cluster, gen = self.cache.snapshot()
            dirty = self.cache.encoder.take_dirty_rows()
            with self._lock:
                dev = self.dev.update(cluster, dirty_rows=dirty)
                self.generation = gen
                self.refreshes += 1
                self._last = (cluster, gen, dev)
            return self._last

    def invalidate(self) -> None:
        """Device fault: drop every resident buffer (the next refresh
        re-uploads the whole snapshot) and poison the generation so no
        fast path trusts state that predates the fault."""
        with self._lock:
            self.dev.invalidate()
            self.generation = -1
            self._last = None

    def resident(self, names):
        with self._lock:
            return self.dev.resident(names)


class ConflictReconciler:
    """Sequenced commit admission for optimistic replica cycles.

    One instance is shared by every replica; reconcile() runs under the
    cache lock (the commit critical section), stamps the cycle's commit
    sequence number, and returns the admitted winners plus the two
    loser classes (race-conflicted -> readd to the owner shard;
    quota-vetoed -> park unschedulable with backoff)."""

    def __init__(self, use_jit: bool = True):
        self.use_jit = use_jit
        self._seq_lock = threading.Lock()
        self.commit_seq = 0
        # stats (reads are approximate outside the cache lock — fine for
        # debug surfaces)
        self.fast_path_total = 0
        self.scans_total = 0
        self.conflicts_total = 0
        self.quota_vetoes_total = 0
        # stale-fence winners carrying constraints the scan cannot
        # re-validate (ports/pod-affinity/volumes, or any winner while
        # nominations are outstanding): requeued conservatively
        self.strict_requeues_total = 0
        self.kernel_calls = 0
        self._kernels: Dict[Tuple[int, int], object] = {}

    # ------------------------------------------------------------ kernel

    def _kernel(self, bp: int, r: int):
        """The fused admission check, jitted per padded (B, R) shape:
        ONE lax.scan over the DRF-ordered candidates carrying per-row
        and per-tenant spent matrices, so depletion chains exactly like
        a sequential admit loop — in one launch."""
        fn = self._kernels.get((bp, r))
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        from jax import lax

        def run(u_node, u_ns, reqs, node_head, ns_head, order):
            z = jnp.zeros((bp, r), jnp.float32)

            def step(carry, x):
                spent_n, spent_t = carry
                un, ut, rq, hn, ht = x
                node_ok = jnp.all(rq <= hn - spent_n[un] + _EPS)
                ns_ok = jnp.all(rq <= ht - spent_t[ut] + _EPS)
                ok = node_ok & ns_ok
                w = jnp.where(ok, rq, 0.0)
                return (
                    (spent_n.at[un].add(w), spent_t.at[ut].add(w)),
                    (ok, ns_ok),
                )

            xs = (
                u_node[order], u_ns[order], reqs[order],
                node_head[order], ns_head[order],
            )
            _, (ok_s, ns_ok_s) = lax.scan(step, (z, z), xs)
            admit = jnp.zeros(bp, bool).at[order].set(ok_s)
            quota_ok = jnp.zeros(bp, bool).at[order].set(ns_ok_s)
            return admit, quota_ok

        fn = jax.jit(run)
        self._kernels[(bp, r)] = fn
        return fn

    @staticmethod
    def _admit_np(u_node, u_ns, reqs, node_head, ns_head, order):
        """Bit-identical numpy twin of the fused kernel (degraded-cycle
        path + the test oracle): the same DRF-ordered prefix admit."""
        bp, r = reqs.shape
        spent_n = np.zeros((bp, r), np.float32)
        spent_t = np.zeros((bp, r), np.float32)
        admit = np.zeros(bp, bool)
        quota_ok = np.zeros(bp, bool)
        for j in order:
            un, ut = u_node[j], u_ns[j]
            rq = reqs[j]
            node_ok = bool(np.all(rq <= node_head[j] - spent_n[un] + _EPS))
            ns_ok = bool(np.all(rq <= ns_head[j] - spent_t[ut] + _EPS))
            ok = node_ok and ns_ok
            if ok:
                spent_n[un] += rq
                spent_t[ut] += rq
            admit[j] = ok
            quota_ok[j] = ns_ok
        return admit, quota_ok

    # --------------------------------------------------------- reconcile

    def prewarm(self, max_width: int, r: int) -> None:
        """Pre-pay the admission kernel's compiles for the pow2 width
        ladder up to max_width (the bench/prewarm seam: a first-scan
        compile inside a timed or latency-sensitive window would read
        as a conflict-cost regression)."""
        if not self.use_jit:
            return
        w = 1
        while w <= _pow2(max(1, max_width)):
            fn = self._kernel(w, r)
            z = np.zeros((w, r), np.float32)
            u = np.zeros(w, np.int32)
            o = np.arange(w, dtype=np.int32)
            fn(u, u, z, z, z, o)
            w *= 2

    def next_seq(self) -> int:
        with self._seq_lock:
            self.commit_seq += 1
            return self.commit_seq

    def reconcile(self, sched, inf, winners, hosts):
        """Admission for one cycle's winners.  MUST run under the cache
        lock (the caller then assumes the admitted pods in the same
        critical section).  Returns (kept_winners, race_lost, quota_lost)
        where the loser lists hold (batch_index, pod) pairs.

        Fast path: generation unchanged since dispatch and no quota
        configured -> the input winners list is returned AS IS (no
        allocation, no kernel launch — pinned by test)."""
        enc = sched.cache.encoder
        inf.commit_seq = self.next_seq()
        if not winners:
            return winners, [], []
        gen_now = enc.generation
        quotas = enc.ns_quota_set
        stale = gen_now != inf.generation
        if not stale and not quotas:
            self.fast_path_total += 1
            return winners, [], []
        self.scans_total += 1
        # a STALE fence invalidates engine verdicts the scan cannot
        # re-check: winners carrying host ports / pod-(anti-)affinity /
        # volumes — and every winner while preemption nominations are
        # outstanding (the two-pass mask was host-computed at encode) —
        # requeue conservatively and re-dispatch against fresh state.
        # A quota-only scan (generation unchanged) trusts the verdicts.
        strict: list = []
        if stale:
            strict_all = bool(sched.queue.has_nominated())
            scanned = []
            for w in winners:
                if strict_all or not _lean_pod(w[1]):
                    strict.append((w[0], w[1]))
                else:
                    scanned.append(w)
            winners = scanned
        if strict:
            self.strict_requeues_total += len(strict)
            self.conflicts_total += len(strict)
            m.REPLICA_CONFLICTS.inc(
                len(strict), replica=str(sched._replica_id)
            )
            m.REPLICA_REQUEUED.inc(len(strict))
        if not winners:
            return [], strict, []
        B = len(winners)
        R = enc.dims.R
        idx = np.fromiter((w[0] for w in winners), np.int64, B)
        rows = np.asarray(hosts, np.int64)[idx]
        # per-winner requested vectors: the encoded batch's request
        # matrix (stashed at encode; R may have grown since — pad)
        reqs_src = np.asarray(inf.reqs, np.float32)
        reqs = np.zeros((B, R), np.float32)
        rc = min(R, reqs_src.shape[1])
        reqs[:, :rc] = reqs_src[idx][:, :rc]
        # tenant rows + DRF dominant shares (host-side: B-sized gathers)
        t_rows = np.fromiter(
            (enc._ns_row(w[1].namespace) for w in winners), np.int64, B
        )
        caps = enc.capacity_totals()
        with np.errstate(divide="ignore", invalid="ignore"):
            shares_t = np.where(
                caps > 0.0, enc.a_ns_usage[t_rows] / caps, 0.0
            )
        shares = shares_t.max(axis=1)
        order = np.lexsort((idx, shares)).astype(np.int32)
        # live headroom gathers (aligned per candidate position)
        node_head = (
            enc.a_allocatable[rows] - enc.a_requested[rows]
        ).astype(np.float32)
        ns_head = (
            enc.a_ns_quota[t_rows, :R] - enc.a_ns_usage[t_rows, :R]
        ).astype(np.float32)
        # first-occurrence index per row / tenant: the scan's segment ids
        u_node = np.zeros(B, np.int32)
        seen: Dict[int, int] = {}
        for j in range(B):
            u_node[j] = seen.setdefault(int(rows[j]), j)
        u_ns = np.zeros(B, np.int32)
        seen = {}
        for j in range(B):
            u_ns[j] = seen.setdefault(int(t_rows[j]), j)
        # pad to the pow2 ladder so the jitted kernel compiles a bounded
        # shape family; pad slots point at a dummy segment with zero
        # request and +inf headroom (always admitted, sliced off below)
        Bp = _pow2(B)
        if Bp != B:
            pad = Bp - B
            u_node = np.concatenate([u_node, np.full(pad, B, np.int32)])
            u_ns = np.concatenate([u_ns, np.full(pad, B, np.int32)])
            reqs = np.vstack([reqs, np.zeros((pad, R), np.float32)])
            inf_head = np.full((pad, R), np.inf, np.float32)
            node_head = np.vstack([node_head, inf_head])
            ns_head = np.vstack([ns_head, inf_head])
            order = np.concatenate(
                [order, np.arange(B, Bp, dtype=np.int32)]
            )
            # segment ids must stay in-range for the carry gather
            u_node = np.minimum(u_node, Bp - 1)
            u_ns = np.minimum(u_ns, Bp - 1)
        use_jit = self.use_jit and not inf.degraded
        if use_jit:
            try:
                self.kernel_calls += 1
                admit, quota_ok = self._kernel(Bp, R)(
                    u_node, u_ns, reqs, node_head, ns_head, order
                )
                admit = np.asarray(admit)[:B]
                quota_ok = np.asarray(quota_ok)[:B]
            except Exception as e:  # noqa: BLE001 — the numpy twin is
                # always available; a kernel fault must not lose a cycle
                klog.errorf("reconcile kernel failed (%s); numpy twin", e)
                use_jit = False
        if not use_jit:
            admit, quota_ok = self._admit_np(
                u_node, u_ns, reqs, node_head, ns_head, order
            )
            admit, quota_ok = admit[:B], quota_ok[:B]
        kept, race_lost, quota_lost = [], list(strict), []
        for j, w in enumerate(winners):
            if admit[j]:
                kept.append(w)
            elif not quota_ok[j]:
                quota_lost.append((w[0], w[1]))
            else:
                race_lost.append((w[0], w[1]))
        n_scan_lost = len(race_lost) - len(strict)  # strict counted above
        if n_scan_lost:
            self.conflicts_total += n_scan_lost
            m.REPLICA_CONFLICTS.inc(
                n_scan_lost, replica=str(sched._replica_id)
            )
        if quota_lost:
            self.quota_vetoes_total += len(quota_lost)
        if n_scan_lost or quota_lost:
            m.REPLICA_REQUEUED.inc(n_scan_lost + len(quota_lost))
        return kept, race_lost, quota_lost

    def stats(self) -> dict:
        return {
            "commit_seq": self.commit_seq,
            "fast_path_total": self.fast_path_total,
            "scans_total": self.scans_total,
            "conflicts_total": self.conflicts_total,
            "strict_requeues_total": self.strict_requeues_total,
            "quota_vetoes_total": self.quota_vetoes_total,
            "kernel_calls": self.kernel_calls,
        }


# ---------------------------------------------------- replica registry
#
# The explicit PROCESS AGGREGATE the per-scheduler observability
# installs roll up into (ISSUE 14 satellite): every Scheduler registers
# itself under its replica id (latest wins, the set_default discipline),
# and GET /debug/replicas on both servers serves this roll-up.

_REG_LOCK = threading.Lock()
_SCHEDULERS: Dict[int, object] = {}  # replica id -> weakref(Scheduler)


def register_scheduler(sched) -> None:
    import weakref

    with _REG_LOCK:
        _SCHEDULERS[int(getattr(sched, "_replica_id", 0))] = weakref.ref(
            sched
        )


def registered_schedulers() -> Dict[int, object]:
    """Live registered schedulers by replica id — weakly held, so a
    torn-down replica set disappears from /debug/replicas instead of
    reporting frozen stats (and pinning its cache) forever."""
    with _REG_LOCK:
        out = {}
        for rid, ref in sorted(_SCHEDULERS.items()):
            s = ref()
            if s is not None:
                out[rid] = s
        return out


def debug_payload(limit: Optional[int] = None) -> dict:
    """GET /debug/replicas body: per-replica cycle/outcome/conflict
    facts, the shared reconciler's sequencing stats, and the tenant
    usage/quota table.  `limit` bounds the tenant table (the shared
    debug_body cap discipline)."""
    per: Dict[str, dict] = {}
    recon = None
    tenants: Dict[str, dict] = {}
    n_live = 0
    for rid, s in registered_schedulers().items():
        try:
            per[str(rid)] = {
                "replica_of": getattr(s, "_replica_of", 1),
                # THIS replica's committed cycles (the per-scheduler
                # observatory counts its own on_cycle calls; the
                # queue's scheduling_cycle is process-global)
                "cycles": s.perfobs.summary().get("cycles", 0),
                "queue_cycles": s.queue.scheduling_cycle,
                "placed": s._outcome_totals.get("placed", 0),
                "unschedulable": s._outcome_totals.get("unschedulable", 0),
                "conflicts": getattr(s, "conflicts_total", 0),
                "race_requeued": getattr(s, "race_requeued_total", 0),
                "quota_vetoed": getattr(s, "quota_vetoed_total", 0),
                "breaker": s.device_health.state,
                "engine": getattr(s, "_engine_kind", "?"),
                "queue_shard": getattr(s, "_replica_id", 0),
            }
            n_live += 1
            if recon is None and getattr(s, "_reconciler", None) is not None:
                recon = s._reconciler
            if not tenants:
                tenants = s.cache.encoder.namespace_usage()
        except Exception as e:  # noqa: BLE001 — a debug read must never
            # throw out of the HTTP handler
            per[str(rid)] = {"error": str(e)}
    if limit is not None and limit >= 0 and len(tenants) > limit:
        tenants = dict(list(tenants.items())[:limit])
    return {
        "replicas": n_live,
        "per_replica": per,
        "reconciler": recon.stats() if recon is not None else None,
        "tenants": tenants,
    }
