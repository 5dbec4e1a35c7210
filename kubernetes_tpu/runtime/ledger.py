"""Decision ledger: durable per-cycle decision record + offline replay.

PR 5's flight recorder answers *when* a cycle was slow; this module
answers *why a pod landed where it did* and *which predicate rejected
every node* — and makes both replayable.  Three pieces:

  * `DecisionLedger`: an opt-in, bounded, append-only record of every
    scheduling cycle's INPUTS (the host snapshot as a delta against the
    previously recorded one, the encoded pod batch / ports / nominated /
    in-batch-affinity tensors, the extender/framework extra mask+score,
    the selectHost rotation base) and OUTCOMES (winners, engine kind,
    tier, fault class/attempts, degraded flag, trace id).  Recording is
    off the hot path: `record_cycle` is an O(1) ring append plus a
    non-blocking enqueue to a persistent writer thread (the fetch/
    bind-tail worker pattern) that serializes and appends length-prefixed
    npz blocks to one file.  Bounded twice — a full writer queue DROPS
    the record (never blocks a cycle) and `max_cycles` caps the file.

  * an in-memory decisions ring served at `GET /debug/decisions` (health
    server + apiserver), each entry cross-linked to /debug/traces by the
    cycle's trace id.

  * `replay(path)`: reconstructs each recorded cycle's snapshot by
    folding the deltas (codec/transfer.apply_snapshot_delta), re-executes
    it through a freshly built engine, and compares winners bit-for-bit.
    Replaying through the RECORDED engine is deterministic (the
    bit-identity gate CI pins, fault-injected recordings included);
    cross-engine replay is a comparison tool — the engines match
    one-at-a-time semantics, but argmax-tie rotation can pick different
    winners on tie-heavy workloads.  This is the substrate ROADMAP item
    4's weight-tuning loop re-scores against: same records, different
    weight vector, evaluate the counterfactual placements.

File format: `u64le length + npz` blocks; block 0 is the header (engine
config as JSON under `__meta__`), every later block one cycle.
"""

from __future__ import annotations

import dataclasses
import io
import json
import struct
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from kubernetes_tpu.codec.schema import (
    ClusterTensors,
    FilterConfig,
    ScoreConfig,
    reason_message,
    reason_name,
)
from kubernetes_tpu.codec.transfer import apply_snapshot_delta, snapshot_delta
from kubernetes_tpu.utils import klog
from kubernetes_tpu.utils import metrics as m

_LEN = struct.Struct("<Q")

# hard ceiling for one /debug/* response body; the handlers halve their
# entry limit until the rendered JSON fits (a long-lived ring must never
# produce an unbounded response)
MAX_DEBUG_BODY_BYTES = 4 << 20


# ------------------------------------------------------------ explain

def explain_unschedulable(counts) -> Tuple[str, str]:
    """Attribution reason counts (i32[NUM_REASONS]) -> (dominant plugin
    name, kubectl-describe-parity message):

        0/5000 nodes are available: 4987 Insufficient resources,
        13 node(s) had taints that the pod didn't tolerate.
    """
    counts = np.asarray(counts)
    total = int(counts.sum())
    order = np.argsort(-counts, kind="stable")
    parts = [
        f"{int(counts[k])} {reason_message(int(k))}"
        for k in order if counts[k] > 0
    ]
    dominant = reason_name(int(order[0])) if parts else ""
    msg = f"0/{total} nodes are available"
    if parts:
        msg += ": " + ", ".join(parts)
    return dominant, msg + "."


# ------------------------------------------------- pytree (de)serialization

def _component_fields(obj) -> List[str]:
    if dataclasses.is_dataclass(obj):
        return [f.name for f in dataclasses.fields(obj)]
    return list(obj._fields)  # NamedTuple


def _pack_component(out: Dict[str, np.ndarray], prefix: str, obj) -> None:
    for fname in _component_fields(obj):
        out[f"{prefix}.{fname}"] = np.asarray(getattr(obj, fname))


def _unpack_component(z, prefix: str, cls):
    if dataclasses.is_dataclass(cls):
        names = [f.name for f in dataclasses.fields(cls)]
    else:
        names = list(cls._fields)
    return cls(**{n: z[f"{prefix}.{n}"] for n in names})


def _tuplify(x):
    if isinstance(x, list):
        return tuple(_tuplify(v) for v in x)
    return x


def engine_meta(cfg: FilterConfig, weights, unsched_taint_key: int,
                zone_key_id: int, score_cfg: Optional[ScoreConfig],
                percentage_of_nodes_to_score: int, engine: str) -> dict:
    """JSON-serializable engine identity for the ledger header — enough
    to rebuild a bit-identical engine in a fresh process (interner ids in
    the recorded tensors already agree with these key ids)."""
    return {
        "version": 1,
        "engine": engine,
        "filter_config": dataclasses.asdict(cfg),
        "weights": (
            [float(w) for w in np.asarray(weights, np.float32)]
            if weights is not None else None
        ),
        "unsched_taint_key": int(unsched_taint_key),
        "zone_key_id": int(zone_key_id),
        "score_cfg": (
            dataclasses.asdict(score_cfg) if score_cfg is not None else None
        ),
        "percentage_of_nodes_to_score": int(percentage_of_nodes_to_score),
    }


def build_replay_fn(header: dict, engine: Optional[str] = None):
    """Rebuild the recorded engine (or the other one — placements are
    pinned bit-identical across engines) from a ledger header."""
    fc = {k: _tuplify(v) for k, v in header["filter_config"].items()}
    if fc.get("enabled") is not None:
        fc["enabled"] = tuple(fc["enabled"])
    cfg = FilterConfig(**fc)
    sc = header.get("score_cfg")
    score_cfg = (
        ScoreConfig(**{k: _tuplify(v) for k, v in sc.items()})
        if sc is not None else None
    )
    kind = engine or header.get("engine", "speculative")
    if kind == "speculative":
        from kubernetes_tpu.models.speculative import (
            make_speculative_scheduler as maker,
        )
    else:
        from kubernetes_tpu.models.batched import (
            make_sequential_scheduler as maker,
        )
    return maker(
        cfg=cfg,
        weights=header.get("weights"),
        unsched_taint_key=header["unsched_taint_key"],
        zone_key_id=header["zone_key_id"],
        score_cfg=score_cfg,
        percentage_of_nodes_to_score=header.get(
            "percentage_of_nodes_to_score", 100
        ),
    )


# ------------------------------------------------------------- the ledger

class DecisionLedger:
    """Bounded append-only cycle record + in-memory decisions ring.

    `path=None` keeps the ring (the /debug/decisions source) without
    touching disk.  Scope: plain scheduling cycles (both tiers, both
    engines, degraded included) — gang launches and preemption what-ifs
    have their own device paths and are not recorded.  Thread-safety:
    record_cycle is called from the scheduling thread only; the writer
    thread owns the file and the delta-base snapshot; readers (HTTP
    handlers) take the ring lock."""

    def __init__(
        self,
        path: Optional[str] = None,
        max_cycles: int = 4096,
        ring_capacity: int = 256,
        queue_capacity: int = 64,
        meta: Optional[dict] = None,
    ):
        self.path = path
        self.max_cycles = int(max_cycles)
        self.meta = dict(meta or {})
        self._ring: "deque[dict]" = deque(maxlen=max(1, int(ring_capacity)))
        self._lock = threading.Lock()
        self.cycles_total = 0     # records accepted (ring + file intent)
        self.bytes_total = 0      # bytes appended to the file
        self.dropped_total = 0    # queue-full or max_cycles drops
        self._written = 0
        self._busy = False
        self._q: Optional["deque"] = None
        self._cv: Optional[threading.Condition] = None
        self._queue_capacity = max(1, int(queue_capacity))
        self._prev_snap: Optional[ClusterTensors] = None
        self._header_written = False
        self._closed = False
        if path:
            # fresh file per ledger session: the delta chain starts at a
            # full snapshot, so stale blocks from an older run would not
            # reconstruct
            open(path, "wb").close()
            self._cv = threading.Condition()
            self._q = deque()
            t = threading.Thread(
                target=self._writer_loop, name="ktpu-ledger", daemon=True
            )
            t.start()
            self._thread = t

    def ensure_meta(self, meta: dict) -> None:
        """Fill the header meta lazily (the Scheduler calls this with its
        engine identity); first writer-thread record freezes it."""
        if not self._header_written and not self.meta:
            self.meta = dict(meta)

    # ------------------------------------------------------------ record

    def record_cycle(self, inputs: dict, outcome: dict,
                     decisions: List[dict]) -> bool:
        """O(1) hot-path submit: ring append + non-blocking enqueue.
        `inputs` holds the cycle's tensors (cluster/batch/ports/nominated/
        aff_state/extra_mask/extra_score/last_index0), `outcome` the JSON
        facts (cycle/tier/engine/winners/pods/...), `decisions` the
        per-pod ring entries.  Returns False when the record was dropped
        (queue full or max_cycles reached)."""
        with self._lock:
            entry = {
                "cycle": outcome.get("cycle"),
                "trace_id": outcome.get("trace_id", ""),
                "tier": outcome.get("tier", ""),
                "engine": outcome.get("engine", ""),
                "degraded": bool(outcome.get("degraded", False)),
                "time": time.time(),
                "pods": decisions,
                # queue-sharded replicas (ISSUE 14): dispatching replica
                # + reconciler commit sequence, so /debug/decisions
                # readers can reconstruct the cross-replica interleaving
                **({"replica": outcome["replica"]}
                   if outcome.get("replica") is not None else {}),
                **({"seq": outcome["seq"]}
                   if outcome.get("seq") is not None else {}),
            }
            self._ring.append(entry)
            self.cycles_total += 1
        m.LEDGER_CYCLES.inc()
        if self._q is None:
            return True
        if self._written + len(self._q) >= self.max_cycles:
            self._drop()
            return False
        with self._cv:
            if len(self._q) >= self._queue_capacity:
                self._drop()
                return False
            self._q.append((inputs, outcome))
            self._cv.notify()
        return True

    def _drop(self) -> None:
        with self._lock:
            self.dropped_total += 1
        m.LEDGER_DROPPED.inc()

    def record_event(self, entry: dict) -> None:
        """Ring-only append of a non-cycle event (ISSUE 19: autoscaler
        actuations) so /debug/decisions interleaves scale events with
        the scheduling cycles they bracket.  Never touches the binary
        file — the authoritative actuation record is the autoscaler's
        own JSONL ledger; this is the observability mirror."""
        with self._lock:
            self._ring.append(dict(entry))

    def decisions(self, limit: Optional[int] = None) -> List[dict]:
        with self._lock:
            out = list(self._ring)
        if limit is not None and limit >= 0:
            out = out[-limit:] if limit else []
        return out

    # ------------------------------------------------------------ writer

    def _writer_loop(self) -> None:
        while True:
            with self._cv:
                while not self._q and not self._closed:
                    self._cv.wait(0.1)
                if not self._q:
                    if self._closed:
                        return
                    continue
                inputs, outcome = self._q.popleft()
                self._busy = True
            try:
                if self._written >= self.max_cycles:
                    # authoritative cap check (the submit-side check is
                    # a cheap racy early-out): the file never exceeds
                    # max_cycles records
                    self._drop()
                    continue
                self._write_record(inputs, outcome)
            except Exception as e:  # noqa: BLE001 — never kill the loop
                klog.errorf("ledger write failed: %s", e)
                self._drop()
                # the delta base may be out of sync with the file now;
                # force the next record full
                self._prev_snap = None
            finally:
                self._busy = False

    def _serialize(self, inputs: dict, outcome: dict) -> bytes:
        arrays: Dict[str, np.ndarray] = {}
        meta = dict(outcome)
        cluster = inputs["cluster"]
        delta = snapshot_delta(self._prev_snap, cluster)
        for name, d in delta.items():
            if d[0] == "full":
                arrays[f"snap.full.{name}"] = np.asarray(d[1])
            else:
                arrays[f"snap.rows.{name}.idx"] = d[1]
                arrays[f"snap.rows.{name}.val"] = np.asarray(d[2])
        _pack_component(arrays, "batch", inputs["batch"])
        _pack_component(arrays, "ports", inputs["ports"])
        present = {}
        for key, prefix in (("nominated", "nom"), ("aff_state", "aff")):
            obj = inputs.get(key)
            present[key] = obj is not None
            if obj is not None:
                _pack_component(arrays, prefix, obj)
        for key in ("extra_mask", "extra_score"):
            arr = inputs.get(key)
            present[key] = arr is not None
            if arr is not None:
                arrays[key] = np.asarray(arr)
        arrays["winners"] = np.asarray(outcome["winners"], np.int32)
        meta.pop("winners", None)
        # optional quality top-k (ISSUE 13): the winner-pinned ranking +
        # feasible counts ride the block so bench --replay can recompute
        # margins offline without re-running a quality-enabled engine
        for key, dtype in (
            ("quality_top_nodes", np.int32),
            ("quality_top_scores", np.float32),
            ("quality_feasible", np.int32),
        ):
            arr = outcome.get(key)
            present[key] = arr is not None
            if arr is not None:
                arrays[key] = np.asarray(arr, dtype)
            meta.pop(key, None)
        meta["present"] = present
        meta["last_index0"] = int(inputs["last_index0"])
        arrays["__meta__"] = np.frombuffer(
            json.dumps({"kind": "cycle", **meta}).encode(), np.uint8
        )
        buf = io.BytesIO()
        np.savez_compressed(buf, **arrays)
        self._prev_snap = cluster
        return buf.getvalue()

    def _write_record(self, inputs: dict, outcome: dict) -> None:
        blocks = []
        if not self._header_written:
            hdr = io.BytesIO()
            np.savez_compressed(hdr, __meta__=np.frombuffer(
                json.dumps({"kind": "header", **self.meta}).encode(),
                np.uint8,
            ))
            blocks.append(hdr.getvalue())
        blocks.append(self._serialize(inputs, outcome))
        with open(self.path, "ab") as f:
            for b in blocks:
                f.write(_LEN.pack(len(b)))
                f.write(b)
        # only after the write landed: a failed first write must retry
        # the header with the next record, or the file never reconstructs
        self._header_written = True
        n = sum(len(b) + _LEN.size for b in blocks)
        with self._lock:
            self.bytes_total += n
            self._written += 1
        m.LEDGER_BYTES.inc(n)

    def flush(self, timeout_s: float = 10.0) -> bool:
        """Wait for every enqueued record to reach the file (tests /
        bench exit).  True when drained."""
        if self._q is None:
            return True
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._cv:
                if not self._q and not self._busy:
                    return True
            time.sleep(0.002)
        return False

    def close(self, timeout_s: float = 10.0) -> None:
        self.flush(timeout_s)
        self._closed = True
        if self._cv is not None:
            with self._cv:
                self._cv.notify_all()


# process-wide default: the ring /debug/decisions serves when no
# instance was wired explicitly.  A Scheduler configured with
# decision_ledger=True installs its ledger here unless one was
# injected.  Replicas normally SHARE one ledger (replica id + commit
# seq in every block), so the registry usually holds one instance under
# several ids (runtime/defaults.py ProcessDefault).
from kubernetes_tpu.runtime.defaults import ProcessDefault  # noqa: E402

_DEFAULT = ProcessDefault("ledger", DecisionLedger)


def get_default() -> DecisionLedger:
    return _DEFAULT.get()


def set_default(ledger: DecisionLedger, replica: int = 0) -> None:
    _DEFAULT.set(ledger, replica)


def replica_instances() -> dict:
    """{replica id: DecisionLedger} of every install this process saw."""
    return _DEFAULT.replicas()


def __getattr__(name):  # legacy alias: ledger.LEDGER
    if name == "LEDGER":
        return _DEFAULT.get()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def bounded_json(render, limit: Optional[int],
                 cap: int = MAX_DEBUG_BODY_BYTES) -> bytes:
    """Render `render(limit) -> jsonable` and enforce the hard
    response-size cap by halving the entry limit until the body fits;
    if even one entry exceeds the cap, a tiny well-formed error body is
    served instead of truncated JSON."""
    lim = limit
    while True:
        body = json.dumps(render(lim)).encode()
        if len(body) <= cap:
            return body
        if lim == 1:
            return json.dumps(
                {"truncated": True,
                 "error": "single entry exceeds the response-size cap"}
            ).encode()
        # over cap: halve, seeding from a generous default when the
        # caller asked for everything
        lim = max(1, (lim if lim is not None else 4096) // 2)


def debug_query_limit(query: str) -> Optional[int]:
    """?limit=N from a raw query string (None = unbounded request)."""
    from urllib.parse import parse_qs

    try:
        v = parse_qs(query).get("limit")
        return max(0, int(v[0])) if v else None
    except (ValueError, TypeError):
        return None


def debug_body(render, query: str = "",
               cap: int = MAX_DEBUG_BODY_BYTES) -> bytes:
    """Shared /debug/* body builder (health server + apiserver):
    `render(limit) -> jsonable` (zero-arg callables tolerated — the cap
    then falls back to serving their full body or the error stub)."""
    limit = debug_query_limit(query)

    def _render(lim):
        try:
            return render(lim)
        except TypeError:
            return render()

    return bounded_json(_render, limit, cap)


# the debug surface, one line per endpoint — served at GET /debug/ by
# both the health server and the apiserver (inflight-exempt like its
# peers) so an operator can discover the whole family from any one URL
DEBUG_ENDPOINTS = {
    "/debug/traces": (
        "flight-recorder cycle spans + postmortems as Chrome "
        "trace-event JSON (Perfetto-loadable; ?limit=N)"
    ),
    "/debug/decisions": (
        "recent decision-ledger entries: per-pod winners + dominant "
        "rejection reasons, trace-id cross-linked (?limit=N)"
    ),
    "/debug/cluster": (
        "telemetry time series: cluster analytics, HBM, compile facts, "
        "SLO burn rates (?limit=N)"
    ),
    "/debug/perf": (
        "performance observatory: host/device cycle split, phase x "
        "width EWMA matrix, transfer byte accounting, profiler status "
        "(?limit=N)"
    ),
    "/debug/profile": (
        "start a bounded on-demand jax.profiler capture "
        "(?seconds=N; throttled, no-op where unsupported)"
    ),
    "/debug/quality": (
        "placement-quality observatory: winner margins, feasible "
        "counts, FFD-counterfactual regret, packing-drift detectors "
        "(?limit=N)"
    ),
    "/debug/replicas": (
        "queue-sharded scheduler replicas: per-replica cycle/conflict "
        "facts, the sequenced reconciler's stats, and the per-namespace "
        "usage/quota table (?limit=N bounds the tenant table)"
    ),
    "/debug/capacity": (
        "capacity planner: class-compressed what-if binpack of the "
        "pending backlog — scale-up/scale-down recommendation, "
        "compression/absorption/overflow facts (?limit=N)"
    ),
    "/debug/autoscaler": (
        "guarded autoscaler actuation: managed fleet, hysteresis "
        "streaks, cooldown window, cost (node-seconds), recent "
        "actuation records (?limit=N)"
    ),
    "/debug/capacity/enact": (
        "POST: run one guarded actuation round NOW against the live "
        "capacity plan (?dryRun=1 decides + records without mutating)"
    ),
    "/debug/timeline": (
        "metrics timeline store: sampled series over every registered "
        "family + typed event annotations + anomaly firings "
        "(?series=a,b* ?window=S ?step=S ?limit=N)"
    ),
}


def debug_index() -> dict:
    """GET /debug/ body: every debug endpoint with a one-line
    description."""
    return {"endpoints": dict(DEBUG_ENDPOINTS)}


# --------------------------------------------------- shared debug routing
# ONE table drives BOTH servers (ISSUE 20 satellite): the health server
# and the apiserver used to hand-code parallel if/elif chains over the
# same endpoints, so a new endpoint could be exposed on one and
# forgotten on the other.  Every GET /debug/* now routes through
# debug_dispatch() and every debug POST through debug_post(); the
# renderer table's keys are asserted against DEBUG_ENDPOINTS at import,
# so an endpoint cannot be listed without a handler or vice versa.
# Renderer factories take (query, overrides) and return the
# `render(limit) -> jsonable` callable debug_body expects; imports stay
# lazy inside each factory (the servers must not drag every subsystem
# in at import).  `overrides` carries caller-injected seams — the
# health server's constructor-injected `traces` callable.

def _r_traces(query, overrides):
    traces = overrides.get("traces")
    if traces is None:
        from kubernetes_tpu.runtime import flightrecorder

        traces = flightrecorder.get_default().chrome_trace
    return traces


def _r_decisions(query, overrides):
    return lambda lim: {"decisions": get_default().decisions(lim)}


def _r_cluster(query, overrides):
    from kubernetes_tpu.runtime import telemetry

    return telemetry.get_default().debug_payload


def _r_perf(query, overrides):
    from kubernetes_tpu.runtime import perfobs

    return perfobs.get_default().debug_payload


def _r_profile(query, overrides):
    from kubernetes_tpu.runtime import perfobs

    return lambda _lim=None: perfobs.profile_request(query)


def _r_quality(query, overrides):
    from kubernetes_tpu.runtime import quality

    return quality.get_default().debug_payload


def _r_replicas(query, overrides):
    from kubernetes_tpu.runtime import reconciler

    return reconciler.debug_payload


def _r_capacity(query, overrides):
    from kubernetes_tpu.runtime import capacity

    return capacity.get_default().debug_payload


def _r_autoscaler(query, overrides):
    from kubernetes_tpu.runtime import autoscaler

    ctrl = autoscaler.get_default()
    if ctrl is None:
        # tolerates no wired controller (reports disabled) — unlike
        # the planner, actuation is commonly off
        return lambda _lim=None: {"enabled": False}
    return ctrl.debug_payload


def _r_enact_peek(query, overrides):
    # GET is a status peek — the actuation verb is POST (debug_post);
    # serving the peek keeps the /debug/ index walk uniform (every
    # listed endpoint GETs 200)
    from kubernetes_tpu.runtime import autoscaler

    ctrl = autoscaler.get_default()
    return lambda _lim=None: {
        "method": "POST",
        "hint": "POST runs one guarded round now; ?dryRun=1 decides "
                "+ records without mutating",
        "enabled": ctrl is not None,
        "last": (ctrl.summary().get("last")
                 if ctrl is not None else None),
    }


def _r_timeline(query, overrides):
    from kubernetes_tpu.runtime import timeline

    return lambda lim: timeline.get_default().debug_payload(
        limit=lim, query=query
    )


DEBUG_RENDERERS = {
    "/debug/traces": _r_traces,
    "/debug/decisions": _r_decisions,
    "/debug/cluster": _r_cluster,
    "/debug/perf": _r_perf,
    "/debug/profile": _r_profile,
    "/debug/quality": _r_quality,
    "/debug/replicas": _r_replicas,
    "/debug/capacity": _r_capacity,
    "/debug/autoscaler": _r_autoscaler,
    "/debug/capacity/enact": _r_enact_peek,
    "/debug/timeline": _r_timeline,
}

# the can't-forget guarantee: a path listed without a renderer (or
# rendered without a listing) fails at import, not in production
assert set(DEBUG_RENDERERS) == set(DEBUG_ENDPOINTS), (
    set(DEBUG_RENDERERS) ^ set(DEBUG_ENDPOINTS)
)


def debug_dispatch(path: str, query: str = "",
                   overrides: Optional[dict] = None) -> Optional[bytes]:
    """Route one GET /debug/* request through the shared table.
    Returns the JSON body bytes, or None when the path is not a debug
    endpoint (the caller 404s)."""
    if path in ("/debug", "/debug/"):
        return debug_body(lambda _lim=None: debug_index(), query)
    factory = DEBUG_RENDERERS.get(path)
    if factory is None:
        return None
    return debug_body(factory(query, overrides or {}), query)


def debug_post(path: str, query: str = ""
               ) -> Optional[Tuple[int, bytes]]:
    """Route one debug POST verb.  Returns (status, body) or None when
    the path has no POST handler (the caller falls through/404s).
    Currently one verb: /debug/capacity/enact — run ONE guarded
    actuation round NOW (same lock as the loop, so a manual enact
    can't interleave with a scheduled one; ?dryRun=1 decides +
    records without mutating the fleet)."""
    if path != "/debug/capacity/enact":
        return None
    from urllib.parse import parse_qs

    from kubernetes_tpu.runtime import autoscaler

    ctrl = autoscaler.get_default()
    if ctrl is None:
        return 409, json.dumps({"error": "no autoscaler wired"}).encode()
    q = parse_qs(query)
    dry = None
    if "dryRun" in q:
        dry = q["dryRun"][-1] not in ("0", "false", "")
    try:
        return 200, json.dumps(ctrl.enact(dry_run=dry)).encode()
    except Exception as e:  # noqa: BLE001 — the verb reports, never raises
        return 500, json.dumps({"error": str(e)}).encode()


# ------------------------------------------------------------- replay

def read_ledger_stream(path: str) -> Tuple[dict, Iterator[dict]]:
    """Ledger file -> (header meta, LAZY cycle-record iterator).  Each
    record: {meta..., "winners", "cluster" (reconstructed
    ClusterTensors), "batch", "ports", "nominated", "aff_state",
    "extra_mask", "extra_score", "last_index0"}.  Streaming matters:
    only the running delta-base snapshot stays alive, so replaying a
    full 4096-cycle ledger holds one record's tensors at a time instead
    of the whole file's."""
    from kubernetes_tpu.models.batched import (
        BatchPortState,
        LeanBatchAffinity,
        NominatedState,
    )
    from kubernetes_tpu.codec.schema import PodBatch

    f = open(path, "rb")

    def _next_block():
        head = f.read(_LEN.size)
        if not head:
            return None
        (n,) = _LEN.unpack(head)
        blob = f.read(n)
        if len(blob) != n:
            raise ValueError(f"truncated ledger block in {path}")
        z = np.load(io.BytesIO(blob), allow_pickle=False)
        return z, json.loads(bytes(z["__meta__"]).decode())

    first = _next_block()
    header: dict = {}
    pending = None
    if first is not None:
        z0, meta0 = first
        if meta0.get("kind") == "header":
            header = meta0
        else:
            pending = first

    def _records():
        nonlocal pending
        prev: Optional[ClusterTensors] = None
        try:
            while True:
                block = pending if pending is not None else _next_block()
                pending = None
                if block is None:
                    return
                z, meta = block
                if meta.get("kind") == "header":
                    continue
                delta: dict = {}
                for key in z.files:
                    if key.startswith("snap.full."):
                        delta[key[len("snap.full."):]] = ("full", z[key])
                    elif (
                        key.startswith("snap.rows.")
                        and key.endswith(".idx")
                    ):
                        name = key[len("snap.rows."):-len(".idx")]
                        delta[name] = (
                            "rows", z[key], z[f"snap.rows.{name}.val"]
                        )
                cluster = apply_snapshot_delta(
                    prev, delta, cls=ClusterTensors
                )
                prev = cluster
                present = meta.get("present", {})
                rec = dict(meta)
                rec["cluster"] = cluster
                rec["batch"] = _unpack_component(z, "batch", PodBatch)
                rec["ports"] = _unpack_component(
                    z, "ports", BatchPortState
                )
                rec["nominated"] = (
                    _unpack_component(z, "nom", NominatedState)
                    if present.get("nominated") else None
                )
                rec["aff_state"] = (
                    _unpack_component(z, "aff", LeanBatchAffinity)
                    if present.get("aff_state") else None
                )
                rec["extra_mask"] = (
                    z["extra_mask"] if present.get("extra_mask") else None
                )
                rec["extra_score"] = (
                    z["extra_score"] if present.get("extra_score")
                    else None
                )
                rec["winners"] = z["winners"]
                rec["quality"] = (
                    {
                        "top_nodes": z["quality_top_nodes"],
                        "top_scores": z["quality_top_scores"],
                        "feasible": z["quality_feasible"],
                    }
                    if present.get("quality_top_nodes") else None
                )
                yield rec
        finally:
            f.close()

    return header, _records()


def read_ledger(path: str) -> Tuple[dict, List[dict]]:
    """Eager twin of read_ledger_stream (tests / small ledgers)."""
    header, records = read_ledger_stream(path)
    return header, list(records)


def replay_record(fn, rec: dict) -> np.ndarray:
    """Re-execute one recorded cycle through engine `fn`; returns the
    replayed winners i32[n_pods] (truncated to the live batch)."""
    out = fn(
        rec["cluster"], rec["batch"], rec["ports"],
        np.int32(rec["last_index0"]), rec["nominated"],
        rec["extra_mask"], rec["extra_score"], rec["aff_state"],
    )
    hosts = np.asarray(out[0])
    return hosts[: int(rec["n_pods"])]


def replay(path: str, engine: Optional[str] = None,
           cluster_stats: bool = True) -> dict:
    """Replay every recorded cycle and compare winners bit-for-bit.
    Returns {"cycles", "pods", "mismatches", "bit_identical",
    "engine", "mismatch_detail"} plus — with `cluster_stats` (the
    default) — per-run utilization/fragmentation columns computed from
    each reconstructed snapshot via the bit-exact numpy analytics
    reference (ops/analytics.py): the packing-quality series the
    offline weight-tuning loop (ROADMAP item 4) scores candidate
    weights against."""
    header, records = read_ledger_stream(path)
    fns: Dict[str, Any] = {}

    def fn_for(rec: dict):
        # degraded cycles were served by the CPU reference engine, whose
        # commit/tie-rotation semantics are the SEQUENTIAL scan's — they
        # replay bit-identically through it whatever the header engine
        kind = engine or rec.get("engine") or header.get(
            "engine", "speculative"
        )
        if kind == "cpu":
            kind = "sequential"
        if kind not in fns:
            fns[kind] = build_replay_fn(header, engine=kind)
        return fns[kind]

    mismatches = 0
    pods = 0
    cycles = 0
    detail: List[dict] = []
    util_cpu: List[float] = []
    util_mem: List[float] = []
    frag: List[float] = []
    # offline quality recompute (ISSUE 13): margins + feasible counts
    # re-derived from the recorded top-k blocks — the same math the
    # live observatory runs, so the replayed figures are directly
    # comparable to the /debug/quality ones banked alongside
    q_margins: List[float] = []
    q_feasible: List[int] = []
    q_cycles = 0
    for rec in records:
        cycles += 1
        qual = rec.get("quality")
        if qual is not None:
            from kubernetes_tpu.runtime.quality import normalized_margin

            q_cycles += 1
            n = int(rec["n_pods"])
            tn = np.asarray(qual["top_nodes"])[:n]
            ts = np.asarray(qual["top_scores"])[:n]
            q_feasible.extend(
                int(f) for f in np.asarray(qual["feasible"])[:n]
            )
            if tn.shape[-1] >= 2:
                two = (tn[:, 0] >= 0) & (tn[:, 1] >= 0)
                if two.any():
                    # THE shared margin formula (runtime/quality.py) —
                    # offline figures stay bit-comparable to the live
                    # /debug/quality ones by construction
                    q_margins.extend(
                        normalized_margin(ts[two, 0], ts[two, 1]).tolist()
                    )
        if cluster_stats:
            from kubernetes_tpu.ops.analytics import cluster_analytics_np

            snap = rec["cluster"]
            a = cluster_analytics_np(
                snap.allocatable, snap.requested, snap.valid
            )
            u = np.asarray(a.utilization)
            util_cpu.append(float(u[0, 0]))
            util_mem.append(float(u[1, 0]))
            frag.append(float(np.asarray(a.fragmentation)))
        got = replay_record(fn_for(rec), rec)
        want = np.asarray(rec["winners"])[: int(rec["n_pods"])]
        pods += len(want)
        if not np.array_equal(got, want):
            mismatches += 1
            if len(detail) < 8:
                bad = np.flatnonzero(got != want)
                detail.append({
                    "cycle": rec.get("cycle"),
                    "pods": [int(i) for i in bad[:16]],
                    "want": [int(want[i]) for i in bad[:16]],
                    "got": [int(got[i]) for i in bad[:16]],
                })
    out = {
        "cycles": cycles,
        "pods": pods,
        "mismatches": mismatches,
        "bit_identical": mismatches == 0,
        "engine": engine or header.get("engine", "?"),
        "mismatch_detail": detail,
    }
    if cluster_stats and cycles:
        def _col(series: List[float]) -> dict:
            return {
                "first": round(series[0], 4),
                "last": round(series[-1], 4),
                "mean": round(sum(series) / len(series), 4),
            }

        out["cluster"] = {
            "utilization_cpu_mean": _col(util_cpu),
            "utilization_memory_mean": _col(util_mem),
            "fragmentation": _col(frag),
        }
    if q_cycles:
        out["quality"] = {
            "cycles_with_topk": q_cycles,
            "margin_p50": (
                round(float(np.percentile(np.asarray(q_margins), 50)), 6)
                if q_margins else 0.0
            ),
            "margin_mean": (
                round(float(np.mean(q_margins)), 6) if q_margins else 0.0
            ),
            "margins": len(q_margins),
            "feasible_p50": (
                round(float(np.percentile(np.asarray(q_feasible), 50)), 1)
                if q_feasible else 0.0
            ),
        }
    return out
