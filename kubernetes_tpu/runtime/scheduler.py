"""The scheduling service: the scheduleOne loop, batched.

Mirrors Scheduler.Run / scheduleOne (ref pkg/scheduler/scheduler.go:250-593)
with the one structural change that unlocks TPU throughput: instead of one
pod per cycle, each cycle drains a batch from the queue and places it with
the sequential-commit device program (models/batched.py) — semantically the
same as running scheduleOne B times against a continuously-updated cache,
but in a single XLA launch.

Per cycle:
  1. queue.pop_batch                      (NextPod, scheduler.go:438-447)
  2. cache.snapshot -> device tensors     (the snapshot seam, :176-179)
  3. sequential-commit schedule on device
  4. per pod: assume + bind via the binder callback (async),
     or add_unschedulable on failure     (:463-475, MakeDefaultErrorFunc)
"""

from __future__ import annotations

import dataclasses
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from kubernetes_tpu.api.types import Pod, PodDisruptionBudget
from kubernetes_tpu.codec.schema import FilterConfig
from kubernetes_tpu.models.batched import (
    batch_has_pod_affinity,
    encode_batch_affinity,
    encode_batch_ports,
    encode_nominated,
    encode_nominated_block,
    make_sequential_scheduler,
)
from kubernetes_tpu.models.preemption import (
    make_preempt_eval,
    pick_preemption_node,
    preemption_candidates,
    sorted_victim_slots,
    verify_nomination,
)
from kubernetes_tpu.codec.faults import (
    FAULT_PERSISTENT,
    CorruptedFetchError,
    classify_device_error,
)
from kubernetes_tpu.codec import faults as device_faults
from kubernetes_tpu.codec import transfer
from kubernetes_tpu.codec.transfer import AsyncFetch, fence_split, host_fetch
from kubernetes_tpu.ops.predicates import filter_batch, required_affinity_ok
from kubernetes_tpu.runtime.cache import SchedulerCache
from kubernetes_tpu.runtime.health import DeviceHealth
from kubernetes_tpu.runtime.events import (
    EVENT_TYPE_NORMAL,
    EVENT_TYPE_WARNING,
    EventRecorder,
)
from kubernetes_tpu.runtime.flightrecorder import RECORDER, FlightRecorder
from kubernetes_tpu.runtime.queue import (
    TIER_BULK,
    TIER_EXPRESS,
    PriorityQueue,
    classify_tier,
)
from kubernetes_tpu.utils import klog
from kubernetes_tpu.utils import metrics as m
from kubernetes_tpu.utils.compilecache import (
    install_metrics_listeners,
    program_loads,
)
from kubernetes_tpu.utils.trace import (
    WORKER_LANE,
    PhaseSpan,
    Span,
    current_trace_id,
    use_traceparent,
)

TAINT_NODE_UNSCHEDULABLE = "node.kubernetes.io/unschedulable"

# the phase_seconds keys that partition run_once's wall time: every
# second of a cycle lands in exactly one of them (tests/test_tracing.py
# pins the sum against the wall).  The other keys overlap them:
#   fetch         dispatch -> host copy landed, on the fetch worker
#   fence_queue   } host_stall split by what the fetch worker was doing:
#   fence_device  } not started on this job / result not ready / the
#   fence_copy    } copy landing and the checks after it (they sum to it)
#   program_load  backend compiles + persistent-cache retrievals
#   commit_state  } commit split: the state half (assume into the cache)
#   commit_tail   } and the bind tail (binds, events, requeues)
#   encode_affinity  the part of encode that builds a batch's affinity
#                 state: the in-batch cross-match factors and the
#                 encoder's per-pod term and pair columns
#   fence_redo    the part of host_stall spent on batches whose engine
#                 answer came from the exact redo
#   encode_pairs  the part of encode that spreads a batch's TP-wide pair
#                 leaves over its pods: each class row copied to the
#                 class's pods (dense), or the class rows and row index
#                 handed on as they are (factored)
PARTITION_PHASES = (
    "pop", "encode", "dispatch", "host_stall", "commit", "preempt",
    "observe",
)
OVERLAP_PHASES = (
    "fetch", "fence_queue", "fence_device", "fence_copy", "program_load",
    "commit_state", "commit_tail", "encode_affinity", "fence_redo",
    "encode_pairs",
)


@dataclass
class SchedulerConfig:
    batch_size: int = 256
    batch_window_s: float = 0.001
    # "speculative" (default) = parallel placement + conflict repair with
    # the HYBRID exactness fallback: contention sentinels (order
    # inversion, real bounce, unscheduled pod) trigger a sequential-scan
    # redo of the batch, so the scheduled/unschedulable split always
    # matches one-at-a-time semantics while uncontended batches keep the
    # one-launch fast path.  "sequential" = always the exact lax.scan.
    # Both engines carry in-batch affinity and nominated-pod state.
    engine: str = "speculative"
    percentage_of_nodes_to_score: int = 100  # TPU path scans all; knob for parity
    disable_preemption: bool = False
    # batched commit: apply a cycle's winners as ONE cache/encoder delta
    # under a single lock acquisition, with batched event/metric emission,
    # instead of the per-pod assume->bind loop.  State-equivalent to the
    # per-pod loop (pinned by tests/test_batched_commit.py); automatically
    # bypassed when a framework with plugins is attached (Reserve/Permit/
    # Prebind are per-pod extension points).
    batched_commit: bool = True
    # pipelined commit: overlap batch k's host bind/event/requeue tail with
    # batch k+1's device dispatch (double-buffered cycles).  Placement
    # correctness is preserved because the STATE half of the commit
    # (assume + encoder delta) still happens before batch k+1 encodes;
    # only the side-effect tail (binds, events, metrics, preemption) runs
    # while the device crunches the next batch.  Bind failures roll back
    # via the standard optimistic ForgetPod + requeue, exactly like the
    # reference's async bind goroutine (scheduler.go:523).
    pipeline_commit: bool = False
    # --- device-fault resilience (runtime/health.DeviceHealth +
    # cpuref/adapter.CpuEngineAdapter; faults classified by codec/faults) ---
    # transient retries of the SAME in-flight batch before giving up on it
    device_retry_max: int = 2
    # jittered exponential backoff between those retries (base * 2^attempt,
    # jitter-scaled, hard-capped at max so chaos tests stay sub-100ms)
    device_backoff_base_s: float = 0.005
    device_backoff_max_s: float = 0.05
    device_backoff_jitter: float = 0.5
    # consecutive classified failures that trip the breaker (a persistent
    # "device lost" trips immediately regardless)
    breaker_failure_threshold: int = 3
    # open -> half-open cool-down before a canary batch probes the device
    breaker_open_s: float = 0.05
    # graceful degradation: while the breaker is open, serve cycles from
    # the CPU reference engine instead of stalling/requeueing forever.
    # False = legacy behavior (device faults requeue the batch and raise).
    cpu_fallback: bool = True
    # --- overload protection & backpressure ---
    # bound the scheduling queue (runtime/queue.py PriorityQueue capacity):
    # at capacity a new arrival sheds the lowest-priority longest-
    # unschedulable pod (backoff pods are starvation-guarded) or is itself
    # rejected; None = unbounded (legacy).  Only applied to a queue THIS
    # scheduler constructs — a caller-owned queue keeps its own capacity.
    queue_capacity: Optional[int] = None
    # AIMD adaptive batch sizing: each cycle pops up to the CURRENT batch
    # size, which grows additively (+batch_size_min) toward batch_size
    # while active-queue depth exceeds it and halves (floored at
    # batch_size_min) when a cycle overruns cycle_deadline_s — sustained
    # pressure converts into bigger device launches instead of queue
    # growth, and latency overruns shed batch width first.
    adaptive_batch: bool = False
    batch_size_min: int = 16
    # per-cycle wall-clock budget driving the multiplicative decrease;
    # 0 = no deadline (depth alone steers the batch size)
    cycle_deadline_s: float = 0.0
    # --- tracing (utils/trace.py spans + runtime/flightrecorder.py) ---
    # a cycle whose root span exceeds this logs the full span breakdown
    # (the utiltrace 100ms convention, now configurable); <=0 disables
    # the slow-cycle log (spans still record to the flight recorder)
    trace_threshold_s: float = 0.1
    # --- latency tiers (ISSUE 6): the express lane ---
    # two-tier dispatch: pods classified express at queue admission
    # (annotation opt-in, or spec.priority >= express_priority_threshold)
    # schedule through a small pre-compiled batch shape that the run loop
    # serves BEFORE each bulk cycle, so a latency-sensitive pod never
    # waits out a 2048-wide bulk dispatch.  Both lanes share the cache,
    # snapshot, rotation counter, and the full resilience stack (retry/
    # breaker/CPU degradation/shed guards) — placements are bit-identical
    # to running the same pop order through one lane (pinned by test).
    express_lane: bool = False
    # express encode width (padded to pow2; also the per-cycle pop cap) —
    # small enough that an express cycle costs ~ms, large enough to
    # absorb an arrival burst without queueing a second cycle
    express_batch_size: int = 64
    # pods at or above this priority classify express without the
    # annotation (None = annotation opt-in only)
    express_priority_threshold: Optional[int] = None
    # --- raw-speed knobs (ISSUE 6) ---
    # persistent XLA compile cache directory (utils/compilecache.py):
    # restarts pay zero recompiles.  None/"" = leave the process default
    # (cmd/scheduler and bench enable it); the literal "off" disables
    compile_cache_dir: Optional[str] = None
    # pre-pay engine compiles at startup for every AIMD pow2 width plus
    # the express width (Scheduler.prewarm) instead of stalling the first
    # cycle at each new width mid-traffic
    prewarm_widths: bool = False
    # --- decision ledger + attribution (ISSUE 7) ---
    # per-plugin attribution: the engine launch ALSO emits per-pod
    # first-failing-predicate node counts and a top-k per-plugin score
    # breakdown (models/batched.py Attribution — a separate executable
    # behind a static flag; placements stay bit-identical).  Forces the
    # sequential engine (the scan owns the per-step state the attribution
    # is computed against); FailedScheduling events and the
    # kubernetes-tpu.io/unschedulable-reason annotation then name the
    # dominant failing predicate with per-reason node counts.
    attribution: bool = False
    # decision ledger (runtime/ledger.py): record every cycle's inputs
    # (snapshot delta, encoded batch, rotation base) and outcomes
    # (winners, engine, tier, faults) off the hot path, replayable via
    # Scheduler.replay_cycle / bench.py --replay.  ledger_dir=None keeps
    # the in-memory /debug/decisions ring without touching disk.
    decision_ledger: bool = False
    ledger_dir: Optional[str] = None
    # bounded append-only file: recording stops (and counts drops) after
    # this many cycles
    ledger_max_cycles: int = 4096
    # --- cluster + device telemetry (ISSUE 8: runtime/telemetry.py) ---
    # the telemetry hub: device-resident cluster analytics (utilization/
    # fragmentation/imbalance/occupancy percentiles from ops/analytics),
    # HBM + compile-cache + launch-EWMA runtime facts, and the
    # multi-window SLO burn-rate evaluator firing slo_burn postmortems.
    # Always-on by design (the <2%-of-cycle budget is pinned by
    # perf_smoke); False removes the hook entirely.
    telemetry: bool = True
    # analytics side-launch cadence: every Nth committed cycle dispatches
    # the fused snapshot reduction (the previous launch's tiny result is
    # materialized first, so the scheduling thread never blocks on it)
    telemetry_interval_cycles: int = 1
    # SLO objectives for the burn evaluator: list of dicts ({name,
    # objective, fastWindowSeconds, slowWindowSeconds, burnThreshold});
    # None = the defaults (cycle_deadline, goodput, degraded)
    slo_objectives: Optional[list] = None
    # liveness heartbeat: a once-per-interval one-line klog summary
    # (cycles, placed/unschedulable, depths, breaker, AIMD width, HBM
    # live) so a quiet log still proves the loop is alive; 0 = off
    heartbeat_s: float = 0.0
    # --- multi-chip sharding (ISSUE 9): the live control plane over a
    # device Mesh (parallel/mesh.py).  shard_devices splits the
    # snapshot's node axis across that many devices — every engine
    # launch, the incremental dirty-row upload, and the telemetry
    # analytics side-launch then run sharded, with only the argmax/
    # normalize reductions crossing shards (XLA-inserted ICI/DCN
    # collectives; placements are bit-identical to single-chip, pinned
    # by tests/test_sharded_live.py).  0 = today's single-chip path
    # bit-for-bit.  Must be a power of two (node axes pad to pow2).
    shard_devices: int = 0
    # mesh topology: None = a 1D node mesh over shard_devices; "OxI"
    # (e.g. "2x4") = a two-level dcn x ici mesh (hosts x chips-per-host)
    # with the node axis sharded over both axes flattened, so
    # cross-shard reductions lower hierarchically (intra-host partials
    # over ICI, per-host partials over DCN)
    mesh_shape: Optional[str] = None
    # --- elastic degradation ladder (ISSUE 10) ---
    # mesh shrink-on-failure: when a classified fault is ATTRIBUTED to
    # one mesh device (codec/faults.fault_device_index) and that shard's
    # breaker trips, rebuild the mesh over the widest pow2 of the
    # surviving devices instead of tripping the global breaker — the
    # ladder full mesh -> shrunken mesh -> single chip -> CPU adapter,
    # with the in-flight batch served bit-identically by the CPU engine
    # during the one-cycle transition and a half-open canary probing the
    # LOST device to restore the original mesh on recovery.  False =
    # the PR 3 behavior (any persistent fault demotes the whole mesh).
    mesh_shrink: bool = True
    # consecutive classified failures attributed to ONE shard that lose
    # that shard (a persistent shard fault loses it immediately); below
    # the global breaker_failure_threshold by default so a single sick
    # device is carved out before the whole mesh is condemned
    shard_breaker_failure_threshold: int = 2
    # online invariant checker (runtime/invariants.py): conservation
    # (every popped pod ends bound/requeued/shed exactly once), no
    # double-bind, committed usage <= allocatable — fed from the commit
    # seams, firing scheduler_invariant_violations_total{rule=} + a
    # flight-recorder postmortem on violation.  Always-on by design
    # (dict-ops per event); False removes the hooks entirely.
    invariant_checks: bool = True
    # --- performance observatory (ISSUE 11: runtime/perfobs.py) ---
    # on-demand jax.profiler capture directory for GET /debug/profile
    # (None = $KTPU_PROFILE_DIR or /tmp/ktpu_profile).  The observatory
    # itself — host/device cycle split, phase x width EWMA, transfer
    # accounting — is always-on by design (dict ops per cycle; the <2%
    # budget is pinned by perf_smoke alongside the span/telemetry pins)
    profile_dir: Optional[str] = None
    # --- placement-quality observatory (ISSUE 13: runtime/quality.py) ---
    # in-launch top-k width: every engine launch ALSO returns, per pod,
    # the K best feasible node rows (winner pinned at column 0), their
    # scores, and the feasible-candidate count — fetched at the same
    # commit fence as attribution (one extra D2H copy, no extra sync)
    # and folded into margin/feasible/regret/drift records served at
    # /debug/quality.  Always-on by design like telemetry/perfobs (the
    # <2%-of-cycle budget is pinned by perf_smoke); 0 disables the seam
    # entirely (the engines compile their classic executables).
    # Placements are bit-identical whatever the value (pinned by
    # tests/test_quality.py).
    quality_top_k: int = 3
    # regret-counterfactual cadence: every Nth committed cycle the
    # cycle's pod requests are FFD-binpacked into the pre-cycle free
    # capacity as a side launch (dispatched now, materialized next
    # interval — the telemetry amortization), yielding the
    # scheduler_placement_regret ratio
    quality_interval_cycles: int = 32
    # dual-window EWMA step-detector threshold for the packing-drift
    # alerts (relative deviation of the fast window from the slow one)
    quality_drift_threshold: float = 0.25
    # --- device-resident capacity planner (ISSUE 15: runtime/capacity.py) ---
    # what-if binpack of the pending+unschedulable backlog: every
    # capacityIntervalCycles the backlog is CLASS-COMPRESSED (distinct
    # request vector -> count) and packed — existing node headroom
    # first, the overflow over the node-shape catalog — as an amortized
    # side-launch behind the scheduling loop, emitting a scale-up/
    # scale-down recommendation at /debug/capacity + the
    # scheduler_capacity_* families.  Placements are bit-identical with
    # the planner on or off (purely observational).
    capacity_planner: bool = False
    capacity_interval_cycles: int = 256
    # candidate node shapes ([{name, cpu, memory, ephemeral-storage?,
    # pods?, <extended resources>...}]); None = the small built-in
    # default catalog (runtime/capacity.DEFAULT_SHAPE_CATALOG)
    node_shape_catalog: Optional[list] = None
    # --- metrics timeline store (ISSUE 20: runtime/timeline.py) ---
    # bounded in-process time-series: every registered metric family is
    # sampled once per timeline_interval_s (counters as per-interval
    # deltas, gauges as values, histograms as p50/p99), interleaved with
    # typed event annotations from the existing seams (breaker/shard
    # transitions, mesh rebuilds, AIMD resizes, sheds, degraded fetches,
    # invariant violations, autoscaler rounds, chaos windows) and run
    # through the online AnomalyDetector (threshold/zscore/slope rules,
    # edge-triggered, flight-recorder postmortems).  Served at
    # /debug/timeline; exported as JSONL + static HTML by bench
    # --timeline-out and the scenario engine.  False removes the
    # sampling hook entirely.
    timeline: bool = True
    # sampling cadence (wall seconds between samples; the hook rides the
    # commit tail + the idle heartbeat path, so a busy loop samples at
    # most once per interval and an idle loop still samples)
    timeline_interval_s: float = 1.0
    # points retained per series (ring buffer; also bounds events)
    timeline_retention: int = 512
    # anomaly rules ([{rule: threshold|zscore|slope, series, ...}]);
    # None = the conservative defaults (timeline.DEFAULT_RULES: degraded
    # cycles, invariant violations, pending-depth zscore)
    timeline_rules: Optional[list] = None
    # --- queue-sharded scheduler replicas (ISSUE 14) ---
    # horizontal scale-out inside one process: run this many Scheduler
    # replicas (threads) over ONE cache/queue, each popping a stable
    # hash-shard of the PriorityQueue and dispatching against the SAME
    # resident snapshot generation, with commits sequenced through the
    # optimistic conflict reconciler (runtime/reconciler.py).  1 = the
    # classic single-loop scheduler bit-for-bit.  Consumed by
    # SchedulerReplicaSet (runtime/replicas.py) / cmd --replicas; an
    # individual Scheduler instance reads its own replica identity from
    # the replica_id/replica_of constructor args instead.
    replicas: int = 1
    # per-namespace placement quotas ({namespace: {resource: quantity}}):
    # committed usage beyond a namespace's quota is vetoed by the
    # reconciler at commit (the pod parks unschedulable with backoff).
    # None = no quotas.  Rides the encoder's per-namespace usage/quota
    # columns; also the DRF tiebreak's fairness substrate.
    namespace_quotas: Optional[dict] = None
    # multi-scheduler: only pods whose spec.schedulerName names THIS
    # scheduler enter its queue (eventhandlers.go responsibleForPod)
    scheduler_name: str = "default-scheduler"
    weights: Optional[Sequence[float]] = None
    filter_config: FilterConfig = field(default_factory=FilterConfig)
    profile: Optional[object] = None  # config.SchedulingProfile; overrides
                                      # filter_config/weights when set

    @staticmethod
    def from_component_config(cc, interner=None) -> "SchedulerConfig":
        """Build from a KubeSchedulerConfiguration (config/types.py)."""
        profile = cc.build_profile(interner=interner)
        return SchedulerConfig(
            batch_size=cc.batch_size,
            batch_window_s=cc.batch_window_s,
            engine=cc.engine,
            percentage_of_nodes_to_score=cc.percentage_of_nodes_to_score,
            disable_preemption=cc.disable_preemption,
            scheduler_name=cc.scheduler_name,
            weights=profile.weights_array(),
            filter_config=profile.filter_config,
            profile=profile,
            batched_commit=getattr(cc, "batched_commit", True),
            pipeline_commit=getattr(cc, "pipeline_commit", False),
            device_retry_max=getattr(cc, "device_retry_max", 2),
            device_backoff_base_s=getattr(cc, "device_backoff_base_s", 0.005),
            device_backoff_max_s=getattr(cc, "device_backoff_max_s", 0.05),
            device_backoff_jitter=getattr(cc, "device_backoff_jitter", 0.5),
            breaker_failure_threshold=getattr(
                cc, "breaker_failure_threshold", 3
            ),
            breaker_open_s=getattr(cc, "breaker_open_s", 0.05),
            cpu_fallback=getattr(cc, "cpu_fallback", True),
            queue_capacity=getattr(cc, "queue_capacity", None),
            adaptive_batch=getattr(cc, "adaptive_batch", False),
            batch_size_min=getattr(cc, "batch_size_min", 16),
            cycle_deadline_s=getattr(cc, "cycle_deadline_s", 0.0),
            trace_threshold_s=getattr(cc, "trace_threshold_s", 0.1),
            express_lane=getattr(cc, "express_lane", False),
            express_batch_size=getattr(cc, "express_batch_size", 64),
            express_priority_threshold=getattr(
                cc, "express_priority_threshold", None
            ),
            compile_cache_dir=getattr(cc, "compile_cache_dir", None),
            prewarm_widths=getattr(cc, "prewarm_widths", False),
            attribution=getattr(cc, "attribution", False),
            decision_ledger=getattr(cc, "decision_ledger", False),
            ledger_dir=getattr(cc, "ledger_dir", None),
            ledger_max_cycles=getattr(cc, "ledger_max_cycles", 4096),
            telemetry=getattr(cc, "telemetry", True),
            telemetry_interval_cycles=getattr(
                cc, "telemetry_interval_cycles", 1
            ),
            slo_objectives=getattr(cc, "slo_objectives", None),
            heartbeat_s=getattr(cc, "heartbeat_s", 0.0),
            shard_devices=getattr(cc, "shard_devices", 0),
            mesh_shape=getattr(cc, "mesh_shape", None),
            mesh_shrink=getattr(cc, "mesh_shrink", True),
            shard_breaker_failure_threshold=getattr(
                cc, "shard_breaker_failure_threshold", 2
            ),
            invariant_checks=getattr(cc, "invariant_checks", True),
            profile_dir=getattr(cc, "profile_dir", None),
            quality_top_k=getattr(cc, "quality_top_k", 3),
            quality_interval_cycles=getattr(
                cc, "quality_interval_cycles", 32
            ),
            quality_drift_threshold=getattr(
                cc, "quality_drift_threshold", 0.25
            ),
            capacity_planner=getattr(cc, "capacity_planner", False),
            capacity_interval_cycles=getattr(
                cc, "capacity_interval_cycles", 256
            ),
            node_shape_catalog=getattr(cc, "node_shape_catalog", None),
            timeline=getattr(cc, "timeline", True),
            timeline_interval_s=getattr(cc, "timeline_interval_s", 1.0),
            timeline_retention=getattr(cc, "timeline_retention", 512),
            timeline_rules=getattr(cc, "timeline_rules", None),
            replicas=getattr(cc, "replicas", 1),
            namespace_quotas=getattr(cc, "namespace_quotas", None),
        )


def responsible_for(pod, scheduler) -> bool:
    """eventhandlers.go responsibleForPod: does this scheduler own the
    pod?  Shared by both event-wiring paths (runtime.cluster
    wire_scheduler and client.informer wire_scheduler_informers)."""
    my_name = getattr(getattr(scheduler, "config", None),
                      "scheduler_name", "default-scheduler")
    return (getattr(pod.spec, "scheduler_name", "default-scheduler")
            or "default-scheduler") == my_name


@dataclass
class ScheduleResult:
    pod: Pod
    node: Optional[str]          # None = unschedulable
    generation: int = 0


@dataclass
class _InFlight:
    """One dispatched-but-unfetched cycle: the double-buffer slot of the
    pipelined commit path.  `fetch` is the FETCH-IN-FLIGHT half: an
    AsyncFetch whose D2H copy was started the moment the winners buffer
    was dispatched (codec/transfer.py), materializing on a worker thread
    while the scheduling thread encodes/dispatches the next batch."""

    pods: List[Pod]
    hosts_dev: object            # device i32[B] winners buffer (None when
    #                              the cycle ran degraded on the CPU engine)
    fetch: object                # AsyncFetch (device) or _HostResult (CPU)
    generation: int
    cycle: int
    ext_failed: Dict[int, str]
    pc: object                   # shared PluginContext (framework cycles)
    t_cycle0: float
    trace: Span                  # the cycle's ROOT span (one trace id per
    #                              cycle, propagated to binds/extenders)
    # --- device-fault resilience ---
    # re-dispatch the SAME encoded batch (transient-retry path); None for
    # degraded cycles
    relaunch: Optional[Callable[[], Tuple[object, AsyncFetch]]] = None
    # compute this batch's winners on the CPU engine (degradation path);
    # returns a _HostResult
    cpu_fetch: Optional[Callable[[], "_HostResult"]] = None
    degraded: bool = False       # True once served by the CPU engine
    last_index0: int = 0         # selectHost rotation base for this batch
    tier: str = TIER_BULK        # latency tier this cycle serves: labels
    #                              the phase/e2e metrics and the span
    # --- attribution + decision ledger (ISSUE 7) ---
    attrib_dev: object = None    # device Attribution pytree (attribution
    #                              launches only; None when off/degraded)
    attrib: object = None        # host-materialized Attribution (set at
    #                              the commit fence)
    ledger_inputs: Optional[dict] = None  # the cycle's encode-time launch
    #                              inputs, stashed for the ledger record
    # --- telemetry (ISSUE 8) ---
    # host refs to the snapshot fields the analytics kernel reduces
    # (immutable by the encoder's cow contract): the fallback input when
    # the resident device buffers are unavailable (degraded cycles)
    telemetry_host: Optional[tuple] = None
    # the ENCODED batch width (batch.n_pods — the executable's padded
    # shape, NOT len(pods)): the launch-EWMA label, so the per-width
    # family tracks real executables instead of leaking a series per
    # raw pod count
    width: int = 0
    # --- performance observatory (ISSUE 11) ---
    # scheduling-thread seconds from encode start to the dispatch
    # returning (host_enqueue in the cost model)
    enqueue_s: float = 0.0
    # codec.transfer.transfer_totals() snapshot at encode time: the
    # commit tail diffs against it to get THIS cycle's wire traffic
    xfer0: Optional[dict] = None
    # --- placement-quality observatory (ISSUE 13) ---
    quality_dev: object = None   # device TopKQuality pytree (quality
    #                              launches only; None when off/degraded)
    quality: object = None       # host-materialized TopKQuality (set at
    #                              the commit fence, like attrib)
    # the speculative engine's (repair rounds, exact-redo flag) for this
    # launch, device scalars fetched at the fence (None for engines that
    # report neither, and for degraded cycles)
    engine_dev: Optional[tuple] = None
    # the encoded batch's request matrix (host ref) — the regret
    # counterfactual's pod-side input
    quality_reqs: object = None
    # i32[len(pods)] pod class per batch index, from encode_pods'
    # classes_out (-1 = no static key): the state commit derives each
    # class's encoder inputs once (encoder.add_pods)
    classes: Optional[np.ndarray] = None
    # the snapshot refs the regret counterfactual packs into: THIS
    # cycle's pre-dispatch state
    quality_snapshot: Optional[tuple] = None
    # --- device-resident capacity planner (ISSUE 15) ---
    # the cycle's host (allocatable, requested, valid) refs for the
    # capacity solve (immutable by the encoder's cow contract) — kept
    # separate from telemetry_host/quality_snapshot so the planner
    # works whatever combination of observatories is enabled
    capacity_snapshot: Optional[tuple] = None
    # --- queue-sharded replicas (ISSUE 14) ---
    # the encoded batch's request matrix (host ref) when a conflict
    # reconciler is attached: the admission scan's pod-side input
    reqs: object = None
    # commit sequence number stamped by the reconciler (the "sequenced
    # winner" order; rides the ledger block for cross-replica audit)
    commit_seq: int = -1


class _HostResult:
    """AsyncFetch-shaped handle for an already-materialized winners
    buffer (the degraded CPU-engine path): never faults."""

    def __init__(self, hosts: np.ndarray, seconds: float = 0.0):
        self._hosts = hosts
        self.seconds = seconds

    def ready(self) -> bool:
        return True

    def result(self) -> np.ndarray:
        return self._hosts


@dataclass
class _Staged:
    """A fetched cycle whose cache-STATE half (batched assume) has been
    applied; the side-effect tail (binds/events/metrics/preemption) is
    still pending and may overlap the next batch's device dispatch."""

    inf: _InFlight
    hosts: np.ndarray
    algo_dt: float
    batched: bool
    t_state0: float = 0.0
    state_seconds: float = 0.0
    # (batch index, pod, assumed copy, node name) per device winner
    winners: List[Tuple] = field(default_factory=list)
    fit_idx: List[int] = field(default_factory=list)
    # residual host wait at the ready fence (host_stall in the perf
    # observatory's cost model and in phase_seconds)
    stall_s: float = 0.0
    # THIS cycle's wire traffic (codec.transfer.transfer_delta vs the
    # encode-time watermark), taken at the commit fence — under
    # pipeline_commit the tail runs AFTER the next cycle's dispatch, so
    # computing the delta there would double-count the next cycle's
    # uploads into this cycle's span
    xfer_delta: Optional[dict] = None
    # (batch index, pod) losers of the optimistic cross-replica race
    # (ISSUE 14): their node headroom was spent by a sequenced-earlier
    # commit — the tail readds them to the owner shard (shed-exempt)
    race_lost: List[Tuple] = field(default_factory=list)
    # (batch index, pod) vetoed by a namespace quota: parked
    # unschedulable with backoff (spinning on a full quota helps nobody)
    quota_lost: List[Tuple] = field(default_factory=list)


class Scheduler:
    """Binder: callable (pod, node_name) -> bool (the POST .../binding analog,
    scheduler.go:411-435).  A False/raising binder triggers ForgetPod + requeue."""

    def __init__(
        self,
        cache: Optional[SchedulerCache] = None,
        queue: Optional[PriorityQueue] = None,
        binder: Optional[Callable[[Pod, str], bool]] = None,
        config: Optional[SchedulerConfig] = None,
        victim_deleter: Optional[Callable[[Pod], None]] = None,
        pdb_lister: Optional[Callable[[], List[PodDisruptionBudget]]] = None,
        framework=None,  # framework.v1alpha1.Framework; None = no plugins
        recorder: Optional[EventRecorder] = None,
        extenders: Optional[Sequence] = None,  # extender.client.HTTPExtender
        flight_recorder: Optional[FlightRecorder] = None,  # None = the
        #                       process-wide ring (flightrecorder.RECORDER)
        ledger=None,  # runtime/ledger.DecisionLedger; None = built from
        #               config.decision_ledger (and installed as the
        #               process default serving /debug/decisions)
        # --- queue-sharded replicas (ISSUE 14, runtime/replicas.py) ---
        replica_id: int = 0,     # this instance's replica index (= its
        #                          stable queue hash-shard)
        replica_of: int = 1,     # total replicas sharing the queue; 1 =
        #                          the classic single-loop scheduler
        reconciler=None,         # shared runtime/reconciler
        #                          .ConflictReconciler sequencing commits
        snapshot_hub=None,       # shared runtime/reconciler.SnapshotHub
        #                          (THE resident device snapshot; None =
        #                          this instance owns its own cache)
        share_engines_with=None,  # a sibling Scheduler whose compiled
        #                          engines/preempt-eval this one reuses
        #                          (replicas share executables — N
        #                          replicas must not pay N compiles)
    ):
        # NB: PriorityQueue defines __len__, so `queue or PriorityQueue()`
        # would silently replace an *empty* caller-owned queue
        self.cache = cache if cache is not None else SchedulerCache()
        self.config = config if config is not None else SchedulerConfig()
        self.queue = (
            queue if queue is not None
            else PriorityQueue(capacity=self.config.queue_capacity)
        )
        # shed audit trail: a bounded queue dropping a pod is operator-
        # visible (the FailedScheduling analog for overload); attach only
        # where no other owner wired one
        if getattr(self.queue, "on_shed", "n/a") is None:
            self.queue.on_shed = self._on_shed
        # latency-tier classifier (ISSUE 6): express_lane routes opted-in/
        # high-priority pods to the queue's express heap at admission.
        # Attach only where no other owner wired one (a caller-owned queue
        # keeps its own policy, exactly like on_shed/capacity).
        if (
            self.config.express_lane
            and getattr(self.queue, "tier_of", "n/a") is None
        ):
            self.queue.tier_of = self._tier_of
        # online invariant checker (ISSUE 10, runtime/invariants.py):
        # conservation of popped pods, no double-bind, capacity — fed
        # from the pop/bind/requeue/shed seams below.  The queue's
        # on_requeue observer funnels EVERY requeue path (unschedulable
        # verdicts, bind rollbacks, gang surplus readds, batch-loss
        # guards) through one hook.  The conservation/double-bind rules
        # are only SOUND when that seam is observable: a requeue the
        # checker never hears makes the next pop (or re-bind) read as a
        # false violation.  A caller-owned observer is chained; a
        # duck-typed queue without the hook disables the checker (with a
        # log line) rather than crying wolf on a healthy control plane.
        self.invariants = None
        if self.config.invariant_checks:
            if hasattr(self.queue, "on_requeue"):
                from kubernetes_tpu.runtime.invariants import InvariantChecker

                self.invariants = InvariantChecker(
                    on_violation=self._on_invariant_violation
                )
                prior = self.queue.on_requeue
                if prior is None:
                    self.queue.on_requeue = self.invariants.note_requeued
                else:
                    note = self.invariants.note_requeued

                    def _chained_requeue(pod, _prior=prior, _note=note):
                        _prior(pod)
                        _note(pod)

                    self.queue.on_requeue = _chained_requeue
            else:
                klog.infof(
                    "invariant checker disabled: queue %s has no "
                    "on_requeue seam to observe",
                    type(self.queue).__name__,
                )
        self.binder = binder if binder is not None else (lambda pod, node: True)
        # --- queue-sharded replicas (ISSUE 14) ---
        # replica identity (= the stable queue hash-shard this loop
        # drains), the shared sequenced reconciler, and the shared
        # snapshot hub.  replica_of == 1 with no hub/reconciler is the
        # classic single-loop scheduler bit-for-bit.
        self._replica_id = int(replica_id)
        self._replica_of = max(1, int(replica_of))
        self._reconciler = reconciler
        self._hub = snapshot_hub
        self.conflicts_total = 0        # race losers this replica requeued
        self.race_requeued_total = 0
        self.quota_vetoed_total = 0
        if self._reconciler is not None and not self.config.batched_commit:
            raise ValueError(
                "replica mode requires batched_commit: the conflict "
                "reconciler admits a cycle's winners as one sequenced "
                "critical section"
            )
        enc = self.cache.encoder
        prof = self.config.profile
        if prof is not None:
            self.config.filter_config = prof.filter_config
            self.config.weights = prof.weights_array()
        enc.hard_pod_affinity_weight = self.config.filter_config.hard_pod_affinity_weight
        self.config.filter_config = enc.adopt_filter_config(
            self.config.filter_config
        )
        self._unsched_key = enc.interner.intern(TAINT_NODE_UNSCHEDULABLE)
        # placement-quality top-k width (ISSUE 13): a STATIC output-only
        # engine flag — both engines return the winner-pinned top-k +
        # feasible counts alongside the winners, placements
        # bit-identical flag-on/off
        self._quality_k = max(0, int(self.config.quality_top_k))
        engine_kw = dict(
            cfg=self.config.filter_config,
            weights=self.config.weights,
            unsched_taint_key=self._unsched_key,
            zone_key_id=enc.getzone_key,
            score_cfg=prof.score_config if prof is not None else None,
            percentage_of_nodes_to_score=self.config.percentage_of_nodes_to_score,
            quality_topk=self._quality_k,
        )
        # attribution rides the sequential engine: the scan owns the
        # per-step state (resources/ports/affinity as committed so far)
        # the first-failure attribution is computed against.  The flag
        # itself is output-only (sequential winners are bit-identical
        # with it on or off, pinned by test); note that selecting the
        # sequential engine is itself semantics-preserving but can
        # rotate argmax TIES differently than the speculative engine.
        # replica siblings REUSE the first replica's compiled engines
        # (jitted callables are pure + thread-safe; N replicas paying N
        # identical XLA compiles would dwarf the scale-out win)
        self._shared_engines = share_engines_with is not None
        if self._shared_engines:
            self._schedule_fn = share_engines_with._schedule_fn
            self._preempt_eval = share_engines_with._preempt_eval
        else:
            self._schedule_fn = make_sequential_scheduler(
                **engine_kw, attribution=self.config.attribution
            )
            self._preempt_eval = make_preempt_eval(
                self.config.filter_config, self._unsched_key
            )
        # multi-chip sharding (config.shard_devices/mesh_shape): build the
        # node-axis Mesh ONCE at startup; every snapshot upload and engine
        # launch then carries NamedShardings and XLA inserts the
        # cross-shard collectives (no hand-written comms — the
        # parallel/mesh.py recipe, promoted from the bench-only harness)
        self.mesh = None
        mesh_spec_axis = None
        if self.config.shard_devices or self.config.mesh_shape:
            from kubernetes_tpu.parallel.mesh import build_mesh

            self.mesh, mesh_spec_axis = build_mesh(
                self.config.shard_devices or None, self.config.mesh_shape
            )
            # floor the node arena at the mesh size NOW: otherwise a
            # small fleet's arena (e.g. 64 rows under a 128-device mesh)
            # fails the divisibility check inside the fault-classified
            # dispatch path, where a static config error would read as a
            # device fault and flap the breaker into permanent CPU
            # degradation instead of failing at startup
            self.cache.encoder.ensure_node_capacity(self.mesh.size)
        # elastic degradation ladder (ISSUE 10): the STARTUP mesh is the
        # ladder's top rung — shrinks rebuild from it minus the lost
        # shards, the climb-back restores it whole.  ShardHealth is the
        # per-device breaker bank the fault attribution feeds.
        self._full_mesh = self.mesh
        self._full_spec_axis = mesh_spec_axis
        self._mesh_spec_axis = mesh_spec_axis
        self.shard_health = None
        if self.mesh is not None:
            from kubernetes_tpu.parallel.mesh import mesh_device_ids
            from kubernetes_tpu.runtime.health import ShardHealth

            self._mesh_ids = mesh_device_ids(self.mesh)
            self.shard_health = ShardHealth(
                device_ids=sorted(self._mesh_ids),
                failure_threshold=(
                    self.config.shard_breaker_failure_threshold
                ),
                open_duration_s=self.config.breaker_open_s,
                on_transition=self._on_shard_transition,
            )
        else:
            self._mesh_ids = None
        # incremental host->device snapshot upload: unchanged fields reuse
        # their resident device buffers between cycles (codec/transfer.py);
        # with a mesh, every node-axis field stays sharded across it and
        # dirty-row deltas scatter to the owning shard
        from kubernetes_tpu.codec.transfer import DeviceSnapshotCache

        self._dev_snapshot = DeviceSnapshotCache(
            mesh=self.mesh, spec_axis=mesh_spec_axis
        )
        m.MESH_WIDTH.set(float(self.mesh.size if self.mesh is not None else 0))
        if self._shared_engines:
            self._speculative_fn = share_engines_with._speculative_fn
        elif (
            self.config.engine == "speculative"
            and not self.config.attribution
        ):
            from kubernetes_tpu.models.speculative import (
                make_speculative_scheduler,
            )

            self._speculative_fn = make_speculative_scheduler(**engine_kw)
        else:
            self._speculative_fn = None
        # the engine that ACTUALLY serves device cycles (attribution
        # forces sequential whatever config.engine says): spans, ledger
        # records, and the replay header must all agree on this
        self._engine_kind = (
            "sequential" if self._speculative_fn is None else "speculative"
        )
        self.framework = framework
        # scheduler-side extender chain (core/extender.go; chained in config
        # order at generic_scheduler.go:527-554); built from the Policy's
        # "extenders" entries when not injected directly
        if extenders is None and prof is not None and prof.extender_configs:
            from kubernetes_tpu.extender.client import HTTPExtender

            extenders = [HTTPExtender(c) for c in prof.extender_configs]
        self.extenders = list(extenders or [])
        # "Scheduled"/"FailedScheduling"/"Preempted" audit trail
        # (tools/record; scheduler.go:268,433,325); wire_scheduler replaces a
        # defaulted recorder with the cluster's shared one
        self._recorder_defaulted = recorder is None
        self.recorder = recorder if recorder is not None else EventRecorder()
        # PodPreemptor.DeletePod analog (scheduler.go:319-326); default
        # removes the victim straight from the cache
        self._victim_deleter_defaulted = victim_deleter is None
        self.victim_deleter = victim_deleter or (lambda pod: self.cache.remove_pod(pod))
        self._pdb_defaulted = pdb_lister is None
        self.pdb_lister = pdb_lister or (lambda: [])
        self._last_index = 0
        # AIMD adaptive batch sizing (config.adaptive_batch): the CURRENT
        # cycle width, starting at the baseline batch_size_min and steered
        # by _adapt_batch after every non-empty cycle
        self._cur_batch = (
            max(1, self.config.batch_size_min)
            if self.config.adaptive_batch
            else self.config.batch_size
        )
        self._stop = threading.Event()
        # device-fault resilience: classified retry/backoff + circuit
        # breaker (runtime/health.py) + CPU-engine degradation
        # (cpuref/adapter.py, built lazily on first degraded cycle)
        self.device_health = DeviceHealth(
            failure_threshold=self.config.breaker_failure_threshold,
            open_duration_s=self.config.breaker_open_s,
            backoff_base_s=self.config.device_backoff_base_s,
            backoff_max_s=self.config.device_backoff_max_s,
            backoff_jitter=self.config.device_backoff_jitter,
            on_transition=self._on_breaker_transition,
        )
        self._cpu_engine = None
        # double-buffer slot for pipeline_commit: at most one dispatched
        # batch whose host tail has not run yet
        self._in_flight: Optional[_InFlight] = None
        # the most recently dispatched cycle's root span: what a
        # postmortem attaches as in_flight when an anomaly fires before
        # that cycle retires into the flight-recorder ring
        self._cur_span: Optional[Span] = None
        # latency tier of the most recently dispatched cycle — joins the
        # in-flight span in postmortem snapshots (an express-cycle anomaly
        # reads differently from a bulk one)
        self._cur_tier: str = TIER_BULK
        # per-phase seconds, cumulative (bench reporting, the
        # benchmark's per-layer metrics).  PARTITION_PHASES split
        # run_once's wall time: pop (queue drain, including its wait),
        # encode (host tensors + snapshot + extender fan-out), dispatch
        # (async enqueue), host_stall (the scheduling thread's wait at
        # the ready fence — the perf observatory's name), commit
        # (assume + bind + events + requeues), preempt, observe (the
        # post-bind hooks: flight recorder, ledger, telemetry, perfobs,
        # quality, capacity, timeline, slow-cycle log, and run_once's
        # heartbeat + idle timeline tick).  OVERLAP_PHASES are subsets
        # or overlaps of those and never add into a sum of them.
        self.phase_seconds: Dict[str, float] = {
            k: 0.0 for k in PARTITION_PHASES + OVERLAP_PHASES
        }
        # program loads (utils/compilecache.py), folded per cycle: the
        # listeners are installed here so they are live whoever set up
        # the cache, and the watermark is reset at every run_once
        install_metrics_listeners()
        self._loads_seen = program_loads()
        # always-on cycle-span ring + anomaly postmortems (ISSUE 5); the
        # default is the process-wide recorder served at /debug/traces
        self.flight_recorder = (
            flight_recorder if flight_recorder is not None else RECORDER
        )
        # decision ledger (ISSUE 7): opt-in per-cycle record + the
        # /debug/decisions ring.  A config-built ledger installs itself
        # as the process default (the RECORDER pattern) so the debug
        # endpoints serve it without extra wiring.
        self.ledger = ledger
        if self.ledger is None and self.config.decision_ledger:
            import os

            from kubernetes_tpu.runtime import ledger as ledger_mod

            path = None
            if self.config.ledger_dir:
                os.makedirs(self.config.ledger_dir, exist_ok=True)
                path = os.path.join(
                    self.config.ledger_dir, "decisions.ledger"
                )
            self.ledger = ledger_mod.DecisionLedger(
                path=path, max_cycles=self.config.ledger_max_cycles
            )
            ledger_mod.set_default(self.ledger, replica=self._replica_id)
        if self.ledger is not None:
            self.ledger.ensure_meta(self._engine_meta())
        # cluster + device telemetry (ISSUE 8): analytics side-launches,
        # HBM/compile/launch-EWMA runtime facts, SLO burn-rate alerting.
        # A config-built hub installs itself as the process default (the
        # RECORDER pattern) so /debug/cluster serves it unwired.
        self.telemetry = None
        if self.config.telemetry:
            from kubernetes_tpu.runtime import telemetry as telemetry_mod

            self.telemetry = telemetry_mod.TelemetryHub(
                interval_cycles=self.config.telemetry_interval_cycles,
                objectives=telemetry_mod.build_objectives(
                    self.config.slo_objectives
                ),
                postmortem=self._postmortem,
            )
            telemetry_mod.set_default(self.telemetry, replica=self._replica_id)
        # performance observatory (ISSUE 11, runtime/perfobs.py):
        # host/device time attribution per cycle, the phase x width
        # EWMA cost matrix, per-cycle transfer deltas, and the
        # on-demand profiler capture — always-on (dict ops per cycle;
        # the <2% budget is pinned by perf_smoke), installed as the
        # process default so /debug/perf serves it unwired
        from kubernetes_tpu.runtime import perfobs as perfobs_mod

        self.perfobs = perfobs_mod.PerfObservatory(
            profile_dir=self.config.profile_dir
        )
        perfobs_mod.set_default(self.perfobs, replica=self._replica_id)
        # placement-quality observatory (ISSUE 13, runtime/quality.py):
        # per-decision margin/feasible records off the engines' in-launch
        # top-k, amortized FFD-counterfactual regret, dual-window
        # packing-drift alerts through the postmortem seam — always-on
        # like telemetry/perfobs (<2% budget pinned by perf_smoke),
        # installed as the process default so /debug/quality serves it
        self.quality = None
        if self._quality_k > 0:
            from kubernetes_tpu.runtime import quality as quality_mod

            self.quality = quality_mod.QualityObservatory(
                top_k=self._quality_k,
                interval_cycles=self.config.quality_interval_cycles,
                postmortem=self._postmortem,
                drift_threshold=self.config.quality_drift_threshold,
            )
            quality_mod.set_default(self.quality, replica=self._replica_id)
        # device-resident capacity planner (ISSUE 15, runtime/capacity.py):
        # every capacityIntervalCycles the pending+unschedulable backlog
        # is class-compressed and what-if binpacked — existing headroom
        # first, overflow over the node-shape catalog — as an amortized
        # side-launch behind the loop (the telemetry discipline; the <2%
        # budget pinned by perf_smoke), emitting a scale-up/scale-down
        # recommendation at /debug/capacity.  Placements are
        # bit-identical planner on/off (purely observational; pinned by
        # tests/test_capacity.py).  The mesh is read through a getter at
        # dispatch time so the elastic ladder's shrinks/rebuilds are
        # always honored.
        self.capacity = None
        if self.config.capacity_planner:
            from kubernetes_tpu.runtime import capacity as capacity_mod

            self.capacity = capacity_mod.CapacityPlanner(
                catalog=self.config.node_shape_catalog,
                interval_cycles=self.config.capacity_interval_cycles,
                mesh=lambda: self.mesh,
            )
            capacity_mod.set_default(
                self.capacity, replica=self._replica_id
            )
        # metrics timeline store (ISSUE 20, runtime/timeline.py): every
        # registered metric family sampled once per timelineInterval
        # (counters as deltas, gauges as values, histograms as p50/p99)
        # into a bounded ring, interleaved with typed event annotations
        # from the breaker/shard/mesh/AIMD/shed/invariant seams, and run
        # through the online anomaly detector (edge-triggered rules ->
        # scheduler_timeline_anomalies_total + a flight-recorder
        # postmortem).  The hook rides the commit tail AND the idle
        # heartbeat path so quiet loops keep sampling; the <2% budget is
        # pinned by perf_smoke.  Installed as the process default so
        # /debug/timeline serves it unwired.
        self.timeline = None
        if self.config.timeline:
            from kubernetes_tpu.runtime import timeline as timeline_mod

            self.timeline = timeline_mod.TimelineStore(
                interval_s=self.config.timeline_interval_s,
                retention=self.config.timeline_retention,
                detector=timeline_mod.AnomalyDetector(
                    rules=self.config.timeline_rules,
                    postmortem=self._postmortem,
                ),
            )
            timeline_mod.set_default(self.timeline, replica=self._replica_id)
        # shed watermark (per-cycle deltas feed the goodput SLO) +
        # heartbeat clock + liveness totals (heartbeat line + bench)
        self._shed_seen = 0
        self._last_heartbeat = time.monotonic()
        self._outcome_totals = {"placed": 0, "unschedulable": 0}
        self.results: List[ScheduleResult] = []
        # (preemptor key, node name, victim keys) per successful preemption
        self.preemptions: List[Tuple[Tuple[str, str], str, List[Tuple[str, str]]]] = []
        # per-namespace placement quotas (ISSUE 14): seed the encoder's
        # quota columns before the first commit can consult them
        for ns, q in (self.config.namespace_quotas or {}).items():
            enc.set_namespace_quota(ns, q)
        # replica registry (ISSUE 14): GET /debug/replicas rolls every
        # registered scheduler into the process aggregate — the explicit
        # cross-replica roll-up next to the per-replica default installs
        from kubernetes_tpu.runtime import reconciler as reconciler_mod

        reconciler_mod.register_scheduler(self)
        m.REPLICAS.set(float(self._replica_of))

    def attach_hub(self, hub) -> None:
        """Late-bind the shared SnapshotHub (the ReplicaSet builds the
        hub FROM replica 0's DeviceSnapshotCache, then attaches it).
        Only valid before any cycle dispatched."""
        self._hub = hub

    def _engine_meta(self) -> dict:
        """The ledger header: everything a fresh process needs to rebuild
        a bit-identical engine for replay (runtime/ledger.build_replay_fn)."""
        from kubernetes_tpu.runtime.ledger import engine_meta

        prof = self.config.profile
        return engine_meta(
            self.config.filter_config,
            self.config.weights,
            self._unsched_key,
            self.cache.encoder.getzone_key,
            prof.score_config if prof is not None else None,
            self.config.percentage_of_nodes_to_score,
            self._engine_kind,
        )

    # ------------------------------------------------------------- one cycle

    def schedule_cycle(self, pods: Sequence[Pod],
                       tier: str = TIER_BULK) -> List[ScheduleResult]:
        """Place a batch of pods against the current cache state; assume+bind
        winners, requeue losers.  Returns per-pod results.

        Internally split into encode/dispatch -> state-commit -> tail so
        the pipelined run loop (config.pipeline_commit) can overlap batch
        k's tail with batch k+1's device dispatch; called directly it is
        strictly synchronous (any in-flight pipelined batch is drained
        first so cycles never interleave).  `tier` labels the cycle's
        metrics/span and — for TIER_EXPRESS — pins the express encode
        width; placement semantics are tier-independent."""
        self.flush_pipeline()
        try:
            # climb-back check between cycles (cheap no-op while no shard
            # is lost): runs with the pipeline drained so a mesh swap
            # never races an in-flight batch.  INSIDE the batch-loss
            # guard: an unclassified probe error (a real runtime's
            # device_put can raise anything) must requeue the
            # already-popped batch, not drop it
            self._maybe_probe_shards()
            inf = self._encode_and_dispatch(pods, tier=tier)
        except BaseException:
            # popped pods must never be lost: a fault that escaped the
            # classified-retry/degrade machinery (or a plain bug) still
            # leaves the batch schedulable later
            self.queue.add_unschedulable_batch(
                list(pods), self.queue.scheduling_cycle
            )
            raise
        if inf is None:
            return []
        return self._commit_tail(self._commit_state_or_requeue(inf))

    def _commit_state_or_requeue(self, inf: _InFlight) -> _Staged:
        """The resilient fence with the batch-loss guard: classified
        device faults retry/degrade inside _commit_state_resilient; if
        even that fails (unclassified error, or cpu_fallback disabled),
        the batch's pods — already popped from the queue — are requeued
        ALL (plain error requeue, the extender-error discipline) before
        propagating, so a device fault degrades to a retry instead of the
        batch staying Pending forever."""
        try:
            return self._commit_state_resilient(inf)
        except BaseException as e:
            self.queue.add_unschedulable_batch(inf.pods, inf.cycle)
            # the failing cycle's span retires into the ring FIRST so the
            # postmortem snapshot below contains it
            inf.trace.annotate(error=f"{type(e).__name__}: {e}")
            inf.trace.finish()
            self.flight_recorder.record(inf.trace)
            if classify_device_error(e) is None:
                # an error that escaped the classified machinery is by
                # definition the case nobody predicted: snapshot the ring
                self._postmortem(
                    "unclassified_error", f"{type(e).__name__}: {e}"
                )
            raise

    # -------------------------------------------------- tracing/postmortems

    def _phase(self, name: str, dt: float, tier: str = TIER_BULK) -> None:
        """One accumulation point for per-phase seconds: the driver-
        visible phase_seconds dict (bench reporting, tiers aggregated)
        AND the tier-labeled /metrics counter family move together.
        Timed phases reach it through _phase_span; the derived keys
        (fetch, fence_*, program_load) directly."""
        self.phase_seconds[name] += dt
        m.CYCLE_PHASE_SECONDS.inc(dt, phase=name, tier=tier)

    def _phase_span(self, span: Optional[Span], name: str,
                    tier: str = TIER_BULK, child: Optional[str] = None,
                    part: Optional[str] = None, **attrs) -> PhaseSpan:
        """THE phase primitive (utils/trace.PhaseSpan): phase counter,
        cycle-span child (named `child`, default the phase) and, while
        a profiler session is active, a `ktpu.<name>` annotation.  A
        `part` key (commit_state, commit_tail) records the same seconds
        as a sub-key of the phase, outside the partition."""
        record = self._phase
        if part is not None:
            def record(phase, dt, tier_):
                self._phase(phase, dt, tier_)
                self._phase(part, dt, tier_)
        return PhaseSpan(record, name, tier, span, child, **attrs)

    def _fence_split(self, fetch, fence: PhaseSpan, tier: str) -> None:
        """Split one fence wait (fence.t0..t1, already in host_stall) by
        what the fetch worker was doing, and hang the worker's own
        stamps on the cycle span: device_wait (job start -> result
        ready) and d2h (ready -> copy landed)."""
        q, d, c = fence_split(fetch, fence.t0, fence.t1)
        self._phase("fence_queue", q, tier)
        self._phase("fence_device", d, tier)
        self._phase("fence_copy", c, tier)
        t_start = getattr(fetch, "t_start", None)
        if t_start is not None and fence.parent is not None:
            fence.parent.add_child(
                "device_wait", t_start, fetch.t_ready, lane=WORKER_LANE
            )
            fence.parent.add_child(
                "d2h", fetch.t_ready, fetch.t_done, lane=WORKER_LANE
            )

    def _engine_outcome(self, inf: _InFlight,
                        fence: PhaseSpan) -> bool:
        """Count the speculative engine's repair rounds and which path
        answered the batch (the exact redo or the speculative rounds),
        and put both on the cycle's fence span.  Returns whether the
        redo answered; False where the engine reports neither."""
        if inf.engine_dev is None:
            return False
        rounds = int(np.asarray(inf.engine_dev[0]))
        redo = bool(np.asarray(inf.engine_dev[1]))
        m.ENGINE_BATCHES.inc(path="redo" if redo else "speculative")
        m.ENGINE_ROUNDS.inc(rounds)
        fence.attrs.update(engine_rounds=rounds, exact_redo=redo)
        return redo

    def _fold_program_loads(self, span: Optional[Span],
                            tier: str = TIER_BULK) -> None:
        """Fold the program loads since the last fold into
        phase_seconds["program_load"], and count them on the cycle
        span (`program_loads`), so /debug/traces shows which cycle
        loaded a program."""
        n, secs = program_loads()
        n0, secs0 = self._loads_seen
        self._loads_seen = (n, secs)
        if n != n0:
            self._phase("program_load", secs - secs0, tier)
        if span is not None:
            span.annotate(
                program_loads=span.attrs.get("program_loads", 0) + n - n0
            )

    def _postmortem(self, trigger: str, detail: str = "") -> None:
        """Dump a flight-recorder postmortem for one anomaly trigger
        (throttled per trigger inside the recorder): the last N cycle
        spans + the CURRENT cycle's in-flight span (a breaker trips
        mid-cycle, before that span retires into the ring) + queue/
        breaker/AIMD state + the metrics registry text.  State and
        metrics are passed as THUNKS: a shed storm hits this once per
        dropped pod, and throttled calls must cost ~nothing."""
        snap = self.flight_recorder.postmortem(
            trigger, detail,
            state=self._postmortem_state,
            metrics_text=m.REGISTRY.expose,
            in_flight=[self._cur_span] if self._cur_span is not None else None,
        )
        # a fired postmortem is also a timeline annotation — riding the
        # recorder's per-trigger throttle (snap is None inside the
        # window), so a shed storm marks the timeline once, not once per
        # pod.  The anomaly detector's own firings already annotate
        # kind="anomaly" inside maybe_sample — don't double-mark those.
        if snap is not None and not trigger.startswith("anomaly_"):
            self._annotate("postmortem", f"{trigger}: {detail}",
                           trigger=trigger)

    def _annotate(self, kind: str, detail: str = "", **fields) -> None:
        """Push one typed event onto the timeline store (no-op when the
        timeline is off).  Annotation must never break the loop."""
        tl = getattr(self, "timeline", None)  # None mid-__init__ too
        if tl is None:
            return
        try:
            tl.annotate(kind, detail, **fields)
        except Exception as e:  # pragma: no cover - defensive
            klog.errorf("timeline annotate failed: %s", e)

    def _postmortem_state(self) -> dict:
        """Point-in-time control-plane state for a postmortem snapshot —
        the numbers an operator reaches for first in an incident."""
        q = self.queue
        return {
            "queue_depth": len(q),
            "active_depth": (
                q.active_depth() if hasattr(q, "active_depth") else None
            ),
            "queue_capacity": getattr(q, "capacity", None),
            "shed_total": getattr(q, "shed_total", 0),
            "breaker": self.device_health.state,
            "consecutive_failures": self.device_health.consecutive_failures,
            "fault_counts": dict(self.device_health.fault_counts),
            # elastic-ladder facts: the rung + shard states a postmortem
            # reader joins against the fault class/shard on the span
            "mesh_width": self.mesh.size if self.mesh is not None else 0,
            "ladder_rung": self.ladder_rung,
            "shard_breakers": (
                {str(k): v for k, v in self.shard_health.states().items()}
                if self.shard_health is not None else None
            ),
            "invariants": (
                self.invariants.summary()
                if self.invariants is not None else None
            ),
            "adaptive_batch": self._cur_batch,
            "pipeline_pending": self.pipeline_pending,
            "scheduling_cycle": self.queue.scheduling_cycle,
            # latency tier of the most recently dispatched cycle — pairs
            # with the in_flight span in the postmortem
            "tier": self._cur_tier,
            "express_depth": (
                self.queue.express_depth()
                if hasattr(self.queue, "express_depth") else None
            ),
        }

    # ------------------------------------------ resident-snapshot seams
    #
    # Every touch of the resident device snapshot goes through these
    # three, so replica mode (ISSUE 14) can swap in the SHARED
    # SnapshotHub without the call sites caring: the hub re-snapshots
    # under the cache lock on every update (a retry can therefore never
    # scatter stale rows over a sibling replica's newer upload), while
    # classic mode keeps this instance's own DeviceSnapshotCache and
    # its incremental dirty-row contract bit-for-bit.

    def _device_update(self, cluster, dirty_rows):
        if self._hub is not None:
            return self._hub.refresh()[2]
        return self._dev_snapshot.update(cluster, dirty_rows=dirty_rows)

    def _device_invalidate(self) -> None:
        if self._hub is not None:
            self._hub.invalidate()
        else:
            self._dev_snapshot.invalidate()

    def _device_resident(self, fields):
        if self._hub is not None:
            return self._hub.resident(fields)
        return self._dev_snapshot.resident(fields)

    # ----------------------------------------------- device-fault handling

    @property
    def cpu_engine(self):
        """Lazy CpuEngineAdapter (cpuref/adapter.py): the degraded-mode
        engine serving cycles while the device breaker is open."""
        if self._cpu_engine is None:
            from kubernetes_tpu.cpuref.adapter import CpuEngineAdapter

            self._cpu_engine = CpuEngineAdapter(self.cache, self.config)
        return self._cpu_engine

    def _on_breaker_transition(self, frm: str, to: str) -> None:
        """Breaker transitions are operator-visible: one Event each (the
        audit trail the failure-mode table in README documents)."""
        reason = {
            "open": "BreakerOpen",
            "half_open": "BreakerHalfOpen",
            "closed": "BreakerClosed",
        }[to]
        self.recorder.eventf(
            "Scheduler", "", self.config.scheduler_name,
            EVENT_TYPE_WARNING if to == "open" else EVENT_TYPE_NORMAL,
            reason,
            "device breaker %s -> %s (consecutive failures: %d)",
            frm, to, self.device_health.consecutive_failures,
        )
        if to == "open":
            self._postmortem("breaker_open", f"{frm} -> {to}")
        self._annotate("breaker", f"{frm} -> {to}", to=to)
        m.LADDER_RUNG.set(float(self.RUNG_GAUGE[self.ladder_rung]))

    # ----------------------------------------- elastic degradation ladder
    #
    # full mesh -> shrunken mesh (widest pow2 of survivors) -> single
    # chip (a 1-device mesh) -> CPU adapter.  Shard-ATTRIBUTED faults
    # (codec/faults.fault_device_index) feed the per-shard breaker bank;
    # a shard's breaker tripping rebuilds the mesh without it instead of
    # tripping the global breaker, the in-flight batch is served
    # bit-identically by the CPU engine for the one gap cycle, and the
    # half-open canary probes the LOST device to climb back up.
    # Unattributed faults keep the PR 3 whole-mesh policy.

    RUNG_FULL = "full_mesh"
    RUNG_SHRUNKEN = "shrunken_mesh"
    RUNG_SINGLE = "single_chip"
    RUNG_CPU = "cpu"
    RUNG_GAUGE = {RUNG_FULL: 0, RUNG_SHRUNKEN: 1, RUNG_SINGLE: 2,
                  RUNG_CPU: 3}

    @property
    def ladder_rung(self) -> str:
        """Which rung currently serves cycles.  The global breaker wins
        (open/half-open = the device path as a whole is untrusted); an
        unsharded scheduler's healthy rung is single_chip."""
        if self.config.cpu_fallback and not self.device_health.device_available:
            return self.RUNG_CPU
        if self.mesh is None or self.mesh.size == 1:
            return self.RUNG_SINGLE
        if (
            self._full_mesh is not None
            and self.mesh.size < self._full_mesh.size
        ):
            return self.RUNG_SHRUNKEN
        return self.RUNG_FULL

    def _on_shard_transition(self, shard: int, frm: str, to: str) -> None:
        """Shard-breaker transitions are operator-visible, like the
        global breaker's (the per-shard rows in the README failure
        table)."""
        reason = {
            "open": "ShardBreakerOpen",
            "half_open": "ShardBreakerHalfOpen",
            "closed": "ShardBreakerClosed",
        }[to]
        self.recorder.eventf(
            "Scheduler", "", self.config.scheduler_name,
            EVENT_TYPE_WARNING if to == "open" else EVENT_TYPE_NORMAL,
            reason,
            "device shard %d breaker %s -> %s", shard, frm, to,
        )
        self._annotate("shard_breaker", f"shard {shard}: {frm} -> {to}",
                       shard=shard, to=to)

    def _on_invariant_violation(self, rule: str, detail: str) -> None:
        """An invariant violation is the anomaly class the flight
        recorder exists for: the control plane's own accounting broke."""
        self.recorder.eventf(
            "Scheduler", "", self.config.scheduler_name,
            EVENT_TYPE_WARNING, "InvariantViolation",
            "%s: %s", rule, detail,
        )
        self._postmortem("invariant_violation", f"{rule}: {detail}")

    def _shard_of(self, err: BaseException) -> Optional[int]:
        """Which shard (device id of the STARTUP mesh) a classified fault
        blames, or None for whole-mesh attribution.  Only ids the shard
        bank tracks count — a foreign id from a message pattern must not
        grow the bank."""
        if self.shard_health is None:
            return None
        idx = device_faults.fault_device_index(err)
        if idx is None or idx not in self.shard_health._state:
            return None
        return idx

    def _note_shard_fault(self, shard: Optional[int], fc: str) -> bool:
        """Feed one shard-attributed fault to the ladder.  Returns True
        when the fault was ABSORBED by a mesh shrink (the caller then
        serves the in-flight batch degraded and skips the global breaker
        accounting); False routes the fault to the whole-mesh policy —
        unattributed faults, shrink disabled, faults below the shard
        threshold (global transient retry still applies), and repeat
        faults on an already-lost shard (so a wrong rebuild cannot loop:
        the global breaker eventually trips)."""
        if (
            shard is None
            or self.shard_health is None
            or not self.config.mesh_shrink
        ):
            return False
        newly_lost = self.shard_health.record_failure(shard, fc)
        if not newly_lost:
            return False
        self._rebuild_mesh(
            direction="shrink",
            reason=f"shard {shard} lost ({fc})",
        )
        return True

    def _rebuild_mesh(self, direction: str, reason: str) -> None:
        """Rebuild the live mesh from the startup mesh minus the
        currently-lost shards (the widest valid sub-mesh), swap in a
        FRESH DeviceSnapshotCache (the invalidate seam: the next cycle's
        update() re-uploads the host-truth snapshot sharded onto the new
        mesh), and re-partition the compile-cache topology tag.  Runs on
        the scheduling thread only (the _dev_snapshot single-thread
        invariant)."""
        from kubernetes_tpu.codec.transfer import DeviceSnapshotCache
        from kubernetes_tpu.parallel.mesh import (
            mesh_device_ids,
            rebuild_without,
        )

        lost = self.shard_health.lost() if self.shard_health else frozenset()
        if lost:
            new_mesh, axis = rebuild_without(self._full_mesh, lost)
        else:
            new_mesh, axis = self._full_mesh, self._full_spec_axis
        self.mesh = new_mesh
        self._mesh_spec_axis = axis
        self._mesh_ids = mesh_device_ids(new_mesh) if new_mesh else None
        self._dev_snapshot = DeviceSnapshotCache(
            mesh=new_mesh, spec_axis=axis
        )
        width = new_mesh.size if new_mesh is not None else 0
        m.MESH_WIDTH.set(float(width))
        m.MESH_REBUILDS.inc(direction=direction)
        m.LADDER_RUNG.set(float(self.RUNG_GAUGE[self.ladder_rung]))
        full = self._full_mesh.size if self._full_mesh is not None else 0
        klog.errorf(
            "mesh %s: %s -> serving from %d/%d devices (rung %s)",
            direction, reason, width, full, self.ladder_rung,
        )
        self.recorder.eventf(
            "Scheduler", "", self.config.scheduler_name,
            EVENT_TYPE_WARNING if direction == "shrink"
            else EVENT_TYPE_NORMAL,
            "MeshShrunk" if direction == "shrink" else "MeshRestored",
            "%s: live mesh now %d/%d devices (%s)",
            reason, width, full, self.ladder_rung,
        )
        if direction == "shrink":
            self._postmortem("mesh_shrink", reason)
        self._annotate(
            "mesh_rebuild", f"{direction}: {reason} ({width}/{full})",
            direction=direction, width=width,
        )

    def _maybe_probe_shards(self) -> None:
        """The climb-back: once per lost shard whose cool-down elapsed,
        probe THE LOST DEVICE (not the surviving mesh — the scheduling
        cycles already canary that) and restore the widest mesh when it
        answers.  Runs on the scheduling thread between cycles, so mesh
        swaps never race a dispatch."""
        sh = self.shard_health
        if sh is None or not sh.lost():
            return
        if self._in_flight is not None:
            # a dispatched batch still references the current mesh's
            # buffers/closures: let it land first (its commit path calls
            # back here via schedule_cycle/run_once soon enough)
            return
        recovered = False
        for d in sorted(sh.lost()):
            if not sh.probe_due(d):
                continue
            try:
                self._probe_shard(d)
            except BaseException as e:
                fc = classify_device_error(e)
                if fc is None:
                    raise
                # failed canary: the shard re-opens and the cool-down
                # restarts (record_failure in HALF_OPEN always re-opens)
                sh.record_failure(d, fc)
                continue
            sh.record_success(d)
            recovered = True
        if recovered:
            self._rebuild_mesh(
                direction="restore",
                reason="lost shard recovered (half-open probe passed)",
            )

    def _probe_shard(self, shard: int) -> None:
        """One canary round-trip against a single (lost) device: the
        injection seams fire for exactly this device, and on real
        hardware a put+fetch raises the runtime's device-lost error while
        the chip is gone.  Any classified error = still lost."""
        device_faults.check(
            device_faults.SITE_DISPATCH, devices=(shard,)
        )
        dev = None
        if self._full_mesh is not None:
            dev = next(
                (d for d in np.asarray(self._full_mesh.devices).ravel()
                 if int(getattr(d, "id", -1)) == shard),
                None,
            )
        if dev is not None:
            buf = jax.device_put(np.zeros(8, np.float32), dev)
            device_faults.check(
                device_faults.SITE_FENCE, devices=(shard,)
            )
            np.asarray(buf)

    def _on_shed(self, pod: Pod, reason: str) -> None:
        """Bounded-queue shed audit (runtime/queue.py on_shed): one
        Warning event per dropped pod, mirroring the FailedScheduling
        trail (the metric lives with the queue)."""
        if self.invariants is not None:
            self.invariants.note_shed(pod)
        self.recorder.eventf(
            "Pod", pod.namespace, pod.name,
            EVENT_TYPE_WARNING, "SchedulingQueueFull",
            "pod shed from the scheduling queue (%s, capacity %s)",
            reason, self.queue.capacity,
        )
        # per-trigger throttling in the recorder turns a storm of sheds
        # into ONE postmortem capturing the lead-up, not one per pod
        self._postmortem("shed_burst", reason)

    def _adapt_batch(self, cycle_s: float) -> None:
        """AIMD batch-size update, once per non-empty cycle: halve on a
        deadline overrun (multiplicative decrease — latency wins), grow
        by +batch_size_min while the active queue outpaces the current
        width (additive increase — pressure converts into wider device
        launches), decay by halving once depth falls away (the batch
        returns to baseline after a storm, so post-overload cycles keep
        the low-latency shape)."""
        cfg = self.config
        if not cfg.adaptive_batch:
            return
        floor = max(1, cfg.batch_size_min)
        cur = self._cur_batch
        if cfg.cycle_deadline_s > 0 and cycle_s > cfg.cycle_deadline_s:
            m.CYCLE_DEADLINE_EXCEEDED.inc()
            self._postmortem(
                "cycle_deadline",
                f"cycle took {cycle_s:.3f}s > {cfg.cycle_deadline_s:.3f}s "
                f"budget (batch {cur})",
            )
            cur = max(floor, cur // 2)
        else:
            depth = self.queue.active_depth()
            if depth > cur:
                cur = min(cfg.batch_size, cur + floor)
            elif depth <= cur // 2:
                cur = max(floor, cur // 2)
        if cur != self._cur_batch:
            self._annotate(
                "aimd_resize", f"batch {self._cur_batch} -> {cur}",
                batch=cur,
            )
        self._cur_batch = cur
        m.ADAPTIVE_BATCH.set(float(cur))

    def _note_device_fault(self, fault_class: str, err: BaseException,
                           phase: str) -> None:
        klog.errorf(
            "device fault (%s) at %s: %s", fault_class, phase, err
        )
        self.recorder.eventf(
            "Scheduler", "", self.config.scheduler_name,
            EVENT_TYPE_WARNING, "DeviceFault",
            "%s device fault at %s: %s", fault_class, phase, err,
        )

    def _degrade_fetch(self, inf: _InFlight) -> None:
        """Serve an in-flight batch from the CPU engine: swap its fetch
        handle for a host-computed result and mark the cycle degraded."""
        inf.fetch = inf.cpu_fetch()
        inf.degraded = True
        # the CPU engine carries no attribution or quality seam, and the
        # device pytrees may belong to the failed launch
        inf.attrib_dev = None
        inf.quality_dev = None
        inf.quality = None
        inf.engine_dev = None
        # overwrite the dispatch-time attrs: the placements this cycle
        # commits came from the CPU engine, whatever was launched first
        inf.trace.annotate(degraded=True, engine="cpu")
        m.DEGRADED_CYCLES.inc()
        self._postmortem("degraded_cycle", "fence gave up on the device")

    def _fault_retry_allowed(
        self, fc: str, attempt: int, can_relaunch: bool = True,
        shard: Optional[int] = None,
    ) -> bool:
        """THE retry policy, shared by the dispatch and fence wrappers:
        account the classified failure, and decide whether one more
        same-batch attempt is allowed (counting the retry metric and
        sleeping the jittered backoff when it is).  On False the device
        has been given up on FOR THIS BATCH — the resident snapshot
        buffers are invalidated (a partial upload may have landed) and
        the caller degrades or raises.

        Shard-attributed faults (`shard` = a startup-mesh device id) try
        the elastic ladder first: a fault that LOSES the shard rebuilds
        the mesh without it and returns False without touching the
        global breaker — the next cycle dispatches on the shrunken mesh
        while only this batch rides the CPU adapter.  Shard faults below
        the shard threshold fall through to the global policy (same-
        batch transient retry), as do unattributed faults."""
        if self._note_shard_fault(shard, fc):
            # the mesh was rebuilt: _dev_snapshot is already a fresh
            # cache for the NEW mesh; this batch's launch state belongs
            # to the old one, so no same-batch retry
            return False
        tripped = self.device_health.record_failure(fc)
        if (
            not tripped
            and fc != FAULT_PERSISTENT
            and can_relaunch
            and attempt < self.config.device_retry_max
        ):
            m.FAULT_RETRIES.inc(**{"class": fc})
            time.sleep(self.device_health.backoff_s(attempt))
            return True
        self._device_invalidate()
        return False

    def _commit_state_resilient(self, inf: _InFlight) -> _Staged:
        """_commit_state wrapped in the classified retry/backoff/breaker
        policy: transient faults re-dispatch the SAME batch up to
        device_retry_max times with jittered backoff; a persistent fault
        (or a failure streak reaching the breaker threshold, or a failed
        half-open canary) trips the breaker and serves THIS batch from the
        CPU engine — popped pods are never lost, and commit/event
        semantics are identical either way."""
        attempt = 0
        relaunch_pending = False
        while True:
            try:
                if relaunch_pending:
                    (inf.hosts_dev, inf.fetch, inf.attrib_dev,
                     inf.quality_dev, inf.engine_dev) = inf.relaunch()
                    relaunch_pending = False
                staged = self._commit_state(inf)
            except BaseException as e:
                fc = classify_device_error(e)
                if fc is None:
                    raise
                shard = self._shard_of(e)
                self._note_device_fault(
                    fc, e, "dispatch" if relaunch_pending else "fence"
                )
                # the span carries the LAST retry class + attempt count —
                # the two facts a postmortem reader joins against the
                # breaker state (plus the blamed shard when attributed)
                inf.trace.annotate(fault_class=fc, fault_attempts=attempt + 1)
                if shard is not None:
                    inf.trace.annotate(fault_shard=shard)
                if self._fault_retry_allowed(
                    fc, attempt,
                    can_relaunch=(
                        not inf.degraded and inf.relaunch is not None
                    ),
                    shard=shard,
                ):
                    attempt += 1
                    relaunch_pending = True
                    continue
                if not self.config.cpu_fallback or inf.cpu_fetch is None:
                    raise
                self._degrade_fetch(inf)
                staged = self._commit_state(inf)  # CPU result: cannot fault
            if not inf.degraded:
                # an actual device round-trip succeeded: heal the streak
                # (and close the breaker if this was the half-open canary)
                self.device_health.record_success()
                if self.shard_health is not None and self._mesh_ids:
                    # ...and the per-shard streaks of the devices that
                    # served it (keeps "consecutive" consecutive)
                    self.shard_health.heal(self._mesh_ids)
            return staged

    def _encode_and_dispatch(self, pods: Sequence[Pod],
                             tier: str = TIER_BULK) -> Optional[_InFlight]:
        """Encode the batch + snapshot under the cache lock, run the
        extender/framework fan-out, and LAUNCH the engine.  Returns with
        the device still computing (hosts_dev is an async handle).

        TIER_EXPRESS cycles encode under the encoder's batch-width
        override: the batch pads to the small express shape (its own
        pre-compiled program) instead of the bulk lane's sticky width."""
        if not pods:
            return None
        with self._phase_span(None, "encode", tier) as encode:
            t_cycle0 = encode.t0
            # transfer watermark BEFORE any device work: the commit
            # tail diffs against it so the cycle's sample/span carry
            # exactly the bytes THIS cycle moved (codec/transfer.py)
            from kubernetes_tpu.codec.transfer import transfer_totals

            xfer0 = transfer_totals()
            enc = self.cache.encoder
            cycle = self.queue.scheduling_cycle
            express_width = (
                self.config.express_batch_size if tier == TIER_EXPRESS
                else None
            )
            # the cycle's ROOT span: one fresh trace id per cycle, child
            # spans per phase, annotated with the device-path facts (batch
            # width, dirty rows, breaker state, retry class) — retired
            # into the flight recorder when the commit tail finishes
            trace = encode.parent = Span(
                "schedule_cycle", start=t_cycle0, pods=len(pods),
                cycle=cycle, tier=tier,
            )
            self._cur_span = trace
            self._cur_tier = tier
            batch_keys = {(p.namespace, p.name) for p in pods}
            # engine choice is made BEFORE the encode so degraded cycles
            # leave the encoder's dirty-row stream unconsumed (the device
            # cache isn't listening; it is invalidated on trip and rebuilt
            # on recovery).  allow_device() may transition open ->
            # half_open: the canary.
            use_device = (
                self.device_health.allow_device()
                if self.config.cpu_fallback
                else True
            )
            with self.cache._lock, enc.batch_width(express_width):
                # in-batch affinity state when pods carry ANY pod-affinity
                # terms (required or preferred) AND can interact (B > 1);
                # built BEFORE encode_pods so novel term topology keys
                # register (and possibly grow the pair vocabulary) before
                # any TP-wide tensor is cut
                def aff_phase():
                    return self._phase_span(trace, "encode_affinity", tier)

                aff_state = None
                if len(pods) > 1 and batch_has_pod_affinity(pods):
                    with aff_phase():
                        aff_state = encode_batch_affinity(enc, pods)
                def pair_phase(rows, factored):
                    m.ENCODE_PAIR_LEAVES.inc(
                        form="factored" if factored else "dense")
                    return self._phase_span(trace, "encode_pairs", tier,
                                            pair_rows=rows)

                classes = np.full(len(pods), -1, np.int32)
                # the speculative engine's device launch packs the batch
                # with row factoring (pack_tree), so it takes the pair
                # leaves as class rows; every other reader gets the
                # dense batch (batch.dense())
                batch = enc.encode_pods(
                    pods, classes, affinity_phase=aff_phase,
                    pair_rows=(use_device and self._speculative_fn is not None
                               and transfer.factors_by_default()),
                    pair_phase=pair_phase,
                )
                ports = encode_batch_ports(enc, pods)
                # two-pass evaluation: nominated pods (other than those
                # being scheduled now) are added to their nominated nodes
                # in pass one
                nominated_pairs = [
                    (p, n)
                    for p, n in self.queue.nominated_pods()
                    if (p.namespace, p.name) not in batch_keys
                ]
                nominated = encode_nominated(enc, nominated_pairs)
                cluster, generation = self.cache.snapshot()
                # rows the incremental snapshot refreshed: lets the
                # device cache scatter-update just those rows instead of
                # re-shipping whole tensors (codec/transfer.py); taken
                # under the lock so the row set corresponds exactly to
                # THIS snapshot.  In hub mode the dirty stream's SINGLE
                # consumer is the hub itself (its refresh() takes under
                # the cache lock) — a replica taking here would starve
                # its siblings' resident state.
                dirty_rows = (
                    enc.take_dirty_rows()
                    if use_device and self._hub is None else None
                )
                # ports + anti-affinity contributions of nominated pods
                # (the non-resource half of podFitsOnNode's pass one) as a
                # host mask folded into extra_mask below
                nom_block = encode_nominated_block(
                    enc, nominated_pairs, pods, batch.n_pods,
                    cluster.n_nodes,
                )
                # point-in-time name->row map consistent with THIS
                # snapshot; extender round-trips below run outside the
                # lock, and the live node_rows dict may be mutated (rows
                # recycled/regrown) meanwhile
                node_row_map = dict(enc.node_rows)
            fwk = self.framework
            pc = None
            extra_mask = extra_score = None
            if fwk is not None:
                # ONE PluginContext per cycle, shared across every
                # extension point (the CycleState pattern: a plugin
                # computes at the tensor Filter point and consumes at
                # Prebind)
                from kubernetes_tpu.framework.v1alpha1 import PluginContext

                pc = PluginContext()
            if fwk is not None and (
                fwk.tensor_filter_plugins or fwk.tensor_score_plugins
            ):
                B, N = batch.n_pods, cluster.n_nodes
                dense = batch.dense()
                if fwk.tensor_filter_plugins:
                    extra_mask = np.asarray(fwk.run_filter_tensor(
                        pc, cluster, dense, np.ones((B, N), bool)
                    ))
                if fwk.tensor_score_plugins:
                    extra_score = np.asarray(
                        fwk.run_score_tensor(
                            pc, cluster, dense, np.zeros((B, N), np.float32)
                        ),
                        np.float32,
                    )
            ext_failed: Dict[int, str] = {}
            # bind-/preempt-only extenders don't participate in
            # filter/score; skip the fan-out (and keep extra_mask None)
            # when none do
            if any(
                e.config.filter_verb or e.config.prioritize_verb
                for e in self.extenders
            ):
                ext_span = trace.child("extenders", n=len(self.extenders))
                extra_mask, extra_score, ext_failed = self._apply_extenders(
                    pods, node_row_map, cluster, extra_mask, extra_score,
                    n_rows=batch.n_pods, trace_ctx=trace.traceparent(),
                )
                ext_span.finish()
            if nom_block is not None:
                # pass-one infeasibility from nominated ports/anti-affinity
                extra_mask = (
                    ~nom_block if extra_mask is None
                    else (extra_mask & ~nom_block)
                )
        fn = self._schedule_fn
        if self._speculative_fn is not None:
            fn = self._speculative_fn
        last_index0 = self._last_index
        # launch-state box (ISSUE 14): hub mode re-snapshots at every
        # (re-)dispatch, so the cluster the engine ACTUALLY consumed —
        # the one the ledger must record and the generation the
        # reconciler's fast path fences on — is written here by launch()
        launch_box = {"cluster": cluster, "generation": generation,
                      "ledger": None}

        def launch():
            """(Re-)dispatch THIS encoded batch on the device.  Captured
            by _InFlight.relaunch so the transient-retry path re-runs the
            same computation with the same rotation base; dirty_rows are
            re-passed safely — fields whose upload already landed identity-
            skip, fields whose upload faulted re-scatter.  Hub mode
            (shared resident snapshot) refreshes to the CURRENT cache
            truth instead: replicas dispatch against the newest resident
            generation, and a retry can never scatter stale rows over a
            sibling's newer upload."""
            device_faults.check(
                device_faults.SITE_DISPATCH, devices=self._mesh_ids
            )
            if self._hub is not None:
                c2, g2, dev_cluster = self._hub.refresh()
                launch_box["cluster"], launch_box["generation"] = c2, g2
                if launch_box["ledger"] is not None:
                    launch_box["ledger"]["cluster"] = c2
            else:
                dev_cluster = self._dev_snapshot.update(
                    cluster, dirty_rows=dirty_rows
                )
            out = fn(
                dev_cluster, batch, ports,
                np.int32(last_index0), nominated,
                extra_mask, extra_score, aff_state,
            )
            hosts = out[0]
            # optional extra outputs, in fixed order after new_cluster:
            # Attribution (sequential attribution launches), then the
            # quality TopKQuality — both materialized at the commit
            # fence, after the winners land
            idx = 2
            attrib = None
            if getattr(fn, "attribution", False):
                attrib = out[idx]
                idx += 1
            qual = out[idx] if self._quality_k else None
            # the speculative engine's repair rounds and exact-redo flag
            # for this call (scalars of the same launch); None where the
            # engine reports neither
            engine = (getattr(fn, "last_rounds", None),
                      getattr(fn, "last_redo", None))
            if engine[0] is None:
                engine = None
            # enqueue the tiny D2H copies (top-k, engine scalars)
            # alongside the winners buffer so the fence materialize is a
            # copy wait, never a compute sync
            for leaf in (*(qual or ()), *(engine or ())):
                if hasattr(leaf, "copy_to_host_async"):
                    leaf.copy_to_host_async()
            # async result path: only the compact winners buffer (i32[B]
            # node rows) crosses the wire — the D2H copy is enqueued NOW
            # and materializes on a worker thread, so the blocking fence in
            # _commit_state is usually a no-op by the time the pipelined
            # loop reaches it (batch k's fetch overlaps batch k's host tail
            # and batch k+1's dispatch)
            return hosts, AsyncFetch(hosts), attrib, qual, engine

        def cpu_fetch():
            """Winners for THIS batch from the CPU reference engine, in the
            device path's exact shape (cpuref/adapter.py) — the graceful-
            degradation seam.  Reads the LIVE cache state, which at call
            time equals the state this batch's snapshot saw (single
            scheduling thread; the pipelined loop commits batch k's state
            before dispatching k+1)."""
            t0 = time.monotonic()
            if self._hub is not None:
                # degraded replica cycle: the adapter reads the LIVE
                # cache, which sibling replicas mutate concurrently —
                # serialize the host compute under the cache lock (the
                # reconciler still re-checks its verdicts at commit)
                with self.cache._lock:
                    hosts = self.cpu_engine.schedule_batch(
                        pods, last_index0,
                        extra_mask=extra_mask, extra_score=extra_score,
                        nominated=nominated_pairs,
                        masked=frozenset(ext_failed),
                        row_map=node_row_map,
                    )
            else:
                hosts = self.cpu_engine.schedule_batch(
                    pods, last_index0,
                    extra_mask=extra_mask, extra_score=extra_score,
                    nominated=nominated_pairs,
                    masked=frozenset(ext_failed),
                    row_map=node_row_map,
                )
            return _HostResult(hosts, seconds=time.monotonic() - t0)

        with self._phase_span(trace, "dispatch", tier) as dispatch:
            degraded = False
            hosts_dev = attrib_dev = quality_dev = engine_dev = None
            if use_device:
                launched = self._launch_resilient(launch)
            else:
                launched = None
            if launched is None:
                # breaker open (or dispatch gave up): degraded CPU cycle
                degraded = True
                m.DEGRADED_CYCLES.inc()
                self._postmortem(
                    "degraded_cycle",
                    "breaker open at dispatch" if not use_device
                    else "dispatch gave up on the device",
                )
                fetch = cpu_fetch()
            else:
                hosts_dev, fetch, attrib_dev, quality_dev, engine_dev = (
                    launched)
            self._last_index += len(pods)
            trace.annotate(
                batch=len(pods),
                dirty_rows=len(dirty_rows) if dirty_rows is not None else -1,
                breaker=self.device_health.state,
                degraded=degraded,
                engine="cpu" if degraded else self._engine_kind,
                shards=self.mesh.size if self.mesh is not None else 0,
            )
            # programs the launch loaded (a new width compiles here)
            self._fold_program_loads(trace, tier)
        t_disp_end = dispatch.t1
        # hub mode: the launch refreshed to the newest resident state —
        # inf carries the generation/cluster the engine ACTUALLY saw
        # (the reconciler's fast-path fence and the ledger's truth)
        cluster_used = launch_box["cluster"]
        inf = _InFlight(
            pods=list(pods), hosts_dev=hosts_dev, fetch=fetch,
            generation=launch_box["generation"], cycle=cycle,
            ext_failed=ext_failed,
            pc=pc, t_cycle0=t_cycle0, trace=trace,
            relaunch=None if degraded else launch,
            cpu_fetch=cpu_fetch, degraded=degraded,
            last_index0=last_index0, tier=tier, attrib_dev=attrib_dev,
            quality_dev=quality_dev,
            engine_dev=engine_dev,
            quality_reqs=(
                batch.req if self.quality is not None else None
            ),
            quality_snapshot=(
                (cluster_used.allocatable, cluster_used.requested,
                 cluster_used.valid)
                if self.quality is not None else None
            ),
            telemetry_host=(
                (cluster_used.allocatable, cluster_used.requested,
                 cluster_used.valid)
                if self.telemetry is not None else None
            ),
            capacity_snapshot=(
                (cluster_used.allocatable, cluster_used.requested,
                 cluster_used.valid)
                if self.capacity is not None else None
            ),
            width=batch.n_pods,
            enqueue_s=t_disp_end - t_cycle0,
            xfer0=xfer0,
            classes=classes,
            reqs=batch.req if self._reconciler is not None else None,
        )
        if self._replica_of > 1:
            trace.annotate(replica=self._replica_id)
        if self.ledger is not None:
            # the exact launch inputs, stashed for the off-hot-path
            # ledger write after the commit tail (the snapshot arrays are
            # immutable by the encoder's dirty-row contract, so handing
            # references to the writer thread is safe).  Registered in
            # the launch box so a hub-mode retry re-points the recorded
            # cluster at the snapshot the retry actually consumed.
            inf.ledger_inputs = dict(
                cluster=cluster_used, batch=batch.dense(), ports=ports,
                nominated=nominated, aff_state=aff_state,
                extra_mask=extra_mask, extra_score=extra_score,
                last_index0=last_index0,
            )
            launch_box["ledger"] = inf.ledger_inputs
        return inf

    def _launch_resilient(self, launch):
        """Run a device launch under the classified retry/backoff policy.
        Returns (hosts_dev, fetch), or None when the device was given up on
        for this batch (caller degrades to the CPU engine); unclassified
        errors propagate (the schedule_cycle/_run_pipelined guards requeue
        the batch)."""
        attempt = 0
        while True:
            try:
                return launch()
            except BaseException as e:
                fc = classify_device_error(e)
                if fc is None:
                    raise
                self._note_device_fault(fc, e, "dispatch")
                if self._fault_retry_allowed(
                    fc, attempt, shard=self._shard_of(e)
                ):
                    attempt += 1
                    continue
                if not self.config.cpu_fallback:
                    raise
                return None

    def _validate_hosts(self, hosts, n_pods: int) -> np.ndarray:
        """Structural validation of a fetched winners buffer: a corrupted
        D2H transfer must surface as a CLASSIFIED fault (retried like a
        transient error) instead of a KeyError deep in row_name or a
        silently-wrong placement on a never-allocated row.  In-range
        corruption is undetectable without a checksum — out of scope; the
        injector's corrupt mode scrambles values out of range on purpose."""
        hosts = np.asarray(hosts)
        enc = self.cache.encoder
        structural = (
            hosts.ndim == 1
            and hosts.shape[0] >= n_pods
            and hosts.dtype.kind in ("i", "u")
        )
        if structural and n_pods:
            head = hosts[:n_pods]
            # winners live in [-1, next_row): -1 = unschedulable, rows
            # below the arena high-water mark; anything outside (either
            # direction) is wire corruption, not a placement
            structural = (
                int(head.max(initial=-1)) < max(enc._next_row, 1)
                and int(head.min(initial=0)) >= -1
            )
        if not structural:
            raise CorruptedFetchError(
                "fetched winners buffer failed validation: shape=%s "
                "dtype=%s row_range=%s live_rows<%d"
                % (
                    hosts.shape, hosts.dtype,
                    (int(hosts[:n_pods].min(initial=0)),
                     int(hosts[:n_pods].max(initial=-1)))
                    if hosts.ndim == 1 and hosts.shape[0] >= n_pods
                    else "?",
                    enc._next_row,
                )
            )
        return hosts

    def _commit_state(self, inf: _InFlight) -> _Staged:
        """Fetch the placements and apply the cache-STATE half of the
        commit.  In batched mode (config.batched_commit, no framework) the
        whole batch of winners is assumed as ONE encoder delta under a
        single lock acquisition; the side-effect tail runs in
        _commit_tail.  In per-pod mode this only fetches — the tail runs
        the classic loop."""
        pods = inf.pods
        with self._phase_span(
            inf.trace, "host_stall", inf.tier, child="fence"
        ) as fence:
            hosts = inf.fetch.result()  # ready-fence: blocks only if the async
            #                             D2H copy hasn't landed yet
            hosts = self._validate_hosts(hosts, len(pods))
            if inf.attrib_dev is not None:
                # attribution rides the same launch: by the time the winners
                # landed the rest of the outputs are computed, so this fetch
                # costs one extra D2H copy, not a second device round-trip.
                # Inside the resilient fence on purpose — a fault here
                # retries/degrades exactly like a winners-fetch fault.
                inf.attrib = type(inf.attrib_dev)(
                    *(np.asarray(x) for x in inf.attrib_dev)
                )
            if inf.quality_dev is not None:
                # the quality top-k rides the same launch and the same
                # discipline: its async copies were enqueued at dispatch, so
                # this is a copy wait behind the landed winners, never a new
                # sync; a fault here retries/degrades like the winners fetch
                inf.quality = type(inf.quality_dev)(
                    *(np.asarray(x) for x in inf.quality_dev)
                )
            redo = self._engine_outcome(inf, fence)
        t_state0 = fence.t1
        with self._phase_span(
            inf.trace, "commit", inf.tier, part="commit_state"
        ) as commit:
            # "fetch" records the ASYNC window (dispatch -> copy landed,
            # stamped on the fetch worker): it overlaps the dispatch/commit
            # host phases.  The fence wait (host_stall) is split by what
            # the worker was doing meanwhile.
            self._phase("fetch", inf.fetch.seconds, inf.tier)
            self._fence_split(inf.fetch, fence, inf.tier)
            if redo:
                self._phase("fence_redo", fence.seconds, inf.tier)
            # algorithm latency: encode + device filter/score/select, amortized
            # per pod (metrics.go SchedulingAlgorithmLatency)
            algo_dt = (time.monotonic() - inf.t_cycle0) / len(pods)
            m.ALGO_LATENCY.observe_n(algo_dt, len(pods))
            batched = self.config.batched_commit and self.framework is None
            staged = _Staged(
                inf=inf, hosts=hosts, algo_dt=algo_dt, batched=batched,
                t_state0=t_state0, stall_s=fence.seconds,
            )
            if inf.xfer0 is not None:
                # the fence is the honest cycle boundary for transfer
                # accounting: every upload/fetch this cycle caused has
                # landed (AsyncFetch notes bytes before its done-event), and
                # the pipelined loop has not dispatched the next batch yet
                from kubernetes_tpu.codec.transfer import transfer_delta

                staged.xfer_delta = transfer_delta(inf.xfer0)
            if not batched:
                return staged
            enc = self.cache.encoder
            row_name = enc.row_name
            ext_failed = inf.ext_failed
            winners = staged.winners
            for i, (pod, row) in enumerate(
                zip(pods, hosts[: len(pods)].tolist())
            ):
                if i in ext_failed:
                    continue
                if row < 0:
                    staged.fit_idx.append(i)
                    continue
                node_name = row_name(row)
                winners.append((i, pod, pod.assumed_on(node_name), node_name))
            if self._reconciler is not None:
                # SEQUENCED optimistic-concurrency commit: the
                # admission scan and the assume run as ONE critical section
                # under the cache lock, so the headroom the scan read is
                # exactly the headroom the delta lands on.  Race losers
                # readd to their owner shard in the tail; quota losers park
                # unschedulable.  Zero-conflict cycles (generation fence
                # unchanged, no quotas) admit with one integer comparison.
                with self.cache._lock:
                    kept, race_lost, quota_lost = self._reconciler.reconcile(
                        self, inf, winners, hosts
                    )
                    if kept is not winners:
                        staged.winners = winners = list(kept)
                    staged.race_lost = race_lost
                    staged.quota_lost = quota_lost
                    self._assume_winners(inf, winners, hosts, commit)
                    if self.invariants is not None and winners:
                        rows = sorted(
                            {int(hosts[i]) for i, _, _, _ in winners}
                        )
                        self.invariants.check_capacity(
                            rows, enc.a_requested, enc.a_allocatable,
                            row_name=enc.row_name,
                        )
                if race_lost or quota_lost:
                    self.conflicts_total += len(race_lost)
                    self.race_requeued_total += len(race_lost)
                    self.quota_vetoed_total += len(quota_lost)
                    inf.trace.annotate(
                        conflicts=len(race_lost), quota_vetoed=len(quota_lost)
                    )
                staged.state_seconds = time.monotonic() - t_state0
                commit.attrs["winners"] = len(winners)
                return staged
            # ONE lock acquisition + one encoder delta for the whole batch
            self._assume_winners(inf, winners, hosts, commit)
            if self.invariants is not None and winners:
                # capacity invariant over exactly the rows this batch
                # committed to — O(batch), read under the cache lock so the
                # arrays are consistent with the delta just applied
                rows = sorted({int(hosts[i]) for i, _, _, _ in winners})
                with self.cache._lock:
                    self.invariants.check_capacity(
                        rows, enc.a_requested, enc.a_allocatable,
                        row_name=enc.row_name,
                    )
            staged.state_seconds = time.monotonic() - t_state0
            commit.attrs["winners"] = len(winners)
            return staged

    def _assume_winners(self, inf: _InFlight, winners: list, hosts,
                        commit: PhaseSpan) -> None:
        """Assume a batched commit's winners in one encoder delta, on the
        rows the engine picked, class by class where encode gave the pods
        classes, and count which path each pod took."""
        idx = np.fromiter((w[0] for w in winners), np.intp, len(winners))
        rows = hosts[idx]
        # a row whose node left since dispatch has no name (row_name ""),
        # so its pod is charged to no row, as a lookup by name would
        rows = np.where(self.cache.encoder.a_valid[rows], rows, -1)
        classes = inf.classes[idx] if inf.classes is not None else None
        n_classes = self.cache.assume_pods(
            [a for _, _, a, _ in winners], classes, rows
        )
        n_class = (
            int(np.count_nonzero(classes >= 0)) if n_classes else 0
        )
        m.COMMIT_PODS.inc(n_class, path="class")
        m.COMMIT_PODS.inc(len(winners) - n_class, path="per_pod")
        commit.attrs["commit_classes"] = n_classes

    def _commit_tail(self, staged: _Staged) -> List[ScheduleResult]:
        """Side-effect tail of a cycle: binds, events, metrics, requeues,
        preemption.  Under pipeline_commit this overlaps the next batch's
        device dispatch (the state half already ran, so the next snapshot
        is exact)."""
        inf = staged.inf
        t_tail0 = time.monotonic()
        # the cycle's trace context is CURRENT for the whole tail: binds
        # (RemoteBinder / bind-verb extenders attach the traceparent
        # header) and Scheduled/FailedScheduling events (trace_id field)
        # all join back to this cycle's root span
        with use_traceparent(inf.trace):
            with self._phase_span(
                inf.trace, "commit", inf.tier, child="bind-tail",
                part="commit_tail",
            ):
                if staged.batched:
                    results, fit_errors = self._tail_batched(staged)
                else:
                    results, fit_errors = self._tail_perpod(staged)
            if not self.config.disable_preemption:
                with self._phase_span(
                    inf.trace, "preempt", inf.tier, failed=len(fit_errors)
                ):
                    for pod in fit_errors:
                        self.preempt(pod)
        with self._phase_span(inf.trace, "observe", inf.tier):
            results = self._observe(staged, results, t_tail0)
        inf.trace.finish()
        # slow-cycle log LAST, once the ENTIRE tail (ledger record +
        # telemetry included) has run and the span is finished, so the
        # logged total is the duration /debug/traces reports; its own
        # time still counts as observe
        if self.config.trace_threshold_s > 0:
            with self._phase_span(None, "observe", inf.tier):
                inf.trace.log_if_long(self.config.trace_threshold_s)
        return results

    def _observe(self, staged: _Staged, results: List[ScheduleResult],
                 t_tail0: float) -> List[ScheduleResult]:
        """The observe phase of a committed cycle: every post-bind hook
        (flight recorder, ledger, telemetry, perfobs, quality, capacity,
        timeline).  Each hook keeps its own /metrics cost counter, and
        none may fail a cycle whose placements are already committed."""
        inf = staged.inf
        placed = sum(1 for r in results if r.node is not None)
        inf.trace.annotate(placed=placed, unschedulable=len(results) - placed)
        # the cycle's wire traffic (taken at the commit fence — see
        # _Staged.xfer_delta), annotated onto the span before it
        # retires (ISSUE 11): total bytes + the dominant seam — the two
        # facts a Perfetto reader joins against the phase children
        xfer_delta = staged.xfer_delta
        if xfer_delta:
            top = max(xfer_delta.items(), key=lambda kv: kv[1]["bytes"])
            inf.trace.annotate(
                transfer_bytes=sum(
                    v["bytes"] for v in xfer_delta.values()
                ),
                transfer_top_seam=top[0],
            )
        # into the ring now, so a postmortem fired by a hook below sees
        # this cycle; the span finishes when the observe phase ends
        self.flight_recorder.record(inf.trace)
        if self.ledger is not None and inf.ledger_inputs is not None:
            self._ledger_record(inf, staged, results)
        self._outcome_totals["placed"] += placed
        self._outcome_totals["unschedulable"] += len(results) - placed
        if self.telemetry is not None:
            t_tel = time.perf_counter()
            try:
                self._telemetry_cycle(inf, results, placed)
            except Exception as e:  # noqa: BLE001 — telemetry must never
                # fail a cycle whose placements are already committed: a
                # device fault in the analytics SIDE-launch (dispatched
                # outside the resilient fence on purpose) costs one
                # sample, not the batch
                klog.errorf(
                    "telemetry hook failed (cycle %d): %s", inf.cycle, e
                )
            finally:
                m.TELEMETRY_SECONDS.inc(time.perf_counter() - t_tel)
        # performance observatory (ISSUE 11): fold this cycle's
        # host/device split + transfer delta into the cost model.  Like
        # telemetry, the hook must never fail a committed cycle, and its
        # scheduling-thread cost is stamped into its own counter (the
        # <2% budget perf_smoke pins).
        t_perf = time.perf_counter()
        try:
            fetch = inf.fetch
            commit_s = staged.state_seconds + time.monotonic() - t_tail0
            wall_s = time.monotonic() - inf.t_cycle0
            self.perfobs.on_cycle(
                width=inf.width or len(inf.pods),
                tier=inf.tier,
                degraded=inf.degraded,
                enqueue_s=inf.enqueue_s,
                execute_s=getattr(fetch, "execute_seconds", 0.0),
                materialize_s=getattr(fetch, "materialize_seconds", 0.0),
                stall_s=staged.stall_s,
                commit_s=commit_s,
                wall_s=wall_s,
                transfers=xfer_delta,
                trace_id=inf.trace.trace_id,
            )
        except Exception as e:  # noqa: BLE001 — observability must
            # never fail a cycle whose placements are already committed
            klog.errorf(
                "perf observatory hook failed (cycle %d): %s", inf.cycle, e
            )
        finally:
            m.PERFOBS_SECONDS.inc(time.perf_counter() - t_perf)
        # placement-quality observatory (ISSUE 13): margins off the
        # in-launch top-k, feasible counts, drift detectors, and the
        # amortized regret counterfactual.  Same discipline as the
        # telemetry/perfobs hooks — never fails a committed cycle, cost
        # stamped into its own counter (the <2% budget perf_smoke pins).
        if self.quality is not None:
            t_q = time.perf_counter()
            try:
                self.quality.on_cycle(
                    cycle=inf.cycle,
                    tier=inf.tier,
                    degraded=inf.degraded,
                    hosts=staged.hosts,
                    n_pods=len(inf.pods),
                    quality=inf.quality,
                    reqs=inf.quality_reqs,
                    snapshot=inf.quality_snapshot,
                    attrib=inf.attrib,
                    analytics=(
                        self.telemetry.analytics
                        if self.telemetry is not None else None
                    ),
                )
            except Exception as e:  # noqa: BLE001
                klog.errorf(
                    "quality hook failed (cycle %d): %s", inf.cycle, e
                )
            finally:
                m.QUALITY_SECONDS.inc(time.perf_counter() - t_q)
        # device-resident capacity planner (ISSUE 15): the amortized
        # class-compressed what-if solve over the backlog + shape
        # catalog.  Same discipline as the telemetry/quality hooks —
        # never fails a committed cycle, cost stamped into its own
        # counter (the <2% budget perf_smoke pins).
        if self.capacity is not None:
            t_cap = time.perf_counter()
            try:
                self._capacity_cycle(inf)
            except Exception as e:  # noqa: BLE001
                klog.errorf(
                    "capacity hook failed (cycle %d): %s", inf.cycle, e
                )
            finally:
                m.CAPACITY_SECONDS.inc(time.perf_counter() - t_cap)
        m.PENDING_PODS.set(float(len(self.queue)))
        # metrics timeline (ISSUE 20): the cadence-gated sampling sweep
        # + online anomaly detection, AFTER every gauge above settled so
        # the sample reads this cycle's truth.  Same discipline as the
        # telemetry/quality/capacity hooks — never fails a committed
        # cycle, cost stamped into its own counter (the <2% budget
        # perf_smoke pins).  The idle path in run_once ticks the same
        # store so quiet loops keep sampling.
        if self.timeline is not None:
            t_tl = time.perf_counter()
            try:
                self.timeline.maybe_sample()
            except Exception as e:  # noqa: BLE001
                klog.errorf(
                    "timeline hook failed (cycle %d): %s", inf.cycle, e
                )
            finally:
                m.TIMELINE_SECONDS.inc(time.perf_counter() - t_tl)
        self.results.extend(results)
        # programs loaded since dispatch (a side launch's first compile)
        self._fold_program_loads(inf.trace, inf.tier)
        return results

    def _telemetry_cycle(self, inf: _InFlight, results, placed: int) -> None:
        """Feed the telemetry hub one committed cycle: SLO good/bad
        events (deadline overrun, goodput vs shed, degraded), per-tier
        pending pressure, the per-width launch EWMA, and the amortized
        analytics side-launch over the RESIDENT snapshot buffers (host
        fallback when the device state is untrusted)."""
        hub = self.telemetry
        q = self.queue
        shed_total = getattr(q, "shed_total", 0)
        shed_delta = shed_total - self._shed_seen
        self._shed_seen = shed_total
        express = (
            q.express_depth() if hasattr(q, "express_depth") else 0
        )
        active = q.active_depth() if hasattr(q, "active_depth") else len(q)
        hub.record_pressure(
            bulk=max(0, active - express), express=express,
            parked=max(0, len(q) - active),
        )
        # ladder telemetry (ISSUE 10): live mesh width, the rung serving
        # cycles, per-shard breaker states, invariant-checker totals —
        # sampled fresh every cycle so /debug/cluster reflects rebuilds
        rung = self.ladder_rung
        m.LADDER_RUNG.set(float(self.RUNG_GAUGE[rung]))
        hub.record_mesh(
            width=self.mesh.size if self.mesh is not None else 0,
            full_width=(
                self._full_mesh.size if self._full_mesh is not None else 0
            ),
            rung=rung,
            shard_states=(
                self.shard_health.states()
                if self.shard_health is not None else None
            ),
            invariants=(
                self.invariants.summary()
                if self.invariants is not None else None
            ),
        )
        if not inf.degraded and inf.fetch is not None:
            hub.note_launch(inf.width or len(inf.pods), inf.fetch.seconds)
        from kubernetes_tpu.runtime.telemetry import ANALYTICS_FIELDS

        resident = (
            None if inf.degraded
            else self._device_resident(ANALYTICS_FIELDS)
        )
        hub.on_cycle(
            cycle=inf.cycle,
            tier=inf.tier,
            cycle_s=time.monotonic() - inf.t_cycle0,
            placed=placed,
            unschedulable=len(results) - placed,
            shed=shed_delta,
            degraded=inf.degraded,
            deadline_s=self.config.cycle_deadline_s,
            resident=resident,
            host_snapshot=inf.telemetry_host,
            span=inf.trace,
        )

    def _capacity_cycle(self, inf: _InFlight) -> None:
        """Feed the capacity planner one committed cycle: the cycle's
        host snapshot refs, a lazy backlog reader (invoked only on due
        interval cycles), the node-name resolver for the drainable
        report, and the encoder's read-only extended-resource column
        lookup for catalog vectors."""
        enc = self.cache.encoder

        def node_names():
            return {row: name for name, row in enc.node_rows.items()}

        self.capacity.on_cycle(
            cycle=inf.cycle,
            backlog=self._capacity_backlog,
            snapshot=inf.capacity_snapshot,
            node_names=node_names,
            res_col=enc.res_col_readonly,
        )

    def _capacity_backlog(self, cap: int):
        """The pending+unschedulable backlog in the planner's
        PRE-GROUPED form (distinct request vectors f32[G, R], counts
        i[G]; bounded at `cap` pods), encoded READ-ONLY — the planner
        must not grow the encoder's resource axis or intern anything.
        Controller-stamped backlogs collapse to a handful of distinct
        request contents, so pods group by content (the encoder's
        _req_memo key scheme) and each distinct content encodes once —
        the walk is dict ops per pod and the planner never
        materializes (or re-sorts) a per-pod matrix (the
        <2%-of-cycle hook budget)."""
        enc = self.cache.encoder
        q = self.queue
        pods = (
            q.backlog_pods(cap) if hasattr(q, "backlog_pods") else []
        )
        if not pods:
            return np.zeros((0, enc.dims.R), np.float32)
        groups: Dict[tuple, list] = {}
        for p in pods:
            rk = (
                tuple(
                    tuple(c.requests.items()) for c in p.spec.containers
                ),
                () if not p.spec.init_containers else tuple(
                    tuple(c.requests.items())
                    for c in p.spec.init_containers
                ),
            )
            g = groups.get(rk)
            if g is None:
                groups[rk] = [enc.backlog_req_vector(p), 1]
            else:
                g[1] += 1
        vecs = np.stack([v for v, _ in groups.values()])
        counts = np.asarray([c for _, c in groups.values()], np.int64)
        return vecs, counts

    def _ledger_record(self, inf: _InFlight, staged: _Staged,
                       results: List[ScheduleResult]) -> None:
        """Submit this cycle to the decision ledger: the stashed launch
        inputs (snapshot delta computed on the writer thread), the
        outcome facts, and the per-pod decision summaries the
        /debug/decisions ring serves (cross-linked by trace id)."""
        pods = inf.pods
        attrs = inf.trace.attrs
        decisions: List[dict] = []
        for i, pod in enumerate(pods):
            r = results[i] if i < len(results) else None
            node = r.node if r is not None else None
            d: dict = {"pod": f"{pod.namespace}/{pod.name}", "node": node}
            if node is None and inf.attrib is not None:
                from kubernetes_tpu.runtime.ledger import (
                    explain_unschedulable,
                )

                dominant, msg = explain_unschedulable(
                    inf.attrib.reason_counts[i]
                )
                if dominant:
                    d["reason"] = dominant
                    d["detail"] = msg
            decisions.append(d)
        outcome = {
            "cycle": inf.cycle,
            "tier": inf.tier,
            "engine": "cpu" if inf.degraded else self._engine_kind,
            "degraded": inf.degraded,
            "fault_class": attrs.get("fault_class"),
            "fault_attempts": int(attrs.get("fault_attempts", 0)),
            "trace_id": inf.trace.trace_id,
            "n_pods": len(pods),
            "pods": [[p.namespace, p.name] for p in pods],
            "winners": np.asarray(staged.hosts[: len(pods)], np.int32),
            "time": time.time(),
            # queue-sharded replicas (ISSUE 14): which replica dispatched
            # this cycle, and its reconciler commit sequence number —
            # cross-replica replay stays deterministic because every
            # block carries the exact snapshot its launch consumed, and
            # the sequence orders the interleaving for audit
            "replica": self._replica_id,
            **(
                {"seq": inf.commit_seq} if inf.commit_seq >= 0 else {}
            ),
            # quality top-k (ISSUE 13): the winner-pinned ranking rides
            # the block so bench --replay recomputes margins offline
            **(
                {
                    "quality_top_nodes": np.asarray(
                        inf.quality.top_nodes[: len(pods)], np.int32
                    ),
                    "quality_top_scores": np.asarray(
                        inf.quality.top_scores[: len(pods)], np.float32
                    ),
                    "quality_feasible": np.asarray(
                        inf.quality.feasible[: len(pods)], np.int32
                    ),
                }
                if inf.quality is not None else {}
            ),
        }
        self.ledger.record_cycle(inf.ledger_inputs, outcome, decisions)

    def replay_cycle(self, rec: dict) -> np.ndarray:
        """Re-execute one recorded cycle (a runtime/ledger.read_ledger
        record) through THIS scheduler's engine against the record's
        reconstructed snapshot, asserting bit-identical winners — the
        substrate the offline weight-tuning loop (ROADMAP item 4)
        re-scores against.  Offline: touches neither the cache, the
        resident device snapshot, nor the rotation counter."""
        from kubernetes_tpu.runtime.ledger import replay_record

        fn = (
            self._speculative_fn
            if self._speculative_fn is not None
            else self._schedule_fn
        )
        if rec.get("engine") == "cpu":
            # a degraded cycle's winners carry the CPU reference
            # engine's (= the sequential scan's) tie-rotation semantics
            fn = self._schedule_fn
        got = replay_record(fn, rec)
        want = np.asarray(rec["winners"])[: int(rec["n_pods"])]
        if not np.array_equal(got, want):
            raise AssertionError(
                f"replay mismatch at cycle {rec.get('cycle')}: "
                f"recorded {want.tolist()} != replayed {got.tolist()}"
            )
        return got

    # the dominant-failing-predicate explanation stamped onto an
    # unschedulable pod (the kubectl-describe FitError parity surface)
    UNSCHED_REASON_ANNOTATION = "kubernetes-tpu.io/unschedulable-reason"

    def _unsched_message(self, inf: _InFlight, i: int, n_nodes: int,
                         pod: Pod) -> str:
        """FailedScheduling audit text for batch index i: with
        attribution on, name the dominant failing predicate with
        per-reason node counts ("0/5000 nodes are available: 4987
        Insufficient resources, 13 node(s) had taints that the pod
        didn't tolerate.") and stamp the unschedulable-reason annotation
        + the per-plugin counter; else the classic count-only line."""
        if inf.attrib is not None:
            from kubernetes_tpu.runtime.ledger import explain_unschedulable

            dominant, msg = explain_unschedulable(
                inf.attrib.reason_counts[i]
            )
            if dominant:
                pod.metadata.annotations[
                    self.UNSCHED_REASON_ANNOTATION
                ] = msg
                m.UNSCHEDULABLE_REASONS.inc(plugin=dominant)
                return msg
        return "0/%d nodes are available" % n_nodes

    def _tail_perpod(self, staged: _Staged):
        """The classic per-pod commit loop (framework cycles, or
        config.batched_commit=False): reserve/assume/bind one pod at a
        time, emitting events and metrics inline."""
        inf = staged.inf
        pods, hosts = inf.pods, staged.hosts
        generation, cycle, pc = inf.generation, inf.cycle, inf.pc
        ext_failed, algo_dt = inf.ext_failed, staged.algo_dt
        enc = self.cache.encoder
        results = []
        fit_errors: List[Pod] = []
        for i, pod in enumerate(pods):
            row = int(hosts[i])
            if i in ext_failed:
                # non-ignorable extender error: plain error requeue, NOT a
                # FitError — no preemption (scheduler.go:463 preempts only
                # on core.FitError; extender errors surface as plain errors)
                self.queue.add_unschedulable(pod, cycle)
                results.append(ScheduleResult(pod, None, generation))
                m.SCHEDULE_ATTEMPTS.inc(result=m.SCHEDULE_ERROR)
                self.recorder.eventf(
                    "Pod", pod.namespace, pod.name,
                    EVENT_TYPE_WARNING, "FailedScheduling",
                    "extender error: %s", ext_failed[i],
                    trace_id=inf.trace.trace_id,
                )
                continue
            if row < 0:
                # FitError path: park in unschedulableQ with backoff
                # (factory.go MakeDefaultErrorFunc), then try preemption
                # (scheduler.go:463-475)
                self.queue.add_unschedulable(pod, cycle)
                results.append(ScheduleResult(pod, None, generation))
                fit_errors.append(pod)
                m.SCHEDULE_ATTEMPTS.inc(result=m.UNSCHEDULABLE)
                self.recorder.eventf(
                    "Pod", pod.namespace, pod.name,
                    EVENT_TYPE_WARNING, "FailedScheduling",
                    "%s", self._unsched_message(
                        inf, i, len(self.cache.encoder.node_rows), pod
                    ),
                    trace_id=inf.trace.trace_id,
                )
                continue
            node_name = enc.row_name(row)
            assumed = dataclasses.replace(
                pod, spec=dataclasses.replace(pod.spec, node_name=node_name)
            )
            # post-assume failures (permit/prebind/bind) requeue WITHOUT
            # preemption: the reference preempts only on a scheduling
            # FitError (scheduler.go:463: `if fitError, ok := err.(...)`),
            # not on binding hiccups for a pod that fits somewhere
            t_pod = time.monotonic()
            outcome = self._reserve_and_bind(
                pod, assumed, node_name, cycle, pc, algo_dt, t_pod
            )
            if outcome == "failed":
                results.append(ScheduleResult(pod, None, generation))
                m.SCHEDULE_ATTEMPTS.inc(result=m.SCHEDULE_ERROR)
            else:
                self.queue.delete_nominated_pod_if_exists(pod)
                results.append(ScheduleResult(pod, node_name, generation))
                if outcome == "bound":
                    # "waiting" pods record on async bind completion instead
                    self._record_scheduled(
                        pod, node_name, algo_dt + (time.monotonic() - t_pod),
                        tier=inf.tier,
                    )
        return results, fit_errors

    def _tail_batched(self, staged: _Staged):
        """Batched side-effect tail: per-pod bind callbacks (the only
        irreducibly per-pod step — each is an external call), then ONE
        batched emission each for requeues, metrics histograms, counters,
        and events, all in batch-index order so the audit trail matches
        the per-pod loop exactly."""
        inf = staged.inf
        pods, hosts = inf.pods, staged.hosts
        generation, cycle = inf.generation, inf.cycle
        B = len(pods)
        results: List[Optional[ScheduleResult]] = [None] * B
        events: List[Optional[Tuple]] = [None] * B
        n_nodes = len(self.cache.encoder.node_rows)
        # every event of this cycle joins the cycle's trace (7th tuple
        # element; eventf_batch splits it off the aggregation key)
        tid = inf.trace.trace_id
        losers: List[Pod] = []
        for i in staged.fit_idx:
            pod = pods[i]
            results[i] = ScheduleResult(pod, None, generation)
            losers.append(pod)
            events[i] = (
                "Pod", pod.namespace, pod.name,
                EVENT_TYPE_WARNING, "FailedScheduling",
                self._unsched_message(inf, i, n_nodes, pod), tid,
            )
        for i, msg in inf.ext_failed.items():
            pod = pods[i]
            results[i] = ScheduleResult(pod, None, generation)
            losers.append(pod)
            events[i] = (
                "Pod", pod.namespace, pod.name,
                EVENT_TYPE_WARNING, "FailedScheduling",
                "extender error: %s" % msg, tid,
            )
        # optimistic-concurrency losers (ISSUE 14): a sequenced-earlier
        # replica commit spent this pod's node headroom — requeue it to
        # its OWNER SHARD via readd (active queue, shed-exempt: no
        # popped pod is ever lost), not the unschedulable parking lot
        # (the pod fits elsewhere; it lost a race, not a FitError)
        for i, pod in staged.race_lost:
            results[i] = ScheduleResult(pod, None, generation)
            events[i] = (
                "Pod", pod.namespace, pod.name,
                EVENT_TYPE_NORMAL, "PlacementConflict",
                "lost optimistic concurrency race for node headroom; "
                "requeued", tid,
            )
        # namespace-quota vetoes park unschedulable WITH backoff: the
        # quota stays full until something terminates, and spinning the
        # pod through the active queue would starve its shard
        for i, pod in staged.quota_lost:
            results[i] = ScheduleResult(pod, None, generation)
            losers.append(pod)
            events[i] = (
                "Pod", pod.namespace, pod.name,
                EVENT_TYPE_WARNING, "QuotaExceeded",
                "namespace %s placement quota exhausted" % pod.namespace,
                tid,
            )
        # enqueue stamps BEFORE the bind fan-out: a bind's informer echo
        # (bound-pod update -> queue.delete) races a later take and would
        # drop the queue wait from the e2e histogram; failed binds restore
        # their stamp below so a requeued pod keeps its first-enqueue time
        winner_qts = self.queue.take_enqueue_times(
            [pod for _, pod, _, _ in staged.winners]
        )
        # bind fan-out: one _invoke_binder call per winner (each is an
        # external call — the only irreducibly per-pod step)
        bind_dts: List[float] = []
        bound: List[Tuple[int, Pod, str]] = []
        bound_qts: List[Optional[float]] = []
        bound_ts: List[float] = []   # per-pod bind-commit stamp: e2e must
        #                              end at THIS pod's bind, not the
        #                              whole fan-out's end (the per-pod
        #                              loop stamps each pod individually)
        n_bind_failed = 0
        for w, (i, pod, assumed, node_name) in enumerate(staged.winners):
            t0b = time.monotonic()
            ok = self._invoke_binder(pod, assumed, node_name)
            tb = time.monotonic()
            bind_dts.append(tb - t0b)
            if ok:
                bound.append((i, pod, node_name))
                bound_qts.append(winner_qts[w])
                bound_ts.append(tb)
                if self.invariants is not None:
                    self.invariants.note_bound(pod, node_name)
                # a pod that failed an earlier cycle may carry the
                # unschedulable-reason annotation: stale once it binds
                pod.metadata.annotations.pop(
                    self.UNSCHED_REASON_ANNOTATION, None
                )
                results[i] = ScheduleResult(pod, node_name, generation)
                events[i] = (
                    "Pod", pod.namespace, pod.name,
                    EVENT_TYPE_NORMAL, "Scheduled",
                    "Successfully assigned %s/%s to %s"
                    % (pod.namespace, pod.name, node_name), tid,
                )
            else:
                # optimistic rollback: ForgetPod + requeue, exactly the
                # per-pod _reject_assumed path (scheduler.go:416-426)
                self.cache.forget_pod(assumed)
                self.queue.restore_enqueue_time(pod, winner_qts[w])
                n_bind_failed += 1
                losers.append(pod)
                results[i] = ScheduleResult(pod, None, generation)
                events[i] = (
                    "Pod", pod.namespace, pod.name,
                    EVENT_TYPE_WARNING, "FailedScheduling",
                    self._BIND_REJECT_MSG
                    % (pod.namespace, pod.name, node_name), tid,
                )
        # batched bookkeeping: one lock acquisition per structure
        self.queue.add_unschedulable_batch(losers, cycle)
        for _, pod in staged.race_lost:
            self.queue.readd(pod)
        if staged.quota_lost:
            m.SCHEDULE_ATTEMPTS.inc(
                len(staged.quota_lost), result=m.UNSCHEDULABLE
            )
        if bound and self.queue.has_nominated():
            self.queue.delete_nominated_batch([p for _, p, _ in bound])
        m.BINDING_LATENCY.observe_batch(bind_dts)
        if staged.fit_idx:
            m.SCHEDULE_ATTEMPTS.inc(len(staged.fit_idx), result=m.UNSCHEDULABLE)
        if inf.ext_failed or n_bind_failed:
            m.SCHEDULE_ATTEMPTS.inc(
                len(inf.ext_failed) + n_bind_failed, result=m.SCHEDULE_ERROR
            )
        if bound:
            m.SCHEDULE_ATTEMPTS.inc(len(bound), result=m.SCHEDULED)
            e2es = [
                tb - qt if qt is not None
                else staged.algo_dt + (tb - staged.t_state0)
                for qt, tb in zip(bound_qts, bound_ts)
            ]
            m.E2E_LATENCY.observe_batch(e2es, tier=inf.tier)
            if klog.V(2).enabled:
                for (_, pod, node_name), e2e in zip(bound, e2es):
                    klog.V(2).infof(
                        "scheduled %s/%s to %s (%.1fms e2e)",
                        pod.namespace, pod.name, node_name, e2e * 1000,
                    )
        entries = [e for e in events if e is not None]
        eventf_batch = getattr(self.recorder, "eventf_batch", None)
        if eventf_batch is not None:
            eventf_batch(entries)
        else:  # duck-typed recorder without the batch entry point
            for kind, ns, name, type_, reason, msg, _tid in entries:
                self.recorder.eventf(kind, ns, name, type_, reason, "%s", msg)
        return list(results), [pods[i] for i in staged.fit_idx]

    # --------------------------------------------------------- extenders

    def _apply_extenders(self, pods, rows, cluster, extra_mask, extra_score,
                         n_rows=None, trace_ctx=""):
        """Chain the configured HTTP extenders per pod: each filter
        round-trip intersects the feasibility mask (an extender can only
        veto, never resurrect — generic_scheduler.go:527-554), prioritize
        results add score*weight (:774-804, merged before selectHost).

        `rows` is the snapshot-consistent name->row map captured under the
        cache lock.  The extender chain is sequential per pod (each link
        sees the previous link's narrowed list), but pods fan out across a
        small thread pool — the reference's 16-goroutine analog for the
        network-bound section.  Returns (mask, score, failed{batch index:
        message}); a pod whose non-ignorable extender errored is fully
        masked and listed in failed."""
        from concurrent.futures import ThreadPoolExecutor

        from kubernetes_tpu.extender.client import ExtenderError

        # mask/score are allocated at the ENGINE batch width (n_rows =
        # batch.n_pods, a pow2 pad >= len(pods)); the pad-row tail stays
        # all-true/zero and pods.valid masks it on device.  Allocating at
        # len(pods) broke any non-pow2 batch with extenders configured.
        B, N = len(pods), cluster.n_nodes
        Bp = n_rows if n_rows is not None else B
        mask = (
            np.ones((Bp, N), bool)
            if extra_mask is None else np.array(extra_mask, bool)
        )
        score = (
            np.zeros((Bp, N), np.float32)
            if extra_score is None else np.array(extra_score, np.float32)
        )
        failed: Dict[int, str] = {}
        all_names = [n for n, r in rows.items() if r < N]

        def one_pod(i_pod):
            i, pod = i_pod
            # pool workers re-enter the CYCLE's trace context explicitly
            # (thread-locals don't cross the executor boundary), so every
            # extender round-trip carries the cycle's traceparent header
            with use_traceparent(trace_ctx):
                return _one_pod_traced(i, pod)

        def _one_pod_traced(i, pod):
            names = list(all_names)
            for ext in self.extenders:
                if not ext.is_interested(pod):
                    continue
                try:
                    ok, _failed_nodes = ext.filter(pod, names)
                except ExtenderError as e:
                    if ext.is_ignorable:
                        # skip it, let the rest decide (:534-537)
                        continue
                    failed[i] = str(e)
                    mask[i, :] = False
                    return
                okset = set(ok)
                for n in names:
                    if n not in okset:
                        mask[i, rows[n]] = False
                names = [n for n in names if n in okset]
                if not names:
                    return
            for ext in self.extenders:
                if not ext.is_interested(pod) or not ext.config.prioritize_verb:
                    continue
                try:
                    scores, weight = ext.prioritize(pod, names)
                except ExtenderError:
                    # prioritize errors are ignorable by design (:784-787)
                    continue
                for n, s in scores.items():
                    r = rows.get(n)
                    if r is not None and r < N:
                        score[i, r] += s * weight

        if B == 1:
            one_pod((0, pods[0]))
        else:
            with ThreadPoolExecutor(max_workers=16) as pool:
                list(pool.map(one_pod, enumerate(pods)))
        return mask, score, failed

    # ------------------------------------------------- reserve/permit/bind

    def _record_scheduled(self, pod: Pod, node_name: str, e2e: float,
                          tier: str = TIER_BULK) -> None:
        """Scheduled event + counters, only once a bind actually succeeded
        (scheduler.go:268 emits after bind, not at assume).  The e2e
        histogram records queue-add -> bind-commit when the pod came
        through the queue (the density SLO pair: throughput + p99,
        density.go:988-990); the caller's algo+bind figure is the fallback
        for direct schedule_cycle() calls."""
        if self.invariants is not None:
            self.invariants.note_bound(pod, node_name)
        qt = self.queue.take_enqueue_time(pod)
        if qt is not None:
            e2e = time.monotonic() - qt
        # a FitError retry that now succeeded: the explain annotation a
        # previous cycle stamped is stale the moment the pod binds
        pod.metadata.annotations.pop(self.UNSCHED_REASON_ANNOTATION, None)
        klog.V(2).infof(
            "scheduled %s/%s to %s (%.1fms e2e)",
            pod.namespace, pod.name, node_name, e2e * 1000,
        )
        m.SCHEDULE_ATTEMPTS.inc(result=m.SCHEDULED)
        m.E2E_LATENCY.observe(e2e, tier=tier)
        self.recorder.eventf(
            "Pod", pod.namespace, pod.name,
            EVENT_TYPE_NORMAL, "Scheduled",
            "Successfully assigned %s/%s to %s",
            pod.namespace, pod.name, node_name,
            trace_id=current_trace_id(),  # set during the cycle tail;
            #                               "" on gang/async-bind paths
        )

    def _reserve_and_bind(
        self, pod: Pod, assumed: Pod, node_name: str, cycle: int, pc=None,
        algo_dt: float = 0.0, t_pod: float = 0.0,
    ) -> str:
        """Framework extension points around assume->bind (scheduleOne,
        scheduler.go:507-580): Reserve -> assume -> Permit -> Prebind ->
        bind, with Unreserve + ForgetPod + requeue on any later rejection.
        `pc` is the cycle's shared PluginContext.  Returns "bound",
        "waiting" (bind completes asynchronously), or "failed"."""
        fwk = self.framework
        if fwk is not None:
            st = fwk.run_reserve_plugins(pc, assumed, node_name)
            if not st.is_success():
                # reserve failure is an internal error: requeue, no preemption
                self.queue.add_unschedulable(pod, cycle)
                return "failed"
        self.cache.assume_pod(assumed)
        m.COMMIT_PODS.inc(path="per_pod")
        if fwk is not None and fwk.permit_plugins:
            status, wp, timeout = fwk.start_permit(pc, assumed, node_name)
            if wp is not None:
                # the GOROUTINE BOUNDARY (scheduler.go:523): binding of a
                # waiting pod completes asynchronously; the cycle moves on
                # with the pod optimistically assumed
                threading.Thread(
                    target=self._finish_waiting_pod,
                    args=(fwk, pc, pod, assumed, node_name, cycle, wp, timeout,
                          algo_dt, t_pod),
                    daemon=True,
                ).start()
                return "waiting"
            if not status.is_success():
                self._reject_assumed(
                    fwk, pc, pod, assumed, node_name, cycle, status.message
                )
                return "failed"
        ok = self._prebind_and_bind(fwk, pc, pod, assumed, node_name, cycle)
        return "bound" if ok else "failed"

    # single source of truth for the bind-rejection audit message (the
    # batched/per-pod equivalence test compares event text verbatim)
    _BIND_REJECT_MSG = "Binding rejected for %s/%s on %s"

    def _invoke_binder(self, pod, assumed, node_name) -> bool:
        """The actual bind call, shared by the per-pod and batched commit
        paths: a bind-verb extender binds pods it manages in place of the
        default binder (extender.go:360-387; scheduler.go bind path); any
        exception counts as a rejection."""
        binder_ext = next(
            (e for e in self.extenders
             if e.is_binder and e.is_interested(pod)),
            None,
        )
        try:
            if binder_ext is not None:
                binder_ext.bind(
                    pod.namespace, pod.name, pod.metadata.uid, node_name
                )
                return True
            return bool(self.binder(assumed, node_name))
        except Exception:
            return False

    def _prebind_and_bind(self, fwk, pc, pod, assumed, node_name, cycle) -> bool:
        if fwk is not None and fwk.prebind_plugins:
            st = fwk.run_prebind_plugins(pc, assumed, node_name)
            if not st.is_success():
                self._reject_assumed(
                    fwk, pc, pod, assumed, node_name, cycle, st.message
                )
                return False
        t0 = time.monotonic()
        ok = self._invoke_binder(pod, assumed, node_name)
        m.BINDING_LATENCY.observe(time.monotonic() - t0)
        if not ok:
            self._reject_assumed(
                fwk, pc, pod, assumed, node_name, cycle,
                self._BIND_REJECT_MSG
                % (pod.namespace, pod.name, node_name),
            )
            return False
        return True

    def _reject_assumed(
        self, fwk, pc, pod, assumed, node_name, cycle, message: str = ""
    ) -> None:
        """Rollback for a pod rejected after assume (scheduler.go:416-426
        ForgetPod + MakeDefaultErrorFunc requeue + unreserve plugins +
        FailedScheduling event, scheduler.go:433)."""
        self.cache.forget_pod(assumed)
        if fwk is not None:
            fwk.run_unreserve_plugins(pc, assumed, node_name)
        self.queue.add_unschedulable(pod, cycle)
        self.recorder.eventf(
            "Pod", pod.namespace, pod.name,
            EVENT_TYPE_WARNING, "FailedScheduling",
            "%s", message or f"rejected after assume on {node_name}",
            trace_id=current_trace_id(),
        )

    def _finish_waiting_pod(
        self, fwk, pc, pod, assumed, node_name, cycle, wp, timeout,
        algo_dt: float = 0.0, t_pod: float = 0.0,
    ) -> None:
        try:
            st = wp.wait(timeout)
        finally:
            fwk.waiting_pods.remove(assumed)
        if st.is_success():
            if self._prebind_and_bind(fwk, pc, pod, assumed, node_name, cycle):
                self._record_scheduled(
                    pod, node_name,
                    algo_dt + (time.monotonic() - t_pod) if t_pod else algo_dt,
                )
        else:
            self._reject_assumed(
                fwk, pc, pod, assumed, node_name, cycle, st.message
            )

    # ---------------------------------------------------------- preemption

    def preempt(self, pod: Pod) -> Optional[str]:
        """Try to make room for a pod that failed to fit: pick a node +
        minimal victim set on device, verify the nomination host-side against
        the full predicate set, delete the victims, and record the nominated
        node so the two-pass evaluation protects the claim.

        Mirrors Scheduler.preempt (scheduler.go:292-342) + genericScheduler
        .Preempt (generic_scheduler.go:310-369).  Returns the nominated node
        name, or None if preemption does not help."""
        if self.config.disable_preemption:
            return None
        m.PREEMPTION_ATTEMPTS.inc()
        t0 = time.monotonic()
        try:
            return self._preempt_inner(pod)
        finally:
            # every attempt's evaluation cost lands in the histogram, not
            # just successful nominations
            m.PREEMPTION_LATENCY.observe(time.monotonic() - t0)

    def _preempt_inner(self, pod: Pod) -> Optional[str]:
        enc = self.cache.encoder
        # preemption must not consume the breaker's half-open canary (the
        # scheduling cycle is the probe), so it keys off the NON-mutating
        # availability check: anything but CLOSED routes the candidate scan
        # through the CPU engine
        use_device = (
            self.device_health.device_available
            if self.config.cpu_fallback
            else True
        )
        with self.cache._lock:
            if not self._eligible_to_preempt(pod):
                return None
            batch = enc.encode_pods([pod])
            cluster, _ = self.cache.snapshot()
            dirty_rows = (
                enc.take_dirty_rows()
                if use_device and self._hub is None else None
            )
        # device work OUTSIDE the cache lock: a first-shape preempt pays a
        # multi-second XLA compile, and informer/event threads must not
        # stall on the lock for it.  The snapshot is a point-in-time copy;
        # cands may be one event stale vs the re-acquired state below —
        # the same optimistic semantics as the reference (the pick loop's
        # verify/veto and the next cycle resolve races).
        # Resident-buffer reuse: preemption runs right after a failed
        # cycle, when the snapshot is mostly byte-identical, so only the
        # changed rows upload (codec/transfer.py).
        #
        # SINGLE-SCHEDULING-THREAD INVARIANT: _dev_snapshot (and the
        # encoder's take_dirty_rows stream feeding it) is the same mutable
        # DeviceSnapshotCache schedule_cycle uses, mutated here OUTSIDE
        # cache._lock.  This is safe only because preempt is invoked solely
        # from the scheduling thread's commit tail (_commit_tail) — the
        # pipelined commit path keeps every _dev_snapshot.update on that
        # one thread, interleaved never concurrent.  If preempt ever
        # becomes callable from another thread, give preemption its own
        # DeviceSnapshotCache (and its own dirty-row take stream).
        if use_device:
            try:
                cluster = self._device_update(cluster, dirty_rows)
                if jax.default_backend() != "cpu":
                    if self.mesh is not None:
                        from kubernetes_tpu.parallel.mesh import replicate

                        batch = replicate(batch, self.mesh)
                    else:
                        batch = jax.device_put(batch)
                cands = host_fetch(
                    self._preempt_eval(cluster, batch), tag="preempt"
                )[0].copy()
            except BaseException as e:
                fc = classify_device_error(e)
                if fc is None:
                    raise
                # preempt device faults feed the same breaker accounting
                # (shard-attributed ones the ladder, like a cycle fault);
                # the candidate scan degrades to the CPU engine in place
                self._note_device_fault(fc, e, "preempt")
                if not self._note_shard_fault(self._shard_of(e), fc):
                    self.device_health.record_failure(fc)
                    self._device_invalidate()
                if not self.config.cpu_fallback:
                    raise
                cands = self.cpu_engine.preempt_candidates(
                    pod, cluster.n_nodes
                )
        else:
            cands = self.cpu_engine.preempt_candidates(pod, cluster.n_nodes)
        if not cands.any():
            # nodesWherePreemptionMightHelp came back empty: clear any
            # previous nomination (generic_scheduler.go:328-333)
            self._clear_nomination(pod)
            return None
        with self.cache._lock:
            if not self._eligible_to_preempt(pod):
                return None
            arena = enc.pods_snapshot()
            violating = self._pdb_violating_flags(enc, len(arena.node))
            slots = sorted_victim_slots(
                arena.priority,
                arena.valid,
                arena.node,
                pod.spec.priority,
                violating,
                arena.start,
            )
            row, _, victims, res = pick_preemption_node(
                enc, pod, cands, arena, slots, violating,
                self.config.filter_config.max_vols,
            )
            if row < 0:
                self._clear_nomination(pod)
                return None
            node_name = enc.row_name(row)
        # preempt-verb extenders vet the candidate + victim set
        # (processPreemptionWithExtenders, generic_scheduler.go:342-369);
        # HTTP round-trips happen outside the cache lock
        victims = self._extender_preemption(pod, node_name, victims, res)
        if victims is None:
            self._clear_nomination(pod)
            return None
        for v in victims:
            self.victim_deleter(v)
            if self.invariants is not None:
                # the victim left the cluster: a same-name successor must
                # not read as a double-bind
                self.invariants.note_removed(v)
            self.recorder.eventf(
                "Pod", v.namespace, v.name,
                EVENT_TYPE_NORMAL, "Preempted",
                "by %s/%s on node %s", pod.namespace, pod.name, node_name,
            )
        m.PREEMPTION_VICTIMS.set(float(len(victims)))
        pod.status.nominated_node_name = node_name
        self.queue.update_nominated_pod(pod, node_name)
        self.preemptions.append(
            (
                (pod.namespace, pod.name),
                node_name,
                [(v.namespace, v.name) for v in victims],
            )
        )
        # victim deletions are cluster events (eventhandlers.go ->
        # MoveAllToActiveQueue); in standalone mode emulate the move so the
        # preemptor retries promptly
        self.queue.move_all_to_active()
        return node_name

    def _extender_preemption(self, pod, node_name, victims, res):
        """Run ProcessPreemption through every preempt-verb extender that is
        interested; each may narrow the victim set or drop the node entirely
        (return None -> abort, nothing evicted).  Non-preempt-verb extenders
        are skipped, ignorable errors skip just that extender
        (generic_scheduler.go:342-369)."""
        chain = [
            e for e in self.extenders
            if e.supports_preemption and e.is_interested(pod)
        ]
        if not chain:
            return victims
        from kubernetes_tpu.extender.client import ExtenderError

        meta = {
            node_name: {
                "pods": [{"uid": v.metadata.uid or f"{v.namespace}/{v.name}"}
                         for v in victims],
                "numPDBViolations": int(getattr(res, "n_pdb_violations", 0)),
            }
        }
        for ext in chain:
            try:
                meta = ext.process_preemption(pod, meta)
            except ExtenderError:
                if ext.is_ignorable:
                    continue
                return None
            if node_name not in meta:
                return None
        keep = {
            p.get("uid") for p in meta[node_name].get("pods", [])
        }
        return [
            v for v in victims
            if (v.metadata.uid or f"{v.namespace}/{v.name}") in keep
        ]

    def _eligible_to_preempt(self, pod: Pod) -> bool:
        """podEligibleToPreemptOthers (generic_scheduler.go:1159-1180): if the
        pod already nominated a node and a lower-priority pod there is still
        terminating, wait instead of preempting more."""
        nom = pod.status.nominated_node_name
        if not nom:
            return True
        enc = self.cache.encoder
        row = enc.node_rows.get(nom, -1)
        if row < 0:
            return True
        for key in enc._row_pods.get(row, ()):
            rec = enc.pods.get(key)
            if (
                rec is not None
                and rec.pod is not None
                and rec.pod.metadata.deletion_timestamp is not None
                and rec.priority < pod.spec.priority
            ):
                return False
        return True

    def _clear_nomination(self, pod: Pod) -> None:
        pod.status.nominated_node_name = ""
        self.queue.delete_nominated_pod_if_exists(pod)

    def _pdb_violating_flags(self, enc, m_cap: int) -> np.ndarray:
        """bool[M]: evicting arena pod m would violate a PodDisruptionBudget
        (filterPodsWithPDBViolation, generic_scheduler.go:990-1035)."""
        flags = np.zeros(m_cap, bool)
        pdbs = [p for p in self.pdb_lister() if p.disruptions_allowed <= 0]
        if not pdbs:
            return flags
        for rec in enc.pods.values():
            if rec.pod is None or rec.node_row < 0:
                continue
            if any(pdb.matches(rec.pod) for pdb in pdbs):
                flags[rec.m] = True
        return flags

    def _verify_preemption(self, pod: Pod, row: int, victims: List[Pod]) -> bool:
        return verify_nomination(
            self.cache.encoder, pod, row, victims, self.config.filter_config.max_vols
        )

    # ------------------------------------------------------------- run loop

    # pods carrying this label pair schedule as all-or-nothing PodGroups
    # (the scheduler-plugins lightweight-coscheduling convention:
    # .../name = group, .../min-available = minMember).  Scope and limits,
    # deliberately matching the convention's own semantics:
    #  * atomicity covers the members CO-PENDING in one scheduling cycle
    #    (the plugin likewise gates on min-available pods being Pending);
    #    a group split across cycles schedules per co-arriving cohort, and
    #    min-available larger than the engine batch width can never be
    #    satisfied in one cycle and parks with backoff each retry;
    #  * gangs do not trigger preemption (a failed gang parks like a
    #    FitError pod but never evicts victims);
    #  * when EXTENDERS are configured the gang path is bypassed (members
    #    schedule as plain pods, no atomicity) — the gang launch cannot
    #    consult extender filter verdicts, and silently ignoring them
    #    would place pods on extender-vetoed nodes.
    POD_GROUP_LABEL = "pod-group.scheduling.sigs.k8s.io/name"
    POD_GROUP_MIN_MEMBER = "pod-group.scheduling.sigs.k8s.io/min-available"

    def _tier_of(self, pod: Pod) -> str:
        """The queue's admission-time tier classifier (wired when
        config.express_lane): annotation opt-in / priority threshold via
        classify_tier, EXCEPT gang members — the express lane has no gang
        path (atomicity needs the bulk cycle's gang machinery), so a
        pod-group pod always rides bulk whatever its priority."""
        if self.POD_GROUP_LABEL in pod.labels:
            return TIER_BULK
        return classify_tier(pod, self.config.express_priority_threshold)

    def _run_express(self) -> int:
        """Serve ONE express-lane cycle if express pods are pending: pop up
        to express_batch_size from the express heap (never blocking — the
        tier exists to remove waiting, not add batch-formation windows)
        and schedule them synchronously at the express encode width.
        Returns pods placed.  Bounded to one small batch per call, so the
        interleave with the caller's bulk cycle is the starvation guard in
        BOTH directions: sustained express load still yields a bulk cycle
        per iteration, and a saturating bulk backlog still yields an
        express cycle per iteration."""
        pop_express = getattr(self.queue, "pop_express_batch", None)
        if pop_express is None:
            return 0  # caller-owned queue without tier lanes
        with self._phase_span(None, "pop", TIER_EXPRESS):
            pods = pop_express(max(1, self.config.express_batch_size))
            if pods and self.invariants is not None:
                self.invariants.note_popped(
                    pods, self.queue.scheduling_cycle
                )
        if not pods:
            return 0
        results = self.schedule_cycle(pods, tier=TIER_EXPRESS)
        return sum(1 for r in results if r.node is not None)

    def _maybe_heartbeat(self) -> None:
        """Once per config.heartbeat_s (0 = off): ONE klog line with the
        liveness numbers an operator greps for first — so a quiet log
        still proves the loop is alive.  Called from run_once on every
        iteration (including idle polls: an empty queue must still
        heartbeat)."""
        hb = self.config.heartbeat_s
        if hb <= 0:
            return
        now = time.monotonic()
        if now - self._last_heartbeat < hb:
            return
        self._last_heartbeat = now
        q = self.queue
        express = q.express_depth() if hasattr(q, "express_depth") else 0
        active = q.active_depth() if hasattr(q, "active_depth") else len(q)
        hbm = self.telemetry.hbm_in_use() if self.telemetry is not None else 0
        # observatory window since the last heartbeat (ISSUE 11): host
        # vs device milliseconds and the transfer seam that moved the
        # most bytes — the three numbers that say WHERE the interval's
        # wall time went without opening /debug/perf
        host_ms, dev_ms, xfer_top = self.perfobs.heartbeat_window()
        # placement-quality satellites (ISSUE 13): sliding margin p50 +
        # the last sampled regret ratio — decision confidence and
        # packing density on the same liveness line
        q_margin, q_regret = (
            self.quality.heartbeat_fields()
            if self.quality is not None else (0.0, 0.0)
        )
        # timeline satellites (ISSUE 20): anomaly firings so far + how
        # far sampling lags its cadence — detection liveness on the same
        # line as the loop's
        tl = self.timeline
        tl_anoms = (
            tl.detector.anomalies_total
            if tl is not None and tl.detector is not None else 0
        )
        tl_lag = tl.lag_s if tl is not None else 0.0
        klog.infof(
            "heartbeat: cycles=%d placed=%d unschedulable=%d depth=%d "
            "active=%d express=%d breaker=%s batch=%d hbm_bytes=%d "
            "mesh=%d rung=%s shards_lost=%d invariant_violations=%d "
            "host_ms=%d dev_ms=%d xfer_top=%s margin=%.4f regret=%.2f "
            "replicas=%d conflicts=%d anomalies=%d timeline_lag_s=%.3f",
            q.scheduling_cycle,
            self._outcome_totals["placed"],
            self._outcome_totals["unschedulable"],
            len(q), active, express,
            self.device_health.state, self._cur_batch, hbm,
            self.mesh.size if self.mesh is not None else 0,
            self.ladder_rung,
            len(self.shard_health.lost()) if self.shard_health else 0,
            (
                self.invariants.violations_total()
                if self.invariants is not None else 0
            ),
            int(host_ms), int(dev_ms), xfer_top,
            q_margin, q_regret,
            self._replica_of, self.conflicts_total,
            tl_anoms, tl_lag,
        )

    def prewarm(self, widths: Optional[Sequence[int]] = None,
                pod_factory: Optional[Callable[[int], Pod]] = None) -> Dict[int, float]:
        """Pre-pay the engine's XLA compiles for every batch width the
        runtime can dispatch — the AIMD pow2 ladder (shared with bench
        warmup via codec.schema.aimd_pow2_widths) plus the express width —
        against the CURRENT snapshot shape, so the first cycle at each
        width serves traffic instead of stalling on a compile.  With a
        persistent compile cache (utils/compilecache.py) warm, each width
        is a cache hit and this is seconds, not minutes.

        Runs the engine on throwaway pods and discards the result: nothing
        commits, the rotation counter does not advance, and the resident
        device snapshot ends exactly as a normal cycle would leave it.
        Returns {width: seconds}.

        `pod_factory(i) -> Pod` should build a pod REPRESENTATIVE of the
        live workload: jit executables are keyed on every PodBatch leaf
        shape, and per-pod pad dims (selector/affinity/port/volume axes)
        grow from the pods actually encoded — warming with pods shaped
        differently from traffic pre-grows the wrong dims and the first
        real batch at each width still compiles.  Default: minimal
        cpu-request-only pods (right for homogeneous simple workloads)."""
        from kubernetes_tpu.api.factory import make_pod
        from kubernetes_tpu.codec.schema import _pow2, aimd_pow2_widths
        from kubernetes_tpu.models.batched import encode_batch_ports

        cfg = self.config
        if widths is None:
            widths = aimd_pow2_widths(
                cfg.batch_size_min if cfg.adaptive_batch else cfg.batch_size,
                cfg.batch_size,
            )
            if cfg.express_lane:
                widths = sorted(
                    set(widths) | {_pow2(max(1, cfg.express_batch_size))}
                )
        enc = self.cache.encoder
        fn = (
            self._speculative_fn
            if self._speculative_fn is not None
            else self._schedule_fn
        )
        if pod_factory is None:
            def pod_factory(i: int) -> Pod:  # noqa: F811 — default factory
                return make_pod(f"prewarm-{i}", cpu="1m")
        # extra-mask/score presence also selects a jit variant: with
        # filter/prioritize extenders or tensor framework plugins
        # configured, every live cycle passes non-None arrays — warm THAT
        # variant (all-true mask / zero score match the no-op fan-out).
        # Nominated-pod cycles still pick a transient different variant;
        # those are rare and self-limiting, not the steady state.
        fwk = self.framework
        want_mask = any(
            e.config.filter_verb or e.config.prioritize_verb
            for e in self.extenders
        ) or bool(fwk is not None and fwk.tensor_filter_plugins)
        want_score = any(
            e.config.filter_verb or e.config.prioritize_verb
            for e in self.extenders
        ) or bool(fwk is not None and fwk.tensor_score_plugins)
        timings: Dict[int, float] = {}
        for w in widths:
            t0 = time.monotonic()
            pods = [pod_factory(i) for i in range(w)]
            # the width override pins each warm batch to its own pow2
            # shape WITHOUT growing the sticky dims.B floor — runtime
            # width selection stays exactly as it would be unwarmed
            with self.cache._lock, enc.batch_width(w):
                # in-batch affinity state exactly as _encode_and_dispatch
                # builds it: its presence selects a DIFFERENT traced
                # variant, so an affinity-carrying pod_factory must warm
                # that one (and the encode ordering matters — novel term
                # topology keys register before the TP-wide tensors cut)
                aff_state = (
                    encode_batch_affinity(enc, pods)
                    if len(pods) > 1 and batch_has_pod_affinity(pods)
                    else None
                )
                batch = enc.encode_pods(pods)
                ports = encode_batch_ports(enc, pods)
                cluster, _ = self.cache.snapshot()
                dirty_rows = (
                    enc.take_dirty_rows() if self._hub is None else None
                )
            dev_cluster = self._device_update(cluster, dirty_rows)
            B, N = batch.n_pods, cluster.n_nodes
            extra_mask = np.ones((B, N), bool) if want_mask else None
            extra_score = (
                np.zeros((B, N), np.float32) if want_score else None
            )
            # index instead of unpack: the attribution variant returns a
            # third output this warm launch discards
            hosts = fn(
                dev_cluster, batch, ports, np.int32(self._last_index),
                None, extra_mask, extra_score, aff_state,
            )[0]
            jax.block_until_ready(hosts)
            timings[w] = time.monotonic() - t0
            klog.V(1).infof(
                "prewarm: width %d compiled in %.2fs", w, timings[w]
            )
        return timings

    @property
    def pipeline_pending(self) -> bool:
        """True while a dispatched batch awaits its commit tail (the
        public liveness predicate for drain loops)."""
        return self._in_flight is not None

    def flush_pipeline(self) -> int:
        """Drain the double-buffer slot: fetch + commit any in-flight
        pipelined batch.  No-op when nothing is in flight.  Returns the
        number of pods placed from the drain."""
        inf, self._in_flight = self._in_flight, None
        if inf is None:
            return 0
        results = self._commit_tail(self._commit_state_or_requeue(inf))
        return sum(1 for r in results if r.node is not None)

    def _run_pipelined(self, pods: Sequence[Pod]) -> int:
        """Double-buffered cycle: apply the in-flight batch's STATE half
        (fetch + batched assume — the part the next snapshot must see),
        dispatch the new batch, then run the previous batch's side-effect
        tail while the device computes.  Device idle time shrinks to the
        fetch->dispatch gap (assume + encode), and the per-pod Python tail
        (binds, events, metrics, preemption) hides behind device compute."""
        prev, self._in_flight = self._in_flight, None
        staged: Optional[_Staged] = None
        dispatched = False
        try:
            if prev is not None:
                staged = self._commit_state_or_requeue(prev)
            self._in_flight = self._encode_and_dispatch(pods)
            dispatched = True
        finally:
            if not dispatched:
                # batch k+1 was popped but never reached the device
                # (batch k's ready-fence raised, or the dispatch itself
                # did): requeue it — popped pods must never be lost
                self.queue.add_unschedulable_batch(
                    list(pods), self.queue.scheduling_cycle
                )
            # batch k's tail MUST run even if batch k+1's dispatch raises:
            # its losers were already popped from the queue (the requeue
            # happens in the tail) and its winners sit assumed-but-unbound
            results = [] if staged is None else self._commit_tail(staged)
        return sum(1 for r in results if r.node is not None)

    def run_once(self, timeout: float = 0.1) -> int:
        """Pop one cycle's batch and schedule it; returns the number of
        pods PLACED (both the gang and plain paths count placements).

        With config.pipeline_commit, plain batches double-buffer: the call
        dispatches this batch and returns the PREVIOUS batch's placements
        (flush_pipeline drains the last one); gang cycles and empty polls
        drain the pipeline first so snapshots never go stale."""
        # program loads count from here: a compile between cycles
        # (prewarm, a caller's own jit) is no cycle's
        self._loads_seen = program_loads()
        with self._phase_span(None, "observe"):
            self._maybe_heartbeat()
            # idle-path timeline tick: an empty queue must
            # still sample — the commit tail only runs on committed
            # cycles, and a quiet interval is exactly when a breaker/SLO
            # excursion needs surrounding samples.  Cadence-gated inside
            # the store, so a busy loop that just sampled in the commit
            # tail pays one monotonic read here.
            if self.timeline is not None:
                t_tl = time.perf_counter()
                try:
                    self.timeline.maybe_sample()
                except Exception as e:  # noqa: BLE001
                    klog.errorf("timeline idle tick failed: %s", e)
                finally:
                    m.TIMELINE_SECONDS.inc(time.perf_counter() - t_tl)
        self._maybe_probe_shards()
        with self._phase_span(None, "pop"):
            express = self.config.express_lane
            # tiered mode only adds the kwarg (an express arrival
            # interrupts the bulk wait so the express cycle below runs
            # immediately), and only for a queue that actually has tier
            # lanes — a caller-owned duck-typed queue without them never
            # sees it
            pop_kw = (
                {"yield_to_express": True}
                if express and hasattr(self.queue, "pop_express_batch")
                else {}
            )
            if self._replica_of > 1:
                # queue-sharded replica: drain only this
                # replica's stable hash-shard — pops are disjoint across
                # replicas by construction, and every requeue of a popped
                # pod lands back on this shard
                pop_kw.update(shard=self._replica_id, of=self._replica_of)
            pods = self.queue.pop_batch(
                # adaptive mode pops at the CURRENT AIMD width; static mode
                # keeps the configured batch size
                self._cur_batch if self.config.adaptive_batch
                else self.config.batch_size,
                # with a batch in flight, don't block in the pop: its
                # binds/events/requeues must not wait out the poll
                # timeout when the queue momentarily empties (trickle
                # arrival, burst tails)
                0.0 if self.pipeline_pending else timeout,
                self.config.batch_window_s,
                **pop_kw,
            )
            if self.invariants is not None:
                self.invariants.note_popped(
                    pods, self.queue.scheduling_cycle
                )
        # express lane between the bulk pop and the bulk dispatch: pending
        # latency-sensitive pods schedule (and commit) BEFORE this cycle's
        # bulk batch, and at most one small express batch runs per
        # iteration (the bulk lane's starvation guard)
        try:
            n_express = self._run_express() if express else 0
        except BaseException:
            # the just-popped bulk batch is held only in this frame: an
            # express-cycle failure must not strand it (popped pods are
            # never lost; the express cycle's own pods were requeued by
            # schedule_cycle's guard)
            self.queue.add_unschedulable_batch(
                list(pods), self.queue.scheduling_cycle
            )
            raise
        # the AIMD deadline window starts AFTER the express cycle: express
        # work must not read as a bulk overrun and shrink the bulk batch
        t_cycle0 = time.monotonic()
        if not pods:
            # idle poll: drain any in-flight batch so binds/events/requeues
            # don't wait for the next arrival; idle cycles also DECAY the
            # adaptive batch width (no pressure -> back toward baseline,
            # even when the last pop emptied the queue in one gulp)
            n = self.flush_pipeline()
            self._adapt_batch(0.0)
            return n + n_express
        # gang-eligibility is conservative: extenders and framework
        # plugins enforce verdicts the gang launch cannot consult, and an
        # outstanding preemption nomination must not be absorbed by a
        # gang (the plain path's two-pass protection, scheduler.py
        # nominated handling) — any of these routes the members through
        # the plain cycle (no atomicity) rather than risk a placement
        # the normal path would reject.  The same demotion applies while
        # the device breaker is not closed: the gang launch has its own
        # device path with no degraded engine, so during an outage
        # members schedule as plain pods (liveness over atomicity)
        # through the CPU fallback.
        gang_eligible = (
            not self.extenders
            and self.framework is None
            and not self.queue.nominated_pods()
            and (
                self.device_health.device_available
                or not self.config.cpu_fallback
            )
            # replica mode demotes gangs to plain pods (no atomicity):
            # the gang launch snapshots and commits outside the
            # sequenced reconciler section, so its claims could race a
            # sibling's — same liveness-over-atomicity policy as the
            # extender/breaker demotions above
            and self._replica_of == 1
        )
        plain = [p for p in pods
                 if not gang_eligible or self.POD_GROUP_LABEL not in p.labels]
        grouped: dict = {}
        if gang_eligible:
            for p in pods:
                gname = p.labels.get(self.POD_GROUP_LABEL)
                if gname is not None:
                    grouped.setdefault((p.namespace, gname), []).append(p)
        n = 0
        if grouped:
            # gangs first: they were popped in priority order and the
            # plain sub-cycle must not strip capacity from them.  Gang
            # launches snapshot the cache directly, so any in-flight
            # pipelined batch must land its state first
            n += self.flush_pipeline()
            from kubernetes_tpu.models.gang import GangScheduler, PodGroup

            cycle = self.queue.scheduling_cycle
            gangs = []
            for (ns, gname), members in grouped.items():
                mm = 0
                for p in members:
                    try:
                        mm = max(mm, int(
                            p.labels.get(self.POD_GROUP_MIN_MEMBER, 0)))
                    except ValueError:
                        pass
                gangs.append(
                    (PodGroup(gname, namespace=ns, min_member=mm), members)
                )
            t_cycle = time.monotonic()
            try:
                results = GangScheduler(self).schedule_gangs(gangs)
            except BaseException as e:
                # popped gang members must never be lost — but
                # schedule_gangs commits gang-by-gang, so members of
                # gangs that already committed are ASSUMED+BOUND: record
                # their success and recover only the genuinely unplaced
                # ones (re-scheduling a bound pod would double-bind and
                # double-charge the cache).  A CLASSIFIED device fault
                # feeds the breaker and demotes the unplaced members to
                # THIS cycle's plain path (which owns retry/degrade);
                # anything else requeues them and propagates.
                enc = self.cache.encoder
                unplaced = []
                for _, ms in gangs:
                    for p in ms:
                        rec = enc.pods.get((p.namespace, p.name))
                        if (
                            rec is not None
                            and rec.node_row >= 0
                            and rec.pod is not None
                            and rec.pod.spec.node_name
                        ):
                            node = rec.pod.spec.node_name
                            n += 1
                            self._outcome_totals["placed"] += 1
                            self.results.append(ScheduleResult(p, node))
                            self._record_scheduled(
                                p, node, time.monotonic() - t_cycle
                            )
                        else:
                            unplaced.append(p)
                fc = classify_device_error(e)
                if fc is None:
                    self.queue.add_unschedulable_batch(unplaced, cycle)
                    raise
                self._note_device_fault(fc, e, "gang")
                if not self._note_shard_fault(self._shard_of(e), fc):
                    self.device_health.record_failure(fc)
                    self._device_invalidate()
                plain = plain + unplaced
                gangs, results = [], []
            for (group, members), (nodes, placed) in zip(gangs, results):
                if nodes is None:
                    # gang did not reach min_member: members park in the
                    # unschedulableQ with backoff like any failed pod,
                    # with the same failure bookkeeping
                    for p in members:
                        self.queue.add_unschedulable(p, cycle)
                        self.results.append(ScheduleResult(p, None))
                        self._outcome_totals["unschedulable"] += 1
                        m.SCHEDULE_ATTEMPTS.inc(result=m.UNSCHEDULABLE)
                        self.recorder.eventf(
                            "Pod", p.namespace, p.name,
                            EVENT_TYPE_WARNING, "FailedScheduling",
                            "pod group %s/%s: %d/%d members placed",
                            group.namespace, group.name, placed,
                            group.min_member or len(members),
                        )
                    continue
                n += placed
                for p, node in zip(members, nodes):
                    if not node:
                        # surplus member beyond min_member was NOT bound:
                        # requeue (still-pending pod, not a failure) —
                        # shed-exempt like every requeue of a popped pod
                        self.queue.readd(p)
                        continue
                    # success bookkeeping identical to the plain path:
                    # Scheduled event, counters, e2e histogram, results
                    self._outcome_totals["placed"] += 1
                    self.results.append(ScheduleResult(p, node))
                    self._record_scheduled(
                        p, node, time.monotonic() - t_cycle
                    )
        if plain:
            if (
                self.config.pipeline_commit
                and self.config.batched_commit
                and self.framework is None
            ):
                n += self._run_pipelined(plain)
            else:
                n += sum(
                    1 for r in self.schedule_cycle(plain) if r.node is not None
                )
        # the cycle deadline budget covers the SCHEDULING work (encode ->
        # commit), not the pop wait — an idle poll must not read as an
        # overrun and shrink the batch
        self._adapt_batch(time.monotonic() - t_cycle0)
        return n + n_express

    def run(self) -> None:
        """wait.Until(scheduleOne) analog (scheduler.go:250-256)."""
        while not self._stop.is_set():
            self.run_once(timeout=0.5)
        self.flush_pipeline()

    def stop(self) -> None:
        self._stop.set()
        self.queue.close()
