"""Cluster + device telemetry hub and multi-window SLO burn alerting.

PRs 5 and 7 made the scheduler's *decisions* observable (spans, flight
recorder, ledger, attribution); this module reports the *state* — of the
fleet and of the device — and watches the SLOs an operator actually
pages on:

  * **Cluster analytics.**  Every `telemetryIntervalCycles` the hub
    dispatches ops/analytics.cluster_analytics as a side-launch over the
    DEVICE-RESIDENT snapshot buffers (DeviceSnapshotCache.resident —
    zero extra upload traffic; one tiny D2H for the ~50-float result),
    materializing the PREVIOUS launch's result first so the scheduling
    thread never blocks on analytics compute.  Degraded cycles (breaker
    open, resident buffers invalidated) fall back to the bit-exact
    numpy reference over the cycle's host snapshot.
  * **Device runtime.**  Per-device HBM live/peak/limit bytes via
    `device.memory_stats()` (a no-op on backends without stats — the
    CPU path reports nothing rather than zeros), compile-cache hit/miss
    and cumulative backend-compile seconds (utils/compilecache.py
    jax.monitoring listeners), and a launch-duration EWMA per
    executable batch width.
  * **SLO burn rates.**  The SRE-workbook multi-window scheme: each
    objective tracks good/bad events over a FAST and a SLOW window;
    burn = (bad fraction) / (error budget).  An alert fires when BOTH
    windows exceed the threshold (fast alone is noise, slow alone is
    stale), incrementing scheduler_slo_burn_alerts_total and dumping a
    throttled `slo_burn` flight-recorder postmortem via the scheduler's
    postmortem seam; the alert re-arms when the fast window recovers.

Samples land in a bounded time-series ring served at GET /debug/cluster
(health server + apiserver, ?limit= + the shared 4MB response cap).
`HUB`/`get_default`/`set_default` follow the flightrecorder.RECORDER
pattern: a Scheduler built with config.telemetry installs its hub as the
process default so the debug endpoints serve it without extra wiring.

The reference has no analog (kube-state-metrics + Prometheus recording
rules live OUTSIDE the scheduler); here the snapshot is already resident
on the engine's device, so fleet analytics are one fused reduction —
the same utilization/fragmentation criteria ROADMAP items 2 and 4 score
candidate packings with.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from kubernetes_tpu.codec.transfer import daemon_worker
from kubernetes_tpu.ops.analytics import (
    analytics_to_dict,
    cluster_analytics_auto,
    cluster_analytics_np,
)
from kubernetes_tpu.utils import metrics as m
from kubernetes_tpu.utils.compilecache import (
    compile_stats,
    install_metrics_listeners,
)

# the snapshot fields the analytics launch consumes, in kernel-argument
# order (DeviceSnapshotCache.resident is keyed on these names)
ANALYTICS_FIELDS = ("allocatable", "requested", "valid")


def device_memory_stats() -> Dict[str, Dict[str, int]]:
    """{device id: {in_use, peak, limit}} from device.memory_stats(),
    updating the ktpu_device_hbm_bytes gauges.  Backends without stats
    (XLA:CPU returns None) yield {} — the documented no-op fallback, so
    callers can invoke this unconditionally on any backend."""
    import jax

    out: Dict[str, Dict[str, int]] = {}
    try:
        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 — backend init failure is not ours
        return out
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — per-device API optional
            stats = None
        if not stats:
            continue
        entry = {
            "in_use": int(stats.get("bytes_in_use", 0)),
            "peak": int(stats.get("peak_bytes_in_use", 0)),
            "limit": int(stats.get("bytes_limit", 0)),
        }
        out[str(getattr(d, "id", len(out)))] = entry
        for kind, v in entry.items():
            m.DEVICE_HBM.set(v, device=str(getattr(d, "id", 0)), kind=kind)
    return out


# ------------------------------------------------------------------- SLO


@dataclass(frozen=True)
class SLOObjective:
    """One service-level objective watched by the burn evaluator.

    `objective` is the target GOOD fraction (0.99 = a 1% error budget);
    burn rate = observed bad fraction / (1 - objective), so burn 1.0
    means spending the budget exactly as fast as allowed."""

    name: str
    objective: float = 0.99
    fast_window_s: float = 60.0
    slow_window_s: float = 300.0
    burn_threshold: float = 1.0

    @staticmethod
    def from_dict(d: dict) -> "SLOObjective":
        """The KubeSchedulerConfiguration `sloObjectives` entry shape."""
        return SLOObjective(
            name=str(d["name"]),
            objective=float(d.get("objective", 0.99)),
            fast_window_s=float(d.get("fastWindowSeconds", 60.0)),
            slow_window_s=float(d.get("slowWindowSeconds", 300.0)),
            burn_threshold=float(d.get("burnThreshold", 1.0)),
        )


# the objectives a telemetry-enabled scheduler watches by default:
#  * cycle_deadline — cycles finishing inside cycleDeadlineSeconds
#    (observed only when a deadline is configured; the express-lane p99
#    story rides this: the deadline is the per-cycle latency budget)
#  * goodput — offered pods served (scheduled OR a verdict) vs shed
#  * degraded — cycles served by the device fast path vs the CPU
#    fallback (breaker-open time, in cycle units)
DEFAULT_OBJECTIVES: Tuple[SLOObjective, ...] = (
    SLOObjective("cycle_deadline", objective=0.99),
    SLOObjective("goodput", objective=0.99),
    SLOObjective("degraded", objective=0.99),
)


def build_objectives(raw: Optional[list]) -> Tuple[SLOObjective, ...]:
    """Config `sloObjectives` (list of dicts) -> objectives; None/empty
    keeps the defaults."""
    if not raw:
        return DEFAULT_OBJECTIVES
    return tuple(
        o if isinstance(o, SLOObjective) else SLOObjective.from_dict(o)
        for o in raw
    )


class _Window:
    """One rolling window: a deque of (t, good, bad) plus RUNNING sums
    maintained on add/expiry, so a burn-rate read is O(1) instead of a
    rescan of every event in the window — at production cycle rates a
    300s window holds tens of thousands of events, and the evaluator
    runs every committed cycle."""

    __slots__ = ("seconds", "events", "good", "bad")

    def __init__(self, seconds: float):
        self.seconds = float(seconds)
        self.events: deque = deque()
        self.good = 0.0
        self.bad = 0.0

    def add(self, t: float, good: float, bad: float) -> None:
        self.events.append((t, good, bad))
        self.good += good
        self.bad += bad

    def prune(self, now: float) -> None:
        horizon = now - self.seconds
        ev = self.events
        while ev and ev[0][0] < horizon:
            _, g, b = ev.popleft()
            self.good -= g
            self.bad -= b
        if not ev:
            # zero the sums whenever the window empties so float
            # accumulation error cannot drift them over long uptimes
            self.good = 0.0
            self.bad = 0.0

    def burn(self, budget: float) -> float:
        total = self.good + self.bad
        frac = self.bad / total if total > 0 else 0.0
        return frac / budget


class SLOEvaluator:
    """Multi-window burn-rate math over per-objective good/bad event
    streams.  Thread-safe: the scheduling thread observes/evaluates
    while HTTP reader threads (snapshot via /debug/cluster) read burn
    rates; `clock` keeps the window tests deterministic."""

    def __init__(
        self,
        objectives: Tuple[SLOObjective, ...] = DEFAULT_OBJECTIVES,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.objectives: Dict[str, SLOObjective] = {
            o.name: o for o in objectives
        }
        self._clock = clock
        self._lock = threading.Lock()
        # name -> (fast window, slow window) with rolling sums
        self._windows: Dict[str, Tuple[_Window, _Window]] = {
            name: (_Window(o.fast_window_s), _Window(o.slow_window_s))
            for name, o in self.objectives.items()
        }
        # alert hysteresis: fire once on crossing, re-arm when the FAST
        # window recovers (so a sustained burn is one alert, not one per
        # cycle — the recorder's per-trigger throttle backstops this)
        self._alert_active: Dict[str, bool] = {}
        # last rates computed by evaluate(): snapshot() reuses them so a
        # per-cycle sample does not recompute every objective twice
        self._last_rates: Dict[str, Tuple[float, float]] = {}
        self.alerts_total = 0

    def observe(self, name: str, good: float = 0.0, bad: float = 0.0,
                t: Optional[float] = None) -> None:
        """Record `good` successes and `bad` budget-burning events for
        one objective (unknown names are ignored so callers need not
        mirror the configured set)."""
        windows = self._windows.get(name)
        if windows is None or (good == 0.0 and bad == 0.0):
            return
        now = self._clock() if t is None else t
        with self._lock:
            for w in windows:
                w.add(now, float(good), float(bad))
                w.prune(now)

    def burn_rates(self, name: str,
                   t: Optional[float] = None) -> Tuple[float, float]:
        """(fast, slow) burn rates for one objective: bad fraction over
        the window divided by the error budget; 0.0 with no events."""
        obj = self.objectives[name]
        now = self._clock() if t is None else t
        budget = max(1.0 - obj.objective, 1e-9)
        fast, slow = self._windows[name]
        with self._lock:
            fast.prune(now)
            slow.prune(now)
            return fast.burn(budget), slow.burn(budget)

    def evaluate(self, t: Optional[float] = None) -> List[Tuple[str, float, float]]:
        """Update every objective's burn gauges; return the objectives
        whose alert NEWLY fired (both windows over threshold while the
        alert was armed)."""
        fired: List[Tuple[str, float, float]] = []
        for name, obj in self.objectives.items():
            fast, slow = self.burn_rates(name, t)
            self._last_rates[name] = (fast, slow)
            m.SLO_BURN_RATE.set(fast, objective=name, window="fast")
            m.SLO_BURN_RATE.set(slow, objective=name, window="slow")
            burning = (
                fast >= obj.burn_threshold and slow >= obj.burn_threshold
            )
            if burning and not self._alert_active.get(name, False):
                self._alert_active[name] = True
                self.alerts_total += 1
                m.SLO_ALERTS.inc(objective=name)
                fired.append((name, fast, slow))
            elif fast < obj.burn_threshold:
                self._alert_active[name] = False
        return fired

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """{objective: {fast, slow, threshold, objective}} for samples —
        served from evaluate()'s cached rates when available (the
        per-cycle sampling path must not rescan the deques twice)."""
        out: Dict[str, Dict[str, float]] = {}
        for name, obj in self.objectives.items():
            cached = self._last_rates.get(name)
            fast, slow = (
                cached if cached is not None else self.burn_rates(name)
            )
            out[name] = {
                "fast": round(fast, 4),
                "slow": round(slow, 4),
                "threshold": obj.burn_threshold,
                "objective": obj.objective,
            }
        return out


# ------------------------------------------------------------------- hub


class TelemetryHub:
    """Per-scheduler telemetry aggregation point.

    The scheduling thread calls `on_cycle` once per committed cycle
    (runtime/scheduler.py stamps the call's cost into
    scheduler_telemetry_seconds_total — the <2% budget perf_smoke pins);
    readers (metrics scrape, /debug/cluster, heartbeat, bench) come from
    other threads and take the hub lock only around ring/summary state.

    Analytics cadence is AMORTIZED: on each due cycle the hub dispatches
    one launch and hands its output to the `ktpu-telemetry` worker
    thread, which fetches it (a ~50-float D2H), sets the cluster gauges
    and appends the ring sample — the scheduling thread never waits on
    analytics compute nor builds the sample."""

    def __init__(
        self,
        interval_cycles: int = 1,
        objectives: Tuple[SLOObjective, ...] = DEFAULT_OBJECTIVES,
        ring_capacity: int = 512,
        postmortem: Optional[Callable[[str, str], None]] = None,
        clock: Callable[[], float] = time.monotonic,
        ewma_alpha: float = 0.2,
    ):
        self.interval_cycles = max(1, int(interval_cycles))
        self.slo = SLOEvaluator(objectives, clock=clock)
        self._postmortem = postmortem
        self._clock = clock
        self._ewma_alpha = float(ewma_alpha)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, int(ring_capacity)))
        # set once the newest dispatched analytics launch is a sample
        self._landed = threading.Event()
        self._landed.set()
        self.analytics: Optional[dict] = None  # last materialized sample
        self.analytics_cycle = -1
        self.samples_total = 0
        self._cycles_since_dispatch = self.interval_cycles  # first is due
        self._launch_ewma: Dict[int, float] = {}
        self._pressure: Optional[Dict[str, int]] = None
        self.last_hbm: Dict[str, Dict[str, int]] = {}
        # elastic-ladder state (ISSUE 10): live mesh width, rung,
        # per-shard breaker states, invariant-checker totals.  Stamped
        # FRESH by the scheduler every committed cycle — the hub must
        # never cache startup topology, because the mesh can now change
        # at runtime (shrink/restore) and a stale width/sharding here
        # would misreport every sample after the first rebuild.
        self._mesh: Optional[dict] = None
        self.cycles_total = 0
        install_metrics_listeners()

    # ------------------------------------------------------ hot-path API

    def note_launch(self, width: int, seconds: float) -> None:
        """Fold one device launch window (dispatch -> copy-complete)
        into the per-width EWMA.  Locked: HTTP reader threads iterate
        the width map while the scheduling thread inserts new widths."""
        with self._lock:
            prev = self._launch_ewma.get(width)
            cur = (
                seconds if prev is None
                else prev + self._ewma_alpha * (seconds - prev)
            )
            self._launch_ewma[width] = cur
        m.LAUNCH_EWMA.set(cur, width=str(width))

    def prune_widths(self, keep) -> None:
        """Retire EWMA series for widths no longer dispatchable (an AIMD
        cap change) so the labeled family stays bounded."""
        keep = set(int(w) for w in keep)
        with self._lock:
            stale = [w for w in self._launch_ewma if w not in keep]
            for w in stale:
                del self._launch_ewma[w]
        for w in stale:
            m.LAUNCH_EWMA.remove(width=str(w))

    def _ewma_snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {
                str(w): round(s, 6)
                for w, s in sorted(self._launch_ewma.items())
            }

    def on_cycle(
        self,
        cycle: int,
        tier: str,
        cycle_s: float,
        placed: int,
        unschedulable: int,
        shed: int = 0,
        degraded: bool = False,
        deadline_s: float = 0.0,
        resident: Optional[tuple] = None,
        host_snapshot: Optional[tuple] = None,
        span=None,
    ) -> None:
        """One committed scheduling cycle's telemetry: SLO events, burn
        evaluation (firing slo_burn postmortems through the scheduler's
        seam), pending-pressure gauges, and the amortized analytics
        side-launch.  `resident` is DeviceSnapshotCache.resident(
        ANALYTICS_FIELDS) — None routes this interval through the numpy
        reference over `host_snapshot` (the degraded path)."""
        self.cycles_total += 1
        now = self._clock()
        if deadline_s > 0:
            over = cycle_s > deadline_s
            self.slo.observe(
                "cycle_deadline", good=0.0 if over else 1.0,
                bad=1.0 if over else 0.0, t=now,
            )
        served = placed + unschedulable
        self.slo.observe("goodput", good=float(served), bad=float(shed),
                         t=now)
        self.slo.observe(
            "degraded", good=0.0 if degraded else 1.0,
            bad=1.0 if degraded else 0.0, t=now,
        )
        for name, fast, slow in self.slo.evaluate(now):
            if self._postmortem is not None:
                self._postmortem(
                    "slo_burn",
                    f"objective {name}: burn fast={fast:.1f} "
                    f"slow={slow:.1f} >= "
                    f"{self.slo.objectives[name].burn_threshold}",
                )
        self._cycles_since_dispatch += 1
        if self._cycles_since_dispatch < self.interval_cycles:
            return
        self._cycles_since_dispatch = 0
        # the span carries the newest sample that has landed (the
        # previous interval's, unless the worker is still on it)
        a = self.analytics
        if a is not None and span is not None:
            span.annotate(
                cluster_util_cpu=a["utilization"]["cpu"]["mean"],
                cluster_fragmentation=a["fragmentation"],
            )
        if resident is not None:
            # mesh-aware dispatch (ops/analytics.py): sharded resident
            # buffers reduce per-shard with a cross-shard fold — the full
            # node tensor never gathers to one chip — and stay bit-exact
            # vs the numpy reference; single-chip buffers take the
            # classic kernel unchanged
            out = cluster_analytics_auto(*resident)
            source = "device"
        elif host_snapshot is not None:
            out = cluster_analytics_np(*host_snapshot)
            source = "host"
        else:
            return
        # the fetch, the gauges and the ring sample run on the telemetry
        # worker, in dispatch order: the scheduling thread neither waits
        # for the launch nor pays for turning it into a sample
        landed = threading.Event()
        self._landed = landed
        pending = (cycle, tier, out, source)
        daemon_worker("ktpu-telemetry").submit(
            lambda: self._materialize(pending, landed)
        )

    def record_mesh(
        self,
        width: int,
        full_width: int = 0,
        rung: str = "single_chip",
        shard_states: Optional[Dict[int, str]] = None,
        invariants: Optional[dict] = None,
    ) -> None:
        """Per-cycle ladder facts from the scheduler: live mesh width vs
        the startup width, the rung serving cycles, each shard's breaker
        state, and the invariant checker's totals.  Joined into every
        /debug/cluster sample and the summary."""
        with self._lock:
            self._mesh = {
                "width": int(width),
                "full_width": int(full_width),
                "rung": rung,
                "shards": (
                    {str(k): v for k, v in shard_states.items()}
                    if shard_states else None
                ),
                "invariants": invariants,
            }

    def record_pressure(self, bulk: int, express: int, parked: int) -> None:
        """Per-tier pending pressure (queue depths, stamped by the
        scheduler alongside on_cycle)."""
        m.PENDING_PRESSURE.set(float(bulk), tier="bulk")
        m.PENDING_PRESSURE.set(float(express), tier="express")
        m.PENDING_PRESSURE.set(float(parked), tier="parked")
        with self._lock:
            self._pressure = {
                "bulk": int(bulk), "express": int(express),
                "parked": int(parked),
            }

    # ------------------------------------------------------ materialize

    def _await_samples(self) -> None:
        """Wait until the newest dispatched analytics launch is a ring
        sample (readers call this so they see the latest snapshot)."""
        self._landed.wait()

    def _materialize(self, pending, landed: threading.Event) -> None:
        """Worker job: fetch one analytics launch (~50 floats) into a ring
        sample, updating the cluster gauges."""
        try:
            self._sample(*pending)
        finally:
            landed.set()

    def _sample(self, cycle, tier, out, source) -> None:
        try:
            host = type(out)(
                *(np.asarray(x) for x in _leaves_in_order(out))
            )
        except Exception:  # noqa: BLE001 — a faulted launch loses ONE
            #                 sample, never the telemetry stream
            return
        a = analytics_to_dict(host)
        self._set_cluster_gauges(a)
        sample = {
            "time": time.time(),
            "cycle": cycle,
            "tier": tier,
            "source": source,
            "analytics": a,
            "pending": self._pressure,
            "mesh": self._mesh,
            "hbm": device_memory_stats(),
            "compile": compile_stats(),
            "launch_ewma_s": self._ewma_snapshot(),
            "slo": self.slo.snapshot(),
        }
        with self._lock:
            self.last_hbm = sample["hbm"]
            self.analytics = a
            self.analytics_cycle = cycle
            self._ring.append(sample)
            self.samples_total += 1
        m.TELEMETRY_SAMPLES.inc()

    @staticmethod
    def _set_cluster_gauges(a: dict) -> None:
        for res, stats in a["utilization"].items():
            for stat, v in stats.items():
                m.CLUSTER_UTILIZATION.set(v, resource=res, stat=stat)
        for res, v in a["largest_free"].items():
            m.CLUSTER_LARGEST_FREE.set(v, resource=res)
        for res, v in a["stranded"].items():
            m.CLUSTER_STRANDED.set(v, resource=res)
        m.CLUSTER_FRAGMENTATION.set(a["fragmentation"])
        m.CLUSTER_IMBALANCE.set(a["imbalance"])
        for i, n in enumerate(a["occupancy"]):
            m.CLUSTER_OCCUPANCY.set(float(n), decile=str(i))
        m.CLUSTER_NODES.set(float(a["nodes"]))
        m.CLUSTER_PODS_RUNNING.set(a["pods_running"])

    # ----------------------------------------------------------- readers

    def hbm_in_use(self) -> int:
        """Total live bytes across devices from the last sample (0 on
        statless backends) — the heartbeat's HBM figure."""
        with self._lock:
            return sum(d.get("in_use", 0) for d in self.last_hbm.values())

    def summary(self) -> dict:
        """Latest materialized analytics + hub accounting — the bench
        `cluster_health` stage body."""
        self._await_samples()
        with self._lock:
            out = {
                "analytics": self.analytics,
                "cycle": self.analytics_cycle,
                "samples": self.samples_total,
                "cycles": self.cycles_total,
                "pending": self._pressure,
                "mesh": self._mesh,
                "hbm": dict(self.last_hbm),
                "launch_ewma_s": {
                    str(w): round(s, 6)
                    for w, s in sorted(self._launch_ewma.items())
                },
            }
        out["compile"] = compile_stats()
        out["slo"] = self.slo.snapshot()
        return out

    def debug_payload(self, limit: Optional[int] = None) -> dict:
        """GET /debug/cluster body: newest-first bounded sample series +
        the summary.  `limit` keeps the newest n samples (the shared
        debug_body halves it further until the body fits the 4MB cap)."""
        self._await_samples()
        with self._lock:
            samples = list(self._ring)
        if limit is not None and limit >= 0:
            samples = samples[-limit:] if limit else []
        return {
            "summary": self.summary(),
            "samples": samples,
            "interval_cycles": self.interval_cycles,
        }


def _leaves_in_order(out):
    """ClusterAnalytics dataclass leaves in field order (works for both
    the jitted pytree output and the numpy reference)."""
    import dataclasses

    return [getattr(out, f.name) for f in dataclasses.fields(out)]


# process-wide default: the hub /debug/cluster serves when none was
# wired explicitly; a Scheduler built with config.telemetry installs
# its own here.  Replica 0 wins the process default, siblings register
# alongside for /debug/replicas (runtime/defaults.py ProcessDefault —
# the shared install/default/replica-registry discipline)
from kubernetes_tpu.runtime.defaults import ProcessDefault  # noqa: E402

_DEFAULT = ProcessDefault("telemetry", TelemetryHub)


def get_default() -> TelemetryHub:
    return _DEFAULT.get()


def set_default(hub: TelemetryHub, replica: int = 0) -> None:
    _DEFAULT.set(hub, replica)


def replica_instances() -> dict:
    """{replica id: TelemetryHub} of every install this process saw."""
    return _DEFAULT.replicas()


def __getattr__(name):  # legacy alias: telemetry.HUB
    if name == "HUB":
        return _DEFAULT.get()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
