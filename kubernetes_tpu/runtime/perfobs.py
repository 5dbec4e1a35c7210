"""Hot-path performance observatory: host/device time attribution,
transfer accounting, and on-demand XLA profiler capture (ISSUE 11).

The r05 phase counters said dispatch was ~20 ms while fetch+commit burned
~370 ms per 10k pods — but nothing attributed that wall time between host
Python, the wire, and actual device execution, which is exactly the
measurement dispatch/fence work and the learned-scoring loop need.  This
module is that instrument:

  * **Per-cycle cost model.**  The scheduler feeds `on_cycle` one record
    per committed cycle, split by the ready-fence timestamps around the
    existing AsyncFetch/dispatch seams (codec/transfer.py):

      - `host_enqueue`     encode + extender fan-out + launch enqueue
                           (scheduling-thread Python until the dispatch
                           returned with the device still computing)
      - `device_execute`   fetch-worker job start -> computation ready
                           (AsyncFetch's t_start / t_ready stamps)
      - `d2h_materialize`  the residual host copy after ready (t_ready ->
                           t_done; with the async copy prefetch this is
                           usually ~0)
      - `host_stall`       the scheduling thread's residual wait at the
                           ready fence (phase_seconds' host_stall)
      - `host_commit`      state commit + bind/event tail + ledger +
                           telemetry (the full host tail)

    host_enqueue + host_stall + host_commit partitions the cycle's wall
    clock (the reconciliation tests/test_perfobs.py pins); the device
    pair OVERLAPS the host phases — that overlap working is the async
    result path doing its job.  Per (phase, executable width) the
    observatory maintains an EWMA matrix — the generalization of PR 8's
    launch EWMA to the whole cycle — exported as
    scheduler_perf_phase_ewma_seconds{phase,width} and at /debug/perf.

  * **Transfer accounting.**  codec/transfer.py notes bytes/calls at
    every wire seam (snapshot upload, dirty-row scatter, batch
    replicate, fetch) from array nbytes with no device sync; the
    scheduler snapshots the totals per cycle and hands the delta here,
    so every sample (and every cycle span) carries what the wire moved.

  * **On-demand profiler capture.**  `ProfilerCapture` wraps
    jax.profiler start/stop in a throttled, bounded window into a
    configurable directory — `GET /debug/profile?seconds=N` on the
    health server and the apiserver.  Where the backend lacks profiler
    support the capture degrades to a graceful no-op.  The scheduler's
    `ktpu.<phase>` ranges (utils/trace.PhaseSpan) make the captured
    device timeline phase-legible.

`OBSERVATORY`/`get_default`/`set_default` follow the flightrecorder
RECORDER pattern: a Scheduler installs its observatory as the process
default so /debug/perf serves it without extra wiring.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Callable, Dict, Optional, Tuple

from kubernetes_tpu.utils import klog
from kubernetes_tpu.utils import metrics as m

# the cost-model phases, in report order.  host_* phases partition the
# cycle's scheduling-thread wall clock; device_execute/d2h_materialize
# are measured on the fetch worker and OVERLAP the host phases.
PHASES = (
    "host_enqueue",
    "device_execute",
    "d2h_materialize",
    "host_stall",
    "host_commit",
)
HOST_PHASES = ("host_enqueue", "host_stall", "host_commit")
DEVICE_PHASES = ("device_execute", "d2h_materialize")


class ProfilerCapture:
    """Throttled, bounded jax.profiler capture window.

    One capture at a time; `min_interval_s` between stop and the next
    start (an operator mashing refresh on /debug/profile must not turn
    the profiler into a load generator); `max_seconds` caps the window
    whatever the query asks.  Backends without profiler support (or a
    jax build without the profiler extra) degrade to a graceful no-op:
    start() reports supported=False instead of raising."""

    def __init__(
        self,
        profile_dir: Optional[str] = None,
        max_seconds: float = 60.0,
        min_interval_s: float = 5.0,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.profile_dir = (
            profile_dir
            or os.environ.get("KTPU_PROFILE_DIR")
            or "/tmp/ktpu_profile"
        )
        self.max_seconds = float(max_seconds)
        self.min_interval_s = float(min_interval_s)
        self._clock = clock
        self._lock = threading.Lock()
        self._active_dir: Optional[str] = None
        self._active_until = 0.0
        self._last_stop: Optional[float] = None
        self.captures_total = 0
        self.last: Optional[dict] = None  # last start/stop outcome

    def start(self, seconds: float) -> dict:
        """Begin a bounded capture; a daemon timer stops it after
        `seconds` (clamped to [0.05, max_seconds]).  Returns a jsonable
        status — started / throttled / in-progress / unsupported —
        never raises (this is a debug endpoint body)."""
        seconds = max(0.05, min(float(seconds), self.max_seconds))
        now = self._clock()
        with self._lock:
            if self._active_dir is not None:
                return {
                    "started": False,
                    "reason": "capture already in progress",
                    "dir": self._active_dir,
                    "retry_after_s": round(
                        max(0.0, self._active_until - now), 2
                    ),
                }
            if (
                self._last_stop is not None
                and now - self._last_stop < self.min_interval_s
            ):
                return {
                    "started": False,
                    "reason": "throttled",
                    "retry_after_s": round(
                        self.min_interval_s - (now - self._last_stop), 2
                    ),
                }
            out_dir = os.path.join(
                self.profile_dir,
                time.strftime("%Y%m%d-%H%M%S") + f"-{self.captures_total}",
            )
            # reserve the slot BEFORE the (possibly slow — profiler
            # server init measures ~10s on some sandboxes) start call,
            # so a concurrent start sees in-progress and status readers
            # never block behind it
            self._active_dir = out_dir
            self._active_until = now + seconds
        try:
            import jax

            os.makedirs(out_dir, exist_ok=True)
            jax.profiler.start_trace(out_dir)
        except Exception as e:  # noqa: BLE001 — no-op where the
            # backend/build lacks profiler support
            with self._lock:
                self._active_dir = None
                self.last = {
                    "started": False, "supported": False, "error": str(e),
                }
                return dict(self.last)
        with self._lock:
            self.last = {
                "started": True, "seconds": seconds, "dir": out_dir,
            }
        t = threading.Timer(seconds, self._stop)
        t.daemon = True
        t.start()
        klog.infof(
            "profiler capture started: %.2fs into %s", seconds, out_dir
        )
        return dict(self.last)

    def _stop(self) -> None:
        with self._lock:
            if self._active_dir is None:
                return
            out_dir, self._active_dir = self._active_dir, None
            self._last_stop = self._clock()
        try:
            import jax

            jax.profiler.stop_trace()
            outcome = {"stopped": True, "dir": out_dir}
        except Exception as e:  # noqa: BLE001 — a failed stop must
            # not wedge the capture state machine
            outcome = {"stopped": False, "error": str(e)}
        with self._lock:
            if outcome.get("stopped"):
                self.captures_total += 1
            self.last = outcome
        klog.infof("profiler capture finished: %s", out_dir)

    def status(self) -> dict:
        with self._lock:
            return {
                "active": self._active_dir is not None,
                "dir": self._active_dir or self.profile_dir,
                "captures_total": self.captures_total,
                "max_seconds": self.max_seconds,
                "min_interval_s": self.min_interval_s,
                "last": dict(self.last) if self.last else None,
            }


class PerfObservatory:
    """Per-scheduler cost-model aggregation point.

    The scheduling thread calls `on_cycle` once per committed cycle
    (runtime/scheduler.py stamps the call's cost into
    scheduler_perfobs_seconds_total — the <2% budget perf_smoke pins);
    readers (/debug/perf, heartbeat, bench) come from other threads and
    take the lock only around ring/summary state."""

    def __init__(
        self,
        ring_capacity: int = 256,
        ewma_alpha: float = 0.2,
        profile_dir: Optional[str] = None,
    ):
        self._lock = threading.Lock()
        self._alpha = float(ewma_alpha)
        # phase -> {width -> ewma seconds}: the phase x executable-width
        # cost matrix (widths are the engine's padded pow2 shapes)
        self._ewma: Dict[str, Dict[int, float]] = {p: {} for p in PHASES}
        self._totals: Dict[str, float] = {p: 0.0 for p in PHASES}
        self._wall_total = 0.0
        self.cycles_total = 0
        self.degraded_total = 0
        self._ring: deque = deque(maxlen=max(1, int(ring_capacity)))
        # heartbeat watermarks: totals at the last heartbeat_window()
        self._hb_host = 0.0
        self._hb_dev = 0.0
        self._hb_xfer: Dict[str, dict] = {}
        self.profiler = ProfilerCapture(profile_dir=profile_dir)

    # ------------------------------------------------------ hot-path API

    def on_cycle(
        self,
        width: int,
        tier: str,
        degraded: bool,
        enqueue_s: float,
        execute_s: float,
        materialize_s: float,
        stall_s: float,
        commit_s: float,
        wall_s: float,
        transfers: Optional[dict] = None,
        trace_id: str = "",
    ) -> None:
        """Fold one committed cycle into the cost model.  `transfers` is
        the cycle's codec.transfer.transfer_delta — what the wire moved
        between this cycle's dispatch and its commit tail."""
        split = {
            "host_enqueue": float(enqueue_s),
            "device_execute": float(execute_s),
            "d2h_materialize": float(materialize_s),
            "host_stall": float(stall_s),
            "host_commit": float(commit_s),
        }
        width = int(width)
        sample = {
            "cycle_wall_s": round(float(wall_s), 6),
            "width": width,
            "tier": tier,
            "degraded": bool(degraded),
            "split_s": {k: round(v, 6) for k, v in split.items()},
            # the wall clock the host split does NOT account for: ~0 on
            # the synchronous path; under pipeline_commit it is the
            # overlap window (the cycle's tail ran while the next batch
            # dispatched), which is the pipeline working, not a leak
            "unaccounted_s": round(
                float(wall_s) - sum(split[p] for p in HOST_PHASES), 6
            ),
            "transfers": transfers or {},
            "trace_id": trace_id,
        }
        with self._lock:
            for phase, v in split.items():
                self._totals[phase] += v
                row = self._ewma[phase]
                prev = row.get(width)
                row[width] = (
                    v if prev is None else prev + self._alpha * (v - prev)
                )
            self._wall_total += float(wall_s)
            self.cycles_total += 1
            if degraded:
                self.degraded_total += 1
            self._ring.append(sample)
        for phase, v in split.items():
            m.PERF_PHASE_EWMA.set(
                self._ewma[phase][width], phase=phase, width=str(width)
            )

    # ----------------------------------------------------------- readers

    def host_device_split(self) -> Dict[str, float]:
        """Cumulative attribution: scheduling-thread host seconds vs
        device-side seconds (the overlapping execute+materialize
        window), plus total cycle wall."""
        with self._lock:
            host = sum(self._totals[p] for p in HOST_PHASES)
            dev = sum(self._totals[p] for p in DEVICE_PHASES)
            return {
                "host_s": round(host, 6),
                "device_s": round(dev, 6),
                "wall_s": round(self._wall_total, 6),
            }

    def heartbeat_window(self) -> Tuple[float, float, str]:
        """(host_ms, dev_ms, top transfer seam) since the LAST call —
        the heartbeat satellite.  The top seam is the direction/seam
        that moved the most bytes in the window ("none" when the wire
        was quiet)."""
        from kubernetes_tpu.codec.transfer import transfer_totals

        xfer = transfer_totals()
        with self._lock:
            host = sum(self._totals[p] for p in HOST_PHASES)
            dev = sum(self._totals[p] for p in DEVICE_PHASES)
            host_ms = (host - self._hb_host) * 1000.0
            dev_ms = (dev - self._hb_dev) * 1000.0
            self._hb_host, self._hb_dev = host, dev
            prev, self._hb_xfer = self._hb_xfer, xfer
        top, top_bytes = "none", 0
        for key, cur in xfer.items():
            delta = cur["bytes"] - prev.get(key, {}).get("bytes", 0)
            if delta > top_bytes:
                top, top_bytes = key, delta
        if top != "none":
            top = f"{top}:{top_bytes}B"
        return host_ms, dev_ms, top

    def summary(self) -> dict:
        from kubernetes_tpu.codec.transfer import transfer_totals

        with self._lock:
            totals = {p: round(v, 6) for p, v in self._totals.items()}
            cycles = self.cycles_total
            degraded = self.degraded_total
            wall = self._wall_total
        host = sum(totals[p] for p in HOST_PHASES)
        dev = sum(totals[p] for p in DEVICE_PHASES)
        return {
            "cycles": cycles,
            "degraded_cycles": degraded,
            "wall_s": round(wall, 6),
            "host_s": round(host, 6),
            "device_s": round(dev, 6),
            # the reconciliation figure the acceptance test pins: on the
            # synchronous path the host split accounts for ~all of wall
            "unaccounted_s": round(wall - host, 6),
            "totals_s": totals,
            "transfers": transfer_totals(),
        }

    def ewma_matrix(self) -> Dict[str, Dict[str, float]]:
        """{phase: {width: ewma seconds}} — the phase x executable-width
        cost matrix (json keys are strings)."""
        with self._lock:
            return {
                p: {str(w): round(s, 6) for w, s in sorted(row.items())}
                for p, row in self._ewma.items()
            }

    def debug_payload(self, limit: Optional[int] = None) -> dict:
        """GET /debug/perf body: summary + EWMA matrix + transfer totals
        + profiler status + the newest `limit` per-cycle samples (the
        shared debug_body halves the limit until the body fits the 4MB
        cap, like its siblings)."""
        with self._lock:
            samples = list(self._ring)
        if limit is not None and limit >= 0:
            samples = samples[-limit:] if limit else []
        return {
            "summary": self.summary(),
            "ewma_s": self.ewma_matrix(),
            "profiler": self.profiler.status(),
            "samples": samples,
        }


def profile_request(query: str = "") -> dict:
    """GET /debug/profile handler body (health server + apiserver):
    ?seconds=N starts a bounded capture through the default
    observatory's ProfilerCapture; malformed/missing seconds default to
    2.  Never raises — the body reports the outcome."""
    from urllib.parse import parse_qs

    try:
        raw = parse_qs(query).get("seconds", ["2"])[0]
        seconds = float(raw)
    except (ValueError, TypeError):
        seconds = 2.0
    return get_default().profiler.start(seconds)


# process-wide default: the observatory /debug/perf serves when none
# was wired explicitly; a Scheduler installs its own here at
# construction.  Replica 0 wins the default, siblings register
# alongside (runtime/defaults.py ProcessDefault)
from kubernetes_tpu.runtime.defaults import ProcessDefault  # noqa: E402

_DEFAULT = ProcessDefault("perfobs", PerfObservatory)


def get_default() -> PerfObservatory:
    return _DEFAULT.get()


def set_default(obs: PerfObservatory, replica: int = 0) -> None:
    _DEFAULT.set(obs, replica)


def replica_instances() -> dict:
    """{replica id: PerfObservatory} of every install this process saw."""
    return _DEFAULT.replicas()


def __getattr__(name):  # legacy alias: perfobs.OBSERVATORY
    if name == "OBSERVATORY":
        return _DEFAULT.get()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
