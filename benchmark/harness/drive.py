"""The measured window's seams around the live path, for every traffic
kind (benchmark/kinds/<kind>.py drives the window through these).

One thread drives everything: pods go to `PriorityQueue.add` between
`Scheduler.run_once` calls, the binder records each bind, and a queue
subclass records each popped batch (the order the engine saw it in).
No thread or child is started.
"""

from __future__ import annotations

import time
from typing import Dict, List

clock = time.perf_counter


def make_queue():
    """A PriorityQueue that records every popped batch."""
    from kubernetes_tpu.runtime.queue import PriorityQueue

    class SeamQueue(PriorityQueue):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.popped: List[List[str]] = []

        def pop_batch(self, *a, **kw):
            with _annotate("bench.pop"):
                out = super().pop_batch(*a, **kw)
            if out:
                self.popped.append([p.name for p in out])
            return out

    return SeamQueue()


class Seams:
    def __init__(self):
        self.bound: Dict[str, str] = {}        # pod name -> node name
        self.bind_t: Dict[str, float] = {}     # pod name -> clock()
        self.cycles: List[dict] = []
        self.failed: set = set()               # pods of degraded cycles

    def binder(self, pod, node) -> bool:
        self.bound[pod.name] = node
        self.bind_t[pod.name] = clock()
        return True


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


# the Scheduler's phases, by the methods a cycle goes through; a method a
# later program no longer has is skipped (its gaps fall to the seams)
PHASE_METHODS = ("_encode_and_dispatch", "_commit_state_or_requeue",
                 "_commit_tail")


def annotate_phases(sched) -> None:
    """Trace runs: a span around each phase of the Scheduler's cycle,
    set on the instance, so idle gaps are named by the phase the host
    was in."""
    for name in PHASE_METHODS:
        fn = getattr(sched, name, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _label="bench.sched" + name, **kw):
            with _annotate(_label):
                return _fn(*a, **kw)

        setattr(sched, name, wrapped)


def run_cycle(sched, queue, seams: Seams, metrics_mod) -> dict:
    """One run_once at the benchmark's seam: wall time, pods placed, pods
    popped, and whether the cycle was degraded (CPU fallback, breaker not
    closed) - its pods then count as failed."""
    deg0 = metrics_mod.DEGRADED_CYCLES.value
    n_pop0 = len(queue.popped)
    t0 = clock()
    with _annotate("bench.run_once"):
        placed = sched.run_once(timeout=0.0)
    t1 = clock()
    popped = [n for b in queue.popped[n_pop0:] for n in b]
    degraded = (metrics_mod.DEGRADED_CYCLES.value > deg0
                or sched.device_health.state != "closed")
    if degraded:
        seams.failed.update(popped)
    rec = {"t0": t0, "t1": t1, "placed": int(placed),
           "popped": len(popped), "degraded": bool(degraded)}
    seams.cycles.append(rec)
    return rec


class Tracer:
    """--trace 1: the profiler over the first `span_s` of the window,
    stopped at a cycle boundary; phase counters are read at its stop so
    every per-layer number describes the traced span."""

    def __init__(self, on: bool, trace_dir: str, span_s: float):
        self.on = on
        self.dir = trace_dir
        self.span_s = span_s
        self.active = False
        self.t_start = None
        self.ann = None
        self.snap = None

    def start(self, snap_fn) -> None:
        if not self.on:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # a span per Python call: 4x slower
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.ann = _annotate("bench.window")
        self.ann.__enter__()
        self.active = True
        self.t_start = clock()
        self.snap0 = snap_fn()

    def maybe_stop(self, snap_fn, force: bool = False) -> None:
        if not self.active:
            return
        if not force and clock() - self.t_start < self.span_s:
            return
        self.snap = snap_fn()
        self.ann.__exit__(None, None, None)
        import jax

        jax.profiler.stop_trace()
        self.active = False
