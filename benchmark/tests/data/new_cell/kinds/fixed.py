"""{"kind": "fixed", "pods": ..., "follow_s": ...}: a test-only kind.

`pods` pods created together when the window opens (scheduler_perf's
b.N pods at once); the window closes once none is left to schedule, or
`seconds` + `follow_s` after it opened.  End to end: `fixed_pods_per_s`,
the pods bound over the window's length.
"""

from __future__ import annotations

from benchmark.harness.drive import _annotate, clock, run_cycle


def window_pods(traffic, seconds, seed, room):
    return int(traffic["pods"]), None


def window(sched, queue, seams, pods, plan, metrics_mod, traffic, seconds,
           batch, tracer, snap_fn):
    t_open = clock()
    tracer.start(snap_fn)
    with _annotate("bench.add"):
        for p in pods:
            queue.add(p)
    deadline = t_open + seconds + float(traffic.get("follow_s", 60.0))
    while queue.has_schedulable() and clock() < deadline:
        run_cycle(sched, queue, seams, metrics_mod)
        tracer.maybe_stop(snap_fn)
    t_close = clock()
    tracer.maybe_stop(snap_fn, force=True)
    return {"t_open": t_open, "t_close": t_close, "added": len(pods)}


def end_to_end(w, seams, offered, popped, seconds, log):
    bound = sum(1 for n in offered if n in seams.bound)
    return {"fixed_pods_per_s": bound / (w["t_close"] - w["t_open"])}, offered
