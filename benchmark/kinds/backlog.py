"""{"kind": "backlog", "pods_per_s_max": ..., "lead_batches": ...}

A backlog kept `lead_batches` full batches ahead of the scheduler;
pods_per_s_max * seconds pod objects are built in set-up (capped by what
the cluster holds), so the backlog outlasts the window up to that rate.
The queue is topped up to `lead_batches` full batches before every
cycle; the window opens at the first add and closes at the end of the
first cycle that ends after `seconds` (its binds are the first
bind-commit after the deadline).  End to end: `pods_per_s`, the popped
pods bound by the close over the window's length.
"""

from __future__ import annotations

from benchmark.harness.drive import Tracer, _annotate, clock, run_cycle


def backlog_size(traffic: dict, seconds: float, cap: int) -> int:
    return int(min(cap, round(float(traffic["pods_per_s_max"]) * seconds)))


def window_pods(traffic, seconds, seed, room):
    return backlog_size(traffic, seconds, room), None


def backlog_window(sched, queue, seams, pods, metrics_mod, traffic, seconds,
                   batch, tracer: Tracer, snap_fn) -> dict:
    """Keep `lead_batches` full batches queued; measure `seconds`."""
    lead = int(traffic.get("lead_batches", 3)) * batch
    n = len(pods)
    added = 0
    t_open = clock()
    tracer.start(snap_fn)
    exhausted = None
    while True:
        pending = added - sum(len(b) for b in queue.popped[seams.n_pop0:])
        if pending < lead and added < n:
            k = min(lead - pending, n - added)
            with _annotate("bench.add"):
                for p in pods[added:added + k]:
                    queue.add(p)
            added += k
        if added >= n and pending == 0:
            exhausted = "backlog"
            break
        rec = run_cycle(sched, queue, seams, metrics_mod)
        tracer.maybe_stop(snap_fn)
        if rec["t1"] - t_open >= seconds:
            break
        if rec["popped"] and rec["placed"] == 0:
            exhausted = "cluster"
            break
    t_close = clock()
    tracer.maybe_stop(snap_fn, force=True)
    return {"t_open": t_open, "t_close": t_close, "added": added,
            "exhausted": exhausted}


def window(sched, queue, seams, pods, plan, metrics_mod, traffic, seconds,
           batch, tracer, snap_fn):
    return backlog_window(sched, queue, seams, pods, metrics_mod, traffic,
                          seconds, batch, tracer, snap_fn)


def end_to_end(w, seams, win_names, popped_win, seconds, log):
    e2e = {}
    attempted_names = popped_win
    bound_in = sum(1 for n in popped_win if n in seams.bound
                   and seams.bind_t[n] <= w["t_close"])
    e2e["pods_per_s"] = bound_in / (w["t_close"] - w["t_open"])
    if w["exhausted"]:
        log("window", {"closed_early_by": w["exhausted"]})
    return e2e, attempted_names
