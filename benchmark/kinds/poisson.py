"""{"kind": "poisson", "rate_per_s": ..., "follow_s": ...}

Open-loop single-pod arrivals at a fixed rate.  The arrival gaps are the
exponential distribution's quantiles at (i + 0.5) / n, in the seed's
order: every seed offers the same set of gaps and the same count, in
another order.  Each pod is added at the first cycle boundary after it
is due and timed from its due time to its bind; every pod due in the
window is followed to its bind, up to `follow_s` past the close.  End to
end: `e2e_p50_ms` and `e2e_p99_ms` over every pod due in the window.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.harness.drive import Tracer, _annotate, clock, run_cycle


def poisson_offsets(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds after the window opens) of the window's pods."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= seconds / gaps.sum()          # the last pod is due at the close
    rng = np.random.default_rng([int(seed), 3])
    gaps = rng.permutation(gaps)
    return np.cumsum(gaps) - gaps[0]


def window_pods(traffic, seconds, seed, room):
    offsets = poisson_offsets(traffic, seconds, seed)
    return len(offsets), offsets


def poisson_window(sched, queue, seams, pods, offsets, metrics_mod,
                   seconds, follow_s, tracer: Tracer, snap_fn) -> dict:
    """Open loop: pod i is due at t_open + offsets[i]."""
    n = len(pods)
    t_open = clock()
    due = t_open + np.asarray(offsets, np.float64)
    nxt = 0
    lag = []
    tracer.start(snap_fn)
    deadline = t_open + seconds + follow_s
    queued_at_close = None
    while True:
        now = clock()
        if queued_at_close is None and now >= t_open + seconds:
            # pods due by the close that the scheduler has not popped yet:
            # about one cycle's arrivals below the knee, growing above it
            queued_at_close = int((due <= t_open + seconds).sum()) - sum(
                len(b) for b in queue.popped[seams.n_pop0:])
        if nxt < n and due[nxt] <= now:
            with _annotate("bench.add"):
                while nxt < n and due[nxt] <= now:
                    queue.add(pods[nxt])
                    lag.append(now - due[nxt])
                    nxt += 1
        popped = sum(len(b) for b in queue.popped[seams.n_pop0:])
        if popped < nxt:
            run_cycle(sched, queue, seams, metrics_mod)
            tracer.maybe_stop(snap_fn)
        elif nxt >= n:
            break
        else:
            tracer.maybe_stop(snap_fn)
            wait = due[nxt] - clock()
            if wait > 0:
                time.sleep(min(wait, 0.05))
        if clock() > deadline:
            break
    t_close = clock()
    tracer.maybe_stop(snap_fn, force=True)
    return {"t_open": t_open, "t_close": t_close, "added": nxt,
            "due": due, "lag": np.asarray(lag),
            "queued_at_close": queued_at_close}


def window(sched, queue, seams, pods, plan, metrics_mod, traffic, seconds,
           batch, tracer, snap_fn):
    return poisson_window(sched, queue, seams, pods, plan, metrics_mod,
                          seconds, float(traffic.get("follow_s", 60.0)),
                          tracer, snap_fn)


def end_to_end(w, seams, win_names, popped_win, seconds, log):
    e2e = {}
    attempted_names = win_names
    due = w["due"]
    lat = np.empty(len(win_names))
    for i, n in enumerate(win_names):
        t_b = seams.bind_t.get(n)
        lat[i] = (t_b if t_b is not None else w["t_close"]) - due[i]
    e2e["e2e_p50_ms"] = float(np.percentile(lat, 50) * 1e3)
    e2e["e2e_p99_ms"] = float(np.percentile(lat, 99) * 1e3)
    log("window", {"generator_lag_ms_p99":
                   float(np.percentile(w["lag"], 99) * 1e3)
                   if len(w["lag"]) else 0.0,
                   "pods": len(win_names),
                   "queued_at_close": w["queued_at_close"],
                   "rate_per_s": len(win_names) / seconds})
    return e2e, attempted_names
