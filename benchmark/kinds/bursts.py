"""{"kind": "bursts", "burst": ..., "interval_s": ..., "follow_s": ...}

Open loop, `burst` pods due together every `interval_s` (a ReplicaSet
scaled up on a fixed cadence), driven, followed and measured as the
poisson kind's arrivals are.  Every seed offers the same bursts.
"""

from __future__ import annotations

import numpy as np

from benchmark.kinds.poisson import end_to_end, window  # noqa: F401


def burst_offsets(traffic: dict, seconds: float) -> np.ndarray:
    """Due times of the window's pods: one burst at the open and one
    every interval_s after it while the window lasts."""
    n = int(np.ceil(seconds / float(traffic["interval_s"]) - 1e-9))
    return np.repeat(np.arange(n) * float(traffic["interval_s"]),
                     int(traffic["burst"]))


def window_pods(traffic, seconds, seed, room):
    offsets = burst_offsets(traffic, seconds)
    return len(offsets), offsets
