"""The one traffic generator.  A mix is a data file of parameters,
benchmark/traffic/<name>.json; nothing about a mix lives in code.

  {"kind": "backlog", "pods_per_s_max": ..., "lead_batches": ...}
      a backlog kept `lead_batches` full batches ahead of the scheduler;
      pods_per_s_max * seconds pod objects are built in set-up (capped by
      what the cluster holds), so the backlog outlasts the window up to
      that rate;
  {"kind": "poisson", "rate_per_s": ..., "follow_s": ...}
      open-loop single-pod arrivals at a fixed rate.  The arrival gaps
      are the exponential distribution's quantiles at (i + 0.5) / n,
      in the seed's order: every seed offers the same set of gaps and
      the same count, in another order.  Every pod due in the window is
      followed to its bind, up to `follow_s` past the close.

  {"kind": "bursts", "burst": ..., "interval_s": ..., "follow_s": ...}
      open loop, `burst` pods due together every `interval_s` (a
      ReplicaSet scaled up on a fixed cadence), followed as above.
      Every seed offers the same bursts.

The warm-up is the harness's, the same for every mix (main.py).
"""

from __future__ import annotations

import json
import os

import numpy as np


def load_traffic(root: str, name: str) -> dict:
    path = os.path.join(root, "benchmark", "traffic", f"{name}.json")
    with open(path) as f:
        t = json.load(f)
    if t.get("kind") not in ("backlog", "poisson", "bursts"):
        raise ValueError(f"{path}: unknown kind {t.get('kind')!r}")
    return t


def backlog_size(traffic: dict, seconds: float, cap: int) -> int:
    return int(min(cap, round(float(traffic["pods_per_s_max"]) * seconds)))


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


def burst_offsets(traffic: dict, seconds: float) -> np.ndarray:
    """Due times of the window's pods: one burst at the open and one
    every interval_s after it while the window lasts."""
    n = int(np.ceil(seconds / float(traffic["interval_s"]) - 1e-9))
    return np.repeat(np.arange(n) * float(traffic["interval_s"]),
                     int(traffic["burst"]))
