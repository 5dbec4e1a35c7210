"""From a profiler trace to numbers: device busy time, the window, the
device operations that took most time, and the longest idle gaps named
by what the host was doing in them.

The reduction works on a flat event list, [plane, line, name, start_ns,
dur_ns], so that a small recorded trace (benchmark/tests/data) checks it
without a chip.  `events_from_xplane` makes that list from the
`.xplane.pb` that `jax.profiler` writes.
"""

from __future__ import annotations

import glob
import os
from typing import Iterable, List, Optional, Sequence, Tuple

# the device line that holds one event per executed operation, and the
# one that holds one per executed program (the TPU runtime's names)
OP_LINES = ("XLA Ops",)
MODULE_LINE = "XLA Modules"
WINDOW_EVENT = "bench.window"
# host spans that name an idle gap, most specific first: the program's
# own annotations, the benchmark's spans around the Scheduler's phases
# (trace runs only), then its seams
HOST_TIERS = (("ktpu.",), ("bench.sched", "bench.pop"), ("bench.",))
HOST_PREFIXES = tuple(p for tier in HOST_TIERS for p in tier)


def short_name(name: str) -> str:
    """'jit_run(1384488898673043743)' -> 'jit_run'; an HLO op's text ->
    its name ('%while.83 = (...) while(...)' -> '%while.83')."""
    name = name.split(" = ", 1)[0]
    if name.endswith(")") and "(" in name:
        name = name[: name.index("(")]
    return name


def events_from_xplane(trace_dir: str) -> List[list]:
    """Every event of the newest .xplane.pb under trace_dir."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return []
    path = max(files, key=os.path.getmtime)
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append([plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)])
    return out


def is_device_plane(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def union_length(iv: Sequence[Tuple[float, float]]) -> Tuple[float, list]:
    """Total length of the union of [start, end) intervals, and the
    merged intervals in order."""
    merged: list = []
    for s, e in sorted(iv):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def reduce(events: Iterable[Sequence], top: int = 10) -> Optional[dict]:
    """busy_s (mean over devices), window_s, device_ops and idle_gaps, or
    None when the trace holds no window or no device operation."""
    events = list(events)
    win = [e for e in events if e[2] == WINDOW_EVENT]
    if not win:
        return None
    w0 = min(e[3] for e in win)
    w1 = max(e[3] + e[4] for e in win)
    per_dev: dict = {}
    op_time: dict = {}
    mod_time: dict = {}
    for plane, line, name, s, d in events:
        if not is_device_plane(plane) or (
                line not in OP_LINES and line != MODULE_LINE):
            continue
        s0, s1 = max(s, w0), min(s + d, w1)
        if s1 <= s0:
            continue
        key = short_name(name)
        if line == MODULE_LINE:
            mod_time[key] = mod_time.get(key, 0.0) + (s1 - s0)
            continue
        per_dev.setdefault(plane, []).append((s0, s1))
        op_time[key] = op_time.get(key, 0.0) + (s1 - s0)
    if not per_dev:
        return None
    busy = {}
    merged_by_dev = {}
    for plane, iv in per_dev.items():
        busy[plane], merged_by_dev[plane] = union_length(iv)
    busy_ns = sum(busy.values()) / len(busy)
    # idle gaps on the first device (one chip per cell here)
    dev = sorted(merged_by_dev)[0]
    merged = merged_by_dev[dev]
    gaps = []
    prev = w0
    for s, e in merged:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if w1 > prev:
        gaps.append((prev, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [(name, s, s + d) for plane, line, name, s, d in events
            if not is_device_plane(plane) and name != WINDOW_EVENT
            and name.startswith(HOST_PREFIXES)]
    # programs when the trace has them (stable names), else operations
    ops = sorted((mod_time or op_time).items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": [[n, t / 1e9] for n, t in ops],
        "idle_gaps": [[name_gap(host, g0, g1), (g1 - g0) / 1e9]
                      for g0, g1 in gaps[:top]],
    }


def name_gap(host, g0: float, g1: float) -> str:
    """The most specific host span that covers at least half of [g0, g1)
    (HOST_TIERS order, the largest cover within a tier); the seam that
    covers most of it when no specific one does; 'host other' when no
    span does."""
    best = {}
    for name, s, e in host:
        ov = min(e, g1) - max(s, g0)
        if ov > 0:
            best[name] = best.get(name, 0.0) + ov
    for i, tier in enumerate(HOST_TIERS):
        cands = {n: v for n, v in best.items() if n.startswith(tier)}
        if not cands:
            continue
        name, ov = max(cands.items(), key=lambda kv: kv[1])
        if i == len(HOST_TIERS) - 1 or ov >= 0.5 * (g1 - g0):
            return name
    return "host other"
