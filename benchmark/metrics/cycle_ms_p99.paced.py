"""Scheduler cycle: the 99th percentile of the wall time of a run_once
call that dispatched or committed, at the benchmark's seam, over the
traced span, in ms."""

import numpy as np


def read(run):
    dts = [c["t1"] - c["t0"] for c in run["cycles"]
           if c["popped"] > 0 or c["placed"] > 0]
    if not dts:
        return None
    return float(np.percentile(dts, 99) * 1e3)
