"""Scheduler cycle: the share of the run_once wall time, summed over the
traced span's cycles at the benchmark's seam, that no phase of the
Scheduler's own partition (pop, encode, dispatch, host_stall, commit,
preempt, observe) accounts for, in %."""

PARTITION = ("pop", "encode", "dispatch", "host_stall", "commit",
             "preempt", "observe")


def read(run):
    ph = run["phase_s"]
    wall = sum(c["t1"] - c["t0"] for c in run["cycles"])
    if run["pods"] <= 0 or wall <= 0 or not all(k in ph for k in PARTITION):
        return None
    return 100.0 * (wall - sum(ph[k] for k in PARTITION)) / wall
