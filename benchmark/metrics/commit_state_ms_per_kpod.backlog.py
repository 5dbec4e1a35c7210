"""Commit + bind tail (runtime/scheduler.py), its state half: host
seconds the Scheduler's own `phase_seconds["commit_state"]` counter took
over the traced span (the assume of a cycle's winners into the cache),
in ms per 1,000 pods bound in it."""


def read(run):
    if run["pods"] <= 0 or "commit_state" not in run["phase_s"]:
        return None
    return run["phase_s"]["commit_state"] / run["pods"] * 1e6
