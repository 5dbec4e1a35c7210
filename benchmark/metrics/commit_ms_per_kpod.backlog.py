"""Commit + bind tail (runtime/scheduler.py): host seconds the Scheduler's own `phase_seconds["commit"]`
counter took over the traced span, in ms per 1,000 pods bound in it."""


def read(run):
    if run["pods"] <= 0 or "commit" not in run["phase_s"]:
        return None
    return run["phase_s"]["commit"] / run["pods"] * 1e6
