"""Engine dispatch (models/speculative.py): host seconds the Scheduler's own `phase_seconds["dispatch"]`
counter took over the traced span, in ms per 1,000 pods bound in it."""


def read(run):
    if run["pods"] <= 0 or "dispatch" not in run["phase_s"]:
        return None
    return run["phase_s"]["dispatch"] / run["pods"] * 1e6
