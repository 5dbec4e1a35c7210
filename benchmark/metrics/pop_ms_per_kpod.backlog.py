"""Queue layer (runtime/queue.py): host seconds the Scheduler's own `phase_seconds["pop"]`
counter took over the traced span, in ms per 1,000 pods bound in it."""


def read(run):
    if run["pods"] <= 0 or "pop" not in run["phase_s"]:
        return None
    return run["phase_s"]["pop"] / run["pods"] * 1e6
