"""Device compute + fetch, as the host waits on it at the fence (AsyncFetch): host seconds the Scheduler's own `phase_seconds["host_stall"]`
counter took over the traced span, in ms per 1,000 pods bound in it."""


def read(run):
    if run["pods"] <= 0 or "host_stall" not in run["phase_s"]:
        return None
    return run["phase_s"]["host_stall"] / run["pods"] * 1e6
