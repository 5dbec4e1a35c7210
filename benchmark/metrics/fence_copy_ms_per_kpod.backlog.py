"""Device compute + fetch (AsyncFetch): the part of the fence wait after
the result was ready, while its device-to-host copy landed and was
checked, the Scheduler's own `phase_seconds["fence_copy"]` counter over
the traced span, in ms per 1,000 pods bound in it."""


def read(run):
    if run["pods"] <= 0 or "fence_copy" not in run["phase_s"]:
        return None
    return run["phase_s"]["fence_copy"] / run["pods"] * 1e6
