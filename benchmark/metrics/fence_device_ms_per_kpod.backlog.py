"""Device compute + fetch (AsyncFetch): the part of the fence wait in
which the fetch worker held the job and the result was not ready yet,
the Scheduler's own `phase_seconds["fence_device"]` counter over the
traced span, in ms per 1,000 pods bound in it."""


def read(run):
    if run["pods"] <= 0 or "fence_device" not in run["phase_s"]:
        return None
    return run["phase_s"]["fence_device"] / run["pods"] * 1e6
