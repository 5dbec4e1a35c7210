"""Device compute + fetch (AsyncFetch), the batches the exactness redo
answered: the part of the fence wait spent on batches whose engine
answer came from the exact scan, the Scheduler's own
`phase_seconds["fence_redo"]` counter over the traced span, in ms per
1,000 pods bound in it.  A program without the counter reports
nothing."""


def read(run):
    if run["pods"] <= 0 or "fence_redo" not in run["phase_s"]:
        return None
    return run["phase_s"]["fence_redo"] / run["pods"] * 1e6
