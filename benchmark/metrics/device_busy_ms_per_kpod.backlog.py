"""Device layer: the union of device operation intervals in the profiler
trace of the traced span, in ms per 1,000 pods bound in it."""


def read(run):
    tr = run["trace"]
    if tr is None or run["pods"] <= 0:
        return None
    return tr["busy_s"] / run["pods"] * 1e6
