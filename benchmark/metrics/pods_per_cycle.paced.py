"""Queue / batch formation: pods bound per scheduling cycle, counted at
the benchmark's run_once seam over the traced span (cycles that popped
at least one pod)."""


def read(run):
    cyc = [c for c in run["cycles"] if c["popped"] > 0]
    if not cyc:
        return None
    return sum(c["placed"] for c in cyc) / len(cyc)
