"""Encode (codec/encoder.py), its affinity part: host seconds the
Scheduler's own `phase_seconds["encode_affinity"]` counter took over the
traced span (a batch's in-batch cross-match factors and the encoder's
per-pod term and pair columns), in ms per 1,000 pods bound in it.  A
program without the counter reports nothing."""


def read(run):
    if run["pods"] <= 0 or "encode_affinity" not in run["phase_s"]:
        return None
    return run["phase_s"]["encode_affinity"] / run["pods"] * 1e6
