"""Encode layer (codec/encoder.py): host seconds the Scheduler's own `phase_seconds["encode"]`
counter took over the traced span, in ms per 1,000 pods bound in it."""


def read(run):
    if run["pods"] <= 0 or "encode" not in run["phase_s"]:
        return None
    return run["phase_s"]["encode"] / run["pods"] * 1e6
