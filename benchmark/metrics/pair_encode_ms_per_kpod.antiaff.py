"""Encode (codec/encoder.py), its pair-leaf part: host seconds the
Scheduler's own `phase_seconds["encode_pairs"]` counter took over the
traced span (spreading a batch's TP-wide pair leaves over its pods:
each class row copied to the class's pods, or the class rows and row
index handed on as they are), in ms per 1,000 pods bound in it.  A
program without the counter reports nothing."""


def read(run):
    if run["pods"] <= 0 or "encode_pairs" not in run["phase_s"]:
        return None
    return run["phase_s"]["encode_pairs"] / run["pods"] * 1e6
