"""Observers (the post-bind hooks: flight recorder, ledger, telemetry,
perfobs, quality, capacity, timeline, heartbeat): host seconds the
Scheduler's own `phase_seconds["observe"]` counter took over the traced
span, in ms per 1,000 pods bound in it."""


def read(run):
    if run["pods"] <= 0 or "observe" not in run["phase_s"]:
        return None
    return run["phase_s"]["observe"] / run["pods"] * 1e6
