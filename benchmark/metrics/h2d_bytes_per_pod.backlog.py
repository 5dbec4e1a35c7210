"""Upload layer (codec/transfer.py): host-to-device bytes over the traced
span, from the program's exact `transfer_totals()`, per pod bound."""


def read(run):
    if run["pods"] <= 0:
        return None
    return run["h2d_bytes"] / run["pods"]
