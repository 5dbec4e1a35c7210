"""Program loads (utils/compilecache.py): seconds of backend compiles
and persistent-cache retrievals inside the Scheduler's cycles over the
traced span, its own `phase_seconds["program_load"]` counter, in ms."""


def read(run):
    if run["pods"] <= 0 or "program_load" not in run["phase_s"]:
        return None
    return run["phase_s"]["program_load"] * 1e3
