"""LFVT walk steps per request in the window (the engine's counters)."""


def read(run):
    r = run.record
    return r["walk_steps"] / r["requests"] if r["requests"] else None
