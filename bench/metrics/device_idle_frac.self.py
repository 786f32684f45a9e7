"""Device idle share of the traced window: 1 - busy / window."""


def read(run):
    t = run.trace
    return None if t is None else 1.0 - t["busy_s"] / t["window_s"]
