"""p99 over the window's requests of the engine's own queue wait (submit
to the pop into a micro-batch, on the program's clock), in ms, from the
``repro.serve.step`` spans."""
import numpy as np

from spans import window_roots


def read(run):
    roots = window_roots(run, "repro.serve.step", "steps")
    if roots is None:
        return None
    waits = [w for r in roots for w in r.attrs["queue_wait_ms"]]
    return float(np.percentile(waits, 99)) if waits else None
