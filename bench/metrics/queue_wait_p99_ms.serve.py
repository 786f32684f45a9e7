"""p99 over all requests of the start of the step() that served each,
less its due time, in ms (the benchmark's clock)."""
import numpy as np


def read(run):
    return float(np.nanpercentile(run.record["queue_wait_ms"], 99))
