"""Least HBM time of the traced self-join calls (bench/work_self.py's
bytes at the chip's HBM peak) over the device time of the ops inside
their ``bench.join_call`` spans, in percent. An HBM bound."""

SPAN = "bench.join_call"


def read(run):
    t = run.trace
    if t is None:
        return None
    device_s = t["span_device_s"].get(SPAN, 0.0)
    least = run.record["least_bytes"].get(SPAN, 0)
    if device_s <= 0 or least <= 0:
        return None
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / device_s
