"""Programs lowered inside the measured window (should read 0)."""


def read(run):
    return run.record["compiles"]
