"""Host waiting on the device in a self-join call (``repro.sync``: each
block's count read and its pair transfer), mean ms a call."""
from spans import ms_per_root, window_roots


def read(run):
    return ms_per_root(window_roots(run, "repro.join", "calls"),
                       {"repro.sync"})
