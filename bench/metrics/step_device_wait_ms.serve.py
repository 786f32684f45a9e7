"""Host waiting on the device in a serve step (``repro.sync`` under
``repro.serve.step``: the walk's counts and the pair transfer), mean ms
a step."""
from spans import ms_per_root, window_roots


def read(run):
    return ms_per_root(window_roots(run, "repro.serve.step", "steps"),
                       {"repro.sync"})
