"""The host work of each block's finalize: regrow, the pair slice's
launch (and its compile for a new count), id map, set update (self time
of ``repro.gather``, its ``repro.sync`` waits left out), mean ms a
call."""
from spans import ms_per_root, window_roots


def read(run):
    return ms_per_root(window_roots(run, "repro.join", "calls"),
                       {"repro.gather"}, self_time=True)
