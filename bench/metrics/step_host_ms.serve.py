"""Host work of a serve step: padding, window bounds, tile plan and
launch (``repro.serve.dispatch``) and the emit work (self time of
``repro.serve.finalize``, its ``repro.sync`` waits left out), mean ms a
step."""
from spans import ms_per_root, window_roots


def read(run):
    roots = window_roots(run, "repro.serve.step", "steps")
    if roots is None:
        return None
    return (ms_per_root(roots, {"repro.serve.dispatch"})
            + ms_per_root(roots, {"repro.serve.finalize"}, self_time=True))
