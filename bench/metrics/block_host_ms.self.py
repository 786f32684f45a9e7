"""Host work of one R block of the self-join: its launch
(``repro.dispatch``) and its finalize's own work (self time of
``repro.gather``, its ``repro.sync`` waits left out), mean ms a block
over the window's calls."""
from spans import window_roots


def read(run):
    roots = window_roots(run, "repro.join", "calls")
    if roots is None:
        return None
    spans = [s for r in roots for s in r.spans]
    blocks = sum(s.name == "repro.dispatch" for s in spans)
    if not blocks:
        return None
    ns = sum(s.duration_ns if s.name == "repro.dispatch" else s.self_ns
             for s in spans if s.name in ("repro.dispatch", "repro.gather"))
    return ns / blocks / 1e6
