"""Input validation, S encode and upload (or its cache lookup) and each
R block's encode, upload and launch (``repro.validate``,
``repro.s_rep``, ``repro.dispatch``), mean ms a call."""
from spans import ms_per_root, window_roots


def read(run):
    return ms_per_root(window_roots(run, "repro.join", "calls"),
                       {"repro.validate", "repro.s_rep", "repro.dispatch"})
