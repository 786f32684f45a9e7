"""Host spans (``repro/obs.py``): the in-memory record (nesting, parent
links, self time, the ring and child bounds, ``recent`` order, compile
counts on the innermost span), the spans the batch join and the dedup
engine open, and the spans in a profiler trace.
"""
import glob
import itertools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import obs
from repro.core.sets import SetCollection
from repro.serve.dedup import DedupServeEngine

_names = itertools.count()


def fresh(prefix="test.obs"):
    """A root name no other test uses, so ``recent`` sees only ours."""
    return f"{prefix}.{next(_names)}"


def test_nesting_and_parent_links():
    name = fresh()
    with obs.span(name, k=1) as root:
        with obs.span("a") as a:
            with obs.span("b") as b:
                pass
        with obs.span("c") as c:
            pass
    assert obs.recent(name, 1) == [root]
    assert root.parent is None and root.attrs == {"k": 1}
    assert [s.name for s in root.spans] == ["a", "b", "c"]
    assert a.parent is root and b.parent is a and c.parent is root
    assert a.spans == [] and b.spans == []  # descendants live on the root
    assert root.start_ns <= a.start_ns <= b.start_ns <= b.end_ns \
        <= a.end_ns <= c.start_ns <= c.end_ns <= root.end_ns


def test_self_time_is_duration_less_children():
    name = fresh()
    with obs.span(name) as root:
        with obs.span("a") as a:
            with obs.span("b") as b:
                pass
        with obs.span("c") as c:
            pass
    assert a.self_ns == a.duration_ns - b.duration_ns
    assert b.self_ns == b.duration_ns
    assert root.self_ns == (root.duration_ns - a.duration_ns
                            - c.duration_ns)
    assert root.self_ns + a.self_ns + b.self_ns + c.self_ns \
        == root.duration_ns


def test_span_records_on_exception():
    name = fresh()
    with pytest.raises(ValueError):
        with obs.span(name):
            with obs.span("inner"):
                raise ValueError("boom")
    (root,) = obs.recent(name, 1)
    assert [s.name for s in root.spans] == ["inner"]
    with obs.span(fresh()) as later:  # the stack unwound: a root again
        pass
    assert later.parent is None


def test_ring_keeps_the_last_roots():
    name = fresh()
    for i in range(obs.RING_ROOTS + 10):
        with obs.span(name, i=i):
            pass
    roots = obs.recent(name, obs.RING_ROOTS + 10)
    assert len(roots) == obs.RING_ROOTS
    assert roots[0].attrs["i"] == 10
    assert roots[-1].attrs["i"] == obs.RING_ROOTS + 9


def test_children_per_root_are_capped():
    name = fresh()
    with obs.span(name) as root:
        for _ in range(obs.MAX_SPANS + 7):
            with obs.span("child"):
                pass
    assert len(root.spans) == obs.MAX_SPANS
    assert root.dropped == 7
    # a dropped child's time still leaves the root's self time
    assert root.child_ns >= sum(s.duration_ns for s in root.spans)


def test_recent_is_oldest_first_and_filters_by_name():
    name, other = fresh(), fresh()
    for i in range(5):
        with obs.span(name, i=i):
            pass
        with obs.span(other):
            pass
    assert [r.attrs["i"] for r in obs.recent(name, 3)] == [2, 3, 4]
    assert [r.attrs["i"] for r in obs.recent(name, 50)] == list(range(5))
    assert obs.recent(name, 0) == []
    assert obs.recent(fresh(), 3) == []


def test_compiles_land_on_the_innermost_span():
    name = fresh()
    x = jnp.arange(7, dtype=jnp.float32)
    with obs.span(name) as root:
        with obs.span("outer") as outer:
            with obs.span("inner") as inner:
                jax.jit(lambda v: v * 3 + 1)(x).block_until_ready()
            with obs.span("cached") as cached:
                pass
    assert inner.attrs["compiles"] >= 1
    assert "compiles" not in outer.attrs
    assert "compiles" not in cached.attrs
    assert "compiles" not in root.attrs


def test_traced_wraps_each_call_in_a_span():
    name = fresh()

    @obs.traced(name)
    def work(x, *, y=1):
        """Doc."""
        obs.current().set(x=x)
        return x + y

    assert work.__name__ == "work" and work.__doc__ == "Doc."
    assert work(2, y=3) == 5 and work(4) == 5
    assert [r.attrs for r in obs.recent(name, 5)] == [{"x": 2}, {"x": 4}]
    with obs.span(fresh()) as root:
        work(1)
    assert [s.name for s in root.spans] == [name]  # nested, not a root
    assert len(obs.recent(name, 5)) == 2


def test_current_is_the_innermost_open_span():
    assert obs.current() is None
    with obs.span(fresh()) as root:
        assert obs.current() is root
        with obs.span("a") as a:
            assert obs.current() is a
        assert obs.current() is root
    assert obs.current() is None


def test_set_adds_attributes():
    name = fresh()
    with obs.span(name, a=1) as sp:
        sp.set(b=2, rids=[1, 2])
    assert sp.attrs == {"a": 1, "b": 2, "rids": [1, 2]}


# ---------------------------------------------------------------------- #
# the program's spans
# ---------------------------------------------------------------------- #
# per R block; "repro.sync" twice: the count read and the pair transfer
BLOCK_SPANS = {"repro.dispatch": 1, "repro.r_rep": 1, "repro.gather": 1,
               "repro.sync": 2}
CALL_SPANS = {"repro.plan", "repro.validate", "repro.s_rep"}


def roots_since(name, t0):
    return [r for r in obs.recent(name, obs.RING_ROOTS) if r.start_ns >= t0]


def sample(seed=3, m=70, n=90, universe=300):
    rng = np.random.default_rng(seed)

    def draw(k):
        return [np.unique(rng.integers(0, universe, rng.integers(2, 12)))
                for _ in range(k)]
    s = draw(n)
    r = draw(m - 10) + [s[i] for i in range(10)]
    return (SetCollection.from_ragged(r, universe=universe),
            SetCollection.from_ragged(s, universe=universe))


@pytest.mark.parametrize("method,r_block", [("popcount", 32),
                                            ("popcount", 128),
                                            ("lfvt", 32)])
def test_join_call_yields_one_root_with_bounded_spans(method, r_block):
    R, S = sample()
    t0 = time.perf_counter_ns()
    res = repro.join(R, S, 0.6, method=method, r_block=r_block)
    (root,) = roots_since("repro.join", t0)
    assert root.attrs["m"] == len(R) and root.attrs["n"] == len(S)
    assert root.attrs["method"] == method
    names = [s.name for s in root.spans]
    r_blocks = res.stats.to_dict()["r_blocks"]
    assert set(names) == CALL_SPANS | set(BLOCK_SPANS)
    for n in CALL_SPANS:
        assert names.count(n) == 1, n
    for n, k in BLOCK_SPANS.items():
        assert names.count(n) == k * r_blocks, n
    # no span in a per-set or per-pair loop
    assert len(root.spans) + 1 <= 4 + 5 * r_blocks
    assert root.dropped == 0
    by = {s.name: s for s in root.spans}
    assert by["repro.r_rep"].parent.name == "repro.dispatch"
    assert by["repro.sync"].parent.name == "repro.gather"
    assert sum(s.attrs["pairs"] for s in root.spans
               if s.name == "repro.gather") == len(res)


def test_served_step_yields_one_root_with_one_wait_per_request():
    _, S = sample()
    eng = DedupServeEngine(S, threshold=0.6, micro_batch=4)
    assert eng.step() == []  # an empty queue makes no span
    rng = np.random.default_rng(8)
    rids = [eng.submit(rng.integers(0, 300, 6)) for _ in range(6)]
    t0 = time.perf_counter_ns()
    out = eng.step()
    (root,) = roots_since("repro.serve.step", t0)
    assert root.attrs["batch"] == len(out) == 4
    assert root.attrs["rids"] == rids[:4] == [r.rid for r in out]
    waits = root.attrs["queue_wait_ms"]
    assert len(waits) == 4 and all(w >= 0 for w in waits)
    assert [s.name for s in root.spans] == [
        "repro.serve.dispatch", "repro.serve.finalize", "repro.sync",
        "repro.sync"]
    assert all(s.parent is root.spans[1] for s in root.spans[2:])
    assert [r.rid for r in eng.drain()] == rids[4:]
    drain = obs.recent("repro.serve.drain", 1)[0]
    assert [s.name for s in drain.spans] == [
        "repro.serve.dispatch", "repro.serve.finalize", "repro.sync",
        "repro.sync"]


def test_spans_reach_a_host_plane_of_the_profiler_trace(tmp_path):
    R, S = sample()
    repro.join(R, S, 0.6, method="popcount")  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        repro.join(R, S, 0.6, method="popcount")
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name.split("#")[0]
             for p in data.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert {"repro.join", "repro.plan", "repro.dispatch",
            "repro.gather", "repro.sync"} <= names
