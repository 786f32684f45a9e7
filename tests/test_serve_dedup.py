"""Dedup serve-engine suite (ISSUE 9): ``serve/dedup.py``.

  * served matches == the brute-force oracle for every request, across
    micro-batch sizes, thresholds and measures (the double-buffered
    ``drain`` path included);
  * the admission gap fix: two identical new docs in one stream — same
    micro-batch or later batches — no longer both survive; the second
    is flagged against the first's freshly-admitted corpus row;
  * ``DedupPipeline.filter_stream`` end-to-end on token docs;
  * ``DedupPipeline`` under every measure: ``filter_batch`` keeps exactly
    the rows the brute-force join leaves, ``filter_stream`` exactly those
    a sequential oracle (admit each survivor before the next doc) keeps;
  * async emission: ``on_result`` callback ordering, the ``results()``
    queue, and per-request latency/batch stats;
  * empty-corpus start, empty queue, validation errors.
"""
import numpy as np
import pytest

from repro.core.join import brute_force_join
from repro.core.sets import SetCollection
from repro.data.pipeline import DedupPipeline
from repro.data.synth import docs_to_sets
from repro.serve.dedup import DedupResult, DedupServeEngine

UNI = 40


def zipf_set(rng, lmax=8):
    size = int(rng.integers(1, lmax + 1))
    return np.unique(np.minimum(rng.zipf(1.3, size=size) - 1, UNI - 1))


def make_corpus(seed=0, n=25):
    rng = np.random.default_rng(seed)
    return SetCollection.from_ragged([zipf_set(rng) for _ in range(n)],
                                     universe=UNI)


# ---------------------------------------------------------------------- #
# oracle parity
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("micro_batch,t,measure",
                         [(3, 0.3, "jaccard"), (8, 0.6, "jaccard"),
                          (4, 0.5, "cosine"), (5, 0.4, "overlap")])
def test_serve_matches_oracle(micro_batch, t, measure):
    corpus = make_corpus()
    rng = np.random.default_rng(1)
    queries = [zipf_set(rng) for _ in range(13)]
    eng = DedupServeEngine(corpus, threshold=t, measure=measure,
                           micro_batch=micro_batch)
    rids = [eng.submit(q) for q in queries]
    res = {r.rid: r for r in eng.drain()}
    R = SetCollection.from_ragged(queries, universe=UNI)
    want: dict = {}
    for r, s in brute_force_join(R, corpus, t, measure):
        want.setdefault(r, set()).add(s)
    for i, rid in enumerate(rids):
        assert set(res[rid].matches) == want.get(i, set()), i
        assert res[rid].is_dup == (i in want)
    assert eng.stats["batches"] == -(-len(queries) // micro_batch)


def test_step_and_drain_agree():
    corpus = make_corpus(2)
    rng = np.random.default_rng(3)
    queries = [zipf_set(rng) for _ in range(9)]
    by_step, by_drain = {}, {}
    for sink, use_drain in ((by_step, False), (by_drain, True)):
        eng = DedupServeEngine(corpus, threshold=0.4, micro_batch=4)
        rids = [eng.submit(q) for q in queries]
        if use_drain:
            served = eng.drain()
        else:
            served = []
            while eng.queue_depth:
                served.extend(eng.step())
        for r in served:
            sink[r.rid] = r.matches
        assert sorted(sink) == sorted(rids)
    assert by_step == by_drain


# ---------------------------------------------------------------------- #
# admission: the intra-stream duplicate gap
# ---------------------------------------------------------------------- #
def fresh_doc(corpus, t=0.9):
    """A set that is not a near-dup of anything in the corpus."""
    rng = np.random.default_rng(99)
    while True:
        d = rng.choice(UNI, size=5, replace=False).astype(np.int32)
        probe = DedupServeEngine(corpus, threshold=t)
        probe.submit(d)
        if not probe.drain()[0].is_dup:
            return np.sort(d)


def test_intra_batch_duplicates_caught():
    corpus = make_corpus(4)
    d = fresh_doc(corpus)
    eng = DedupServeEngine(corpus, threshold=0.9, admit="survivors",
                           micro_batch=8)
    r1, r2 = eng.submit(d), eng.submit(d)
    out = {r.rid: r for r in eng.drain()}
    assert out[r1].admitted and not out[r1].is_dup
    assert out[r2].is_dup and not out[r2].admitted
    assert out[r2].matches == (out[r1].corpus_id,)
    assert eng.stats["intra_batch_dups"] == 1
    eng.encoder.flat.validate()


def test_cross_batch_duplicates_caught():
    corpus = make_corpus(5)
    d = fresh_doc(corpus)
    eng = DedupServeEngine(corpus, threshold=0.9, admit="survivors",
                           micro_batch=1)  # force separate batches
    r1, r2 = eng.submit(d), eng.submit(d)
    out = {r.rid: r for r in eng.drain()}
    assert out[r1].admitted
    # the second copy hits the first's admitted corpus row via the walk
    assert out[r2].is_dup and out[r1].corpus_id in out[r2].matches
    assert eng.corpus_rows == len(corpus.sets) + 1


def test_filter_batch_gap_vs_filter_stream():
    """The pinned DedupPipeline gap: identical new docs in one stream
    both survive filter_batch; filter_stream drops the copy."""
    corpus = make_corpus(6)
    doc = fresh_doc(corpus)
    docs = np.stack([doc, doc])  # token rows == element sets (shingle 1)
    pipe = DedupPipeline(corpus, threshold=0.9)
    kept_batch, _ = pipe.filter_batch(docs)
    assert len(kept_batch) == 2  # the gap: both identical docs survive
    kept_stream, stats = pipe.filter_stream([docs])
    assert len(kept_stream[0]) == 1  # fixed: the second copy is dropped
    assert stats["dups"] == 1 and stats["admitted"] == 1


def _near_dup_batch():
    """20 curated token docs and a 17-doc batch: 10 fresh docs, 5 near
    duplicates of curated docs (3 tokens changed) and 2 exact copies of
    fresh docs later in the batch (duplicates only within the stream)."""
    rng = np.random.default_rng(0)
    curated_docs = rng.integers(0, 500, (20, 64))
    fresh = rng.integers(0, 500, (10, 64))
    dups = curated_docs[:5].copy()
    dups[:, :3] = rng.integers(0, 500, (5, 3))  # near duplicates
    batch = np.concatenate([fresh, dups, fresh[[1, 6]]])
    return docs_to_sets(curated_docs, universe=500), batch


def _sequential_keep(curated, docs, t, measure):
    """Host oracle of ``filter_stream``: each doc is judged against the
    curated corpus plus every survivor before it, then admitted if new."""
    corpus = list(curated.sets)
    keep = []
    for i, doc in enumerate(docs):
        d = docs_to_sets(doc[None], universe=curated.universe)
        S = SetCollection.from_ragged(corpus, universe=curated.universe)
        if not brute_force_join(d, S, t, measure):
            keep.append(i)
            corpus.append(d.sets[0])
    return keep


@pytest.mark.parametrize("mode", ["filter_batch", "filter_stream"])
@pytest.mark.parametrize("measure", ["jaccard", "cosine", "dice", "overlap"])
def test_dedup_pipeline_drops_near_duplicates(measure, mode):
    curated, batch = _near_dup_batch()
    t = 0.8
    pipe = DedupPipeline(curated, threshold=t, n_shards=4, measure=measure)
    if mode == "filter_batch":
        kept, stats = pipe.filter_batch(batch)
        dup_rows = {r for r, _ in brute_force_join(
            docs_to_sets(batch, universe=500), curated, t, measure)}
        want = [i for i in range(len(batch)) if i not in dup_rows]
        assert stats["n_dropped"] == 5   # the stream's copies survive
    else:
        halves = [batch[:8], batch[8:]]  # the copies land in batch 2
        kept_halves, stats = pipe.filter_stream(halves)
        kept = np.concatenate(kept_halves)
        want = _sequential_keep(curated, batch, t, measure)
        assert stats["n_dropped"] == 7   # and are dropped here
    np.testing.assert_array_equal(kept, batch[want])
    assert stats["n_in"] == len(batch)
    assert stats["n_dropped"] == len(batch) - len(want)


def test_empty_corpus_start_admits():
    eng = DedupServeEngine(universe=UNI, threshold=0.8, admit="survivors")
    a = np.asarray([1, 2, 3], np.int32)
    eng.submit(a)
    eng.submit(a)
    out = eng.drain()
    assert [r.admitted for r in out] == [True, False]
    assert out[1].is_dup
    assert eng.corpus_rows == 1


# ---------------------------------------------------------------------- #
# async emission + stats
# ---------------------------------------------------------------------- #
def test_async_emission_and_stats():
    corpus = make_corpus(7)
    rng = np.random.default_rng(8)
    seen: list[DedupResult] = []
    eng = DedupServeEngine(corpus, threshold=0.5, micro_batch=4,
                           on_result=seen.append)
    rids = [eng.submit(zipf_set(rng)) for _ in range(10)]
    eng.drain()
    assert [r.rid for r in seen] == rids  # callback in submission order
    polled = eng.results()
    assert [r.rid for r in polled] == rids
    assert eng.results() == []  # queue drained by the poll
    for r in seen:
        assert r.latency_s >= 0
        assert r.stats["corpus_rows"] == len(corpus.sets)
        assert 0 < r.stats["batch_fill"] <= 1
        assert "walk_steps" in r.stats
    assert eng.stats["requests"] == 10
    assert eng.stats["walk_steps"] > 0


def test_validation_and_idle():
    eng = DedupServeEngine(universe=UNI)
    assert eng.step() == [] and eng.drain() == []
    with pytest.raises(ValueError):
        eng.submit(np.zeros((2, 2), np.int32))
    with pytest.raises(ValueError):
        DedupServeEngine(universe=UNI, admit="everything")


def test_submit_docs_shingle():
    corpus = make_corpus(9)
    eng = DedupServeEngine(corpus, threshold=0.99)
    docs = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]])
    rids = eng.submit_docs(docs)
    assert len(rids) == 2 and eng.queue_depth == 2
    eng.drain()
