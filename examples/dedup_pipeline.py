"""End-to-end example: dedup incoming docs against a curated corpus.

The paper's own LLM use case ([40]): incoming corpus batches are joined
against the curated corpus with MR-CF-RS-Join and exact near-duplicates
are dropped. ``filter_batch`` joins each batch against the static curated
corpus; ``filter_stream`` also admits every survivor into the corpus, so
a duplicate inside the stream is caught against its first copy.

  PYTHONPATH=src python examples/dedup_pipeline.py --batches 5
"""
import argparse

import numpy as np

from repro.data.pipeline import DedupPipeline
from repro.data.synth import docs_to_sets


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=5)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=12)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--threshold", type=float, default=0.75)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    # curated corpus the incoming docs must not duplicate
    curated_docs = rng.integers(0, args.vocab, (64, args.seq))
    curated = docs_to_sets(curated_docs, universe=args.vocab)
    pipe = DedupPipeline(curated, threshold=args.threshold, n_shards=4)

    batches = []
    for b in range(args.batches):
        r = np.random.default_rng(1000 + b)
        docs = r.integers(0, args.vocab, (args.batch, args.seq))
        # plant near-duplicates of curated docs to give the join real work
        i = (b * 2) % 60
        docs[:2] = curated_docs[i: i + 2]
        docs[:2, :4] = r.integers(0, args.vocab, (2, 4))
        # and one copy of this batch's last doc, caught only by the stream
        docs[2] = docs[-1]
        batches.append(docs)

    dropped = 0
    for b, docs in enumerate(batches):
        kept, stats = pipe.filter_batch(docs)
        dropped += stats["n_dropped"]
        print(f"filter_batch  batch {b}: kept {len(kept)}/{len(docs)}, "
              f"dropped {stats['n_dropped']}")
    kept, stats = pipe.filter_stream(batches)
    print(f"filter_batch  dropped {dropped} near-duplicate docs in all")
    print(f"filter_stream dropped {stats['n_dropped']} near-duplicate docs "
          f"in all (kept {sum(len(k) for k in kept)} of {stats['n_in']})")


if __name__ == "__main__":
    main()
