"""Mean over the window's steps of DedupResult.stats["batch_fill"]."""


def read(run):
    fills = run.record["batch_fill"]
    return sum(fills) / len(fills) if fills else None
