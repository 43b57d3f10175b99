"""Staging programs launched per save of the window (``snapshot.dispatch``
spans: one per leaf shard)."""
from bench import spans


def read(run):
    got = spans.save_spans(run, "snapshot.dispatch")
    return spans.per_save(run, float(len(got)), got)
