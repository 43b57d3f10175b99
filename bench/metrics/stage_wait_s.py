"""Seconds the host waited for the staging programs to finish (the chunk
metadata read back after each launch), per save of the window
(``snapshot.wait`` spans)."""
from bench import spans


def read(run):
    got = spans.save_spans(run, "snapshot.wait")
    return spans.per_save(run, spans.seconds(got), got)
