"""Seconds of copying decoded shards into each leaf's global host buffer,
per resume of the window (``restore.assemble`` spans)."""
from bench import spans


def read(run):
    got = spans.window_spans(run, "restore.assemble")
    return spans.per_resume(run, spans.seconds(got), got)
