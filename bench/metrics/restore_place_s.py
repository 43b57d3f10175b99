"""Seconds of ``jax.device_put`` of the restored leaves, per resume of the
window (``restore.place`` spans).  The put is asynchronous: the span
holds the call, not necessarily the whole transfer (see PERF.md)."""
from bench import spans


def read(run):
    got = spans.window_spans(run, "restore.place")
    return spans.per_resume(run, spans.seconds(got), got)
