"""Seconds of reading, verifying and decoding checkpoint files, per resume
of the window (``codec.read`` spans)."""
from bench import spans


def read(run):
    got = spans.window_spans(run, "codec.read")
    return spans.per_resume(run, spans.seconds(got), got)
