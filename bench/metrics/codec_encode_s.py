"""Seconds of digest, compression gate and chunk encoding, summed over the
files and tiers of a save, per save of the window (``codec.encode``
spans)."""
from bench import spans


def read(run):
    got = spans.save_spans(run, "codec.encode")
    return spans.per_save(run, spans.seconds(got), got)
