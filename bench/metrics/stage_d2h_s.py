"""Seconds the snapshot staging spent copying packed words from the device
into the host mirrors, per save of the window (``snapshot.d2h`` spans)."""
from bench import spans


def read(run):
    got = spans.save_spans(run, "snapshot.d2h")
    return spans.per_save(run, spans.seconds(got), got)
