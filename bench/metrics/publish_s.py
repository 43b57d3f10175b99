"""Seconds the tiers took to publish a staged version (directory tree
fsync, rename, metadata, redundancy), summed over the tiers of a save,
per save of the window (``tier.publish`` spans)."""
from bench import spans


def read(run):
    got = spans.save_spans(run, "tier.publish")
    return spans.per_save(run, spans.seconds(got), got)
