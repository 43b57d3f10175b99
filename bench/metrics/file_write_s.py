"""Seconds of payload file writes (temporary file, write, fsync, rename),
summed over the files and tiers of a save, per save of the window
(``file.write`` spans)."""
from bench import spans


def read(run):
    got = spans.save_spans(run, "file.write")
    return spans.per_save(run, spans.seconds(got), got)
