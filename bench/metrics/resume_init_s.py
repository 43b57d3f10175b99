"""Seconds ``train.run`` spent building the initial state and setting up
its checkpoint (which copies that state to the host) before the restore,
per resume of the window (``train.init_state`` and ``train.cp_setup``
spans)."""
from bench import spans


def read(run):
    got = spans.window_spans(run, "train.init_state", "train.cp_setup")
    return spans.per_resume(run, spans.seconds(got), got)
