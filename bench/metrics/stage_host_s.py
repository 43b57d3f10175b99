"""Host seconds of the snapshot staging outside its copies and waits, per
save of the window: the self time (duration less direct children) of the
``cp.update``, ``snapshot.leaf`` and ``snapshot.dispatch`` spans."""
from bench import spans


def read(run):
    got = spans.save_spans(run, "cp.update", "snapshot.leaf",
                           "snapshot.dispatch")
    return spans.per_save(run, spans.self_seconds(run, got), got)
