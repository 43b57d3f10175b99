"""Seconds in which no op ran on the device while the step loop staged a
save, per save of the window: the profile's device idle time inside the
``cp.update`` spans, put on the profile's clock through the benchmark's
own spans named in it."""
from bench import spans


def read(run):
    got = spans.save_spans(run, "cp.update")
    events = spans.profile(run) if got else None
    if events is None:
        return None
    pairs = spans.clock_pairs(run, events)
    if not pairs:
        return None
    return spans.per_save(run, spans.idle_inside(events, pairs, got), got)
