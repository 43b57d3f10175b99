"""Bytes the codec wrote to all tiers per save of the window (program
counter ``Checkpoint.stats["tier_bytes_written"]``)."""


def read(run):
    written = run.counters.get("tier_bytes_written", 0)
    if not run.saves or written <= 0:
        return None
    return written / len(run.saves) / 1e9
