"""Device-to-host bytes the snapshot staging copied per save of the
window (program counter ``snapshot_d2h_bytes``, ``CRAFT_METRICS``)."""


def read(run):
    moved = run.counters.get("snapshot_d2h_bytes", 0)
    if not run.saves or moved <= 0:
        return None
    return moved / len(run.saves) / 1e9
