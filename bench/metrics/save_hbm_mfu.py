"""The whole save's share of the chip's peak: the checkpointed state's
bytes read once at the HBM peak, over the save stall (time inside
``update_and_write`` per save).  Defined on the state, not on a kernel, so
it still bounds a stall claim after the snapshot kernel leaves the path."""
from bench import peaks


def read(run):
    if not run.saves or run.update_seconds <= 0:
        return None
    least = sum(run.leaf_nbytes) / peaks.peaks_for(
        run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / (run.update_seconds / len(run.saves))
