"""The snapshot program's share of its HBM roofline.  Each save runs one
staging program per leaf (``core/device_snapshot``: pack the leaf's bytes
onto the chunk grid and run the snapshot kernel over them).  The leaf is
the program's input and the packed words its output, so a run must at
least read the one from HBM and write the other back (``bench/flops.py``).
The least time is those bytes at the chip's HBM peak; the time taken is the
device time of the window's runs of those programs (the trace's "XLA
Modules" line).  The kernel alone is not held to the HBM peak: XLA places
its operand in on-chip memory (``S(1)`` in its layout), so the kernel's
reads do not reach HBM."""
from bench import flops, peaks, tracefile

# the jitted staging programs, with and without the donated buffer
PROGRAM = r"jit__fused(_donate)?\b"


def read(run):
    if run.reduced is None or not run.saves:
        return None
    seconds = sum(tracefile.module_runs_matching(run.reduced, PROGRAM))
    if seconds <= 0:
        return None
    chunk = int(run.cell.config["craft_env"].get("CRAFT_CHUNK_BYTES",
                                                 4 * 1024 * 1024))
    moved = len(run.saves) * flops.snapshot_program_bytes(run.leaf_nbytes,
                                                          chunk)
    least = moved / peaks.peaks_for(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / seconds
