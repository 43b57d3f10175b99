"""Bytes computed from shapes, kept with the benchmark: what the snapshot
programs must read and write for the chunk grid the write path uses
(``core/device_snapshot._grid``).  A model's FLOP count belongs to its
architecture's reference (``bench/ref_training.py``)."""
from __future__ import annotations

LANES = 128


def chunk_grid(nbytes: int, chunk_bytes: int):
    """(n_chunks, words_per_chunk) of a leaf on the snapshot chunk grid."""
    n_chunks = max(1, -(-nbytes // chunk_bytes))
    if n_chunks == 1:
        words = nbytes // 4
        wpc = max(LANES, -(-words // LANES) * LANES)
    else:
        wpc = chunk_bytes // 4
    return n_chunks, wpc


def snapshot_program_bytes(leaf_nbytes, chunk_bytes: int) -> int:
    """HBM bytes one snapshot of these leaves makes the staging programs
    move at the least: each leaf read once, and its packed copy (the
    padded word matrix on the chunk grid) written once."""
    total = 0
    for nbytes in leaf_nbytes:
        if nbytes == 0 or nbytes % 4:
            continue            # such a leaf takes the host path
        n_chunks, wpc = chunk_grid(nbytes, chunk_bytes)
        total += nbytes + n_chunks * wpc * 4
    return total

