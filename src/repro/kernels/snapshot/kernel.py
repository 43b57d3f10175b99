"""Pallas TPU kernel: fused snapshot pass (digest + dirty mask + histogram).

The device-resident write path (``CRAFT_DEVICE_SNAPSHOT``) needs three
per-chunk facts before any checkpoint byte leaves HBM: the Fletcher digest
(storage integrity + the delta codec's change detector), whether the chunk
differs from the previous snapshot (so only dirty chunks cross the
interconnect), and a byte-nibble histogram (the order-0 entropy estimate
that gates zstd vs raw).  Computing them in one fused pass costs a single
read of the shard instead of three.

TPU mapping: the shard's uint32 words are viewed as
(n_chunks, rows_per_chunk, 128) so every tile is lane-aligned; the grid is
(chunk, row_block) with the row_block axis innermost, each step computing
the tile-local sums/counts on the VPU and accumulating into one (8, 128)
output tile per chunk that every step of the chunk maps to the same location
(the checksum kernel's reduction-across-grid idiom, with the same int32
sums: the TPU has no unsigned reductions, and two's complement gives the
same low 32 bits).  The digest offset shift uses the associative blocking
identity ``s2 += offset * s1``; the dirty flag is resolved on the chunk's
final row block by comparing the accumulated digest against the previous
snapshot's digest table, which stays device-resident between checkpoints
and enters the kernel as one (8, 128) tile per chunk.  Results sit in the
first lanes of row 0 of each output tile, and the wrapper slices them out:
(8, 128) is the smallest block the TPU tiling allows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.checksum.kernel import place_scalars, tile_digest
from repro.kernels.snapshot.ref import HIST_BINS, META_COLS

_LANES = 128
_SUBLANES = 8


def _snapshot_kernel(x_ref, prev_ref, out_ref, *,
                     block_rows: int, rpb: int, with_hist: bool):
    j = pl.program_id(1)                       # row block within the chunk
    tile = jax.lax.bitcast_convert_type(x_ref[...], jnp.int32)
    s1, s2 = tile_digest(tile, j * (block_rows * _LANES))
    parts = [s1, s2, jnp.int32(0)]             # dirty resolved on last block
    if with_hist:
        nibs = [jax.lax.shift_right_logical(tile, sh) & 0xF
                for sh in range(0, 32, 4)]
        for k in range(HIST_BINS):
            hits = sum((nib == k).astype(jnp.int32) for nib in nibs)
            parts.append(jnp.sum(hits, dtype=jnp.int32))
    contrib = place_scalars(parts)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = contrib

    @pl.when(j != 0)
    def _acc():
        out_ref[...] = out_ref[...] + contrib

    @pl.when(j == rpb - 1)
    def _finish():
        acc = out_ref[...]
        row = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        differs = (acc != prev_ref[...]) & (row == 0) & (lane < 2)
        dirty = (jnp.sum(differs.astype(jnp.int32)) > 0).astype(jnp.int32)
        out_ref[...] = acc + jnp.where((row == 0) & (lane == 2), dirty, 0)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "with_hist", "interpret"))
def snapshot(
    x2: jnp.ndarray, prev: jnp.ndarray, *, block_rows: int = 512,
    with_hist: bool = True, interpret: bool = False,
) -> jnp.ndarray:
    """Fused per-chunk [s1, s2, dirty, hist…] of a (n_chunks, wpc) uint32
    matrix (see ref.py for the definition).  ``wpc`` must be a multiple of
    128 and ``wpc // 128`` a multiple of ``block_rows``; on TPU
    ``block_rows`` must also be a multiple of 8 or the whole chunk (ops.py
    zero-pads and picks such a block size — zero words are digest-neutral
    and their histogram counts are corrected on the host from the known pad
    length).
    """
    if x2.ndim != 2 or x2.dtype != jnp.uint32:
        raise TypeError(f"expected 2-D uint32, got {x2.shape} {x2.dtype}")
    n_chunks, wpc = x2.shape
    if prev.shape != (n_chunks, 2) or prev.dtype != jnp.uint32:
        raise TypeError(
            f"expected ({n_chunks}, 2) uint32 prev digests, got "
            f"{prev.shape} {prev.dtype}"
        )
    if wpc % _LANES:
        raise ValueError(f"wpc={wpc} must be a multiple of {_LANES}")
    rows = wpc // _LANES
    if rows % block_rows:
        raise ValueError(
            f"rows_per_chunk={rows} must be a multiple of block_rows="
            f"{block_rows}"
        )
    rpb = rows // block_rows
    width = META_COLS if with_hist else 3
    x3 = x2.reshape(n_chunks, rows, _LANES)
    prev_tiles = jnp.zeros((n_chunks, _SUBLANES, _LANES), jnp.int32)
    prev_tiles = prev_tiles.at[:, 0, :2].set(
        jax.lax.bitcast_convert_type(prev, jnp.int32))
    tile = (None, _SUBLANES, _LANES)
    out = pl.pallas_call(
        functools.partial(_snapshot_kernel, block_rows=block_rows, rpb=rpb,
                          with_hist=with_hist),
        grid=(n_chunks, rpb),
        in_specs=[
            pl.BlockSpec((None, block_rows, _LANES), lambda i, j: (i, j, 0)),
            pl.BlockSpec(tile, lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec(tile, lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (n_chunks, _SUBLANES, _LANES), jnp.int32),
        interpret=interpret,
        # its own line in a profile, apart from the staging program's ops
        name="craft_snapshot",
    )(x3, prev_tiles)
    return jax.lax.bitcast_convert_type(out[:, 0, :width], jnp.uint32)
