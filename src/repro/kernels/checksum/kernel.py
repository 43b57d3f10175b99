"""Pallas TPU kernel: blocked Fletcher-like checksum (checkpoint integrity).

Device-side integrity digests let the node-level tier verify a checkpoint
shard *before* the bytes ever leave HBM (beyond-paper extension of CRAFT's
crc32-on-host).  The digest is a pair of mod-2^32 sums (see ref.py); the
position-weighted ``s2`` makes it order-sensitive, unlike a plain sum.

TPU mapping: the uint32 stream is viewed as (rows, 128) so every tile is
lane-aligned; the grid walks row-blocks sequentially, each step computing the
tile-local (s1, s2) on the VPU, shifting s2 by the tile's element offset
(associativity: s2 += offset · s1, mod 2^32), and accumulating into one
(8, 128) output tile that every grid step maps to the same location — the
canonical Pallas-TPU reduction-across-grid idiom.  The TPU has no unsigned
reductions, so the tile is bit-cast to int32 and summed there: two's
complement addition and multiplication give the same low 32 bits as the
uint32 ones, so the digest is bit-identical to the reference.  The output
tile is the smallest block the (8, 128) tiling allows; s1 and s2 sit in its
first two lanes and the wrapper slices them out.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128
_SUBLANES = 8


def place_scalars(parts: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """An (8, 128) int32 tile holding ``parts[k]`` at row 0, lane k and zeros
    elsewhere — how a kernel writes a few scalars through a legal block."""
    row = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 1)
    tile = jnp.zeros((_SUBLANES, _LANES), jnp.int32)
    for k, v in enumerate(parts):
        tile = jnp.where((row == 0) & (lane == k), v, tile)
    return tile


def tile_digest(tile: jnp.ndarray, offset: jnp.ndarray):
    """(s1, s2) of an int32 (rows, 128) tile whose first word sits at
    element ``offset`` of the stream, as int32 (wrapping = mod 2^32)."""
    row = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    local_pos1 = row * _LANES + lane + 1                   # 1-based
    s1 = jnp.sum(tile, dtype=jnp.int32)
    s2 = jnp.sum(tile * local_pos1, dtype=jnp.int32) + offset * s1
    return s1, s2


def _checksum_kernel(x_ref, out_ref, *, block_rows: int):
    i = pl.program_id(0)
    tile = jax.lax.bitcast_convert_type(x_ref[...], jnp.int32)
    s1, s2 = tile_digest(tile, i * (block_rows * _LANES))
    contrib = place_scalars([s1, s2])

    @pl.when(i == 0)
    def _init():
        out_ref[...] = contrib

    @pl.when(i != 0)
    def _acc():
        out_ref[...] = out_ref[...] + contrib


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def checksum(
    x: jnp.ndarray, *, block_rows: int = 512, interpret: bool = False
) -> jnp.ndarray:
    """Blocked checksum of a 1-D uint32 array; returns (2,) uint32 [s1, s2].

    ``len(x)`` must be a multiple of ``block_rows * 128`` (ops.py zero-pads —
    zero lanes contribute nothing to either sum, so padding is digest-neutral
    given the true length is recorded alongside).
    """
    if x.ndim != 1 or x.dtype != jnp.uint32:
        raise TypeError(f"expected 1-D uint32, got {x.shape} {x.dtype}")
    n = x.shape[0]
    block_n = block_rows * _LANES
    if n % block_n:
        raise ValueError(f"N={n} must be a multiple of block_rows*128={block_n}")
    x2 = x.reshape(n // _LANES, _LANES)
    grid = (n // block_n,)
    out = pl.pallas_call(
        functools.partial(_checksum_kernel, block_rows=block_rows),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_SUBLANES, _LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((_SUBLANES, _LANES), jnp.int32),
        interpret=interpret,
    )(x2)
    return jax.lax.bitcast_convert_type(out[0, :2], jnp.uint32)
