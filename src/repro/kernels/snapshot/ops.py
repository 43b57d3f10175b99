"""Public snapshot ops: fused per-chunk metadata with backend dispatch, plus
the host-side helpers that turn raw nibble histograms into compressibility
estimates (the zstd-vs-raw gate, ``CRAFT_ZSTD_GATE_BITS``)."""
from __future__ import annotations

from typing import Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.snapshot.kernel import snapshot as snapshot_pallas
from repro.kernels.snapshot.ref import HIST_BINS, META_COLS, snapshot_ref

_LANES = 128

_ref_jit = jax.jit(snapshot_ref, static_argnames=("with_hist",))


def _block_rows_for(rows: int) -> int:
    """Largest power-of-two tile height in 8..512 that divides ``rows``, or
    the whole chunk: a block's height must be a multiple of the 8-row
    sublane tile or the array's own."""
    for br in (512, 256, 128, 64, 32, 16, 8):
        if rows % br == 0:
            return br
    return rows


def snapshot_chunks(
    words2: jnp.ndarray, prev_digests: jnp.ndarray, *,
    with_hist: bool = True, use_pallas: bool = None, interpret: bool = False,
) -> jnp.ndarray:
    """Fused per-chunk ``[s1, s2, dirty, hist…]`` of a (n_chunks, wpc) uint32
    matrix — the Pallas kernel on TPU, the jitted oracle elsewhere.  The
    kernel needs whole 128-word lanes per chunk and raises otherwise.  The
    result stays on device; callers slice the digest columns off as the next
    snapshot's ``prev_digests`` without a transfer.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    wpc = words2.shape[1]
    if use_pallas:
        if wpc % _LANES:
            raise ValueError(
                f"snapshot kernel needs whole {_LANES}-word lanes per chunk, "
                f"got {wpc} words")
        return snapshot_pallas(
            words2, prev_digests, block_rows=_block_rows_for(wpc // _LANES),
            with_hist=with_hist, interpret=interpret)
    return _ref_jit(words2, prev_digests, with_hist=with_hist)


_weights_cache: dict = {}


def _word_weights(wpc: int) -> np.ndarray:
    w = _weights_cache.get(wpc)
    if w is None:
        w = _weights_cache[wpc] = np.arange(1, wpc + 1, dtype=np.uint32)
    return w


def snapshot_host(host_bytes: np.ndarray, chunk_bytes: int,
                  prev_digests: np.ndarray) -> np.ndarray:
    """Numpy snapshot pass: per-chunk ``[s1, s2, dirty]`` of a flat uint8
    buffer over the storage chunk grid (no histogram — the zstd gate falls
    back to per-dirty-chunk host counts, which is cheaper than histogramming
    every chunk here).  This is the CPU-backend twin of the fused kernel,
    mirroring the checksum ops' numpy-on-CPU dispatch; it reads the buffer
    in place (no packing copy), so on CPU the whole snapshot costs one
    digest pass over a zero-copy view."""
    nbytes = host_bytes.size
    if nbytes % 4:
        raise ValueError(f"snapshot_host needs 4-byte-aligned size, "
                         f"got {nbytes}")
    words = host_bytes.view(np.uint32)
    wpc = chunk_bytes // 4
    n_chunks = max(1, -(-nbytes // chunk_bytes))
    full = words.size // wpc          # complete chunks; the rest is tail
    out = np.zeros((n_chunks, 3), dtype=np.uint32)
    if full:
        body = words[:full * wpc].reshape(full, wpc)
        # NB: broadcasting the 1-D weights row directly is ~2x faster than a
        # (1, wpc)-shaped operand here — numpy's inner-loop stride handling
        # is better when the broadcast axis is implicit.
        with np.errstate(over="ignore"):
            out[:full, 0] = body.sum(axis=1, dtype=np.uint32)
            out[:full, 1] = (body * _word_weights(wpc)).sum(
                axis=1, dtype=np.uint32)
    tail = words[full * wpc:]
    if tail.size:        # zero-padding is digest-neutral, so weigh as-is
        with np.errstate(over="ignore"):
            out[-1, 0] = tail.sum(dtype=np.uint32)
            out[-1, 1] = (tail * _word_weights(wpc)[:tail.size]).sum(
                dtype=np.uint32)
    out[:, 2] = (out[:, :2] != prev_digests).any(axis=1)
    return out


def chunk_entropy_bits(hist: np.ndarray) -> np.ndarray:
    """Per-chunk order-0 entropy estimate in bits/byte from (n, 16) nibble
    histograms (each byte contributes its high and its low nibble, so a
    chunk's counts sum to ``2 * chunk_len``).  An upper byte entropy of 8
    bits means incompressible-looking data; long-range structure is invisible
    to an order-0 estimate, which is why the gate threshold must sit close
    to 8 (see ``CRAFT_ZSTD_GATE_BITS``)."""
    h = np.asarray(hist, dtype=np.float64)
    tot = h.sum(axis=1, keepdims=True)
    p = np.divide(h, tot, out=np.zeros_like(h), where=tot > 0)
    logp = np.log2(p, out=np.zeros_like(p), where=p > 0)
    return -2.0 * (p * logp).sum(axis=1)


def host_nibble_hist(buf: Union[bytes, bytearray, memoryview, np.ndarray]
                     ) -> np.ndarray:
    """(16,) nibble histogram of a byte buffer — the host fallback of the
    kernel's histogram columns, for gating chunks that never saw a device."""
    a = (np.frombuffer(buf, dtype=np.uint8)
         if isinstance(buf, (bytes, bytearray, memoryview))
         else np.ascontiguousarray(buf).view(np.uint8).ravel())
    if a.size == 0:
        return np.zeros(HIST_BINS, dtype=np.int64)
    return (np.bincount(a >> 4, minlength=HIST_BINS)
            + np.bincount(a & 0xF, minlength=HIST_BINS)).astype(np.int64)
