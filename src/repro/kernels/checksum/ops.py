"""Public checksum ops: byte-buffer digests with backend dispatch."""
from __future__ import annotations

from typing import Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.checksum.kernel import checksum as checksum_pallas
from repro.kernels.checksum.ref import checksum_ref

_BLOCK_BYTES = 512 * 128 * 4  # block_rows=512 tiles of 128 uint32 lanes


def digest_array(x: jnp.ndarray, *, use_pallas: bool = None) -> Tuple[int, int]:
    """(s1, s2) digest of a 1-D uint32 array (padded to block multiple)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    n = x.shape[0]
    block_elems = _BLOCK_BYTES // 4
    pad = (-n) % block_elems
    if pad:
        x = jnp.pad(x, (0, pad))
    if use_pallas:
        out = checksum_pallas(x)
    else:
        out = jax.jit(checksum_ref)(x)
    s1, s2 = np.asarray(out)
    return int(s1), int(s2)


def digest_bytes(buf: Union[bytes, bytearray, np.ndarray]) -> Tuple[int, int]:
    """(s1, s2) digest of a raw byte buffer (zero-padded to 4-byte words)."""
    arr = _as_u8(buf)
    pad = (-arr.size) % 4
    if pad:
        arr = np.concatenate([arr, np.zeros(pad, dtype=np.uint8)])
    words = arr.view(np.uint32)
    return digest_array(jnp.asarray(words))


def _as_u8(buf) -> np.ndarray:
    return (
        np.frombuffer(buf, dtype=np.uint8)
        if isinstance(buf, (bytes, bytearray, memoryview))
        else np.ascontiguousarray(buf).view(np.uint8).ravel()
    )


@jax.jit
def _rows_checksum(x2: jnp.ndarray) -> jnp.ndarray:
    """Per-row [s1, s2] of a (rows, words) uint32 matrix — the same sums the
    blocked kernel computes, batched so one dispatch digests every chunk."""
    idx = jnp.arange(x2.shape[1], dtype=jnp.uint32)[None, :] + jnp.uint32(1)
    s1 = jnp.sum(x2, axis=1, dtype=jnp.uint32)
    s2 = jnp.sum(x2 * idx, axis=1, dtype=jnp.uint32)
    return jnp.stack([s1, s2], axis=1)


def _rows_checksum_np(body: np.ndarray) -> list:
    """Host fallback of :func:`_rows_checksum`: identical mod-2^32 sums via
    numpy's wrapping uint32 arithmetic — no device copy, no dispatch."""
    idx = (np.arange(body.shape[1], dtype=np.uint32) + np.uint32(1))[None, :]
    with np.errstate(over="ignore"):
        s1 = np.sum(body, axis=1, dtype=np.uint32)
        s2 = np.sum(body * idx, axis=1, dtype=np.uint32)
    return [[int(a), int(b)] for a, b in zip(s1, s2)]


_HOST_BLOCK_WORDS = 1 << 18                     # 1 MiB of payload a block
_HOST_WEIGHTS = np.arange(1, _HOST_BLOCK_WORDS + 1, dtype=np.uint32)


def digest_host(buf: Union[bytes, bytearray, np.ndarray]) -> list:
    """[s1, s2] of a byte buffer computed on the host — bit-identical to
    :func:`digest_bytes` (ragged tail zero-padded to a word), with no device
    transfer and no dispatch: the read path verifies bytes already in host
    memory.  Blocks of words are combined by associativity
    (``s2 += offset * s1``); uint32 ``np.dot`` wraps mod 2^32 and releases
    the GIL, so IO workers digest in parallel."""
    arr = _as_u8(buf)
    n_words, rem = divmod(arr.size, 4)
    words = arr[: n_words * 4].view(np.uint32)
    s1 = s2 = 0
    for off in range(0, n_words, _HOST_BLOCK_WORDS):
        block = words[off: off + _HOST_BLOCK_WORDS]
        b1 = int(np.sum(block, dtype=np.uint32))
        b2 = int(np.dot(block, _HOST_WEIGHTS[: block.size]))
        s1 += b1
        s2 += b2 + off * b1
    if rem:
        tail = int.from_bytes(arr[n_words * 4:].tobytes(), "little")
        s1 += tail
        s2 += (n_words + 1) * tail
    return [s1 & 0xFFFFFFFF, s2 & 0xFFFFFFFF]


def digest_chunks(buf: Union[bytes, bytearray, np.ndarray],
                  chunk_bytes: int, *, use_pallas: bool = None) -> list:
    """Per-chunk (s1, s2) digests of ``buf`` split every ``chunk_bytes``.

    Bit-identical to ``digest_bytes(chunk)`` on each slice (zero padding is
    digest-neutral: both sums ignore zero words), but the full-size chunks go
    through **one** batched pass instead of one call per chunk — this is the
    delta codec's change-detection pass, where per-call overhead would
    otherwise dominate a mostly-clean checkpoint.  On TPU the batched rows
    run on-device next to the blocked kernel; on CPU the identical modular
    sums run directly in numpy (the device round-trip costs ~3x the math).
    The ragged tail chunk (if any) is digested separately.  Returns
    ``[[s1, s2], ...]``.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    arr = _as_u8(buf)
    chunk_bytes = int(chunk_bytes)
    if arr.size == 0:
        return []
    if chunk_bytes % 4:
        # word grid doesn't tile the chunk grid — fall back to per-chunk calls
        return [
            list(digest_bytes(arr[off: off + chunk_bytes]))
            for off in range(0, arr.size, chunk_bytes)
        ]
    n_full = arr.size // chunk_bytes
    out = []
    if n_full:
        body = arr[: n_full * chunk_bytes].view(np.uint32)
        body = body.reshape(n_full, chunk_bytes // 4)
        if use_pallas:
            rows = np.asarray(_rows_checksum(jnp.asarray(body)))
            out.extend([int(a), int(b)] for a, b in rows)
        else:
            out.extend(_rows_checksum_np(body))
    tail = arr[n_full * chunk_bytes:]
    if tail.size:
        out.append(list(digest_bytes(tail)))
    return out
