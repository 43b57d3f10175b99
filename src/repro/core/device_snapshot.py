"""Device-resident snapshot pipeline (``CRAFT_DEVICE_SNAPSHOT``).

The host write path round-trips every checkpoint byte: a blocking
device→host copy per shard, then a host-side digest pass, then (for delta
writes) a digest-compare.  This module keeps that work on the accelerator:
one fused pass (``kernels.snapshot``) over the device-resident shard
produces per-chunk Fletcher digests, a dirty mask against the previous
snapshot's digests (kept device-resident between checkpoints), and the
byte-nibble histogram behind the zstd-vs-raw gate — and only the *dirty*
chunks are ever transferred to the host.

On an accelerator backend (``staged`` mode) host-side state per shard is a
**mirror**: a padded word buffer holding the exact bytes of the last
snapshot, patched chunk-wise from the device.  The mirror always equals the
live array's current bytes after ``snapshot()``, so every codec, tier and
delta base works unchanged downstream — the D2H traffic just shrinks to
the dirty fraction.  With ``double_buffer=True`` two mirrors alternate, so
an asynchronous writer can still be reading the previous version's mirror
while the next snapshot patches the other one; each mirror tracks its own
per-chunk digest table and fetches exactly the chunks that changed since
*it* was last current.  The previous snapshot's padded word buffer is
donated back to the packing computation, so the device-side staging buffer
is reused instead of re-allocated every checkpoint (double-buffered in
XLA's aliasing sense).

On CPU there is no transfer to shrink — ``np.asarray`` of a jax CPU array
is a zero-copy view of an immutable buffer — so no staging buffer or
mirror exists at all: the metadata pass fuses the byte-pack into its
reductions (one read of the array, nothing array-sized written) and the
zero-copy view is handed to the writer directly.  Immutability makes the
view snapshot-stable for free: a later update produces a *new* buffer,
while an in-flight asynchronous writer keeps the old one alive through
its view.

Fallbacks (host path, ``meta is None``): empty arrays, byte sizes not a
multiple of 4, complex dtypes, and any shape/dtype change — a reshape
resets the shard's state, which downstream means a full literal write.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import metrics
from repro.kernels.snapshot import ops as snapshot_ops

_LANES = 128


def _pack_words(x: jnp.ndarray, n_chunks: int, wpc: int) -> jnp.ndarray:
    """Flatten ``x`` and bit-cast its bytes to a zero-padded (n_chunks, wpc)
    uint32 matrix — little-endian, so it matches the host's
    ``view(np.uint32)`` of the same bytes exactly."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)     # same 1-byte 0/1 layout as numpy bool
    flat = x.reshape(-1)
    itemsize = np.dtype(x.dtype).itemsize
    if itemsize < 4:
        # Combine k narrow items into one word with shifts over strided lane
        # slices of a (rows, 128 * k) view.  Bit-casting a (n, k) view
        # instead would give the TPU an array whose minor dim of k is
        # padded to 128 lanes: 64x the shard's bytes for bf16.
        k = 4 // itemsize
        bits = 8 * itemsize
        narrow = jax.lax.bitcast_convert_type(
            flat, jnp.dtype(f"uint{bits}"))
        pad = (-narrow.shape[0]) % (_LANES * k)
        if pad:
            narrow = jnp.pad(narrow, (0, pad))
        rows = narrow.reshape(-1, _LANES * k)
        words = rows[:, 0::k].astype(jnp.uint32)
        for i in range(1, k):
            words = words | (rows[:, i::k].astype(jnp.uint32) << (bits * i))
        words = words.reshape(-1)[: -(-flat.shape[0] // k)]
    elif itemsize == 4:
        words = jax.lax.bitcast_convert_type(flat, jnp.uint32)
    else:
        words = jax.lax.bitcast_convert_type(flat, jnp.uint32).reshape(-1)
    pad = n_chunks * wpc - words.shape[0]
    if pad:
        words = jnp.pad(words, (0, pad))
    return words.reshape(n_chunks, wpc)


@functools.partial(
    jax.jit,
    static_argnames=("n_chunks", "wpc", "with_hist", "use_pallas"))
def _fused(arr, prev, *, n_chunks, wpc, with_hist, use_pallas):
    """Pack + fused snapshot in one dispatch, so XLA can feed the digest
    pass straight from the packing reshape without a second memory walk."""
    words2 = _pack_words(arr, n_chunks, wpc)
    meta = snapshot_ops.snapshot_chunks(
        words2, prev, with_hist=with_hist, use_pallas=use_pallas)
    return words2, meta


@functools.partial(
    jax.jit,
    static_argnames=("n_chunks", "wpc", "with_hist", "use_pallas"),
    donate_argnums=(2,))
def _fused_donate(arr, prev, old_words, *, n_chunks, wpc, with_hist,
                  use_pallas):
    """Same, donating the previous snapshot's word buffer so XLA aliases the
    new one into its memory (device-side double buffering)."""
    del old_words
    words2 = _pack_words(arr, n_chunks, wpc)
    meta = snapshot_ops.snapshot_chunks(
        words2, prev, with_hist=with_hist, use_pallas=use_pallas)
    return words2, meta


class _ShardState:
    __slots__ = ("shape", "dtype", "n_chunks", "wpc", "prev_digests",
                 "words", "mirrors", "mirror_digs", "flip")

    def __init__(self, shape, dtype, n_chunks, wpc, buffers):
        self.shape = shape
        self.dtype = dtype
        self.n_chunks = n_chunks
        self.wpc = wpc
        self.prev_digests = None        # (n_chunks, 2) uint32, device
        self.words = None               # last padded word buffer (donation)
        self.mirrors = [None] * buffers
        self.mirror_digs = [None] * buffers
        self.flip = 0


class DeviceSnapshotter:
    """Per-checkpointable device snapshot state (one instance per Cp object,
    shards keyed by the caller — see ``JaxArrayCp`` / ``PytreeCp``)."""

    def __init__(self, chunk_bytes: int, *, with_hist: bool = True,
                 double_buffer: bool = True, staged: Optional[bool] = None):
        self.chunk_bytes = int(chunk_bytes)
        self.with_hist = with_hist
        self.buffers = 2 if double_buffer else 1
        # staged: device words buffer + host mirror (None = auto: only on
        # accelerator backends; CPU hands out zero-copy views instead)
        self.staged = staged
        self._state: dict = {}

    def reset(self) -> None:
        self._state.clear()

    def _grid(self, nbytes: int) -> Tuple[int, int]:
        """(n_chunks, words_per_chunk) matching the storage chunk grid; a
        single-chunk array pads only to the lane multiple, not a full chunk."""
        n_chunks = max(1, -(-nbytes // self.chunk_bytes))
        if n_chunks == 1:
            words = nbytes // 4
            wpc = max(_LANES, -(-words // _LANES) * _LANES)
        else:
            wpc = self.chunk_bytes // 4
        return n_chunks, wpc

    def snapshot(self, key, arr: jax.Array
                 ) -> Tuple[np.ndarray, Optional[dict]]:
        """Snapshot one device shard.  Returns ``(host_array, meta)`` where
        ``host_array`` equals ``np.asarray(arr)`` bit-for-bit and ``meta``
        is the device-produced chunk metadata for
        ``IOContext.record_device_meta`` — or ``None`` when the shard took
        the plain host path."""
        dtype = np.dtype(arr.dtype)
        nbytes = int(arr.size) * dtype.itemsize
        if (nbytes == 0 or nbytes % 4 or self.chunk_bytes % 4
                or dtype.kind == "c"):
            self._state.pop(key, None)
            return np.asarray(arr), None
        shape = tuple(arr.shape)
        n_chunks, wpc = self._grid(nbytes)

        st = self._state.get(key)
        if st is not None and (st.shape != shape or st.dtype != dtype
                               or st.n_chunks != n_chunks or st.wpc != wpc):
            st = None                   # reshape/regrid → full reset
        first = st is None
        if first:
            st = _ShardState(shape, dtype, n_chunks, wpc, self.buffers)
            self._state[key] = st

        backend = jax.default_backend()
        use_pallas = backend == "tpu"
        if use_pallas and wpc % _LANES:
            # only a multi-chunk grid can get here: _grid pads one chunk
            raise ValueError(
                f"CRAFT_CHUNK_BYTES={self.chunk_bytes} is not a multiple of "
                f"{4 * _LANES}: the TPU snapshot kernel needs whole "
                f"{_LANES}-word lanes in every chunk")
        staged = self.staged if self.staged is not None else backend != "cpu"
        if not staged:
            # CPU: zero-copy view of the immutable buffer — snapshot-stable
            # without any mirror — and the numpy snapshot pass over it (the
            # checksum ops' numpy-on-CPU dispatch, one read, no packing).
            host = np.asarray(arr)
            prev_np = (st.prev_digests if st.prev_digests is not None
                       else np.zeros((n_chunks, 2), np.uint32))
            meta_host = snapshot_ops.snapshot_host(
                host.reshape(-1).view(np.uint8), self.chunk_bytes, prev_np)
            cur_dig = meta_host[:, :2]
            st.prev_digests = cur_dig
        else:
            donate = backend != "cpu"          # CPU jit ignores donation
            prev = (st.prev_digests if st.prev_digests is not None
                    else jnp.zeros((n_chunks, 2), jnp.uint32))
            kw = dict(n_chunks=n_chunks, wpc=wpc, with_hist=self.with_hist,
                      use_pallas=use_pallas)
            if donate and st.words is not None:
                words2, meta_dev = _fused_donate(arr, prev, st.words, **kw)
            else:
                words2, meta_dev = _fused(arr, prev, **kw)
            st.prev_digests = meta_dev[:, :2]
            st.words = words2 if donate else None
            meta_host = np.asarray(meta_dev)
            cur_dig = meta_host[:, :2]
            # Patch this round's mirror: fetch exactly the chunks whose
            # digest changed since the mirror was last current (a superset
            # of the device dirty column when double buffering skips a
            # round).
            mi = st.flip
            st.flip = (st.flip + 1) % self.buffers
            mirror = st.mirrors[mi]
            if mirror is None:
                mirror = st.mirrors[mi] = np.empty((n_chunks, wpc),
                                                   np.uint32)
                rows = np.arange(n_chunks)
            else:
                rows = np.flatnonzero(
                    (cur_dig != st.mirror_digs[mi]).any(axis=1))
            if rows.size == n_chunks:
                mirror[...] = np.asarray(words2)         # one full transfer
            elif rows.size:
                mirror[rows] = np.asarray(words2[rows])  # gather, dirty only
            st.mirror_digs[mi] = cur_dig.copy()
            host = (mirror.reshape(-1).view(np.uint8)[:nbytes]
                    .view(dtype).reshape(shape))

        entropy = None
        if staged and self.with_hist:     # numpy pass carries no histogram
            hist = meta_host[:, 3:].astype(np.int64)
            pad_bytes = n_chunks * wpc * 4 - nbytes
            if pad_bytes:       # padded zero bytes: 2 spurious bin-0 nibbles
                hist[-1, 0] -= 2 * pad_bytes
            entropy = [float(e)
                       for e in snapshot_ops.chunk_entropy_bits(hist)]
        meta = {
            "nbytes": nbytes,
            "chunk_bytes": self.chunk_bytes,
            "rdigests": cur_dig.astype(np.int64).tolist(),
            "dirty": (None if first
                      else meta_host[:, 2].astype(bool).tolist()),
            "entropy_bits": entropy,
        }
        if metrics.REGISTRY.enabled:   # keep the unset path numpy-free
            if meta["dirty"] is not None:
                metrics.set_gauge(
                    "snapshot_dirty_fraction",
                    sum(meta["dirty"]) / max(1, n_chunks))
            if staged:
                metrics.inc("snapshot_d2h_bytes", int(rows.size) * wpc * 4)
                metrics.inc("snapshot_d2h_bytes_saved",
                            (n_chunks - int(rows.size)) * wpc * 4)
        return host, meta
