"""Device-resident snapshot pipeline (CRAFT_DEVICE_SNAPSHOT).

Covers the fused snapshot kernel against its jitted oracle (bit-identical),
the entropy helpers behind the zstd gate, the DeviceSnapshotter host-mirror
machinery (dirty-chunk-only D2H, double buffering, fallbacks), restore
equivalence with the device path on vs off across codec v1/v2 for awkward
shapes/dtypes, the zstd compressibility gate's ``enc: raw`` chunks (via a
zlib-backed stand-in when zstandard is absent), and the batched-device_get
coalescing of the host path.
"""
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Box, Checkpoint
from repro.core import storage
from repro.core.device_snapshot import DeviceSnapshotter
from repro.core.env import CraftEnv
from repro.kernels.checksum import ops as checksum_ops
from repro.kernels.snapshot import ops as snapshot_ops
from repro.kernels.snapshot.kernel import snapshot as snapshot_pallas
from repro.kernels.snapshot.ref import META_COLS, snapshot_ref


# ------------------------------------------------------------------ kernel
class TestSnapshotKernel:
    @pytest.mark.parametrize("shape", [(1, 128), (4, 1024), (3, 2048)])
    @pytest.mark.parametrize("with_hist", [True, False])
    def test_kernel_matches_ref_bitexact(self, rng, shape, with_hist):
        words = jnp.asarray(
            rng.integers(0, 2**32, size=shape, dtype=np.uint32))
        prev = jnp.asarray(
            rng.integers(0, 2**32, size=(shape[0], 2), dtype=np.uint32))
        ref = snapshot_ref(words, prev, with_hist=with_hist)
        ker = snapshot_pallas(words, prev, block_rows=shape[1] // 128,
                              with_hist=with_hist, interpret=True)
        np.testing.assert_array_equal(np.asarray(ker), np.asarray(ref))

    @pytest.mark.parametrize("rows", [3, 24, 40])
    @pytest.mark.parametrize("with_hist", [True, False])
    def test_ops_block_choice_matches_ref(self, rng, rows, with_hist):
        """The block height the ops pick (whole chunk when 8 does not
        divide it, else a multiple of 8 over several row blocks) gives the
        oracle's result bit for bit."""
        words = jnp.asarray(
            rng.integers(0, 2**32, size=(2, rows * 128), dtype=np.uint32))
        prev = jnp.asarray(rng.integers(0, 2**32, (2, 2), dtype=np.uint32))
        ker = snapshot_ops.snapshot_chunks(
            words, prev, with_hist=with_hist, use_pallas=True,
            interpret=True)
        ref = snapshot_ref(words, prev, with_hist=with_hist)
        np.testing.assert_array_equal(np.asarray(ker), np.asarray(ref))

    def test_kernel_path_rejects_partial_lanes(self):
        """The kernel path never hands a lane-misaligned grid to the oracle
        behind the caller's back."""
        with pytest.raises(ValueError, match="lanes"):
            snapshot_ops.snapshot_chunks(
                jnp.zeros((2, 100), jnp.uint32), jnp.zeros((2, 2), jnp.uint32),
                use_pallas=True)

    def test_digest_columns_match_checksum_kernel(self, rng):
        data = rng.bytes(4096)
        words = jnp.asarray(
            np.frombuffer(data, np.uint32).reshape(4, 256))
        out = snapshot_ops.snapshot_chunks(
            words, jnp.zeros((4, 2), jnp.uint32))
        expect = checksum_ops.digest_chunks(data, 1024)
        got = [[int(a), int(b)] for a, b in np.asarray(out)[:, :2]]
        assert got == [[int(a), int(b)] for a, b in expect]

    def test_dirty_column_semantics(self):
        words = jnp.ones((2, 256), jnp.uint32)
        first = snapshot_ops.snapshot_chunks(
            words, jnp.zeros((2, 2), jnp.uint32))
        again = snapshot_ops.snapshot_chunks(words, first[:, :2])
        assert np.asarray(first)[:, 2].tolist() == [1, 1]
        assert np.asarray(again)[:, 2].tolist() == [0, 0]

    def test_histogram_counts_sum_to_nibbles(self, rng):
        words = jnp.asarray(
            rng.integers(0, 2**32, size=(3, 512), dtype=np.uint32))
        out = np.asarray(snapshot_ops.snapshot_chunks(
            words, jnp.zeros((3, 2), jnp.uint32)))
        assert out.shape[1] == META_COLS
        # each of the 2048 bytes per chunk contributes 2 nibbles
        assert (out[:, 3:].sum(axis=1) == 2 * 512 * 4).all()

    def test_hist_matches_host_hist(self, rng):
        data = rng.bytes(2048)
        words = jnp.asarray(np.frombuffer(data, np.uint32).reshape(1, 512))
        out = np.asarray(snapshot_ops.snapshot_chunks(
            words, jnp.zeros((1, 2), jnp.uint32)))
        np.testing.assert_array_equal(
            out[0, 3:].astype(np.int64), snapshot_ops.host_nibble_hist(data))

    def test_snapshot_host_matches_kernel_ref(self, rng):
        """The numpy CPU pass and the jit oracle agree on [s1, s2, dirty]
        over the same chunk grid (including a ragged tail chunk)."""
        data = rng.bytes(4096 + 512)          # 4.5 chunks of 1024B
        prev = rng.integers(0, 2**32, (5, 2), dtype=np.uint32)
        got = snapshot_ops.snapshot_host(
            np.frombuffer(data, np.uint8), 1024, prev)
        padded = np.frombuffer(data + bytes(512), np.uint32).reshape(5, 256)
        ref = np.asarray(snapshot_ref(
            jnp.asarray(padded), jnp.asarray(prev), with_hist=False))
        np.testing.assert_array_equal(got, ref)


# ----------------------------------------------------------------- entropy
class TestEntropy:
    def test_zeros_and_random(self, rng):
        zeros = snapshot_ops.host_nibble_hist(bytes(4096))
        rand = snapshot_ops.host_nibble_hist(rng.bytes(1 << 16))
        e = snapshot_ops.chunk_entropy_bits(np.stack([zeros, rand]))
        assert e[0] == pytest.approx(0.0)
        assert e[1] > 7.99

    def test_empty_chunk_is_zero_entropy(self):
        e = snapshot_ops.chunk_entropy_bits(np.zeros((1, 16), np.int64))
        assert e[0] == 0.0


# ---------------------------------------------------------- DeviceSnapshotter
def _host_equals(host, arr):
    ref = np.asarray(arr)
    assert host.dtype == ref.dtype and host.shape == ref.shape
    np.testing.assert_array_equal(host.view(np.uint8), ref.view(np.uint8))


class TestDeviceSnapshotter:
    @pytest.mark.parametrize("staged", [None, True])
    @pytest.mark.parametrize("dtype", [
        np.float32, np.float64, np.float16, np.int8, np.uint8, np.int64,
        np.bool_,
    ])
    def test_host_view_bitexact(self, rng, dtype, staged):
        # jnp.asarray downcasts 64-bit without x64 — compare vs the jax array
        a = jnp.asarray((rng.standard_normal(512) * 8).astype(dtype))
        snap = DeviceSnapshotter(256, staged=staged)
        host, meta = snap.snapshot("k", a)
        _host_equals(host, a)
        assert meta is not None and meta["dirty"] is None

    @pytest.mark.parametrize("staged", [None, True])
    def test_bfloat16(self, staged):
        a = jnp.arange(512, dtype=jnp.bfloat16)
        host, meta = DeviceSnapshotter(256, staged=staged).snapshot("k", a)
        _host_equals(host, a)
        assert meta is not None

    def test_digests_match_host_codec(self, rng):
        a = rng.standard_normal(1024).astype(np.float32)
        _, meta = DeviceSnapshotter(512).snapshot("k", jnp.asarray(a))
        expect = checksum_ops.digest_chunks(a.view(np.uint8).tobytes(), 512)
        assert meta["rdigests"] == [[int(x), int(y)] for x, y in expect]

    @pytest.mark.parametrize("staged", [None, True])
    def test_dirty_tracking_across_rounds(self, rng, staged):
        snap = DeviceSnapshotter(256, double_buffer=False, staged=staged)
        a = rng.standard_normal(512).astype(np.float32)   # 8 chunks
        snap.snapshot("k", jnp.asarray(a))
        a[65] += 1.0                                      # chunk 1
        host, meta = snap.snapshot("k", jnp.asarray(a))
        _host_equals(host, a)
        assert meta["dirty"] == [False, True] + [False] * 6

    def test_double_buffer_mirrors_stay_exact(self, rng):
        """Alternating mirrors each patch the chunks dirtied since *they*
        were last current (two rounds ago), not just the last round's
        (staged mode — the zero-copy CPU path has no mirrors to drift)."""
        snap = DeviceSnapshotter(256, double_buffer=True, staged=True)
        a = rng.standard_normal(512).astype(np.float32)
        for r in range(6):
            a[(r * 64) % 512] += 1.0      # a different chunk every round
            host, meta = snap.snapshot("k", jnp.asarray(a))
            _host_equals(host, a)

    def test_staged_host_view_stable_across_updates(self, rng):
        """In staged mode the returned view must keep the snapshotted bytes
        until the *next-plus-one* snapshot (double buffering), so an async
        writer never sees a torn buffer."""
        snap = DeviceSnapshotter(256, double_buffer=True, staged=True)
        a = rng.standard_normal(512).astype(np.float32)
        h0, _ = snap.snapshot("k", jnp.asarray(a))
        v0 = a.copy()
        a[0] += 1.0
        snap.snapshot("k", jnp.asarray(a))     # patches the other mirror
        np.testing.assert_array_equal(h0, v0)  # h0 untouched

    def test_fallbacks_return_none_meta(self):
        snap = DeviceSnapshotter(1024)
        for arr in (jnp.zeros((0,), jnp.float32),       # empty
                    jnp.zeros((3,), jnp.float16),       # 6 bytes, not /4
                    jnp.zeros((4,), jnp.complex64)):    # complex kind
            host, meta = snap.snapshot("k", arr)
            assert meta is None
            _host_equals(host, arr)

    def test_tpu_misaligned_chunk_grid_names_the_knob(self, monkeypatch):
        """On TPU a multi-chunk grid whose chunks are not whole lanes raises
        and names CRAFT_CHUNK_BYTES (no silent switch to the oracle)."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="CRAFT_CHUNK_BYTES=256"):
            DeviceSnapshotter(256).snapshot("k", jnp.zeros(512, jnp.float32))

    def test_reshape_resets_to_full_write(self, rng):
        snap = DeviceSnapshotter(256)
        snap.snapshot("k", jnp.zeros(512, jnp.float32))
        host, meta = snap.snapshot("k", jnp.zeros(1024, jnp.float32))
        assert meta["dirty"] is None     # fresh state → full literal write
        _host_equals(host, jnp.zeros(1024, jnp.float32))

    def test_tail_pad_entropy_corrected(self, rng):
        # 1200 bytes over 512-byte chunks: last chunk is 176 real bytes +
        # padding; its entropy must reflect only the real bytes (staged
        # mode — the CPU numpy pass carries no histogram).
        a = np.frombuffer(rng.bytes(1200), np.uint8).view(np.float32)
        _, meta = DeviceSnapshotter(512, staged=True).snapshot(
            "k", jnp.asarray(a))
        tail = a.view(np.uint8)[1024:]
        expect = snapshot_ops.chunk_entropy_bits(
            snapshot_ops.host_nibble_hist(tail)[None])[0]
        assert meta["entropy_bits"][2] == pytest.approx(expect)


# ------------------------------------------------- checkpoint equivalence
def _env(tmp_path, tag, **extra):
    base = {
        "CRAFT_CP_PATH": str(tmp_path / f"pfs-{tag}"),
        "CRAFT_USE_SCR": "0",
        "CRAFT_CHUNK_BYTES": "256",
        "CRAFT_KEEP_VERSIONS": "8",
    }
    base.update(extra)
    return CraftEnv.capture(base)


def _payload_cases(rng):
    return {
        "scalar0d": jnp.float32(1.25),
        "empty": jnp.zeros((0, 3), jnp.float32),
        "unaligned": jnp.asarray(
            rng.standard_normal(77).astype(np.float32)),     # 308 bytes
        "odd_f16": jnp.asarray(
            rng.standard_normal(33).astype(np.float16)),     # 66 bytes
        "multichunk": jnp.asarray(
            rng.standard_normal(512).astype(np.float32)),
        "flags": jnp.asarray(rng.integers(0, 2, 300).astype(bool)),
    }


def _run_versions(tmp_path, tag, rng, *, device, codec):
    env = _env(
        tmp_path, tag,
        CRAFT_DEVICE_SNAPSHOT="1" if device else "0",
        CRAFT_CODEC_VERSION=str(codec),
        CRAFT_DELTA="1" if codec == 2 else "0",
    )
    boxes = {k: Box(v) for k, v in _payload_cases(rng).items()}
    cp = Checkpoint(f"eq-{tag}", env=env)
    for k, b in boxes.items():
        cp.add(k, b)
    cp.commit()
    for r in range(3):
        mc = np.asarray(boxes["multichunk"].value).copy()
        mc[r * 64] += 1.0
        boxes["multichunk"].value = jnp.asarray(mc)
        cp.update_and_write()
    cp.close()
    # restore into fresh boxes
    out = {k: Box(jnp.zeros_like(v)) for k, v in _payload_cases(rng).items()}
    out["scalar0d"] = Box(jnp.float32(0))
    cp2 = Checkpoint(f"eq-{tag}", env=env)
    for k, b in out.items():
        cp2.add(k, b)
    cp2.commit()
    assert cp2.restart_if_needed()
    cp2.close()
    return {k: np.asarray(b.value) for k, b in out.items()}, boxes


@pytest.mark.parametrize("codec", [1, 2])
def test_restore_bitexact_device_on_vs_off(tmp_path, rng, codec):
    rng2 = np.random.default_rng(0)
    off, live_off = _run_versions(
        tmp_path, f"off{codec}", rng, device=False, codec=codec)
    on, live_on = _run_versions(
        tmp_path, f"on{codec}", rng2, device=True, codec=codec)
    for k in off:
        assert off[k].dtype == on[k].dtype and off[k].shape == on[k].shape, k
        assert off[k].tobytes() == on[k].tobytes(), k
        assert on[k].tobytes() == np.asarray(live_on[k].value).tobytes(), k


def test_delta_refs_written_with_device_path(tmp_path, rng):
    """With the device path on, unchanged chunks still become delta refs."""
    env = _env(tmp_path, "refs", CRAFT_DEVICE_SNAPSHOT="1",
               CRAFT_CODEC_VERSION="2", CRAFT_DELTA="1")
    box = Box(jnp.asarray(rng.standard_normal(512).astype(np.float32)))
    cp = Checkpoint("refs", env=env)
    cp.add("a", box)
    cp.commit()
    cp.update_and_write()
    a = np.asarray(box.value).copy()
    a[0] += 1.0
    box.value = jnp.asarray(a)
    cp.update_and_write()
    assert cp.stats["delta_chunks_skipped"] >= 6   # 8 chunks, 1 dirty
    cp.close()


def test_reshape_between_versions_falls_back(tmp_path, rng):
    env = _env(tmp_path, "reshape", CRAFT_DEVICE_SNAPSHOT="1",
               CRAFT_CODEC_VERSION="2", CRAFT_DELTA="1")
    box = Box(jnp.asarray(rng.standard_normal(512).astype(np.float32)))
    cp = Checkpoint("rs", env=env)
    cp.add("a", box)
    cp.commit()
    cp.update_and_write()
    final = rng.standard_normal(256).astype(np.float32)
    box.value = jnp.asarray(final)
    cp.update_and_write()
    cp.close()
    out = Box(jnp.zeros(256, jnp.float32))
    cp2 = Checkpoint("rs", env=env)
    cp2.add("a", out)
    cp2.commit()
    assert cp2.restart_if_needed()
    np.testing.assert_array_equal(np.asarray(out.value), final)
    cp2.close()


# ------------------------------------------------------------- zstd gate
class _FakeCompressor:
    def __init__(self, level=3):
        self.level = level

    def compress(self, data):
        return zlib.compress(bytes(data), 6)


class _FakeDecompressor:
    def decompress(self, data):
        return zlib.decompress(bytes(data))


class _FakeZstd:
    ZstdCompressor = staticmethod(
        lambda level=3: _FakeCompressor(level))
    ZstdDecompressor = staticmethod(_FakeDecompressor)


@pytest.fixture()
def fake_zstd(monkeypatch):
    """A zlib-backed stand-in so the gate/enc-raw paths run without the
    optional zstandard dependency (id(_zstd) keying keeps the compressor
    cache coherent across the swap)."""
    monkeypatch.setattr(storage, "_zstd", _FakeZstd)
    return _FakeZstd


class TestZstdGate:
    def _ctx(self, tmp_path, **kw):
        from repro.core.cpbase import IOContext
        kw.setdefault("compress", "zstd")
        kw.setdefault("codec_version", 1)
        kw.setdefault("chunk_bytes", 256)
        return IOContext(**kw)

    def test_incompressible_chunks_stored_raw(self, tmp_path, rng, fake_zstd):
        arr = np.frombuffer(rng.bytes(1024), np.uint8)
        p = tmp_path / "a.bin"
        # 256-byte chunks: small-sample bias puts random data at ~7.96
        # bits/byte, so gate at 7.5 to deterministically catch every chunk
        storage.write_array(
            p, arr, self._ctx(tmp_path, zstd_gate_bits=7.5))
        import json
        raw = p.read_bytes()
        hlen = int.from_bytes(raw[4:12], "little")
        chunks = json.loads(raw[12:12 + hlen])["chunks"]
        assert all(c.get("enc") == "raw" for c in chunks)
        out = storage.read_array(p, self._ctx(tmp_path))
        np.testing.assert_array_equal(out, arr)

    def test_compressible_chunks_still_zstd(self, tmp_path, fake_zstd):
        arr = np.zeros(1024, np.uint8)
        p = tmp_path / "z.bin"
        storage.write_array(
            p, arr, self._ctx(tmp_path, zstd_gate_bits=7.95))
        import json
        raw = p.read_bytes()
        hlen = int.from_bytes(raw[4:12], "little")
        chunks = json.loads(raw[12:12 + hlen])["chunks"]
        assert all("enc" not in c for c in chunks)
        assert chunks[0]["clen"] < chunks[0]["ulen"]
        out = storage.read_array(p, self._ctx(tmp_path))
        np.testing.assert_array_equal(out, arr)

    def test_gate_disabled_compresses_everything(self, tmp_path, rng,
                                                 fake_zstd):
        arr = np.frombuffer(rng.bytes(1024), np.uint8)
        p = tmp_path / "g.bin"
        storage.write_array(p, arr, self._ctx(tmp_path, zstd_gate_bits=0.0))
        import json
        raw = p.read_bytes()
        hlen = int.from_bytes(raw[4:12], "little")
        chunks = json.loads(raw[12:12 + hlen])["chunks"]
        assert all("enc" not in c for c in chunks)
        out = storage.read_array(p, self._ctx(tmp_path))
        np.testing.assert_array_equal(out, arr)

    def test_v2_ref_resolution_against_raw_base(self, tmp_path, rng,
                                                fake_zstd):
        """A v2 ref chunk whose base chunk was gated raw must resolve."""
        env = _env(tmp_path, "rawref", CRAFT_DEVICE_SNAPSHOT="1",
                   CRAFT_CODEC_VERSION="2", CRAFT_DELTA="1",
                   CRAFT_COMPRESS="zstd", CRAFT_ZSTD_GATE_BITS="7.95")
        data = np.frombuffer(rng.bytes(2048), np.uint8).view(np.float32)
        box = Box(jnp.asarray(data))
        cp = Checkpoint("rawref", env=env)
        cp.add("a", box)
        cp.commit()
        cp.update_and_write()      # v1: raw-gated full write
        a = np.asarray(box.value).copy()
        a[0] += 1.0
        box.value = jnp.asarray(a)
        cp.update_and_write()      # v2: refs against raw base chunks
        cp.close()
        out = Box(jnp.zeros_like(box.value))
        cp2 = Checkpoint("rawref", env=env)
        cp2.add("a", out)
        cp2.commit()
        assert cp2.restart_if_needed()
        np.testing.assert_array_equal(np.asarray(out.value), a)
        cp2.close()

    def test_compressor_cache_reused_per_thread(self, fake_zstd):
        c1 = storage._compressor(3)
        c2 = storage._compressor(3)
        c5 = storage._compressor(5)
        assert c1 is c2 and c1 is not c5
        assert storage._decompressor() is storage._decompressor()


# --------------------------------------------------- batched D2H coalescing
class TestBatchedDeviceGet:
    def test_jax_array_update_single_device_get(self, monkeypatch):
        from repro.core import checkpointables
        calls = []
        real = jax.device_get
        monkeypatch.setattr(
            jax, "device_get",
            lambda x: calls.append(1) or real(x))
        box = Box(jnp.arange(128, dtype=jnp.float32))
        cp = checkpointables.JaxArrayCp(box)
        calls.clear()
        cp.update()
        assert len(calls) == 1

    def test_pytree_update_single_device_get(self, monkeypatch):
        from repro.core import checkpointables
        calls = []
        real = jax.device_get
        monkeypatch.setattr(
            jax, "device_get",
            lambda x: calls.append(1) or real(x))
        box = Box({"a": jnp.zeros(64), "b": jnp.ones(32),
                   "c": np.zeros(8), "n": 3})
        cp = checkpointables.PytreeCp(box)
        calls.clear()
        cp.update()
        assert len(calls) == 1
