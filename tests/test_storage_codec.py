"""Array codec: chunked v1 format, legacy v0 compat, truncation, fanout pool."""
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import storage
from repro.core.async_writer import AsyncWriter
from repro.core.cpbase import CheckpointError, IOContext
from repro.core.storage import StorageTier


def ctx_v1(**kw):
    return IOContext(codec_version=1, **kw)


def ctx_v0(**kw):
    return IOContext(codec_version=0, **kw)


# ------------------------------------------------------------------ roundtrip
class TestChunkedRoundtrip:
    @pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32,
                                       np.uint8, np.bool_])
    def test_dtypes(self, tmp_path, rng, dtype):
        arr = (rng.standard_normal((33, 7)) * 10).astype(dtype)
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, ctx_v1())
        out = storage.read_array(p, ctx_v1())
        np.testing.assert_array_equal(out, arr)
        assert out.dtype == arr.dtype

    def test_bfloat16(self, tmp_path):
        arr = np.asarray(jnp.asarray([[1.5, -2.25], [0.125, 7.0]],
                                     jnp.bfloat16))
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, ctx_v1())
        out = storage.read_array(p, ctx_v1())
        np.testing.assert_array_equal(out.astype(np.float32),
                                      arr.astype(np.float32))

    @pytest.mark.parametrize("shape", [(0,), (1,), (), (5, 0, 3)])
    def test_degenerate_shapes(self, tmp_path, shape):
        arr = np.ones(shape, dtype=np.float32)
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, ctx_v1())
        out = storage.read_array(p, ctx_v1())
        assert out.shape == arr.shape
        np.testing.assert_array_equal(out, arr)

    @pytest.mark.parametrize("n_bytes,chunk", [
        (100, 64),         # ragged tail chunk
        (128, 64),         # exact multiple
        (63, 64),          # single partial chunk
        (1024, 16),        # many chunks
    ])
    def test_chunk_boundaries(self, tmp_path, rng, n_bytes, chunk):
        arr = rng.integers(0, 255, n_bytes, dtype=np.uint8)
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, ctx_v1(chunk_bytes=chunk))
        out = storage.read_array(p, ctx_v1())
        np.testing.assert_array_equal(out, arr)

    def test_header_records_chunk_metadata(self, tmp_path, rng):
        arr = rng.integers(0, 255, 100, dtype=np.uint8)
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, ctx_v1(chunk_bytes=64))
        import json
        raw = p.read_bytes()
        hlen = int.from_bytes(raw[4:12], "little")
        header = json.loads(raw[12:12 + hlen])
        assert header["fmt"] == 1
        assert header["nbytes"] == 100
        assert [c["ulen"] for c in header["chunks"]] == [64, 36]
        assert all(c["digest"] != [0, 0] for c in header["chunks"])


# ------------------------------------------------------------------ v0 compat
class TestLegacyCompat:
    def test_v0_write_v1_read(self, tmp_path, rng):
        """A checkpoint written pre-refactor restores through the new reader."""
        arr = rng.standard_normal((17, 3)).astype(np.float64)
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, ctx_v0())
        out = storage.read_array(p, ctx_v1())     # default reader
        np.testing.assert_array_equal(out, arr)

    def test_v0_checksum_still_verified(self, tmp_path, rng):
        arr = rng.standard_normal((64,)).astype(np.float32)
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, ctx_v0())
        raw = bytearray(p.read_bytes())
        raw[-5] ^= 0xFF
        p.write_bytes(bytes(raw))
        with pytest.raises(CheckpointError, match="checksum"):
            storage.read_array(p, ctx_v1())

    def test_future_format_rejected(self, tmp_path):
        import json
        header = json.dumps({"fmt": 99, "dtype": "float32", "shape": [1],
                             "compress": "none"}).encode()
        p = tmp_path / "a.bin"
        p.write_bytes(b"CRFT" + len(header).to_bytes(8, "little") + header)
        with pytest.raises(CheckpointError, match="newer"):
            storage.read_array(p, ctx_v1())


# ------------------------------------------------------------------ integrity
class TestTruncationAndCorruption:
    @pytest.mark.parametrize("make_ctx", [ctx_v0, ctx_v1])
    def test_truncated_payload_is_explicit(self, tmp_path, rng, make_ctx):
        arr = rng.standard_normal((256,)).astype(np.float32)
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, make_ctx(checksum="none"))
        raw = p.read_bytes()
        p.write_bytes(raw[: len(raw) - 40])   # short read at restore
        with pytest.raises(CheckpointError, match="truncated"):
            storage.read_array(p, make_ctx(checksum="none"))

    def test_truncated_header_is_explicit(self, tmp_path, rng):
        arr = rng.standard_normal((8,)).astype(np.float32)
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, ctx_v1())
        p.write_bytes(p.read_bytes()[:7])
        with pytest.raises(CheckpointError, match="truncated header"):
            storage.read_array(p, ctx_v1())

    def test_chunk_corruption_detected(self, tmp_path, rng):
        arr = rng.integers(0, 255, 4096, dtype=np.uint8)
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, ctx_v1(chunk_bytes=1024))
        raw = bytearray(p.read_bytes())
        raw[-100] ^= 0xFF                      # flip a bit in the last chunk
        p.write_bytes(bytes(raw))
        with pytest.raises(CheckpointError, match="checksum mismatch.*chunk"):
            storage.read_array(p, ctx_v1())

    def test_missing_file(self, tmp_path):
        with pytest.raises(CheckpointError, match="missing"):
            storage.read_array(tmp_path / "nope.bin", ctx_v1())

    @pytest.mark.parametrize("codec", [1, 2])
    def test_trailing_bytes_rejected(self, tmp_path, rng, codec):
        arr = rng.integers(0, 255, 300, dtype=np.uint8)
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, IOContext(codec_version=codec,
                                              chunk_bytes=64))
        p.write_bytes(p.read_bytes() + b"x")
        with pytest.raises(CheckpointError, match="trailing bytes"):
            storage.read_array(p, IOContext())


# ------------------------------------------------------------ one-buffer read
CHUNK = 64
SIZES = {"empty": 0, "sub_chunk": 50, "exact": 4 * CHUNK,
         "ragged": 4 * CHUNK + 44, "ragged_odd": 3 * CHUNK + 11}


def _payload_offset(path):
    raw = path.read_bytes()
    return 12 + int.from_bytes(raw[4:12], "little")


def _owns_buffer(arr):
    """True when ``arr`` (or the array it views) owns its memory."""
    while isinstance(arr.base, np.ndarray):
        arr = arr.base
    return arr.base is None and arr.flags.owndata


def _v2_chain(tmp_path, base, head, **kw):
    """``base`` as v-1 and ``head`` as v-2 of a delta chain: head's chunks
    equal to base's are refs.  Returns head's file and its read context."""
    v1, v2 = tmp_path / "v-1", tmp_path / "v-2"
    v1.mkdir()
    v2.mkdir()
    db = {}
    storage.write_array(v1 / "a.bin", base, IOContext(
        codec_version=2, chunk_bytes=CHUNK, rel_root=v1, chunks_db=db, **kw))
    storage.write_array(v2 / "a.bin", head, IOContext(
        codec_version=2, chunk_bytes=CHUNK, rel_root=v2, delta_prev=db,
        delta_base=1, **kw))
    return v2 / "a.bin", IOContext(rel_root=v2, base_dirs={1: v1})


@pytest.fixture()
def pool():
    p = AsyncWriter(workers=3)
    yield p
    p.close()


class TestOneBufferDecode:
    @pytest.mark.parametrize("fanout", [False, True])
    @pytest.mark.parametrize("size", list(SIZES))
    @pytest.mark.parametrize("checksum", ["fletcher", "none"])
    @pytest.mark.parametrize("compress", ["none", "zstd"])
    @pytest.mark.parametrize("codec", [1, 2])
    def test_roundtrip_writable_and_owned(self, tmp_path, rng, pool, codec,
                                          compress, checksum, size, fanout):
        if compress == "zstd":
            pytest.importorskip("zstandard")
        arr = rng.integers(0, 4, SIZES[size], dtype=np.uint8)
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, IOContext(
            codec_version=codec, chunk_bytes=CHUNK, compress=compress,
            checksum=checksum))
        out = storage.read_array(p, IOContext(
            checksum=checksum, fanout=pool.run_parallel if fanout else None))
        assert out.dtype == arr.dtype and out.shape == arr.shape
        assert out.tobytes() == arr.tobytes()
        assert out.flags.writeable and _owns_buffer(out)

    @pytest.mark.parametrize("chunk", [0, 2, 4], ids=["first", "middle",
                                                       "ragged_tail"])
    @pytest.mark.parametrize("codec", [1, 2])
    def test_corrupt_chunk_is_named(self, tmp_path, rng, pool, codec, chunk):
        arr = rng.integers(0, 255, SIZES["ragged_odd"] + CHUNK,
                           dtype=np.uint8)
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, IOContext(codec_version=codec,
                                              chunk_bytes=CHUNK))
        raw = bytearray(p.read_bytes())
        raw[_payload_offset(p) + chunk * CHUNK + 5] ^= 0x10
        p.write_bytes(bytes(raw))
        with pytest.raises(CheckpointError,
                           match=rf"checksum mismatch in .*\(chunk {chunk}\)"):
            storage.read_array(p, IOContext(fanout=pool.run_parallel))

    def test_v2_refs_resolve_into_place(self, tmp_path, rng, pool):
        base = rng.integers(0, 255, SIZES["ragged_odd"], dtype=np.uint8)
        head = base.copy()
        head[CHUNK + 3] ^= 0xFF                 # only chunk 1 is a literal
        p, ctx = _v2_chain(tmp_path, base, head)
        assert sum("ref" in c for c in storage.read_chunk_manifest(p)[
            "chunks"]) == 3
        out = storage.read_array(p, IOContext(
            rel_root=ctx.rel_root, base_dirs=ctx.base_dirs,
            fanout=pool.run_parallel))
        assert out.tobytes() == head.tobytes()
        assert out.flags.writeable and _owns_buffer(out)


class TestReadPathStaysOnHost:
    """Read-side digests are computed on the host: with every device digest
    entry point made to raise, reads still verify and still reject rot."""

    @pytest.fixture()
    def no_device(self, monkeypatch):
        from repro.kernels.checksum import ops as checksum_ops

        def boom(*a, **k):
            raise AssertionError("device digest on the read path")

        def arm():
            monkeypatch.setattr(checksum_ops, "digest_array", boom)
            monkeypatch.setattr(checksum_ops, "_rows_checksum", boom)
        return arm

    @pytest.mark.parametrize("kind", ["v1", "v1_zstd", "v2_refs"])
    def test_verified_read_needs_no_device(self, tmp_path, rng, no_device,
                                           kind):
        if kind == "v1_zstd":
            pytest.importorskip("zstandard")
        arr = rng.integers(0, 4, SIZES["ragged_odd"], dtype=np.uint8)
        if kind == "v2_refs":
            head = arr.copy()
            head[0] ^= 0xFF
            p, ctx = _v2_chain(tmp_path, arr, head)
            arr, rot = head, ctx.base_dirs[1] / "a.bin"   # a referenced base
            rot_chunk = 2
        else:
            p = tmp_path / "a.bin"
            storage.write_array(p, arr, ctx_v1(
                chunk_bytes=CHUNK,
                compress="zstd" if kind == "v1_zstd" else "none"))
            ctx, rot, rot_chunk = ctx_v1(), p, 1
        no_device()
        assert storage.read_array(p, ctx).tobytes() == arr.tobytes()
        rdr = storage.ChunkRangeReader(p, ctx)
        assert bytes(rdr.read(5, 150)) == arr[5:150].tobytes()

        raw = bytearray(rot.read_bytes())
        hdr = storage.read_chunk_manifest(rot)["chunks"]
        off = _payload_offset(rot) + sum(c["clen"] for c in hdr[:rot_chunk])
        raw[off + 1] ^= 0x01
        rot.write_bytes(bytes(raw))
        with pytest.raises(CheckpointError,
                           match=rf"checksum mismatch .*chunk {rot_chunk}\)"):
            storage.read_array(p, ctx)


# ------------------------------------------------------------------ fanout
class TestFanoutPool:
    def test_parallel_encode_matches_serial(self, tmp_path, rng):
        arr = rng.standard_normal((1 << 18,)).astype(np.float32)  # 1 MiB
        serial, parallel = tmp_path / "s.bin", tmp_path / "p.bin"
        storage.write_array(serial, arr, ctx_v1(chunk_bytes=64 * 1024))
        pool = AsyncWriter(workers=4)
        try:
            storage.write_array(
                parallel, arr,
                ctx_v1(chunk_bytes=64 * 1024, fanout=pool.run_parallel))
        finally:
            pool.close()
        assert serial.read_bytes() == parallel.read_bytes()
        np.testing.assert_array_equal(storage.read_array(parallel, ctx_v1()), arr)

    def test_run_parallel_order_and_results(self):
        pool = AsyncWriter(workers=3)
        try:
            out = pool.run_parallel([lambda i=i: i * i for i in range(50)])
        finally:
            pool.close()
        assert out == [i * i for i in range(50)]

    def test_run_parallel_propagates_error(self):
        pool = AsyncWriter(workers=3)

        def boom():
            raise RuntimeError("disk on fire")

        try:
            with pytest.raises(RuntimeError, match="disk on fire"):
                pool.run_parallel([lambda: 1, boom, lambda: 2])
        finally:
            pool.close()

    def test_nested_fanout_no_deadlock(self):
        pool = AsyncWriter(workers=2)

        def outer(i):
            return sum(pool.run_parallel(
                [lambda j=j: i * 10 + j for j in range(4)]))

        try:
            out = pool.run_parallel([lambda i=i: outer(i) for i in range(6)])
        finally:
            pool.close()
        assert out == [sum(i * 10 + j for j in range(4)) for i in range(6)]

    def test_caller_participates_when_pool_busy(self):
        pool = AsyncWriter(workers=1)  # workers=1 → run_parallel goes inline
        seen = []
        try:
            pool.run_parallel([lambda i=i: seen.append(i) for i in range(5)])
        finally:
            pool.close()
        assert sorted(seen) == list(range(5))

    def test_ordered_lane_still_fifo(self, tmp_path):
        pool = AsyncWriter(workers=4)
        order = []
        lock = threading.Lock()

        def job(i):
            with lock:
                order.append(i)

        try:
            for i in range(20):
                pool.submit(lambda i=i: job(i))
            pool.wait()
        finally:
            pool.close()
        assert order == list(range(20))


# ------------------------------------------------------------------ zstd
class TestZstdCodec:
    """Compressed-chunk paths; run where zstandard is installed (CI)."""

    @pytest.fixture(autouse=True)
    def _need_zstd(self):
        pytest.importorskip("zstandard")

    @pytest.mark.parametrize("make_ctx", [ctx_v0, ctx_v1])
    def test_roundtrip(self, tmp_path, rng, make_ctx):
        arr = np.repeat(rng.standard_normal(64), 512).astype(np.float32)
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, make_ctx(compress="zstd"))
        assert p.stat().st_size < arr.nbytes          # it actually compressed
        out = storage.read_array(p, make_ctx(compress="zstd"))
        np.testing.assert_array_equal(out, arr)

    def test_chunked_compressed_boundaries(self, tmp_path, rng):
        arr = rng.integers(0, 4, 100_000, dtype=np.uint8)
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, ctx_v1(compress="zstd", chunk_bytes=16384))
        out = storage.read_array(p, ctx_v1())
        np.testing.assert_array_equal(out, arr)

    def test_corrupt_compressed_chunk_detected(self, tmp_path, rng):
        arr = rng.integers(0, 4, 50_000, dtype=np.uint8)
        p = tmp_path / "a.bin"
        storage.write_array(p, arr, ctx_v1(compress="zstd", chunk_bytes=16384))
        raw = bytearray(p.read_bytes())
        raw[-20] ^= 0xFF
        p.write_bytes(bytes(raw))
        with pytest.raises(CheckpointError, match="checksum|corrupt"):
            storage.read_array(p, ctx_v1())

    def test_corrupt_chunk_without_checksums_still_checkpoint_error(
            self, tmp_path, rng):
        """ZstdError must surface as CheckpointError so tier fallback works."""
        arr = rng.integers(0, 4, 50_000, dtype=np.uint8)
        p = tmp_path / "a.bin"
        storage.write_array(
            p, arr, ctx_v1(compress="zstd", chunk_bytes=16384, checksum="none"))
        raw = bytearray(p.read_bytes())
        raw[-20] ^= 0xFF
        p.write_bytes(bytes(raw))
        with pytest.raises(CheckpointError, match="corrupt"):
            storage.read_array(p, ctx_v1(checksum="none"))


# ------------------------------------------------------------------ manifest
class TestChecksumManifest:
    def test_manifest_persisted_and_collision_free(self, tmp_path, rng):
        from repro.core import Checkpoint
        from repro.core.env import CraftEnv
        env = CraftEnv.capture({"CRAFT_CP_PATH": str(tmp_path / "pfs"),
                                "CRAFT_USE_SCR": "0"})
        a, b = rng.standard_normal((8,)), rng.standard_normal((9,))
        cp = Checkpoint("mf", env=env)
        cp.add("a", a)
        cp.add("b", b)
        cp.commit()
        cp.update_and_write()
        cp.close()
        meta = storage.VersionStore(env.cp_path, "mf", sweep=False).meta()
        # both arrays' files appear, keyed by key-qualified relative path
        assert set(meta["checksums"]) == {"a/array.bin", "b/array.bin"}

    def test_missing_manifest_file_rejected(self, tmp_path, rng):
        from repro.core import Checkpoint
        from repro.core.env import CraftEnv
        env = CraftEnv.capture({"CRAFT_CP_PATH": str(tmp_path / "pfs"),
                                "CRAFT_USE_SCR": "0"})
        a, b = rng.standard_normal((8,)), rng.standard_normal((9,))
        cp = Checkpoint("mf", env=env)
        cp.add("a", a)
        cp.add("b", b)
        cp.commit()
        cp.update_and_write()
        cp.close()
        (env.cp_path / "mf" / "v-1" / "b" / "array.bin").unlink()
        cp2 = Checkpoint("mf", env=env)
        cp2.add("a", np.zeros(8))
        cp2.add("b", np.zeros(9))
        cp2.commit()
        with pytest.raises(CheckpointError, match="incomplete"):
            cp2.restart_if_needed()


# ------------------------------------------------------------------ tier ABC
class TestStorageTierInterface:
    def test_version_store_is_tier(self, tmp_path):
        vs = storage.VersionStore(tmp_path, "cp")
        assert isinstance(vs, StorageTier)

    def test_node_store_is_tier(self, tmp_path):
        from repro.core.env import CraftEnv
        from repro.core.node_level import NodeStore
        from repro.core.comm import NullComm
        env = CraftEnv.capture({"CRAFT_NODE_CP_PATH": str(tmp_path)})
        ns = NodeStore(base=tmp_path, name="cp", comm=NullComm(), env=env)
        assert isinstance(ns, StorageTier)

    def test_default_materialize(self, tmp_path):
        vs = storage.VersionStore(tmp_path, "cp")
        assert vs.materialize(3) is None
        staged = vs.stage(3)
        (staged / "f").write_text("x")
        vs.publish(staged, 3)
        assert vs.materialize(3) == vs.version_dir(3)
