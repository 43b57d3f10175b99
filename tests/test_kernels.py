"""Per-kernel tests: Pallas (interpret=True) and blocked-jnp vs ref oracles.

Shape/dtype sweeps per the assignment; every kernel asserts allclose against
its ``ref.py`` pure-jnp oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.checksum import ops as ck_ops
from repro.kernels.checksum.ref import checksum_ref
from repro.kernels.flash_attention import blocked
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.kernel import flash_attention as fa_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.xor_parity import ops as xor_ops
from repro.kernels.xor_parity.ref import xor_reduce_ref


def _qkv(key, b, hq, hkv, lq, lk, d, dv=None, dtype=jnp.float32):
    dv = dv or d
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, hq, lq, d), dtype)
    k = jax.random.normal(kk, (b, hkv, lk, d), dtype)
    v = jax.random.normal(kv_, (b, hkv, lk, dv), dtype)
    return q, k, v


# ======================================================== flash attention
class TestFlashPallasInterpret:
    """The Pallas kernel body executed on CPU via interpret=True."""

    CASES = [
        # (b, hq, hkv, lq, lk, d, causal, window, dtype)
        (1, 2, 2, 128, 128, 64, True, None, jnp.float32),
        (2, 4, 2, 128, 256, 64, True, None, jnp.float32),
        (1, 2, 1, 256, 128, 128, False, None, jnp.float32),
        (1, 2, 2, 128, 128, 64, True, 64, jnp.float32),
        (1, 4, 4, 128, 128, 64, True, None, jnp.bfloat16),
        (2, 8, 2, 128, 128, 32, True, None, jnp.bfloat16),
    ]

    @pytest.mark.parametrize("case", CASES)
    def test_matches_ref(self, case):
        b, hq, hkv, lq, lk, d, causal, window, dtype = case
        q, k, v = _qkv(jax.random.PRNGKey(0), b, hq, hkv, lq, lk, d,
                       dtype=dtype)
        out = fa_pallas(q, k, v, causal=causal, window=window,
                        interpret=True)
        ref = attention_ref(q, k, v, causal=causal, window=window)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=tol, atol=tol)

    def test_kv_len_masking(self):
        q, k, v = _qkv(jax.random.PRNGKey(1), 1, 2, 2, 128, 256, 64)
        out = fa_pallas(q, k, v, causal=False, kv_len=160, interpret=True)
        ref = attention_ref(q, k, v, causal=False, kv_len=jnp.int32(160))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_q_offset_decode_chunk(self):
        """Chunked prefill: q block at offset 128 attending over 256 keys."""
        q, k, v = _qkv(jax.random.PRNGKey(2), 1, 2, 2, 128, 256, 64)
        out = fa_pallas(q, k, v, causal=True, q_offset=128, interpret=True)
        ref = attention_ref(q, k, v, causal=True, q_offset=jnp.int32(128))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestBlockedJnp:
    """The scan-based flash algorithm (the CPU/backward path)."""

    @pytest.mark.parametrize("lq,lk,block", [(64, 64, 16), (100, 260, 64),
                                             (128, 512, 128)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_forward(self, lq, lk, block, causal):
        q, k, v = _qkv(jax.random.PRNGKey(3), 2, 4, 2, lq, lk, 32)
        out, lse = blocked._fwd(q, k, v, causal, None, 32 ** -0.5,
                                jnp.int32(0), jnp.int32(lk), block)
        ref = attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_window(self):
        q, k, v = _qkv(jax.random.PRNGKey(4), 1, 2, 2, 96, 96, 16)
        out, _ = blocked._fwd(q, k, v, True, 24, 16 ** -0.5,
                              jnp.int32(0), jnp.int32(96), 32)
        ref = attention_ref(q, k, v, causal=True, window=24)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_gradients_match_ref(self):
        """custom-vjp backward vs autodiff through the naive reference."""
        q, k, v = _qkv(jax.random.PRNGKey(5), 1, 2, 1, 64, 64, 16)

        def f_ops(q, k, v):
            return (fa_ops.attention(q, k, v, causal=True) ** 2).sum()

        def f_ref(q, k, v):
            return (attention_ref(q, k, v, causal=True) ** 2).sum()

        g1 = jax.grad(f_ops, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


# ======================================================== xor parity
class TestXorParity:
    @pytest.mark.parametrize("g,n", [(2, 128), (4, 512), (8, 4096)])
    def test_reduce_matches_ref(self, g, n):
        rng = np.random.default_rng(0)
        stacked = jnp.asarray(
            rng.integers(0, 2 ** 32, (g, n), dtype=np.uint32))
        ref = xor_reduce_ref(stacked)
        out = xor_ops.xor_reduce(stacked, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_pallas_interpret(self):
        from repro.kernels.xor_parity.kernel import xor_reduce as xr
        rng = np.random.default_rng(1)
        stacked = jnp.asarray(
            rng.integers(0, 2 ** 32, (4, 256), dtype=np.uint32))
        out = xr(stacked, block_n=128, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(xor_reduce_ref(stacked)))

    def test_parity_reconstruct_roundtrip(self):
        rng = np.random.default_rng(2)
        bufs = [rng.bytes(100 + 13 * i) for i in range(5)]
        parity = xor_ops.parity_of_buffers(bufs)
        for lost in range(5):
            survivors = [b for i, b in enumerate(bufs) if i != lost]
            rebuilt = xor_ops.reconstruct_member(
                parity, survivors, len(bufs[lost]))
            assert rebuilt == bufs[lost]


# ======================================================== checksum
class TestChecksum:
    def test_matches_ref_and_detects_flips(self):
        rng = np.random.default_rng(3)
        data = rng.bytes(10_000)
        d1 = ck_ops.digest_bytes(data)
        assert d1 == ck_ops.digest_bytes(data)          # deterministic
        corrupted = bytearray(data)
        corrupted[1234] ^= 0x40
        assert ck_ops.digest_bytes(bytes(corrupted)) != d1

    @pytest.mark.parametrize("n", [0, 1, 3, 4, 4099, (1 << 20) + 7,
                                   (3 << 20) + 12])
    def test_host_digest_matches_device_digest(self, n):
        """digest_host (the read path's check) against digest_bytes, across
        word tails and the host's 1 MiB block seams, on a misaligned view."""
        rng = np.random.default_rng(n)
        buf = rng.integers(0, 256, n + 1, dtype=np.uint8)[1:]
        assert ck_ops.digest_host(buf) == list(ck_ops.digest_bytes(buf))
        assert ck_ops.digest_host(buf.tobytes()) == ck_ops.digest_host(buf)

    def test_pallas_interpret_matches_ref(self):
        from repro.kernels.checksum.kernel import checksum as ck
        rng = np.random.default_rng(4)
        n = 512 * 128 * 2
        words = jnp.asarray(rng.integers(0, 2 ** 32, n, dtype=np.uint32))
        out = np.asarray(ck(words, interpret=True))
        ref = np.asarray(jax.jit(checksum_ref)(words))
        np.testing.assert_array_equal(out, ref)

    def test_order_sensitivity(self):
        """s2 makes the digest order-sensitive (unlike a plain XOR/sum)."""
        a = np.arange(1024, dtype=np.uint32)
        b = a[::-1].copy()
        assert ck_ops.digest_array(jnp.asarray(a)) != \
            ck_ops.digest_array(jnp.asarray(b))


# ======================================================== ssm selective scan
class TestSsmScan:
    """Pallas selective-scan kernels (interpret) vs naive oracles."""

    @pytest.mark.parametrize("shape", [
        # (B, L, nh, hd, st, blk)
        (1, 64, 2, 8, 8, 32),
        (2, 160, 3, 16, 8, 32),    # L not a multiple of blk (pads)
        (1, 128, 4, 32, 16, 128),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_ssd_matches_ref(self, shape, dtype):
        from repro.kernels.ssm_scan.ops import selective_scan
        from repro.kernels.ssm_scan.ref import ssd_scan_ref
        b, l, nh, hd, st, blk = shape
        rng = np.random.default_rng(0)
        dtx = jnp.asarray(rng.standard_normal((b, l, nh, hd)), dtype)
        bh = jnp.asarray(rng.standard_normal((b, l, nh, st)), dtype)
        ch = jnp.asarray(rng.standard_normal((b, l, nh, st)), dtype)
        dt = jnp.asarray(rng.uniform(0, 0.5, (b, l, nh)), jnp.float32)
        A = jnp.asarray(-rng.uniform(0.5, 2, (nh,)), jnp.float32)
        h0 = jnp.asarray(rng.standard_normal((b, nh, hd, st)), jnp.float32)
        y_k, h_k = selective_scan(dtx, bh, ch, dt, A, h0, blk=blk,
                                  interpret=True, use_pallas=True)
        y_r, h_r = ssd_scan_ref(dtx, bh, ch, dt, A, h0)
        tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("di,blk_d", [(128, 128), (256, 128)])
    def test_s6_matches_ref(self, di, blk_d):
        from repro.kernels.ssm_scan.ops import selective_scan
        from repro.kernels.ssm_scan.ref import s6_scan_ref
        b, l, st = 2, 96, 8
        rng = np.random.default_rng(1)
        dtx = jnp.asarray(rng.standard_normal((b, l, di)), jnp.float32)
        bh = jnp.asarray(rng.standard_normal((b, l, st)), jnp.float32)
        ch = jnp.asarray(rng.standard_normal((b, l, st)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0, 0.5, (b, l, di)), jnp.float32)
        A = jnp.asarray(-rng.uniform(0.5, 2, (di, st)), jnp.float32)
        h0 = jnp.asarray(rng.standard_normal((b, di, st)), jnp.float32)
        y_k, h_k = selective_scan(dtx, bh, ch, dt, A, h0, blk=32,
                                  interpret=True, use_pallas=True)
        y_r, h_r = s6_scan_ref(dtx, bh, ch, dt, A, h0)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                                   rtol=2e-4, atol=2e-4)

    def test_model_fused_path_matches_kernel(self):
        """The model's _fused_ssd_scan == the Pallas kernel (same math)."""
        from repro.kernels.ssm_scan.ops import selective_scan
        from repro.models.ssm import _fused_ssd_scan
        b, l, nh, hd, st = 1, 64, 2, 8, 8
        rng = np.random.default_rng(2)
        dtx = jnp.asarray(rng.standard_normal((b, l, nh, hd)), jnp.float32)
        bh = jnp.asarray(rng.standard_normal((b, l, nh, st)), jnp.float32)
        ch = jnp.asarray(rng.standard_normal((b, l, nh, st)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0, 0.5, (b, l, nh)), jnp.float32)
        A = jnp.asarray(-rng.uniform(0.5, 2, (nh,)), jnp.float32)
        h0 = jnp.asarray(rng.standard_normal((b, nh, hd, st)), jnp.float32)
        y_m, h_m = _fused_ssd_scan(dtx, bh, ch, dt, A, h0, chunk=16)
        y_k, h_k = selective_scan(dtx, bh, ch, dt, A, h0, blk=32,
                                  interpret=True, use_pallas=True)
        np.testing.assert_allclose(np.asarray(y_m), np.asarray(y_k),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(h_m), np.asarray(h_k),
                                   rtol=2e-4, atol=2e-4)
