"""The main path's Pallas kernels compile for a TPU v5e chip.

Each test lowers one kernel at the size the checkpoint write path uses and
compiles it with the TPU compiler for a described (not attached) ``v5e:2x2``
topology, one chip per program.  Interpret-mode tests check what a kernel
computes; only this compile checks that the chip's compiler accepts its
tiling, reductions and block shapes.  The topology is described inside a
fixture, never at import: only one process at a time may load the TPU
library, and test workers import every test file.
"""
import functools

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.device_snapshot import DeviceSnapshotter, _fused
from repro.kernels.checksum.kernel import checksum
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.rs_erasure.kernel import gf_matmul
from repro.kernels.rs_erasure.ops import rs_matrix
from repro.kernels.snapshot.ops import snapshot_chunks
from repro.kernels.xor_parity.kernel import xor_reduce


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one, so keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _hlo(fn, *shapes):
    """HLO text of ``fn`` compiled by the TPU compiler for ``shapes``."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _u32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def test_checksum_compiles(one_chip):
    hlo = _hlo(checksum, _u32((1 << 20,), one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("with_hist", [True, False])
def test_snapshot_compiles(one_chip, with_hist):
    fn = functools.partial(snapshot_chunks, with_hist=with_hist,
                           use_pallas=True)
    hlo = _hlo(fn, _u32((16, 1 << 20), one_chip), _u32((16, 2), one_chip))
    assert "tpu_custom_call" in hlo
    # the kernel's own name, so its device time has its own profile line
    assert "%craft_snapshot" in hlo


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_snapshot_of_a_train_state_leaf_compiles(one_chip, dtype):
    """The write path's pack + kernel program for h2o-danube's stacked MLP
    weight (4 layers), at 4 MiB chunks.  Packing bf16 through a (n, 2)
    view once asked the chip for 18 GB; the program must stay within a
    few copies of the leaf."""
    shape = (4, 2560, 6912)
    nbytes = 4 * 2560 * 6912 * jnp.dtype(dtype).itemsize
    snap = DeviceSnapshotter(4 << 20, with_hist=False)
    n_chunks, wpc = snap._grid(nbytes)
    compiled = _fused.lower(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip),
        _u32((n_chunks, 2), one_chip), n_chunks=n_chunks, wpc=wpc,
        with_hist=False, use_pallas=True).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes <= 4 * nbytes


def test_flash_attention_forward_compiles(one_chip):
    """h2o-danube's attention: 32 query heads over 8 KV heads of dim 80."""
    def shape(h):
        return jax.ShapeDtypeStruct((1, h, 2048, 80), jnp.bfloat16,
                                    sharding=one_chip)
    fn = functools.partial(flash_attention, causal=True, window=4096)
    hlo = _hlo(fn, shape(32), shape(8), shape(8))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("block_n", [128, 16384])
def test_xor_parity_compiles(one_chip, block_n):
    fn = functools.partial(xor_reduce, block_n=block_n)
    hlo = _hlo(fn, _u32((4, 1 << 18), one_chip))
    assert "tpu_custom_call" in hlo


def test_rs_encode_compiles(one_chip):
    matrix = tuple(tuple(int(c) for c in row) for row in rs_matrix(4, 2))
    fn = functools.partial(gf_matmul, matrix=matrix, block_n=16384)
    hlo = _hlo(fn, _u32((4, 1 << 18), one_chip))
    assert "tpu_custom_call" in hlo


def test_snapshot_block_rows_are_tileable():
    """The block height the ops pick is a multiple of the 8-row sublane
    tile or the whole chunk, at every chunk size a lane grid allows."""
    from repro.kernels.snapshot.ops import _block_rows_for

    for rows in list(range(1, 70)) + [1001, 8192, 8200]:
        br = _block_rows_for(rows)
        assert rows % br == 0
        assert br % 8 == 0 or br == rows
    assert _block_rows_for(8192) == 512      # a 4 MiB chunk
