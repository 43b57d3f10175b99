"""The benchmark's yardstick arithmetic: the peaks table, parameter and
FLOP counts, the snapshot kernel's bytes, and the state each
configuration checkpoints."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import flops, peaks  # noqa: E402

DANUBE_4L = dict(n_layers=4, d_model=2560, vocab=32000, n_heads=32,
                 n_kv_heads=8, head_dim=80, d_ff=6912, window=4096,
                 tie_embeddings=False)
PHI4_CUT = dict(n_layers=4, d_model=3072, vocab=100032, n_heads=12,
                n_kv_heads=4, head_dim=128, d_ff=4096, window=None,
                tie_embeddings=True)
CONFIGS = sorted((ROOT / "bench" / "configs").glob("*.json"))


def test_peaks_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_param_counts():
    # h2o-danube-1.8b at 4 of 24 layers (norms included), and phi-4-mini
    # cut to 4 layers, 12/4 heads, d_ff 4096, vocab 100032 (norms left out)
    assert flops.param_count(DANUBE_4L) == 441_735_680
    assert flops.param_count(PHI4_CUT, norms=False) == 508_624_896


def test_model_flops_per_token():
    # 3.74e13 model FLOPs per step of 8 x 2048 tokens, causal attention in
    per_step = flops.model_flops_per_token(DANUBE_4L, 2048) * 8 * 2048
    assert per_step == pytest.approx(3.7432e13, rel=1e-4)
    no_attn = 6 * flops.matmul_params(DANUBE_4L)
    assert flops.model_flops_per_token(DANUBE_4L, 2048) > no_attn
    assert flops.attended_keys(DANUBE_4L, 2048) == pytest.approx(1024.5)
    assert flops.attended_keys(dict(DANUBE_4L, window=4), 8) == \
        pytest.approx((1 + 2 + 3 + 4 * 5) / 8)


def test_snapshot_kernel_bytes():
    # the staging program around the kernel reads each leaf and writes its
    # packed copy: three chunks of 2**20 words; a small leaf pads to whole
    # lanes; empty and odd-sized leaves take the host path
    chunk = 4 * 1024 * 1024
    assert flops.snapshot_program_bytes([10 * 2 ** 20], chunk) == \
        10 * 2 ** 20 + 3 * chunk
    assert flops.snapshot_program_bytes([100], chunk) == 100 + 128 * 4
    assert flops.snapshot_program_bytes([0, 6], chunk) == 0
    assert flops.chunk_grid(chunk, chunk) == (1, chunk // 4)


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_state_matches_the_program(path):
    import jax

    from repro.configs import get_config
    from repro.models import model as M
    from repro.optim.adamw import OptimConfig, adamw_init

    conf = json.loads(path.read_text())
    m = conf["model"]
    cfg = get_config(conf["base_arch"]).replace(**m)
    assert flops.param_count(m) == cfg.param_count()
    ps = jax.eval_shape(lambda k: M.init_params(k, cfg),
                        jax.random.PRNGKey(0))
    os_ = jax.eval_shape(
        lambda p: adamw_init(p, OptimConfig(master_fp32=False)), ps)
    leaves = jax.tree_util.tree_leaves((ps, os_))
    assert sorted(flops.leaf_bytes(m)) == sorted(
        x.size * x.dtype.itemsize for x in leaves)
