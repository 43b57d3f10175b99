"""The benchmark's yardstick arithmetic: the peaks table, the dense
reference's parameter and FLOP counts, the snapshot kernel's bytes, the
state each configuration checkpoints, and the FLOPs per token that
``train_mfu`` reads from each configuration's reference."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import flops, harness, peaks  # noqa: E402
from bench import reference as dense  # noqa: E402

DANUBE_4L = dict(n_layers=4, d_model=2560, vocab=32000, n_heads=32,
                 n_kv_heads=8, head_dim=80, d_ff=6912, window=4096,
                 tie_embeddings=False)
PHI4_CUT = dict(n_layers=4, d_model=3072, vocab=100032, n_heads=12,
                n_kv_heads=4, head_dim=128, d_ff=4096, window=None,
                tie_embeddings=True)
CONFIGS = sorted((ROOT / "bench" / "configs").glob("*.json"))
DENSE = [p for p in CONFIGS
         if json.loads(p.read_text())["reference"] == "bench/reference.py"]
# forward and backward FLOPs per token at seq 2048, as the counts gave them
# before they moved into the reference
FLOPS_2048 = {"danube1.8b-pp8vocab-staged-async": 509_721_600,
              "phi4mini3.8b-pp8vocab-defaults": 1_102_694_400}


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
    assert dense.param_count(DANUBE_4L) == 441_735_680
    assert dense.param_count(PHI4_CUT, norms=False) == 508_624_896


def test_model_flops_per_token():
    # 3.74e13 model FLOPs per step of 8 x 2048 tokens, causal attention in
    per_step = dense.model_flops_per_token(DANUBE_4L, 2048) * 8 * 2048
    assert per_step == pytest.approx(3.7432e13, rel=1e-4)
    no_attn = 6 * dense.matmul_params(DANUBE_4L)
    assert dense.model_flops_per_token(DANUBE_4L, 2048) > no_attn
    assert dense.attended_keys(DANUBE_4L, 2048) == pytest.approx(1024.5)
    assert dense.attended_keys(dict(DANUBE_4L, window=4), 8) == \
        pytest.approx((1 + 2 + 3 + 4 * 5) / 8)


@pytest.mark.parametrize("name", sorted(FLOPS_2048))
def test_configured_reference_gives_the_flops_per_token(name):
    conf = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())
    arch = harness.load_reference(ROOT / conf["reference"])
    assert arch.model_flops_per_token(conf["model"], 2048) == \
        FLOPS_2048[name]


def test_train_mfu_reads_as_the_dense_formula(tmp_path):
    cell = harness.load_cell("danube1.8b.train-save-dense")
    run = harness.Run(cell, 2 ** 31 + 5, 30.0, True, tmp_path, 0.0)
    run.device_kind = "TPU v5 lite"
    run.step_times = [0.318] * 96
    run.window.t0, run.window.t1 = 100.0, 131.0
    m, n = run.model, run.seq_len
    # the dense formula, written out: 3 x (2 x matmul parameters + 4 x
    # layers x heads x head_dim x mean attended keys) per token
    matmul = m["n_layers"] * (
        m["d_model"] * m["n_heads"] * m["head_dim"] * 2
        + m["d_model"] * m["n_kv_heads"] * m["head_dim"] * 2
        + 3 * m["d_model"] * m["d_ff"]) + m["vocab"] * m["d_model"]
    keys = sum(min(i + 1, m["window"] or n) for i in range(n)) / n
    per_token = 3 * (2 * matmul + 4 * m["n_layers"] * m["n_heads"]
                     * m["head_dim"] * keys)
    assert per_token == FLOPS_2048[cell.config["name"]]
    want = 100.0 * per_token * 96 * 16 * 2048 / 31.0 / 197e12
    assert harness.read_metric("train_mfu", run) == pytest.approx(
        want, rel=1e-12)


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


def program_state(conf):
    import jax

    from repro.configs import get_config
    from repro.models import model as M
    from repro.optim.adamw import OptimConfig, adamw_init

    cfg = get_config(conf["base_arch"]).replace(**conf["model"])
    ps = jax.eval_shape(lambda k: M.init_params(k, cfg),
                        jax.random.PRNGKey(0))
    os_ = jax.eval_shape(
        lambda p: adamw_init(p, OptimConfig(master_fp32=False)), ps)
    return cfg, ps, jax.tree_util.tree_leaves((ps, os_))


@pytest.mark.parametrize("path", DENSE, ids=lambda p: p.stem)
def test_state_matches_the_program(path):
    conf = json.loads(path.read_text())
    m = conf["model"]
    cfg, _, leaves = program_state(conf)
    assert dense.param_count(m) == cfg.param_count()
    assert sorted(dense.leaf_bytes(m)) == sorted(
        x.size * x.dtype.itemsize for x in leaves)


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_reference_parameters_are_the_programs(path):
    """At the configuration's own sizes, the parameters its reference
    builds have the program's tree, shapes and dtypes."""
    import jax

    conf = json.loads(path.read_text())
    arch = harness.load_reference(ROOT / conf["reference"])
    cfg, ps, _ = program_state(conf)
    got = jax.eval_shape(lambda: arch.init_params(0, conf["model"]))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(ps)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ps)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert sum(x.size for x in jax.tree_util.tree_leaves(got)) == \
        cfg.param_count()
