"""The benchmark's plain reference against the program, at a tiny size on
the CPU, through the reference file that each cell's configuration names:
same weights and batches from the seed, close losses and norms, and a
float8 control that the cell's limits reject."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness  # noqa: E402
from bench import ref_training as rt  # noqa: E402
from bench.tiny import tiny_cell  # noqa: E402

CELLS = ["danube1.8b.train-save-dense", "phi4mini3.8b.resume-node"]
PIN_SEED = 2718
# The tiny cells' reference readings on PIN_SEED (three steps, each cell's
# own row blocks, float32 and the float8 control), to the last digit: the
# shared reference with none of the optional hooks of ref_training must
# compute exactly these, or what every cell compares has moved.
PINNED = {
    ("danube1.8b.train-save-dense", None): {
        "losses": [
            6.563601493835449, 6.488067626953125, 6.5918869972229,
        ],
        "grad_norms": [
            0.17278075218200684, 0.4579344391822815, 0.17131295800209045,
            0.43923676013946533, 0.3514942228794098, 0.26793375611305237,
            0.2611459493637085, 0.05649294704198837, 0.04365254566073418,
            0.4390302896499634, 0.039797473698854446, 0.27423226833343506,
        ],
        "change_norms": [
            0.007099451031535864, 0.011559778824448586, 0.010316886939108372,
            0.007891150191426277, 0.018178636208176613, 0.015328902751207352,
            0.015074660070240498, 0.0, 0.0, 0.010028976947069168, 0.0,
            0.024607514962553978,
        ],
        "embed_zero_rows": 437,
    },
    ("danube1.8b.train-save-dense", "fp8"): {
        "losses": [
            6.559312582015991, 6.491542816162109, 6.584950923919678,
        ],
        "grad_norms": [
            0.23912706971168518, 0.444820374250412, 0.22900891304016113,
            0.42064177989959717, 0.2657535672187805, 0.21366851031780243,
            0.2229948490858078, 0.05729139968752861, 0.0466117188334465,
            0.5171617865562439, 0.04130388796329498, 0.27376729249954224,
        ],
        "change_norms": [
            0.004334903322160244, 0.008459702134132385, 0.004799527581781149,
            0.006226024590432644, 0.0054008131846785545, 0.0048820991069078445,
            0.00487939640879631, 0.0, 0.0, 0.009926109574735165, 0.0,
            0.003971031866967678,
        ],
        "embed_zero_rows": 437,
    },
    ("phi4mini3.8b.resume-node", None): {
        "losses": [
            6.578294992446899, 6.554470777511597, 6.618287563323975,
        ],
        "grad_norms": [
            0.16913193464279175, 0.420080304145813, 0.17989227175712585,
            0.41936299204826355, 0.3699813485145569, 0.25776994228363037,
            0.25263291597366333, 0.05081741139292717, 0.0455872118473053,
            0.558436930179596, 0.05506817623972893,
        ],
        "change_norms": [
            0.006977698765695095, 0.011112989857792854, 0.010162854567170143,
            0.007956261746585369, 0.018138462677598, 0.014825656078755856,
            0.015230926685035229, 0.0, 0.0, 0.024089539423584938, 0.0,
        ],
        "embed_zero_rows": 0,
    },
    ("phi4mini3.8b.resume-node", "fp8"): {
        "losses": [
            6.577559947967529, 6.556848764419556, 6.625110387802124,
        ],
        "grad_norms": [
            0.20172424614429474, 0.4365954101085663, 0.21244379878044128,
            0.41541650891304016, 0.2802078127861023, 0.19605886936187744,
            0.20503172278404236, 0.07108127325773239, 0.04648318141698837,
            0.6171118021011353, 0.0628814771771431,
        ],
        "change_norms": [
            0.004121535457670689, 0.007640978321433067, 0.004429482389241457,
            0.006127225235104561, 0.005184180103242397, 0.004565373994410038,
            0.0046736388467252254, 0.0, 0.0, 0.00995774194598198, 0.0,
        ],
        "embed_zero_rows": 425,
    },
}


def tiny_model(cell):
    return tiny_cell(cell).config["model"]


def arch(cell):
    return harness.load_reference(harness.load_cell(cell).reference)


def program_config(m, base):
    from repro.configs import get_config

    return get_config(base).replace(**m)


@pytest.mark.parametrize("cell", CELLS)
def test_reference_weights_are_the_programs(cell):
    from repro.models import model as M

    m = tiny_model(cell)
    base = harness.load_cell(cell).config["base_arch"]
    seed = (2 ** 31 + 99) % harness.SEED_MOD
    want = M.init_params(jax.random.PRNGKey(seed), program_config(m, base))
    got = arch(cell).init_params(seed, m)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_reference_batches_are_the_programs():
    from repro.data.pipeline import SyntheticTokens

    data = SyntheticTokens(vocab=512, seq_len=32, global_batch=4, seed=7)
    for step in (0, 1, 5):
        tokens, labels = rt.token_batch(7, step, 512, 32, 4, 1.2)
        want = data.batch(step)
        np.testing.assert_array_equal(tokens, want["tokens"])
        np.testing.assert_array_equal(labels, want["labels"])
        assert len({r.tobytes() for r in tokens}) == 4   # rows all differ


@pytest.mark.parametrize("cell", CELLS)
def test_reference_loss_matches_program_forward(cell):
    from repro.models import model as M
    from repro.train.steps import cross_entropy

    m = tiny_model(cell)
    cfg = program_config(m, harness.load_cell(cell).config["base_arch"])
    params = arch(cell).init_params(3, m)
    tokens, labels = rt.token_batch(3, 0, m["vocab"], 32, 2, 1.2)
    logits, _, _ = M.forward(params, cfg, tokens=jnp.asarray(tokens))
    want = float(cross_entropy(logits, jnp.asarray(labels)))
    got, _, _ = rt.loss_and_grad(arch(cell), params, jnp.asarray(tokens),
                              jnp.asarray(labels), m)
    assert abs(got - want) / want < 2e-3


@pytest.mark.parametrize("cell", CELLS)
def test_fp8_control_fails_the_cells_limits(cell):
    """The control: the reference computed in float8 in the program's
    place must exceed at least one of the cell's limits."""
    c = harness.load_cell(cell)
    m = tiny_model(cell)
    opt = dict(c.config["optimizer"], total_steps=10)
    data = {"seq_len": 32, "global_batch": 4, "zipf_a": 1.2}
    full = rt.train_readings(arch(cell), 11, m, opt, data, 3)
    low = rt.train_readings(arch(cell), 11, m, opt, data, 3, quant="fp8")
    limits = c.params["limits"]
    gaps = {
        "loss_gap": rt.loss_gap(low["losses"], full["losses"]),
        "grad_gap": rt.worst_leaf_gap(low["grad_norms"],
                                      full["grad_norms"]),
        "change_gap": rt.worst_leaf_gap(
            low["change_norms"], full["change_norms"],
            rt.moving_leaves(full["grad_norms"])),
    }
    assert any(gaps[k] > limits[k] for k in gaps), gaps


@pytest.mark.parametrize("cell,quant", list(PINNED),
                         ids=lambda v: v or "float32")
def test_dense_readings_are_pinned_to_the_digit(cell, quant):
    c = tiny_cell(cell)
    opt = dict(c.config["optimizer"], total_steps=10)
    data = {"seq_len": c.traffic["seq_len"],
            "global_batch": c.params["global_batch"],
            "zipf_a": c.traffic["zipf_a"]}
    got = rt.train_readings(
        arch(cell), PIN_SEED, c.config["model"], opt, data, 3, quant=quant,
        block_rows=int(c.params["reference_block_rows"]))
    want = PINNED[(cell, quant)]
    assert got["losses"] == want["losses"]
    assert got["grad_norms"].tolist() == want["grad_norms"]
    assert got["change_norms"].tolist() == want["change_norms"]
    assert int(got["embed_zero_rows"].sum()) == want["embed_zero_rows"]
    assert "extra" not in got


@pytest.mark.parametrize("cell", CELLS)
def test_calibrate_reads_program_control_and_fault(cell, tmp_path,
                                                   monkeypatch, capsys):
    """``bench/calibrate.py`` end to end at the tiny size: the program's
    numbers inside the cell's limits, the float8 control outside one."""
    from bench import calibrate
    from repro.launch import compile_cache

    tiny = tiny_cell(cell)
    monkeypatch.setattr(harness, "load_cell", lambda name: tiny)
    monkeypatch.setattr(harness, "device_info",
                        lambda chips, require_tpu: {"platform": "cpu"})
    monkeypatch.setattr(harness, "WORKDIR", tmp_path / "wd")
    monkeypatch.setattr(compile_cache, "setup_compile_cache", lambda: None)
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        assert calibrate.main(["--workload", cell, "--seeds", "2147483653",
                               "--control-seeds", "2147483653"]) == 0
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    got = {r["kind"]: r for r in lines}
    assert set(got) == {"program", "control_fp8", "fault_half_batch"}
    limits = tiny.params["limits"]
    numbers = [k for k in got["program"] if k in limits]
    assert numbers and all(got["program"][k] <= limits[k] for k in numbers)
    assert any(got["control_fp8"][k] > limits[k] for k in numbers)
    assert not (tmp_path / "wd").exists()
