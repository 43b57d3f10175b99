"""The benchmark's plain reference against the program, at a tiny size on
the CPU, through the reference file that each cell's configuration names:
same weights and batches from the seed, close losses and norms, and a
float8 control that the cell's limits reject."""
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
    got, _ = rt.loss_and_grad(arch(cell), params, jnp.asarray(tokens),
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
