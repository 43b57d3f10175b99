"""A configuration of another architecture needs new files only: in a
bench root of its own, a configuration whose ``"reference"`` names a
second reference file is loaded, shrunk, checked, calibrated and counted
through that file and not through ``bench/reference.py``.  A reference
that defines the optional hooks of ``bench/ref_training.py`` (state beside
the parameters, an objective beyond the NLL) has that state carried
through its steps and compared as ``extra_gap``."""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import calibrate, harness  # noqa: E402
from bench import ref_training as rt  # noqa: E402
from bench import reference as dense  # noqa: E402
from bench.tiny import tiny_cell  # noqa: E402

BASE = "danube1.8b.train-save-dense"
CELL = "wrapped.train-save-dense"
SEED = 2 ** 31 + 2718
# the dense reference under another name, which records what was called
WRAPPER = '''
from bench import reference as dense

CALLS = []
TINY = dict(dense.TINY, n_layers=1, d_ff=96)


def init_params(seed, m):
    CALLS.append("init_params")
    return dense.init_params(seed, m)


def nll_sum(params, tokens, labels, m, quant=None):
    CALLS.append("nll_sum")
    return dense.nll_sum(params, tokens, labels, m, quant)


def model_flops_per_token(m, seq_len):
    CALLS.append("model_flops_per_token")
    return dense.model_flops_per_token(m, seq_len)
'''
# the dense reference with a router over 4 experts beside it, in the manner
# of DeepSeek-V3's bias-balanced MoE: a correction bias per expert, moved
# by +-GAMMA on the sign of each expert's load and not by the gradient,
# chooses the top 2; a sequence-wise balance loss trains the router
BALANCED = '''
import math

import jax
import jax.numpy as jnp

from bench import reference as dense
from bench.ref_training import mm

TINY = dense.TINY
EXTRA_KEY = "router_bias"
EXPERTS, TOP_K, GAMMA, ALPHA = 4, 2, 1e-3, 0.01


def init_params(seed, m):
    params = dense.init_params(seed, m)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 7)
    w = jax.random.normal(key, (m["d_model"], EXPERTS), jnp.float32)
    params["router"] = (w / math.sqrt(m["d_model"])).astype(jnp.bfloat16)
    return params


def init_extra(seed, m):
    return {"bias": jnp.zeros((EXPERTS,), jnp.float32)}


def objective(params, extra, tokens, labels, m, quant=None):
    nll = dense.nll_sum(params, tokens, labels, m, quant)
    x = params["embed"]["embedding"][tokens]
    scores = jax.nn.sigmoid(mm("bld,de->ble", x, params["router"], quant))
    _, top = jax.lax.top_k(scores + extra["bias"], TOP_K)
    chosen = jax.nn.one_hot(top, EXPERTS).sum(axis=-2)
    seq = tokens.shape[1]
    f = chosen.sum(axis=1) * EXPERTS / (TOP_K * seq)
    p = (scores / scores.sum(axis=-1, keepdims=True)).mean(axis=1)
    balance = ALPHA * jnp.sum(f * p, axis=-1)        # one per sequence
    return nll + seq * jnp.sum(balance), (
        nll, {"load": chosen.sum(axis=(0, 1))})


def update_extra(extra, stats, m):
    load = stats["load"]
    return {"bias": extra["bias"] + GAMMA * jnp.sign(jnp.mean(load) - load)}


def model_flops_per_token(m, seq_len):
    return dense.model_flops_per_token(m, seq_len)
'''


def make_root(root: Path, reference, text: str = WRAPPER,
              extra_limit=None) -> None:
    """A bench root holding BENCHMARK.json with one more configuration and
    cell, their data files, and (where ``reference`` is given) the
    configuration's ``"reference"`` key and the reference file holding
    ``text``; ``extra_limit`` adds an ``extra_gap`` limit to the cell."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    base = next(w for w in spec["workloads"] if w["name"] == BASE)
    conf = json.loads((ROOT / "bench" / "configs" / f"{base['config']}.json")
                      .read_text())
    conf["name"] = "wrapped"
    del conf["reference"]
    if reference is not None:
        conf["reference"] = reference
        (root / Path(reference).parent).mkdir(parents=True)
        (root / reference).write_text(text)
    spec["configs"].append({"name": "wrapped", "file":
                            "bench/configs/wrapped.json", "source": "-",
                            "reduced": [], "why": "-"})
    spec["workloads"].append(dict(base, name=CELL, config="wrapped"))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    for sub in ("configs", "traffic", "workloads"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    (root / "bench" / "configs" / "wrapped.json").write_text(json.dumps(conf))
    shutil.copy(ROOT / "bench" / "traffic" / f"{base['traffic']}.json",
                root / "bench" / "traffic")
    work = json.loads((ROOT / "bench" / "workloads" / f"{BASE}.json")
                      .read_text())
    if extra_limit is not None:
        work["limits"]["extra_gap"] = extra_limit
    (root / "bench" / "workloads" / f"{CELL}.json").write_text(
        json.dumps(work))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_root")
    make_root(root, "bench/arch/wrapped.py")
    return root


@pytest.fixture
def arch(root):
    mod = harness.load_reference(root / "bench" / "arch" / "wrapped.py")
    mod.CALLS.clear()
    return mod


def test_load_cell_names_the_configured_file(root, arch):
    cell = harness.load_cell(CELL, root)
    assert cell.reference == root / "bench" / "arch" / "wrapped.py"
    assert harness.load_reference(cell.reference) is arch
    assert arch is not dense
    assert harness.Run(cell, SEED, 1.0, False, root, 0.0).reference is arch


def test_tiny_cell_takes_the_references_tiny_sizes(root, arch):
    m = tiny_cell(CELL, root).config["model"]
    assert m["n_layers"] == 1 and m["d_ff"] == 96 and m["window"] == 16
    assert m["d_model"] == dense.TINY["d_model"]


def test_check_training_runs_the_configured_reference(root, arch,
                                                      tmp_path):
    run = harness.Run(tiny_cell(CELL, root), SEED, 1.0, False, tmp_path,
                      0.0)
    run.opt_total_steps = 10
    data = {"seq_len": run.seq_len, "global_batch": run.batch,
            "zipf_a": run.cell.traffic["zipf_a"]}
    got = rt.train_readings(dense, run.prog_seed, run.model,
                            dict(run.opt, total_steps=10), data, 3,
                            block_rows=2)
    assert arch.CALLS == []
    harness._check_training(run, [[x] for x in got["losses"]],
                            {k: got[k] for k in ("grad_norms",
                                                 "embed_zero_rows",
                                                 "change_norms")})
    assert {"init_params", "nll_sum"} <= set(arch.CALLS)
    assert run.correct and set(run.checks) == {
        "loss_gap", "grad_gap", "embed_rows_differing", "change_gap"}
    assert all(v == 0 for v, _ in run.checks.values()), run.checks


def test_calibrate_runs_the_configured_reference(root, arch, tmp_path):
    run = harness.Run(tiny_cell(CELL, root), SEED, 0.0, False, tmp_path,
                      0.0)
    full = calibrate.reference_readings(run)
    assert arch.CALLS.count("init_params") == 1
    low = calibrate.reference_readings(run, quant="fp8")
    half = calibrate.reference_readings(run, rows=slice(0, run.batch // 2))
    assert arch.CALLS.count("init_params") == 3
    assert harness.training_numbers(low, full)["loss_gap"] > 0
    assert harness.training_numbers(half, full)["loss_gap"] > 0
    np.testing.assert_array_equal(
        calibrate.reference_readings(run)["grad_norms"], full["grad_norms"])


def test_train_mfu_counts_with_the_configured_reference(root, arch,
                                                        tmp_path):
    run = harness.Run(harness.load_cell(CELL, root), SEED, 30.0, True,
                      tmp_path, 0.0)
    run.device_kind = "TPU v5 lite"
    run.step_times = [0.3] * 10
    run.window.t0, run.window.t1 = 0.0, 3.0
    got = harness.read_metric("train_mfu", run)
    assert arch.CALLS == ["model_flops_per_token"]
    per_token = dense.model_flops_per_token(run.model, run.seq_len)
    assert got == pytest.approx(
        100.0 * per_token * 10 * run.tokens_per_step / 3.0 / 197e12,
        rel=1e-12)


def test_configuration_without_a_reference_is_refused(tmp_path):
    make_root(tmp_path, None)
    with pytest.raises(ValueError, match="reference"):
        harness.load_cell(CELL, tmp_path)


def test_missing_reference_file_is_refused(tmp_path):
    make_root(tmp_path, "bench/arch/wrapped.py")
    (tmp_path / "bench" / "arch" / "wrapped.py").unlink()
    with pytest.raises(ValueError, match="reference"):
        harness.load_cell(CELL, tmp_path)


def test_whole_run_goes_through_the_configured_reference(root, arch,
                                                         tmp_path):
    res = harness.run_cell(tiny_cell(CELL, root), SEED, 0.5, False,
                           tmp_path / "wd", 0.0, require_tpu=False)
    assert res["correct"], res["checks"]
    assert arch.CALLS.count("init_params") == 1
    assert set(res["checks"]) == {"readback_leaves_differing", "loss_gap",
                                  "grad_gap", "embed_rows_differing",
                                  "change_gap"}


# ------------------------------------------- state beside the parameters
@pytest.fixture(scope="module")
def balanced_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("balanced_root")
    make_root(root, "bench/arch/balanced.py", BALANCED, extra_limit=1e-3)
    return root


@pytest.fixture
def balanced_run(balanced_root, tmp_path):
    return harness.Run(tiny_cell(CELL, balanced_root), SEED, 0.0, False,
                       tmp_path, 0.0)


def test_objective_reports_the_nll_and_trains_on_the_total(balanced_run):
    mod, m = balanced_run.reference, balanced_run.model
    params = mod.init_params(balanced_run.prog_seed, m)
    extra = {"bias": jnp.asarray([0.3, -0.3, 0.1, 0.0], jnp.float32)}
    tokens, labels = map(jnp.asarray, rt.token_batch(
        balanced_run.prog_seed, 0, m["vocab"], balanced_run.seq_len,
        balanced_run.batch, balanced_run.cell.traffic["zipf_a"]))
    loss, grads, stats = rt.loss_and_grad(mod, params, tokens, labels, m,
                                          block_rows=2, extra=extra)
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    n = tokens.size
    total, (nll, want_stats) = mod.objective(f32, extra, tokens, labels, m)
    # float32 sums over two row blocks against one: rounding alone
    assert loss == pytest.approx(float(nll) / n, rel=1e-6)
    assert loss == pytest.approx(
        float(dense.nll_sum(f32, tokens, labels, m)) / n, rel=1e-6)
    assert float(total) / n - loss > 1e-3          # the balance term
    want = jax.grad(lambda p: mod.objective(p, extra, tokens, labels,
                                            m)[0])(f32)
    # float32 sums in another order, which cancel near zero in some
    # elements: each leaf is held to 1e-5 of its largest element
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want)):
        w = np.asarray(w) / n
        np.testing.assert_allclose(np.asarray(g), w, rtol=1e-4,
                                   atol=1e-5 * float(np.abs(w).max()))
    assert float(jnp.abs(grads["router"]).max()) > 0   # not in the NLL
    # loads are whole counts, which float32 sums exactly
    np.testing.assert_array_equal(stats["load"], want_stats["load"])
    assert float(stats["load"].sum()) == mod.TOP_K * n


def test_extra_is_carried_alike_in_any_row_blocks(balanced_run):
    run = balanced_run
    data = {"seq_len": run.seq_len, "global_batch": run.batch,
            "zipf_a": run.cell.traffic["zipf_a"]}
    opt = dict(run.opt, total_steps=10)
    one, whole = (rt.train_readings(run.reference, run.prog_seed, run.model,
                                    opt, data, 3, block_rows=b)["extra"]
                  for b in (1, run.batch))
    # each step moves a bias by +-GAMMA on the sign of whole-count loads,
    # which any row blocks sum exactly; what the blocks change is the last
    # bits of a float32 gradient, which may move a token across an
    # expert's edge only where its scores tie to float32 rounding
    np.testing.assert_allclose(one["bias"], whole["bias"], rtol=1e-6,
                               atol=0)
    assert float(jnp.abs(whole["bias"]).min()) >= run.reference.GAMMA


def test_extra_gap_is_zero_alike_and_grows_with_one_leaf():
    ref = {"a": np.array([3.0, 4.0], np.float32),
           "b": np.array([0.0, 1.0], np.float32),
           "c": np.array([0.0, 0.0], np.float32)}
    assert rt.extra_gap(ref, ref) == 0.0
    # leaf b's norm 1 is the median; c's 0 takes the median's
    for leaf, gap in (("a", 0.5 / 5), ("b", 0.5), ("c", 0.5)):
        moved = dict(ref, **{leaf: ref[leaf] + np.float32([0.5, 0.0])})
        assert rt.extra_gap(moved, ref) == pytest.approx(gap, rel=1e-6)
    with pytest.raises(ValueError, match="layout"):
        rt.extra_gap({"a": ref["a"]}, ref)


def test_check_training_compares_the_extra_state(balanced_run):
    run = balanced_run
    run.opt_total_steps = 10
    data = {"seq_len": run.seq_len, "global_batch": run.batch,
            "zipf_a": run.cell.traffic["zipf_a"]}
    got = rt.train_readings(run.reference, run.prog_seed, run.model,
                            dict(run.opt, total_steps=10), data, 3,
                            block_rows=2)
    rec = {k: got[k] for k in ("grad_norms", "embed_zero_rows",
                               "change_norms")}
    state = {"params": None, "opt": None,
             "router_bias": jax.tree_util.tree_map(jnp.copy, got["extra"])}
    harness.read_extra(run, state, rec)
    assert isinstance(rec["extra"]["bias"], np.ndarray)
    harness._check_training(run, [[x] for x in got["losses"]], rec)
    assert run.correct and run.checks["extra_gap"] == (0.0, 1e-3)
    # one expert's bias a step of GAMMA off: a restart that lost a step
    rec["extra"] = {"bias": rec["extra"]["bias"]
                    + np.float32([run.reference.GAMMA, 0, 0, 0])}
    harness._check_training(run, [[x] for x in got["losses"]], rec)
    assert run.checks["extra_gap"][0] > 1e-3 and not run.correct


def test_calibrate_reads_extra_gap_for_the_control_and_the_fault(
        balanced_run):
    full = calibrate.reference_readings(balanced_run)
    assert set(full["extra"]) == {"bias"}
    low = harness.training_numbers(
        calibrate.reference_readings(balanced_run, quant="fp8"), full)
    half = harness.training_numbers(calibrate.reference_readings(
        balanced_run, rows=slice(0, balanced_run.batch // 2)), full)
    assert harness.training_numbers(full, full)["extra_gap"] == 0.0
    assert low["extra_gap"] > 0 and half["extra_gap"] > 0


def test_extra_state_without_its_limit_is_refused(tmp_path):
    make_root(tmp_path, "bench/arch/balanced.py", BALANCED)
    with pytest.raises(ValueError, match=(
            rf"bench/workloads/{CELL}\.json.*router_bias.*extra_gap")):
        harness.load_cell(CELL, tmp_path)


def test_program_state_without_the_extra_key_is_refused(balanced_root,
                                                        tmp_path):
    with pytest.raises(ValueError, match=(
            r"no 'router_bias' subtree.*bench/arch/balanced\.py")):
        harness.run_cell(tiny_cell(CELL, balanced_root), SEED, 0.5, False,
                         tmp_path / "wd", 0.0, require_tpu=False)
