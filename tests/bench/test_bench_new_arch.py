"""A configuration of another architecture needs new files only: in a
bench root of its own, a configuration whose ``"reference"`` names a
second reference file is loaded, shrunk, checked, calibrated and counted
through that file and not through ``bench/reference.py``."""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

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


def make_root(root: Path, reference) -> None:
    """A bench root holding BENCHMARK.json with one more configuration and
    cell, their data files, and (where ``reference`` is given) the
    configuration's ``"reference"`` key and the wrapper file."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    base = next(w for w in spec["workloads"] if w["name"] == BASE)
    conf = json.loads((ROOT / "bench" / "configs" / f"{base['config']}.json")
                      .read_text())
    conf["name"] = "wrapped"
    del conf["reference"]
    if reference is not None:
        conf["reference"] = reference
        (root / Path(reference).parent).mkdir(parents=True)
        (root / reference).write_text(WRAPPER)
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
    shutil.copy(ROOT / "bench" / "workloads" / f"{BASE}.json",
                root / "bench" / "workloads" / f"{CELL}.json")


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
