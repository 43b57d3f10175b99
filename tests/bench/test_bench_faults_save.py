"""The save cell's harness, driven on the CPU at a tiny size with the
timed path broken underneath: every fault the cell can have must turn
``correct`` false, and the sound run must stay true."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.tiny import tiny_cell  # noqa: E402

CELL = "danube1.8b.train-save-dense"
SEED = 2 ** 31 + 1234


def run(tmp_path):
    return harness.run_cell(tiny_cell(CELL), SEED, 0.5, False,
                            tmp_path / "wd", 0.0, require_tpu=False)


def broken_step(monkeypatch, fault):
    from repro.launch import train

    real = train.make_train_step

    def make(cfg, ocfg, scfg=None):
        step = real(cfg, ocfg, scfg)

        def faulty(params, opt_state, batch):
            if fault == "half_batch":
                batch = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            p, o, metrics = step(params, opt_state, batch)
            if fault == "unchanged":
                return params, opt_state, metrics
            return p, o, metrics

        return faulty

    monkeypatch.setattr(train, "make_train_step", make)


def test_sound_run_is_correct(tmp_path):
    res = run(tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "save_stall_s",
                                   "save_to_durable_s", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(tmp_path, monkeypatch, fault):
    broken_step(monkeypatch, fault)
    res = run(tmp_path)
    assert not res["correct"], res["checks"]


def test_altered_saved_byte_is_not_correct(tmp_path, monkeypatch):
    from repro.core import storage

    real = storage.write_array

    def write_array(path, arr, ctx):
        if path.name == "leaf1-shard-0-0.bin" and "pfs" in str(path):
            arr = np.array(arr, copy=True)
            arr.reshape(-1).view(np.uint8)[0] ^= 1
        return real(path, arr, ctx)

    monkeypatch.setattr(storage, "write_array", write_array)
    res = run(tmp_path)
    assert not res["correct"]
    assert res["checks"]["readback_leaves_differing"]["value"] > 0
    assert res["failed"] > 0
