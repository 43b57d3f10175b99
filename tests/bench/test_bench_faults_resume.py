"""The resume cell's harness, driven on the CPU at a tiny size: a restore
that hands back altered state, or a step after it that is broken, must
turn ``correct`` false; the sound run must stay true."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.tiny import tiny_cell  # noqa: E402

CELL = "phi4mini3.8b.resume-node"
SEED = 2 ** 31 + 4321


def run(tmp_path):
    return harness.run_cell(tiny_cell(CELL), SEED, 0.5, False,
                            tmp_path / "wd", 0.0, require_tpu=False)


def test_sound_run_is_correct(tmp_path):
    res = run(tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"resume_s", "setup_s"}


def test_altered_restore_is_not_correct(tmp_path, monkeypatch):
    import jax

    from repro.core.checkpointables import PytreeCp

    real = PytreeCp.read

    def read(self, dir_path, ctx):
        real(self, dir_path, ctx)
        leaves, treedef = jax.tree_util.tree_flatten(self.box.value)
        leaves[1] = leaves[1] + 1
        self.box.value = jax.tree_util.tree_unflatten(treedef, leaves)

    monkeypatch.setattr(PytreeCp, "read", read)
    res = run(tmp_path)
    assert not res["correct"]
    assert res["checks"]["restore_leaves_differing"]["value"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(tmp_path, monkeypatch, fault):
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
    res = run(tmp_path)
    assert not res["correct"], res["checks"]
