"""Logical sharding rules + elastic restore (cross-mesh checkpoint)."""
import subprocess
import sys
from pathlib import Path

import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.sharding.logical import DEFAULT_RULES, LogicalRules

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh((1,), ("data",))


class TestRules:
    def test_spec_basic(self, mesh1):
        rules = LogicalRules(mesh1)
        assert rules.spec("batch", "seq") == P("data", None)

    def test_missing_axis_dropped(self, mesh1):
        rules = LogicalRules(mesh1)     # no 'model' axis on this mesh
        assert rules.spec("batch", "heads") == P("data", None)

    def test_axis_used_once(self, mesh1):
        rules = LogicalRules(mesh1)
        # both dims map to data — second one must degrade to None
        assert rules.spec("batch", "embed") == P("data", None)

    def test_divisibility_fallback(self):
        # a 16-way data axis cannot shard batch=1 or heads=56 evenly;
        # LogicalRules only reads axis_names/devices.shape, so a stub mesh
        # stands in for real multi-device hardware
        class FakeDevices:
            shape = (16, 16)

        class FakeMesh:
            axis_names = ("data", "model")
            devices = FakeDevices()

        rules = LogicalRules(FakeMesh())
        assert rules.spec("batch", "seq", shape=(1, 64)) == P(None, None)
        assert rules.spec("batch", "seq", shape=(64, 64)) == \
            P("data", None)
        # 56 heads don't divide 16 → replicated; 64 do → sharded
        assert rules.spec("embed", "heads", shape=(128, 56)) == \
            P("data", None)
        assert rules.spec("embed", "heads", shape=(128, 64)) == \
            P("data", "model")

    def test_unknown_logical_raises(self, mesh1):
        with pytest.raises(KeyError):
            LogicalRules(mesh1).spec("nonsense")

    def test_tuple_rule_prefix(self):
        # multi-axis rule keeps only the dividing prefix
        assert DEFAULT_RULES["batch"] == ("pod", "data")


@pytest.mark.slow
class TestElasticRestore:
    """Checkpoint written on a (4,2) mesh restores onto (2,2) — subprocess
    with 8 forced host devices (the test process keeps 1 device)."""

    def test_cross_mesh_restore(self, tmp_path):
        script = tmp_path / "elastic_probe.py"
        script.write_text(f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import Box, Checkpoint
from repro.core.elastic import shrink_mesh, reshard
from repro.core.env import CraftEnv
from repro.launch.mesh import make_mesh

env = CraftEnv.capture({{"CRAFT_CP_PATH": r"{tmp_path}/pfs",
                         "CRAFT_USE_SCR": "0"}})
mesh_a = make_mesh((4, 2), ("data", "model"))
x = jnp.arange(64.0).reshape(8, 8)
xa = jax.device_put(x, NamedSharding(mesh_a, P("data", "model")))
box = Box(xa)
cp = Checkpoint("el", env=env)
cp.add("x", box)
cp.commit()
cp.update_and_write()

# --- shrink: 2 "hosts" lost -> 4 devices usable, same TP degree
mesh_b = shrink_mesh(4, model_parallel=2)
xb = jax.device_put(jnp.zeros((8, 8)),
                    NamedSharding(mesh_b, P("data", "model")))
box2 = Box(xb)
cp2 = Checkpoint("el", env=env)
cp2.add("x", box2)
cp2.commit()
assert cp2.restart_if_needed()
np.testing.assert_array_equal(np.asarray(box2.value), np.asarray(x))
assert box2.value.sharding.mesh.devices.size == 4

# --- live reshard helper
y, _ = reshard({{"w": box2.value}}, {{"w": ("batch", "embed")}}, mesh_b)
np.testing.assert_array_equal(np.asarray(y["w"]), np.asarray(x))
print("OK")
""")
        r = subprocess.run([sys.executable, str(script)], cwd=REPO,
                           capture_output=True, text=True, timeout=300)
        assert "OK" in r.stdout, (r.stdout[-800:], r.stderr[-2000:])


@pytest.mark.slow
class TestTinyDryRun:
    """A reduced-config dry-run cell on an 8-device forced mesh: the full
    specs/lower/compile path plus roofline extraction, end to end."""

    def test_tiny_cell_compiles(self, tmp_path):
        script = tmp_path / "dry_probe.py"
        script.write_text("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax
from repro.configs import ShapeSpec
from repro.launch.specs import build_step
from repro.analysis import roofline as R
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
for kind, name in (("train", "tiny_train"), ("prefill", "tiny_prefill"),
                   ("decode", "tiny_decode")):
    shape = ShapeSpec(name, seq_len=64, global_batch=4, kind=kind)
    built = build_step("zamba2-2.7b", shape, mesh, tiny=True)
    compiled = built.lower(mesh).compile()
    rep = R.analyze(compiled.as_text())
    assert rep.flops > 0, (kind, rep.as_dict())
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes > 0
print("OK")
""")
        r = subprocess.run([sys.executable, str(script)], cwd=REPO,
                           capture_output=True, text=True, timeout=560)
        assert "OK" in r.stdout, (r.stdout[-800:], r.stderr[-2500:])
