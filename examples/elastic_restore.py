import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))
# 8 placeholder devices so this single-process example can demonstrate
# cross-mesh restore (must precede any jax import).

"""Elastic restore — shrinking recovery with automatic resharding.

Beyond-paper extension (DESIGN.md §2): the paper's shrinking recovery
leaves 'redistributing the domain' to the user; CRAFT-JAX's checkpoint
manifest is topology-independent, so the same training state written on a
4×2 mesh restores onto the 2×2 mesh that remains after two hosts fail —
every leaf is resharded automatically onto the live sharding.

    PYTHONPATH=src python examples/elastic_restore.py
"""
import numpy as np

import jax
from jax.sharding import NamedSharding

from repro.core import Box, Checkpoint
from repro.core.elastic import dp_degree, shrink_mesh
from repro.core.env import CraftEnv
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.sharding.logical import LogicalRules, shard_specs


def params_on_mesh(cfg, mesh):
    rules = LogicalRules(mesh)
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    specs = shard_specs(rules, M.param_logical(cfg), shapes)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    with jax.set_mesh(mesh):
        return jax.jit(lambda k: M.init_params(k, cfg),
                       out_shardings=shardings)(jax.random.PRNGKey(0))


def main() -> None:
    env = CraftEnv.capture({"CRAFT_CP_PATH": "craft-elastic",
                            "CRAFT_USE_SCR": "0"})
    cfg = get_config("h2o-danube-1.8b", tiny=True)

    mesh_a = make_mesh((4, 2), ("data", "model"))
    params_a = params_on_mesh(cfg, mesh_a)
    print(f"wrote state on mesh {dict(zip(mesh_a.axis_names, mesh_a.devices.shape))} "
          f"(DP degree {dp_degree(mesh_a)})")
    box = Box(params_a)
    cp = Checkpoint("elastic", env=env)
    cp.add("params", box)
    cp.commit()
    cp.update_and_write()

    # --- two hosts fail; shrinking recovery keeps the 2-way TP groups ----
    mesh_b = shrink_mesh(4, model_parallel=2)
    print(f"shrunk to mesh {dict(zip(mesh_b.axis_names, mesh_b.devices.shape))} "
          f"(DP degree {dp_degree(mesh_b)})")
    params_b = params_on_mesh(cfg, mesh_b)   # fresh state on the new mesh
    box2 = Box(params_b)
    cp2 = Checkpoint("elastic", env=env)
    cp2.add("params", box2)
    cp2.commit()
    assert cp2.restart_if_needed()

    # verify: same global values, new placement
    flat_a = jax.tree_util.tree_leaves(params_a)
    flat_b = jax.tree_util.tree_leaves(box2.value)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32))
    n_dev = {d for leaf in flat_b for d in leaf.sharding.device_set}
    print(f"restored {len(flat_b)} leaves onto {len(n_dev)} devices — "
          "elastic restore OK")


if __name__ == "__main__":
    main()
