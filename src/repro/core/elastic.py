"""Elastic remesh: shrink-recovery resharding (beyond-paper, DESIGN.md §2).

The paper's shrinking recovery leaves domain redistribution to the user.
Here the checkpoint manifest is topology-independent (shard files + global
indices), so after a shrink the framework itself can rebuild a smaller mesh
and restore the same global state resharded — "the user redistributes the
domain" done automatically.

The data-parallel axis absorbs the shrink (every DP slice holds a full
model replica group, so dropping DP slices never strands a weight shard);
the model axis is preserved.  ``shrink_mesh`` computes the largest valid
mesh for the surviving host count; ``reshard`` moves a live pytree onto it.
A restore-from-checkpoint needs no special code at all: build the state on
the new mesh and ``Checkpoint.restart_if_needed()`` — the checkpointables
``device_put`` every leaf onto the live (new-mesh) sharding.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding

from repro.sharding.logical import LogicalRules, shard_specs


def shrink_mesh(n_devices: int, model_parallel: int,
                axis_names: Tuple[str, ...] = ("data", "model")) -> Mesh:
    """Largest (data, model) mesh with the given TP degree that fits
    ``n_devices`` devices.  Raises if fewer than one model group survives."""
    if n_devices < model_parallel:
        raise ValueError(
            f"{n_devices} devices cannot hold one {model_parallel}-way "
            "model-parallel group — shrink recovery impossible; use "
            "non-shrinking recovery with spare nodes instead")
    data = n_devices // model_parallel
    devs = jax.devices()[: data * model_parallel]
    import numpy as np

    arr = np.array(devs).reshape(data, model_parallel)
    return Mesh(arr, axis_names,
                axis_types=(AxisType.Auto,) * len(axis_names))


def reshard(tree, logical_tree, new_mesh: Mesh,
            rules: Optional[LogicalRules] = None):
    """Move a live pytree onto ``new_mesh`` under the same logical rules."""
    rules = rules or LogicalRules(new_mesh)
    specs = shard_specs(rules, logical_tree, tree)
    return jax.tree_util.tree_map(
        lambda x, sp: jax.device_put(x, NamedSharding(new_mesh, sp)),
        tree, specs,
        is_leaf=lambda x: isinstance(x, jax.Array)), specs


def dp_degree(mesh: Mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("data", 1) * sizes.get("pod", 1)


# --------------------------------------------------------------------------
# host-side domain decomposition (ShardCp) + replacement-rank hydration
# --------------------------------------------------------------------------
def block_index(global_shape, rank: int, size: int, axis: int = 0):
    """Balanced contiguous block decomposition of a global array over
    ``size`` ranks along ``axis`` — the extent ``rank`` owns, as a tuple of
    slices (``()`` for 0-d arrays, which every rank replicates whole).

    The first ``shape[axis] % size`` ranks get one extra row, so any N→M
    pair of decompositions tiles the array without gaps — the geometry
    :func:`repro.core.reshard.overlap_runs` maps across topologies.
    """
    global_shape = tuple(int(s) for s in global_shape)
    if not global_shape:
        return ()
    if not 0 <= axis < len(global_shape):
        raise ValueError(f"axis {axis} out of range for {global_shape}")
    if not 0 <= rank < size:
        raise ValueError(f"rank {rank} out of range for size {size}")
    base, rem = divmod(global_shape[axis], size)
    lo = rank * base + min(rank, rem)
    hi = lo + base + (1 if rank < rem else 0)
    return tuple(
        slice(lo, hi) if d == axis else slice(0, s)
        for d, s in enumerate(global_shape)
    )


def hydrate_replacement(cp) -> dict:
    """Restore a spawned replacement rank's slice from the tier chain.

    Called in the zone body a replacement re-enters after NON-SHRINKING
    recovery: the checkpoint restores through the normal chain — with the
    memory tier chained first, the slice comes out of surviving peers'
    RAM-fabric replicas (or an RS group rebuild on the node tier) without
    touching the PFS — and the rank's own fabric slots are re-seeded
    (``CRAFT_ELASTIC_HYDRATE``).  Returns what happened, for recovery
    telemetry::

        {"restored": bool, "tier": label|None, "reseeded": int}
    """
    restored = cp.restart_if_needed()
    return {
        "restored": bool(restored),
        "tier": cp.stats.get("restore_tier"),
        "reseeded": int(cp.stats.get("mem_rehydrations", 0)),
    }
