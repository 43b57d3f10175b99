#!/usr/bin/env python3
"""Smoke test of CRAFT's checkpointed training loop on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: sharded save, N->M restore

One chip: h2o-danube-1.8b's ``one_chip`` preset (every published width, 4
of its 24 layers) trains through ``repro.launch.train.run`` with synthetic
tokens from a seed.  The script runs, in one process:

1. an uninterrupted 8-step reference run, with no saves;
2. 6 steps that save every 3 steps through the node and PFS tiers, with the
   device snapshot kernel and its digests on (``CRAFT_DEVICE_SNAPSHOT=1``);
3. ``train.run`` again with ``steps=8``: it restores step 6 and takes 2
   more steps.

It checks that the restored params and optimizer state are bit-identical to
what step 6 left (SHA-256 of every leaf, on the host), that the resumed
losses of steps 7-8 and the final state equal the reference run's exactly
(same program, same inputs, same chip: XLA on TPU is deterministic, so no
tolerance is needed), and that the snapshot program holds the Pallas kernel
(``tpu_custom_call``).

Four chips (``--chips 4``): the same preset trains 2 steps on a 2x2
``("data", "model")`` mesh and saves; the checkpoint is then restored
through ``train.run`` onto a 2-chip and a 1-chip layout, and each restored
state must be bit-identical to the saved global state.

With no TPU the script exits non-zero before any phase.  The last line of
standard output is one JSON object naming the device; it is printed only
when every phase passed.  Checkpoints go under ``.smoke_ckpt/`` in the
checkout and are deleted at the end.
"""
from __future__ import annotations

import argparse
import gc
import hashlib
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "h2o-danube-1.8b"
PRESET = "one_chip"
SEQ_LEN = 2048
GLOBAL_BATCH = 8        # largest the train step's memory analysis leaves
#                         room for beside the state and its snapshot copy
SEED = 0
SAVE_EVERY = 3
FIRST_STEPS = 6
TOTAL_STEPS = 8


class SmokeError(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def train_config(steps: int, cp_freq: int, name: str, **kw):
    from repro.launch import train

    base = dict(arch=ARCH, preset=PRESET, steps=steps,
                global_batch=GLOBAL_BATCH, seq_len=SEQ_LEN, cp_freq=cp_freq,
                cp_name=name, seed=SEED)
    base.update(kw)
    return train.TrainConfig(**base)


def craft_env(workdir: Path, **extra):
    from repro.core.env import CraftEnv

    env = {"CRAFT_CP_PATH": str(workdir / "pfs"),
           "CRAFT_NODE_CP_PATH": str(workdir / "node"),
           "CRAFT_DEVICE_SNAPSHOT": "1"}
    env.update(extra)
    return CraftEnv.capture(env)


def state_digest(state) -> list:
    """SHA-256 of every leaf's bytes, fetched to the host."""
    import jax
    import numpy as np

    hosts = jax.device_get(jax.tree_util.tree_leaves(state))
    return [hashlib.sha256(np.ascontiguousarray(h).view(np.uint8)).hexdigest()
            for h in hosts]


def describe_model(tc) -> None:
    import jax
    from repro.configs import get_config
    from repro.models import model as M
    from repro.optim.adamw import OptimConfig, adamw_init

    cfg = get_config(tc.arch, tiny=tc.tiny, preset=tc.preset)
    print(f"model: {cfg.arch_id} preset={tc.preset} layers={cfg.n_layers} "
          f"d_model={cfg.d_model} heads={cfg.n_heads} kv_heads="
          f"{cfg.n_kv_heads} head_dim={cfg.hd} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} window={cfg.window}")
    pshapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                             jax.random.PRNGKey(0))
    oshapes = jax.eval_shape(
        lambda p: adamw_init(p, OptimConfig(master_fp32=False)), pshapes)
    nbytes = lambda t: sum(x.size * x.dtype.itemsize
                           for x in jax.tree_util.tree_leaves(t))
    n_leaves = len(jax.tree_util.tree_leaves((pshapes, oshapes)))
    print(f"params: {cfg.param_count()} ({nbytes(pshapes)} bytes); "
          f"optimizer state {nbytes(oshapes)} bytes; {n_leaves} leaves; "
          f"batch {tc.global_batch} x seq {tc.seq_len}")


def print_memory(tag: str) -> None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    print(f"memory[{tag}]: peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use')} bytes_in_use="
          f"{stats.get('bytes_in_use')} bytes_limit="
          f"{stats.get('bytes_limit')}")


def print_run(tag: str, out: dict) -> None:
    times = ", ".join(f"{t:.3f}" for t in out["step_times"])
    losses = ", ".join(repr(x) for x in out["losses"])
    stats = {k: v for k, v in out["stats"].items()
             if k in ("writes", "node_writes", "pfs_writes", "bytes_written",
                      "write_seconds", "reads", "read_seconds",
                      "restore_tier", "restore_read_bytes")}
    print(f"{tag}: steps {out['start_step']}->{out['final_step']} "
          f"wall_s={out['wall_s']:.2f}")
    print(f"{tag}: step_seconds [{times}]")
    print(f"{tag}: losses [{losses}]")
    print(f"{tag}: checkpoint {stats}")


def snapshot_hlo_has_kernel(state) -> bool:
    """Whether the compiled device-snapshot program of the largest state
    leaf holds the Pallas kernel, as the write path calls it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import device_snapshot as ds
    from repro.core.env import CraftEnv

    leaf = max(jax.tree_util.tree_leaves(state), key=lambda x: x.nbytes)
    shard = leaf.addressable_shards[0].data
    snap = ds.DeviceSnapshotter(CraftEnv.capture({}).chunk_bytes,
                                with_hist=False)
    n_chunks, wpc = snap._grid(shard.size * np.dtype(shard.dtype).itemsize)
    prev = jax.ShapeDtypeStruct((n_chunks, 2), jnp.uint32)
    hlo = ds._fused.lower(
        shard, prev, n_chunks=n_chunks, wpc=wpc, with_hist=False,
        use_pallas=jax.default_backend() == "tpu").compile().as_text()
    return "tpu_custom_call" in hlo


def one_chip(workdir: Path, mesh=None, **tc_kw) -> None:
    import jax
    from repro.launch import train
    from repro.launch.mesh import make_mesh

    mesh = mesh if mesh is not None else make_mesh((1,), ("data",))
    describe_model(train_config(TOTAL_STEPS, SAVE_EVERY, "smoke", **tc_kw))
    print_memory("start")

    t0 = time.perf_counter()
    ref = train.run(
        train_config(TOTAL_STEPS, 10 ** 6, "smoke-ref", **tc_kw),
        mesh=mesh, env=craft_env(workdir / "ref", CRAFT_DEVICE_SNAPSHOT="0"))
    print_run("reference", ref)
    print_memory("reference run")
    ref_final = state_digest(ref.pop("state"))
    print(f"phase reference: {time.perf_counter() - t0:.1f}s")
    gc.collect()

    t0 = time.perf_counter()
    env = craft_env(workdir)
    first = train.run(train_config(FIRST_STEPS, SAVE_EVERY, "smoke", **tc_kw),
                      mesh=mesh, env=env)
    print_run("save", first)
    check(first["stats"]["writes"] == FIRST_STEPS // SAVE_EVERY,
          f"expected {FIRST_STEPS // SAVE_EVERY} saves, stats "
          f"{first['stats']}")
    check(first["losses"] == ref["losses"][:FIRST_STEPS],
          "saving changed the losses of steps 1-6")
    saved = state_digest(first["state"])
    kernel = snapshot_hlo_has_kernel(first.pop("state"))
    print(f"snapshot HLO contains tpu_custom_call: {kernel}")
    check(kernel or jax.default_backend() != "tpu",
          "the device snapshot program holds no Pallas kernel")
    print_memory("save run")
    print(f"phase save: {time.perf_counter() - t0:.1f}s")
    gc.collect()

    t0 = time.perf_counter()
    restored = {}

    def on_start(step, state):
        restored["step"] = step
        restored["digest"] = state_digest(state)

    resumed = train.run(
        train_config(TOTAL_STEPS, SAVE_EVERY, "smoke", **tc_kw),
        mesh=mesh, env=env, on_start=on_start)
    print_run("resume", resumed)
    check(restored.get("step") == FIRST_STEPS,
          f"resumed from step {restored.get('step')}, not {FIRST_STEPS}")
    same = restored["digest"] == saved
    print(f"restore: from step {restored['step']} tier "
          f"{resumed['stats']['restore_tier']}; {len(saved)} leaves "
          f"bit-identical={same}")
    check(same, "restored state differs from what step 6 left")
    match = resumed["losses"] == ref["losses"][FIRST_STEPS:]
    print(f"resumed losses of steps {FIRST_STEPS + 1}-{TOTAL_STEPS} equal "
          f"the uninterrupted run's: {match}")
    check(match, f"resumed losses {resumed['losses']} != reference "
                 f"{ref['losses'][FIRST_STEPS:]}")
    final_same = state_digest(resumed.pop("state")) == ref_final
    print(f"final state after resume bit-identical to the uninterrupted "
          f"run's: {final_same}")
    check(final_same, "final state after resume differs from the reference")
    print_memory("end")
    print(f"phase resume: {time.perf_counter() - t0:.1f}s")


def replicated_writes(workdir: Path, name: str) -> str:
    """How often the newest PFS version wrote the same shard index twice."""
    manifests = sorted((workdir / "pfs").glob(f"{name}/v-*/state/tree-*.json"))
    check(bool(manifests), f"no pytree manifest under {workdir / 'pfs'}")
    leaves = json.loads(manifests[-1].read_text())["leaves"]
    files = dup_files = dup_leaves = 0
    for leaf in leaves:
        idx = [json.dumps(s["index"]) for s in leaf.get("shards", [])]
        files += len(idx)
        dup_files += len(idx) - len(set(idx))
        dup_leaves += len(idx) > len(set(idx))
    return (f"{files} shard files, {dup_files} of them repeat another's "
            f"index ({dup_leaves} of {len(leaves)} leaves replicated)")


def four_chips(workdir: Path, **tc_kw) -> None:
    import jax
    from repro.launch import train
    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, have {len(devices)}")
    steps = 2
    describe_model(train_config(steps, steps, "smoke4", **tc_kw))
    env = craft_env(workdir)

    t0 = time.perf_counter()
    mesh4 = make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    out = train.run(train_config(steps, steps, "smoke4", **tc_kw),
                    mesh=mesh4, env=env)
    print_run("save[2x2]", out)
    check(out["stats"]["writes"] == 1, f"expected 1 save: {out['stats']}")
    saved = state_digest(out.pop("state"))
    print(f"save[2x2]: {replicated_writes(workdir, 'smoke4')}")
    print_memory("save[2x2] run, device 0")
    print(f"phase save 4 chips: {time.perf_counter() - t0:.1f}s")
    gc.collect()

    layouts = (("1x2", make_mesh((1, 2), ("data", "model"),
                                 devices=devices[:2])),
               ("1", make_mesh((1,), ("data",), devices=devices[:1])))
    for tag, mesh in layouts:
        t0 = time.perf_counter()
        got = {}

        def on_start(step, state):
            got["step"] = step
            got["digest"] = state_digest(state)
            got["devices"] = len({d for x in jax.tree_util.tree_leaves(state)
                                  for d in x.sharding.device_set})

        res = train.run(train_config(steps, steps, "smoke4", **tc_kw),
                        mesh=mesh, env=env, on_start=on_start)
        same = got.get("digest") == saved
        print(f"restore[4->{tag}]: step {got.get('step')} onto "
              f"{got.get('devices')} devices, tier "
              f"{res['stats']['restore_tier']}, read "
              f"{res['stats']['restore_read_bytes']} bytes in "
              f"{res['stats']['read_seconds']:.2f}s; {len(saved)} leaves "
              f"bit-identical={same}")
        check(got.get("step") == steps,
              f"4->{tag} restored step {got.get('step')}, not {steps}")
        check(same, f"4->{tag} restored state differs from the saved one")
        del res
        gc.collect()
        print(f"phase restore 4->{tag}: {time.perf_counter() - t0:.1f}s")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}")
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{device['platform']!r}); this smoke runs only on a TPU",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import setup_compile_cache

    print(f"compile cache: {setup_compile_cache()}")
    workdir = ROOT / ".smoke_ckpt"
    shutil.rmtree(workdir, ignore_errors=True)
    print(f"disk free: {shutil.disk_usage(ROOT).free} bytes")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(workdir)
        else:
            one_chip(workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(f"smoke passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
