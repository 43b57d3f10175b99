"""End-to-end training driver: model + optimizer + data + CRAFT CR/AFT.

This is the paper's Listing 2/9 pattern at framework scale:

    state = init (params, opt_state, step, data cursor)
    cp = Checkpoint("train", comm); cp.add("state", ...); cp.commit()
    cp.restart_if_needed()
    while step < total:
        batch = data.batch(cursor.step)
        state = train_step(state, batch)
        cp.update_and_write(step, cp_freq)

Wrapped in an AFT zone when a fault-tolerant communicator is supplied, so
process failures re-enter the loop from the latest checkpoint (shrinking or
non-shrinking recovery per CRAFT_COMM_RECOVERY_POLICY).

Runs on any mesh: the production 16×16 (dry-run), a few forced host
devices, or the single CPU device (examples/tests with ``--tiny``).
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from repro.configs import get_config
from repro.core import Box, Checkpoint
from repro.core import trace as craft_trace
from repro.core.aft import aft_zone
from repro.data.pipeline import DataCursor, SyntheticTokens
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models.common import ModelConfig
from repro.optim.adamw import OptimConfig, adamw_init
from repro.sharding.activations import use_rules
from repro.sharding.logical import LogicalRules, shard_specs
from repro.train.steps import StepTimer, TrainStepConfig, make_train_step

log = logging.getLogger("craft.train")


@dataclasses.dataclass
class TrainConfig:
    arch: str = "h2o-danube-1.8b"
    tiny: bool = True
    preset: Optional[str] = None         # named preset of the arch, e.g.
    #                                      "one_chip" (takes precedence)
    steps: int = 50
    global_batch: int = 8
    seq_len: int = 64
    cp_freq: int = 10
    cp_name: str = "train"
    seed: int = 0
    microbatches: int = 1
    lr: float = 3e-4
    sequence_parallel: bool = False
    fail_at_step: Optional[int] = None   # in-process fault injection (tests)


def _mesh_rules(mesh, sequence_parallel: bool):
    rules = LogicalRules(mesh)
    if sequence_parallel:
        rules.rules["embed_act"] = "model"
    return rules


def init_state(cfg: ModelConfig, ocfg: OptimConfig, mesh, rules, seed: int):
    """Sharded (params, opt_state) on the mesh."""
    plog = M.param_logical(cfg)
    pshapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                             jax.random.PRNGKey(seed))
    pspecs = shard_specs(rules, plog, pshapes)
    from repro.optim.adamw import opt_state_logical

    with jax.set_mesh(mesh):
        params = jax.jit(
            lambda k: M.init_params(k, cfg),
            out_shardings=jax.tree_util.tree_map(
                lambda s: jax.sharding.NamedSharding(mesh, s), pspecs,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)),
        )(jax.random.PRNGKey(seed))
        oshapes = jax.eval_shape(lambda p: adamw_init(p, ocfg), params)
        ospecs = shard_specs(
            rules, opt_state_logical(plog, ocfg, params=params), oshapes)
        opt_state = jax.jit(
            lambda p: adamw_init(p, ocfg),
            out_shardings=jax.tree_util.tree_map(
                lambda s: jax.sharding.NamedSharding(mesh, s), ospecs,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)),
        )(params)
    return params, opt_state, pspecs, ospecs


def run(tc: TrainConfig, comm=None, mesh=None,
        on_step: Optional[Callable[[int, Dict], None]] = None,
        env=None,
        on_start: Optional[Callable[[int, Dict], None]] = None) -> Dict:
    """Train; returns {"losses": [...], "step_times": [...], "start_step",
    "final_step", "state": {"params", "opt"}, "stats": {...}}.

    ``on_start(step, state)`` runs once before the first step, after any
    restore, with the step the loop starts from and the live state.  The
    default mesh is data-parallel over every device.  With ``comm`` (an
    FTComm), the whole loop runs inside an AFT zone: the checkpoint is
    (re)opened inside the zone body (paper Listing 9) so every recovery
    re-reads the latest consistent version.
    """
    cfg = get_config(tc.arch, tiny=tc.tiny, preset=tc.preset)
    if mesh is None:
        mesh = make_mesh((jax.device_count(),), ("data",))
    rules = _mesh_rules(mesh, tc.sequence_parallel)
    ocfg = OptimConfig(lr=tc.lr, master_fp32=False, warmup_steps=5,
                       total_steps=max(tc.steps, 10))
    scfg = TrainStepConfig(microbatches=tc.microbatches, loss_chunk=32)
    step_fn = make_train_step(cfg, ocfg, scfg)

    n_shards = comm.size if comm is not None else 1
    shard = comm.rank if comm is not None else 0
    data = SyntheticTokens(
        vocab=cfg.vocab, seq_len=tc.seq_len, global_batch=tc.global_batch,
        seed=tc.seed, n_shards=1, shard=0)   # deterministic global batch
    del shard, n_shards

    def body(comm_inner):
        with craft_trace.span("train.init_state"):
            params, opt_state, _, _ = init_state(cfg, ocfg, mesh, rules,
                                                 tc.seed)
        state_box = Box({"params": params, "opt": opt_state})
        # the box holds the only reference, so a restore that replaces the
        # state frees the initial one instead of keeping both on the device
        del params, opt_state
        step_box = Box(0)
        cursor = DataCursor(0)

        with craft_trace.span("train.cp_setup"):
            cp = Checkpoint(tc.cp_name, comm_inner, env=env)
            cp.add("state", state_box)
            cp.add("step", step_box)
            cp.add("cursor", FuncBox(cursor))
            cp.commit()
        cp.restart_if_needed()
        start_step = step_box.value
        if on_start is not None:
            on_start(start_step, state_box.value)

        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
        losses: List[float] = []
        step_times: List[float] = []
        timer = StepTimer()
        t0 = time.perf_counter()
        try:
            while step_box.value < tc.steps:
                step_t0 = time.perf_counter()
                with craft_trace.span("train.step", step=step_box.value + 1):
                    with craft_trace.span("train.batch"):
                        batch_np = data.batch(cursor.step)
                        with jax.set_mesh(mesh):
                            bspec = rules.spec(
                                "batch", "seq",
                                shape=batch_np["tokens"].shape)
                            batch = {
                                k: jax.device_put(
                                    v, jax.sharding.NamedSharding(mesh, bspec))
                                for k, v in batch_np.items()
                            }
                    with craft_trace.span("train.dispatch"), \
                            jax.set_mesh(mesh), use_rules(rules):
                        p, o, metrics = jit_step(
                            state_box.value["params"],
                            state_box.value["opt"], batch)
                    state_box.value = {"params": p, "opt": o}
                    cursor.step += 1
                    step_box.value += 1
                    with craft_trace.span("train.sync"):
                        loss = float(metrics["loss"])
                losses.append(loss)
                # compute-only step time (checkpoint writes excluded) feeds
                # the scheduler's rework model and the result stats
                step_times.append(time.perf_counter() - step_t0)
                timer.observe(step_times[-1])
                if cp.policy is not None and timer.last is not None:
                    cp.policy.observe_step_seconds(timer.last)
                if on_step is not None:
                    on_step(step_box.value, metrics)
                if (tc.fail_at_step is not None
                        and step_box.value == tc.fail_at_step
                        and comm_inner is not None
                        and getattr(comm_inner, "rank", 0) == 0
                        and getattr(comm_inner, "epoch", 0) == 0):
                    # deterministic in-process fault injection (paper §5.3);
                    # epoch-0 guard: fire once, not on every AFT retry
                    raise_fault(comm_inner)
                cp.update_and_write(step_box.value, tc.cp_freq)
                if cp.should_stop:
                    # preemption flush landed or the walltime guard wrote its
                    # final checkpoint — exit the loop cleanly; the next job
                    # (or the respawned one) resumes from that version
                    break
            cp.wait()
            return {
                "losses": losses,
                "step_times": step_times,
                "start_step": start_step,
                "final_step": step_box.value,
                "state": state_box.value,
                "wall_s": time.perf_counter() - t0,
                "step_seconds": timer.ewma,
                "stats": dict(cp.stats),
            }
        finally:
            cp.close()

    if comm is None:
        return body(None)
    return aft_zone(comm, body)


def raise_fault(comm) -> None:
    """Deterministic fail-stop of this rank (benchmarks use the runtime's
    kill -9 instead; this is the paper's in-program injection variant)."""
    from repro.core.comm import ProcFailedError

    raise ProcFailedError(f"injected fault at rank {comm.rank}",
                          failed=[comm.rank])


class FuncBox:
    """Adapter exposing a DataCursor as a checkpointable POD box."""

    def __init__(self, cursor: DataCursor):
        self.cursor = cursor

    @property
    def value(self) -> int:
        return self.cursor.step

    @value.setter
    def value(self, v: int) -> None:
        self.cursor.step = int(v)


# Box duck-typing: Checkpoint.add() wraps Box instances via isinstance, so
# register FuncBox through the adapter registry instead.
from repro.core.checkpointables import FuncCp, register_adapter  # noqa: E402

register_adapter(
    lambda obj: isinstance(obj, FuncBox),
    lambda obj: FuncCp(lambda: obj.value, lambda v: setattr(obj, "value", v)),
)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    ap.add_argument("--tiny", action="store_true", default=True)
    ap.add_argument("--full", dest="tiny", action="store_false")
    ap.add_argument("--preset", default=None,
                    help="named preset of the arch, e.g. one_chip")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--cp-freq", type=int, default=10)
    args = ap.parse_args()
    tc = TrainConfig(arch=args.arch, tiny=args.tiny, preset=args.preset,
                     steps=args.steps, global_batch=args.global_batch,
                     seq_len=args.seq_len, cp_freq=args.cp_freq)
    logging.basicConfig(level=logging.INFO)
    setup_compile_cache()
    out = run(tc, on_step=lambda s, m: print(
        f"step {s:4d} loss {float(m['loss']):.4f} "
        f"gnorm {float(m['grad_norm']):.3f}"))
    print(f"done: {out['final_step']} steps in {out['wall_s']:.1f}s; "
          f"checkpoint stats {out['stats']}")


if __name__ == "__main__":
    main()
