#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (run on the chip).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 [--out readings.jsonl]

In one process, for each seed: the program's first steps through
``train.run`` at the cell's own sizes, compared with the reference as the
harness compares them (the lower reading); for each control seed: the
reference computed in float8 put in the program's place (the control), and
the reference with half of every batch left out (the half-batch fault).
The reference is the configuration's own (its ``"reference"`` file).
A state left unchanged reads 1 on ``change_gap`` and needs no run.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench import ref_training as rt  # noqa: E402

STEPS = 3   # the harness compares the first three steps


def program_readings(run: harness.Run, arch: str, steps: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.launch import train

    rec = {"losses": []}
    box = {}

    def on_start(step, state):
        rec["init"] = jax.tree_util.tree_map(jnp.copy, state["params"])

    def on_step(step, metrics):
        rec["losses"].append(float(metrics["loss"]))
        st = run.probes.state_box.value
        if step == 1:
            harness.first_gradient(run, st, rec)
        if step == steps:
            box["change"] = np.asarray(
                rt.diff_norms(st["params"], rec.pop("init")), np.float64)

    tc = harness.train_config(run, arch, steps, 10 ** 6)
    out = train.run(tc, env=harness.craft_env(run.cell, run.workdir),
                    on_start=on_start, on_step=on_step)
    del out
    rec["change_norms"] = box["change"]
    return rec


def reference_readings(run: harness.Run, **kw) -> dict:
    """The configuration's reference over the first ``STEPS`` steps of the
    run's seed, at the cell's sizes; ``kw`` puts the control or a fault in
    (``quant``, ``rows``)."""
    data = {"seq_len": run.seq_len, "global_batch": run.batch,
            "zipf_a": run.cell.traffic["zipf_a"]}
    opt = dict(run.opt, total_steps=max(STEPS, 10))
    return rt.train_readings(
        run.reference, run.prog_seed, run.model, opt, data, STEPS,
        block_rows=int(run.cell.params.get("reference_block_rows", 1)), **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax

    from bench.probes import Probes
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    harness.device_info(cell.chips, require_tpu=True)
    arch = harness.register(cell)
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out is not None:
            out.write(line + "\n")
            out.flush()

    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    for seed in sorted(set(seeds) | set(controls)):
        run = harness.Run(cell, seed, 0.0, False, harness.WORKDIR, 0.0)
        t0 = time.perf_counter()
        full = reference_readings(run)
        t_ref = time.perf_counter() - t0
        if seed in seeds:
            run.probes = Probes().install()
            try:
                got = program_readings(run, arch, STEPS)
            finally:
                run.probes.uninstall()
            emit({"kind": "program", "seed": seed, "reference_s": t_ref,
                  **harness.training_numbers(got, full),
                  "losses": got["losses"], "ref_losses": full["losses"]})
        if seed in controls:
            low = reference_readings(run, quant="fp8")
            emit({"kind": "control_fp8", "seed": seed,
                  **harness.training_numbers(low, full)})
            half = reference_readings(run, rows=slice(0, run.batch // 2))
            emit({"kind": "fault_half_batch", "seed": seed,
                  **harness.training_numbers(half, full)})
    if out is not None:
        out.close()
    shutil.rmtree(harness.WORKDIR, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
