"""Run one benchmark cell and print its result line.

The cell names a configuration (``bench/configs``), a traffic mix
(``bench/traffic``) and its own parameters (``bench/workloads``).  The
timed path is ``repro.launch.train.run`` with its ``on_start`` and
``on_step`` hooks: saves go through ``Checkpoint.update_and_write`` and
restores through ``Checkpoint.restart_if_needed``, as the training loop
makes them.  Two traffic modes exist:

* ``train_save`` -- one ``train.run`` call.  Set-up trains the first steps
  (checked against the reference) and makes one warm-up save durable; the
  window then runs whole save periods (K steps, then a save) and ends when
  a save returns at least ``--seconds`` after the window began.  The run
  waits for the last save to become durable, reads every retained version
  of the window back from every tier, and compares it with the state the
  loop held at that save.
* ``resume`` -- set-up trains a few steps and saves one version; each
  resume of the window is a fresh ``train.run`` call that restores that
  version and takes one step.  Resumes repeat until ``--seconds`` have
  passed; the one under way then is completed and counted.

After the window, and after the device peak has been read and the
program's state freed, the reference recomputes the first steps from the
seed (``bench/ref_training.py`` with the architecture module that the
configuration's ``"reference"`` key names), and every compared number is
printed with its limit.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import time
import traceback
from pathlib import Path
from typing import Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
WORKDIR = ROOT / ".bench_run"
SEED_MOD = 2 ** 31          # the program's seeds are 31-bit


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    params: dict
    end_to_end: list
    per_layer: list
    reference: Path             # the configuration's architecture module


def _load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def _applies(metric: dict, cell: str, e2e_names) -> bool:
    """Whether ``cell`` reports ``metric``: the cells it lists, else every
    cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{', '.join(sorted(cells))}")
    w = cells[name]
    bench = root / "bench"
    config = _load_json(bench / "configs" / f"{w['config']}.json")
    if "reference" not in config:
        raise ValueError(f"configuration {w['config']!r} names no "
                         f"\"reference\" file for its architecture")
    ref_path = root / config["reference"]
    if not ref_path.is_file():
        raise ValueError(f"configuration {w['config']!r}: no reference "
                         f"file {config['reference']!r}")
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, ())]
    names = {m["name"] for m in e2e}
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=_load_json(bench / "traffic" / f"{w['traffic']}.json"),
        params=_load_json(bench / "workloads" / f"{name}.json"),
        end_to_end=e2e,
        per_layer=[m for m in spec["per_layer"]
                   if _applies(m, name, names)],
        reference=ref_path)


def load_reference(path: Path):
    """The architecture module in ``path`` (a configuration's
    ``"reference"``, keeping the contract of ``bench/ref_training.py``),
    loaded from its file once per process."""
    path = Path(path).resolve()
    name = "bench_reference_" + re.sub(r"\W", "_", str(path))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


def read_metric(name: str, run) -> Optional[float]:
    """Load ``bench/metrics/<name>.py`` and return its reading of the run
    (None when it finds nothing to read)."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


# ---------------------------------------------------------------- the run
class Window:
    """The measured window: its host-clock bounds, the profiler around it
    in a traced run, and the step at which the loop should stop."""

    def __init__(self, trace_dir: Optional[Path]):
        self.trace_dir = trace_dir
        self.t0 = self.t1 = None
        self.stop_step = None
        self._ann = None

    @property
    def running(self) -> bool:
        return self.t0 is not None and self.t1 is None

    def start(self) -> None:
        import jax

        if self.trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        self.t0 = time.perf_counter()

    def end(self, t1: float) -> None:
        import jax

        self.t1 = t1
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._ann = None


class Run:
    """Everything one run measured; the metric readers read it."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 workdir: Path, t_start: float):
        self.cell = cell
        self.reference = load_reference(cell.reference)
        self.model = cell.config["model"]
        self.opt = cell.config["optimizer"]
        self.seed = seed
        self.prog_seed = seed % SEED_MOD
        self.seconds = seconds
        self.trace = trace
        self.workdir = workdir
        self.t_start = t_start
        self.window = Window(workdir / "trace" if trace else None)
        self.batch = int(cell.params["global_batch"])
        self.seq_len = int(cell.traffic["seq_len"])
        self.tokens_per_step = self.batch * self.seq_len
        self.step_times = []        # the window's steps, host clock
        self.saves = []             # dicts: step, version, t_call, t_return,
        #                             t_durable
        self.update_seconds = 0.0   # inside update_and_write in the window
        self.tier_writes = []       # dicts: slot, version, seconds, dir
        self.backlogged = 0         # window saves behind an earlier write
        self.resumes = []           # dicts: t_call, t_loss, read_s, tier
        self.counters = {}          # program counters over the window
        self.reduced = None         # trace reduction (traced runs)
        self.device_kind = None
        self.checks = {}            # name -> (value, limit)
        self.attempted = 0
        self.failed = 0
        self.probes = None
        self.opt_total_steps = None

    @property
    def window_s(self) -> float:
        return self.window.t1 - self.window.t0

    @property
    def setup_s(self) -> float:
        return self.window.t0 - self.t_start

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks[name] = (float(value), float(limit))

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(
            v <= lim for v, lim in self.checks.values())


def tier_chain(cell: Cell) -> list:
    return cell.config["craft_env"].get(
        "CRAFT_TIER_CHAIN", "node,pfs").split(",")


def version_dir(workdir: Path, slot: str, version: int) -> Optional[Path]:
    """The directory of ``version`` on tier ``slot`` under the run's
    checkpoint roots (the tiers name a version ``v-<K>``; a partner's
    mirror lies under ``mirror-of-*`` and is not this node's copy)."""
    root = workdir / slot
    hits = [p for p in sorted(root.rglob(f"v-{version}"))
            if p.is_dir() and not any(
                part.startswith("mirror-of-") for part in p.parts)]
    return hits[0] if len(hits) == 1 else None


def craft_env(cell: Cell, workdir: Path):
    from repro.core.env import CraftEnv

    env = dict(cell.config["craft_env"])
    env["CRAFT_CP_PATH"] = str(workdir / "pfs")
    env["CRAFT_NODE_CP_PATH"] = str(workdir / "node")
    return CraftEnv.capture(env)


def register(cell: Cell) -> str:
    """Register the configuration's model with the program's registry;
    returns the id ``train.run`` resolves."""
    from repro.configs import get_config, register_config

    cfg = get_config(cell.config["base_arch"]).replace(
        **cell.config["model"])
    register_config(cell.config["name"], cfg)
    return cell.config["name"]


def train_config(run: Run, arch: str, steps: int, cp_freq: int):
    from repro.launch import train

    # train.run sizes its learning-rate schedule as max(steps, 10)
    run.opt_total_steps = max(steps, 10)
    return train.TrainConfig(
        arch=arch, tiny=False, steps=steps, global_batch=run.batch,
        seq_len=run.seq_len, cp_freq=cp_freq, cp_name="bench",
        seed=run.prog_seed, lr=run.opt["lr"])


def _counters(run: Run) -> dict:
    """Program counters read at the window's bounds: the staging layer's
    device-to-host bytes (``CRAFT_METRICS``, traced runs) and the codec's
    bytes written to every tier."""
    from repro.core import metrics as craft_metrics

    counters = craft_metrics.snapshot()["counters"]
    out = {"snapshot_d2h_bytes": sum(
        v for k, v in counters.items()
        if k.split("|")[0] == "snapshot_d2h_bytes")}
    if run.probes.cp is not None:
        out["tier_bytes_written"] = run.probes.cp.stats["tier_bytes_written"]
    return out


def first_gradient(run: Run, state, rec: dict) -> None:
    """The first step's clipped gradient as the optimizer got it, read back
    from Adam's first moment after one step (m = (1 - beta1) g): its
    per-leaf norms and which rows of the input embedding it left zero."""
    from bench import ref_training as rt

    m = state["opt"]["m"]
    rec["grad_norms"] = np.asarray(rt.leaf_norms(m), np.float64) / (
        1.0 - run.opt["beta1"])
    rec["embed_zero_rows"] = np.asarray(rt.zero_embed_rows(m))


# --------------------------------------------------------- train and save
def run_train_save(run: Run, arch: str) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import readback
    from bench import ref_training as rt
    from repro.launch import train

    K = int(run.cell.params["save_every"])
    s0 = int(run.cell.traffic["setup_steps"])
    if not 3 <= s0 < K:
        raise ValueError(f"setup_steps {s0} must be in [3, save_every={K})")
    tc = train_config(run, arch, 10 ** 9, K)
    probes, window = run.probes, run.window
    rec = {"losses": [], "fps": {}}

    def on_start(step, state):
        rec["treedef"] = jax.tree_util.tree_structure(state)
        rec["init_params"] = jax.tree_util.tree_map(jnp.copy,
                                                    state["params"])

    def on_step(step, metrics):
        st = probes.state_box.value
        if step <= 3:
            rec["losses"].append(float(metrics["loss"]))
        if step == 1:
            first_gradient(run, st, rec)
            readback.fingerprint(st).block_until_ready()
        if step == 3:
            rec["change_norms"] = np.asarray(rt.diff_norms(
                st["params"], rec.pop("init_params")), np.float64)
        if step == s0:
            # warm-up save, made durable: loads the snapshot programs that
            # donate the previous staging buffer and fills the second host
            # mirror, so the window's first save finds everything a later
            # one finds; the loop's own call at this step then skips
            probes.cp.update_and_write(step)
            probes.cp.wait()
            os.sync()       # no writeback of set-up's files in the window
            rec["counters0"] = _counters(run)
            window.start()
        elif window.running and step % K == 0:
            rec["fps"][step] = readback.fingerprint(st)
        if window.t1 is not None and window.stop_step is None:
            window.stop_step = step
            tc.steps = step           # the loop ends after this step

    def after_update(span):
        if (window.running and span["wrote"]
                and span["t1"] - window.t0 >= run.seconds):
            window.end(span["t1"])

    probes.after_update = after_update
    out = train.run(tc, env=craft_env(run.cell, run.workdir),
                    on_start=on_start, on_step=on_step)
    probes.after_update = None
    # no save follows the window, so the counters after the last save
    # became durable cover exactly the window's saves
    run.counters = {k: v - rec["counters0"][k]
                    for k, v in _counters(run).items()}

    w0, w1 = window.t0, window.t1
    updates = probes.select("update_and_write", w0, w1)
    run.update_seconds = sum(s["t1"] - s["t0"] for s in updates)
    last_step = max(s["step"] for s in updates if s["wrote"])
    run.step_times = list(out["step_times"][s0:last_step])
    landed = probes.events("tier_write")
    chain = tier_chain(run.cell)
    for s in updates:
        if s["wrote"]:
            at = {t["slot"]: t["t_host"] for t in landed
                  if t["version"] == s["version"]}
            run.saves.append({
                "step": s["step"], "version": s["version"],
                "t_call": s["t0"], "t_return": s["t1"],
                "t_durable": (max(at.values()) if set(chain) <= set(at)
                              else None)})
    versions = {s["version"] for s in run.saves}
    run.tier_writes = [{"slot": t["slot"], "version": t["version"],
                        "seconds": t["seconds"],
                        "dir": version_dir(run.workdir, t["slot"],
                                           t["version"])}
                       for t in landed if t["version"] in versions]
    # saves that found the writer still busy with an earlier version
    run.backlogged = sum(1 for d in probes.events("decision")
                         if d["write"] and d["pending"] > 0
                         and d["it"] > s0)
    run.leaf_nbytes = [int(x.size) * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(out["state"])]
    del out
    probes.uninstall()
    gc.collect()
    return rec


def check_train_save(run: Run, rec: dict) -> None:
    from bench import readback

    chain = tier_chain(run.cell)
    keep = int(run.cell.config["craft_env"].get("CRAFT_KEEP_VERSIONS", "2"))
    retained = sorted(s["version"] for s in run.saves)[-keep:]
    mismatched = 0
    for save in run.saves:
        run.attempted += 1
        bad = save["t_durable"] is None
        if save["version"] in retained:
            want = np.asarray(rec["fps"][save["step"]])
            for slot in chain:
                hits = [t for t in run.tier_writes
                        if t["version"] == save["version"]
                        and t["slot"] == slot]
                if not hits:
                    bad = True
                    continue
                try:
                    if hits[0]["dir"] is None:
                        raise OSError(f"no directory of version "
                                      f"{save['version']} on {slot}")
                    tree = readback.read_state_tree(
                        hits[0]["dir"], "state", rec["treedef"])
                    got = np.asarray(readback.fingerprint(tree))
                    del tree
                except (OSError, ValueError) as exc:
                    print(f"readback v{save['version']} {slot}: {exc}",
                          file=sys.stderr)
                    bad = True
                    continue
                diff = int((got != want).any(axis=1).sum())
                mismatched += diff
                bad |= diff > 0
        run.failed += int(bad)
    run.check("readback_leaves_differing", mismatched,
              run.cell.params["limits"]["readback_leaves_differing"])
    _check_training(run, [[x] for x in rec["losses"]], rec)


def _check_training(run: Run, losses, rec: dict) -> None:
    """Compare the first steps with the reference: ``losses[i]`` holds the
    program's readings of step i + 1 (several where resumes repeat it);
    ``rec`` holds the first gradient and the change after the last step."""
    from bench import ref_training as rt

    data = {"seq_len": run.seq_len, "global_batch": run.batch,
            "zipf_a": run.cell.traffic["zipf_a"]}
    opt = dict(run.opt, total_steps=run.opt_total_steps)
    r = rt.train_readings(run.reference, run.prog_seed, run.model, opt, data,
                          len(losses), block_rows=int(run.cell.params.get(
                              "reference_block_rows", 1)))
    limits = run.cell.params["limits"]
    for name, value in training_numbers(
            {"losses": losses, **rec}, r).items():
        if name in limits:      # a number that cannot fail is not compared
            run.check(name, value, limits[name])


def training_numbers(got: dict, want: dict) -> dict:
    """The numbers ``correct`` compares for the first training steps."""
    from bench import ref_training as rt

    losses = [x if isinstance(x, list) else [x] for x in got["losses"]]
    return {
        "loss_gap": max(rt.loss_gap(g, [w] * len(g))
                        for g, w in zip(losses, want["losses"])),
        "grad_gap": rt.worst_leaf_gap(got["grad_norms"],
                                      want["grad_norms"]),
        "embed_rows_differing": int(np.sum(
            got["embed_zero_rows"] != want["embed_zero_rows"])),
        "change_gap": rt.worst_leaf_gap(
            got["change_norms"], want["change_norms"],
            rt.moving_leaves(want["grad_norms"])),
    }


# ------------------------------------------------------------------ resume
def run_resume(run: Run, arch: str) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import readback
    from bench import ref_training as rt
    from repro.launch import train

    first = int(run.cell.traffic["setup_steps"])
    env = craft_env(run.cell, run.workdir)
    probes, window = run.probes, run.window
    rec = {"losses": []}

    def on_start(step, state):
        rec["init_params"] = jax.tree_util.tree_map(jnp.copy,
                                                    state["params"])

    def on_step(step, metrics):
        rec["losses"].append(float(metrics["loss"]))
        if step == 1:
            first_gradient(run, probes.state_box.value, rec)

    out = train.run(train_config(run, arch, first, first), env=env,
                    on_start=on_start, on_step=on_step)
    if out["stats"]["writes"] != 1:
        raise RuntimeError(f"set-up wrote {out['stats']['writes']} "
                           f"versions, expected 1")
    rec["saved_fp"] = np.asarray(readback.fingerprint(out["state"]))
    del out
    tc = train_config(run, arch, first + 1, first)

    def resume_once() -> dict:
        got = {}

        def start(step, state):
            got["start_step"] = step
            got["fp"] = readback.fingerprint(state)

        def stepped(step, metrics):
            got["t_loss"] = time.perf_counter()
            got["loss"] = float(metrics["loss"])

        got["t_call"] = time.perf_counter()
        if run.trace and window.running:
            import jax.profiler

            with jax.profiler.TraceAnnotation("bench.train_run"):
                res = train.run(tc, env=env, on_start=start, on_step=stepped)
        else:
            res = train.run(tc, env=env, on_start=start, on_step=stepped)
        got["t_end"] = time.perf_counter()
        got["read_s"] = res["stats"]["read_seconds"]
        got["tier"] = res["stats"]["restore_tier"]
        got["reads"] = res["stats"]["reads"]
        got["state"] = res["state"]
        return got

    warm = resume_once()
    rec["change_norms"] = np.asarray(rt.diff_norms(
        warm.pop("state")["params"], rec.pop("init_params")), np.float64)
    rec["resumes"] = [warm]
    rec["counters0"] = _counters(run)
    window.start()
    while True:
        r = resume_once()
        del r["state"]
        run.resumes.append(r)
        if r["t_end"] - window.t0 >= run.seconds:
            break
    window.end(run.resumes[-1]["t_end"])
    run.counters = {k: v - rec["counters0"][k]
                    for k, v in _counters(run).items()}
    run.restore_versions = [e["version"] for e in probes.events("restore")
                            if window.t0 <= e["t_host"] < window.t1]
    probes.uninstall()
    gc.collect()
    return rec


def check_resume(run: Run, rec: dict) -> None:
    differing = 0
    for i, r in enumerate(rec["resumes"] + run.resumes):
        in_window = i > 0
        got = np.asarray(r["fp"])
        diff = int((got != rec["saved_fp"]).any(axis=1).sum())
        wrong = (r["start_step"] != int(run.cell.traffic["setup_steps"])
                 or r["tier"] != "node" or r["reads"] != 1)
        if wrong:
            print(f"resume {i}: start_step={r['start_step']} tier="
                  f"{r['tier']} reads={r['reads']}", file=sys.stderr)
        differing += diff + int(wrong)
        if in_window:
            run.attempted += 1
            run.failed += int(diff > 0 or wrong)
    if any(v != 1 for v in run.restore_versions):
        differing += 1
        print(f"restored versions {run.restore_versions}, expected 1",
              file=sys.stderr)
    run.check("restore_leaves_differing", differing,
              run.cell.params["limits"]["restore_leaves_differing"])
    _check_training(
        run, [[x] for x in rec["losses"]]
        + [[r["loss"] for r in rec["resumes"] + run.resumes]], rec)


MODES = {"train_save": (run_train_save, check_train_save),
         "resume": (run_resume, check_resume)}


# ------------------------------------------------------------- one cell
def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    if require_tpu and (info["platform"] != "tpu" or len(devices) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {info['platform']} device(s)")
    return info


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def window_summary(run: Run) -> str:
    """One line on where the window's wall time went (host clock)."""
    steps = sum(run.step_times)
    parts = [f"window {run.window_s:.4f} s", f"setup {run.setup_s:.4f} s",
             f"{len(run.step_times)} steps {steps:.4f} s"]
    if run.step_times:
        parts.append(f"slowest step {max(run.step_times):.4f} s")
    if run.saves:
        parts.append(f"{len(run.saves)} saves in update_and_write "
                     f"{run.update_seconds:.4f} s, "
                     f"{run.backlogged} behind an earlier write")
    if run.resumes:
        parts.append(f"{len(run.resumes)} resumes")
    return "window: " + "; ".join(parts)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             workdir: Path, t_start: float, require_tpu: bool = True
             ) -> dict:
    """Run one cell; returns the result object (the last output line)."""
    import jax

    device = device_info(cell.chips, require_tpu)
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)
    print(f"disk free: {shutil.disk_usage(workdir).free} bytes "
          f"under {workdir}", flush=True)
    from bench.probes import Probes
    from repro.core import metrics as craft_metrics

    run = Run(cell, seed, seconds, trace, workdir, t_start)
    run.device_kind = device["kind"]
    arch = register(cell)
    if trace:
        craft_metrics.install()
    mode, check = MODES[cell.traffic["mode"]]
    run.probes = Probes(workdir / "craft_trace.jsonl",
                        annotate=trace).install()
    try:
        rec = mode(run, arch)
    finally:
        run.probes.uninstall()
        if trace:
            craft_metrics.uninstall()
    device["memory_peak_bytes"] = memory_peak(jax.devices()[:cell.chips])
    print(window_summary(run), file=sys.stderr, flush=True)
    if trace:
        from bench import tracefile

        events = tracefile.load_events(
            tracefile.find_xplane(run.window.trace_dir))
        run.reduced = tracefile.reduce_events(events)
        device["busy_s"] = run.reduced["busy_s"]
        device["window_s"] = run.reduced["window_s"]
    check(run, rec)
    metrics_spec = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in metrics_spec:
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {
            "device_ops": run.reduced["device_ops"],
            "idle_gaps": run.reduced["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in run.checks.items()}
    return result


def process_start() -> float:
    """``time.perf_counter()`` at the start of this process (Linux
    ``/proc``), so set-up counts interpreter start-up too."""
    import os

    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(0.0, age)
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


def main(argv=None) -> int:
    import argparse

    t_start = process_start()
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
    except (KeyError, OSError, ValueError) as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    import jax

    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    # every program goes to the persistent cache, however fast it compiled,
    # so that a run after the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          WORKDIR, t_start)
    except NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    finally:
        shutil.rmtree(WORKDIR, ignore_errors=True)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
