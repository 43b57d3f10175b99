"""From a profiler trace to device busy time, kernel time and idle gaps.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
a neutral form; ``reduce_events`` does the arithmetic on that form, so the
reduction can be checked on a small recorded trace without a chip.

Neutral form: ``{"device": [[name, start_ns, dur_ns], ...],
"modules": [...], "host": [...]}`` where ``device`` holds the op events of
the first TPU (the "XLA Ops" line), ``modules`` its program runs (the "XLA
Modules" line: one event per run of a compiled program, named after the
jitted function) and ``host`` the spans the benchmark names (``bench.*``),
all on the same clock.
"""
from __future__ import annotations

import glob
import gzip
import json
import re
from pathlib import Path

DEVICE_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
# host spans of the step loop's main thread, innermost first when nested
MAIN_SPANS = ("bench.restart", "bench.cp_add", "bench.update_and_write",
              "bench.train_run")


def find_xplane(log_dir: Path) -> Path:
    hits = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(hits[-1])


def load_events(path: Path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    device, modules, host = [], [], []
    device_plane = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and device_plane is None:
            device_plane = plane.name
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    device += [[e.name, e.start_ns, e.duration_ns]
                               for e in line.events]
                elif line.name == MODULE_LINE:
                    modules += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
    return {"device_plane": device_plane, "device": device,
            "modules": modules, "host": host}


def save_events(events: dict, path: Path) -> None:
    with gzip.open(path, "wt") as fh:
        json.dump(events, fh)


def read_saved(path: Path) -> dict:
    with gzip.open(path, "rt") as fh:
        return json.load(fh)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(gap, spans) -> str:
    """The innermost main-thread span that covers at least half of the
    gap, else the one that covers most of it."""
    s, e = gap
    cover = {}
    for name, t0, t1 in spans:
        ov = min(e, t1) - max(s, t0)
        if ov > 0 and name in MAIN_SPANS:
            cover[name] = max(cover.get(name, 0), ov)
    if not cover:
        return "train loop (no span)"
    for name in MAIN_SPANS:
        if cover.get(name, 0) >= 0.5 * (e - s):
            return name
    return max(cover, key=cover.get)


def short_name(op: str) -> str:
    """``fusion.533`` of ``%fusion.533 = bf16[...] fusion(...)``."""
    head = op.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _self_times(events):
    """Device time of each op net of the ops nested inside it (a ``while``
    spans the ops of its body on the same line)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    self_t = [b - a for a, b, _ in events]
    stack = []
    for i in order:
        a, b, _ = events[i]
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            parent = stack[-1]
            self_t[parent] -= min(b, events[parent][1]) - a
        stack.append(i)
    return self_t


def reduce_events(events: dict, window_name: str = "bench.window",
                  top: int = 10) -> dict:
    """Busy time (union of device op intervals) and idle gaps inside the
    window span, the device self time of each op, and the longest idle
    gaps with the host span each fell in."""
    wins = [(s, s + d) for n, s, d in events["host"] if n == window_name]
    if not wins:
        raise ValueError(f"no {window_name!r} span in the trace")
    w0, w1 = wins[0]
    ops = []
    for name, s, d in events["device"]:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            ops.append((a, b, name))
    per_op = {}
    for (_, _, name), t in zip(ops, _self_times(ops)):
        per_op[name] = per_op.get(name, 0) + t
    per_short = {}
    for name, t in per_op.items():
        per_short[short_name(name)] = per_short.get(short_name(name), 0) + t
    busy = _union([(a, b) for a, b, _ in ops])
    busy_ns = sum(e - s for s, e in busy)
    gaps, prev = [], w0
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if w1 > prev:
        gaps.append((prev, w1))
    runs = {}
    for name, s, d in events.get("modules", []):
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            runs.setdefault(name, []).append((b - a) / 1e9)
    spans = [(n, s, s + d) for n, s, d in events["host"]]
    labelled = sorted(((e - s, _label((s, e), spans)) for s, e in gaps),
                      reverse=True)
    idle_by_label = {}
    for dur, label in labelled:
        idle_by_label[label] = idle_by_label.get(label, 0) + dur
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "op_seconds": {k: v / 1e9 for k, v in per_op.items()},
        "module_runs": runs,
        "device_ops": [[k, v / 1e9] for k, v in sorted(
            per_short.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label, dur / 1e9] for dur, label in labelled[:top]],
        "idle_by_host_span": {k: v / 1e9 for k, v in idle_by_label.items()},
    }


def module_runs_matching(reduced: dict, pattern: str) -> list:
    """Device seconds of every run, inside the window, of a compiled
    program whose name matches ``pattern`` (searched from the start)."""
    rx = re.compile(pattern)
    return [t for k, v in reduced["module_runs"].items() if rx.match(k)
            for t in v]
