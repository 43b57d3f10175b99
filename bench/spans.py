"""The program's own spans (``core/trace.py``), as the metric readers see
them.

The recorder the benchmark arms for every run keeps its spans in memory
and writes them to the run's trace file (``{"kind": "span", ...}`` lines)
when it is closed, before any metric is read.  A span's ``t0_ns`` and
``t1_ns`` are ``time.perf_counter_ns()``, the clock of the benchmark's own
spans.  A program that records no spans leaves no such line, and every
function here then finds nothing: the readers return None.

Save spans are picked by the version they carry (a version's tier writes
end after the window when it is the last save), restore and train spans by
their start inside the window.  To set a span against the device's ops,
it is put on the profiler's clock through the benchmark's spans that are in
both places: each ``bench.*`` annotation of the profile and the host-clock
start of the same span.
"""
from __future__ import annotations

import bisect
import json
from pathlib import Path

from bench import tracefile

_CACHE: dict = {}


def load(run) -> list:
    """Every span the program recorded in this run (dicts, seconds)."""
    probes = getattr(run, "probes", None)
    path = Path(getattr(probes, "trace_path", "") or "")
    if not path.is_file():
        return []
    key = ("spans", str(path), path.stat().st_size)
    if key not in _CACHE:
        out = []
        for line in path.read_text().splitlines():
            if '"span"' not in line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("kind") == "span":
                rec["t0"] = rec["t0_ns"] / 1e9
                rec["t1"] = rec["t1_ns"] / 1e9
                out.append(rec)
        _CACHE[key] = out
    return _CACHE[key]


def save_spans(run, *names) -> list:
    """Spans named one of ``names`` that belong to a save of the window."""
    versions = {s["version"] for s in run.saves}
    return [s for s in load(run)
            if s["name"] in names and s.get("version") in versions]


def window_spans(run, *names) -> list:
    """Spans named one of ``names`` that start inside the window."""
    w0, w1 = run.window.t0, run.window.t1
    return [s for s in load(run)
            if s["name"] in names and w0 <= s["t0"] < w1]


def seconds(spans) -> float:
    return sum(s["t1"] - s["t0"] for s in spans)


def self_seconds(run, spans) -> float:
    """Summed duration of ``spans`` less that of their direct children."""
    ids = {s["id"] for s in spans}
    child = sum(s["t1"] - s["t0"] for s in load(run)
                if s.get("parent") in ids)
    return seconds(spans) - child


def union_seconds(spans) -> float:
    total, end = 0.0, float("-inf")
    for s in sorted(spans, key=lambda s: s["t0"]):
        if s["t1"] > end:
            total += s["t1"] - max(s["t0"], end)
            end = s["t1"]
    return total


def per_save(run, value: float, found) -> float | None:
    if not found or not run.saves:
        return None
    return value / len(run.saves)


def per_resume(run, value: float, found) -> float | None:
    if not found or not run.resumes:
        return None
    return value / len(run.resumes)


# ------------------------------------------------- against the device's ops
def profile(run) -> dict | None:
    """The traced window's profile in ``tracefile``'s neutral form."""
    trace_dir = getattr(run.window, "trace_dir", None)
    if getattr(run, "reduced", None) is None or trace_dir is None:
        return None
    key = ("profile", str(trace_dir))
    if key not in _CACHE:
        _CACHE[key] = tracefile.load_events(tracefile.find_xplane(trace_dir))
    return _CACHE[key]


def clock_pairs(run, events: dict) -> list:
    """``[(host-clock start s, profile-clock start ns)]`` of the spans the
    benchmark both timed and named in the profile, in the window."""
    host = {}
    for name, start, _ in events["host"]:
        host.setdefault(name, []).append(start)
    w0, w1 = run.window.t0, run.window.t1
    timed = {"bench.window": [w0]}
    for s in run.probes.spans:
        if w0 <= s["t0"] < w1:
            timed.setdefault("bench." + s["name"], []).append(s["t0"])
    timed["bench.train_run"] = [r["t_call"] for r in run.resumes]
    pairs = []
    for name, starts in timed.items():
        marks = sorted(host.get(name, []))
        if marks and len(marks) == len(starts):
            pairs += zip(sorted(starts), marks)
    return sorted(pairs)


def to_profile_clock(pairs: list, t: float) -> float:
    """Host-clock seconds ``t`` as profile-clock ns, by the nearest pair."""
    i = bisect.bisect_left(pairs, (t,))
    near = min(pairs[max(0, i - 1):i + 1], key=lambda p: abs(p[0] - t))
    return near[1] + (t - near[0]) * 1e9


def busy_intervals(events: dict) -> list:
    """Union of the device's op intervals, ns on the profile clock."""
    out = []
    for _, s, d in sorted(events["device"], key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return out


def idle_inside(events: dict, pairs: list, spans) -> float:
    """Seconds in which no op ran on the device inside ``spans``."""
    busy = busy_intervals(events)
    starts = [b[0] for b in busy]
    idle = 0.0
    for s in spans:
        a = to_profile_clock(pairs, s["t0"])
        b = to_profile_clock(pairs, s["t1"])
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(busy) and busy[i][0] < b:
            covered += max(0.0, min(b, busy[i][1]) - max(a, busy[i][0]))
            i += 1
        idle += (b - a) - covered
    return idle / 1e9
