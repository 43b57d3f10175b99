"""Where a traced run's idle device time went, by the program's own spans.

    python3 bench/span_report.py --workload <cell> --seed <n> --seconds <s>
        [--out report.json]

Runs one cell as ``bench/run.py --trace 1`` does, with the program's spans
also named in the profile (``core/trace.py`` ``annotate``: each span is a
``craft.<name>`` event on the host plane, on the device ops' clock).  The
result line goes to stdout as ``bench/run.py`` prints it; to stderr go the
window's device idle seconds by the innermost ``craft.*`` span of the step
loop's thread (within each ``bench.*`` span), the spans of other threads
that overlap the longest idle gaps, and the checks that the save and
restore spans cover what the benchmark times from outside.  ``--out``
writes the whole report as JSON.
"""
from __future__ import annotations

import argparse
import functools
import json
import shutil
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import harness, spans, tracefile  # noqa: E402

NONE = "(no span)"
# spans whose idle time is split by the craft.* spans inside them
OUTER = ("bench.update_and_write", "bench.train_run", "craft.cp.update",
         "craft.cp.restore")


def load_program_events(path: Path) -> dict:
    """The profile's device ops and its ``bench.*`` and ``craft.*`` host
    events, each host event with the name of its thread's line:
    ``{"device": [[name, start_ns, dur_ns]], "program": [[name, start_ns,
    dur_ns, line]]}``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    device, program = [], []
    device_plane = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and device_plane is None:
            device_plane = plane.name
            for line in plane.lines:
                if line.name == tracefile.DEVICE_LINE:
                    device += [[e.name, e.start_ns, e.duration_ns]
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                # Python threads' lines may share one name
                label = f"{plane.name}/{i}/{line.name}"
                program += [[e.name, e.start_ns, e.duration_ns, label]
                            for e in line.events
                            if e.name.startswith(("craft.", "bench."))]
    return {"device": device, "program": program}


def main_line(events: dict) -> str:
    """The step loop's thread: the line that holds ``bench.window``."""
    for name, _, _, line in events["program"]:
        if name == "bench.window":
            return line
    raise ValueError("no bench.window event in the profile")


def segments(intervals: list) -> list:
    """Properly nested ``[(name, start, end)]`` of one thread as a
    partition of their span: ``[(start, end, innermost name)]``."""
    bounds = sorted({t for _, s, e in intervals for t in (s, e)})
    order = sorted(intervals, key=lambda x: (x[1], -x[2]))
    out, stack, j = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while stack and stack[-1][2] <= a:
            stack.pop()
        while j < len(order) and order[j][1] <= a:
            if order[j][2] > a:
                stack.append(order[j])
            j += 1
        while stack and stack[-1][2] <= a:
            stack.pop()
        out.append((a, b, stack[-1][0] if stack else NONE))
    return out


def _gaps(events: dict, w0: int, w1: int) -> list:
    busy = spans.busy_intervals(
        {"device": [e for e in events["device"]
                    if e[1] < w1 and e[1] + e[2] > w0]})
    gaps, prev = [], w0
    for s, e in busy:
        if s > prev:
            gaps.append((prev, min(s, w1)))
        prev = max(prev, e)
    if w1 > prev:
        gaps.append((prev, w1))
    return gaps


def _split(pieces: list, segs: list) -> list:
    """Cut each sorted, disjoint ``(start, end, *labels)`` piece at the
    bounds of the sorted, disjoint ``segs``; each part gets one more
    label: its segment's, or NONE outside every segment."""
    out, j = [], 0
    for a, b, *labels in pieces:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        t, k = a, j
        while t < b:
            while k < len(segs) and segs[k][1] <= t:
                k += 1
            if k < len(segs) and segs[k][0] <= t:
                e, label = min(b, segs[k][1]), segs[k][2]
            else:
                e = min(b, segs[k][0]) if k < len(segs) else b
                label = NONE
            out.append((t, e, *labels, label))
            t = e
    return out


def attribute(events: dict, top: int = 5) -> dict:
    """The window's device idle seconds by innermost ``bench.*`` span and
    innermost ``craft.*`` span of the step loop's thread, and the other
    threads' ``craft.*`` spans over the ``top`` longest gaps."""
    main = main_line(events)
    w0, w1 = [(s, s + d) for n, s, d, _ in events["program"]
              if n == "bench.window"][0]
    gaps = _gaps(events, w0, w1)
    mine = [(n, s, s + d) for n, s, d, line in events["program"]
            if line == main and n != "bench.window"]
    craft = segments([x for x in mine if x[0].startswith("craft.")])
    parts = _split(
        _split(gaps, segments([x for x in mine
                               if x[0].startswith("bench.")])), craft)
    idle = {}
    for a, b, outer, inner in parts:
        by_inner = idle.setdefault(outer, {})
        by_inner[inner] = by_inner.get(inner, 0.0) + (b - a) / 1e9
    others = [(n, s, s + d, line) for n, s, d, line in events["program"]
              if line != main and n.startswith("craft.")]
    longest = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        over = sorted(((min(e, b) - max(s, a)) / 1e9, n, line)
                      for n, a, b, line in others if a < e and b > s)
        longest.append({"seconds": (e - s) / 1e9,
                        "at_s": (s - w0) / 1e9,
                        "other_threads": [[n, line, ov] for ov, n, line
                                          in reversed(over)][:10]})
    # inside one span with whatever runs within it, by innermost craft.*
    inside = {}
    for outer in OUTER:
        by_inner = inside.setdefault(outer, {})
        for a, b, label, inner in _split(
                _split(gaps, segments([x for x in mine if x[0] == outer])),
                craft):
            if label == outer:
                by_inner[inner] = by_inner.get(inner, 0.0) + (b - a) / 1e9
    return {"window_s": (w1 - w0) / 1e9,
            "idle_s": sum(e - s for s, e in gaps) / 1e9,
            "idle_by_span": idle, "idle_inside": inside,
            "longest_gaps": longest}


def named_share(inside: dict, unnamed: tuple) -> float | None:
    """Share of idle seconds (by innermost ``craft.*`` span) whose span is
    named and not one of ``unnamed``."""
    total = sum(inside.values())
    if total <= 0:
        return None
    named = sum(v for k, v in inside.items()
                if k != NONE and k not in unnamed)
    return named / total


def coverage(events: dict) -> dict:
    """``craft.cp.update`` against the ``bench.update_and_write`` calls
    around it, the share of it inside ``snapshot.leaf`` spans, and
    ``craft.cp.restore`` per resume."""
    prog = events["program"]
    updates = [(s, s + d) for n, s, d, _ in prog if n == "craft.cp.update"]
    calls = [(s, s + d) for n, s, d, _ in prog
             if n == "bench.update_and_write"]
    around = [next((b - a for a, b in calls if a <= s and e <= b), None)
              for s, e in updates]
    leaves = [(s, s + d) for n, s, d, _ in prog if n == "craft.snapshot.leaf"]
    out = {}
    if updates and all(x is not None for x in around):
        out["cp_update_s"] = sum(e - s for s, e in updates) / 1e9
        out["update_and_write_s"] = sum(around) / 1e9
    if updates:
        inside = sum(min(e, b) - max(s, a) for s, e in updates
                     for a, b in leaves if a < e and b > s)
        out["leaves_cover_of_cp_update"] = inside / sum(
            e - s for s, e in updates)
    restores = [d for n, _, d, _ in prog if n == "craft.cp.restore"]
    resumes = [n for n, _, _, _ in prog if n == "bench.train_run"]
    if restores and resumes:
        out["cp_restore_s_per_resume"] = sum(restores) / len(resumes) / 1e9
    return out


def report(events: dict, result: dict) -> dict:
    att = attribute(events)
    out = {"attribution": att, "coverage": coverage(events),
           "metrics": result.get("metrics", {})}
    out["named_share"] = {
        "bench.update_and_write": named_share(
            att["idle_inside"]["bench.update_and_write"],
            ("craft.cp.update",)),
        "bench.train_run": named_share(
            att["idle_inside"]["bench.train_run"], ("craft.cp.restore",)),
    }
    return out


def print_report(rep: dict) -> None:
    att = rep["attribution"]
    err = sys.stderr
    print(f"window {att['window_s']:.4f} s, device idle "
          f"{att['idle_s']:.4f} s", file=err)
    for outer, inner in sorted(att["idle_by_span"].items()):
        for name, sec in sorted(inner.items(), key=lambda kv: -kv[1]):
            print(f"idle in {outer} / {name}: {sec:.4f} s", file=err)
    for outer, inner in att["idle_inside"].items():
        print(f"idle inside {outer}: {sum(inner.values()):.4f} s", file=err)
    for g in att["longest_gaps"]:
        print(f"gap {g['seconds']:.4f} s at {g['at_s']:.3f} s; other "
              f"threads: {g['other_threads'][:6]}", file=err)
    for k, v in rep["coverage"].items():
        print(f"coverage {k}: {v}", file=err)
    for k, v in rep["named_share"].items():
        print(f"named share of idle in {k}: {v}", file=err)


def main(argv=None) -> int:
    t_start = harness.process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    import jax

    from repro.core import trace
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    install = trace.install
    trace.install = functools.partial(install, annotate=True)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds, True,
                                  harness.WORKDIR, t_start)
        events = load_program_events(
            tracefile.find_xplane(harness.WORKDIR / "trace"))
    finally:
        trace.install = install
    rep = report(events, result)
    shutil.rmtree(harness.WORKDIR, ignore_errors=True)
    print_report(rep)
    if args.out is not None:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(rep, indent=1))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
