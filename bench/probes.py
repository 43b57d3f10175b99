"""What the benchmark sees of CRAFT while the training loop runs.

Two sources, both public:

* Spans around the public ``Checkpoint`` calls the loop makes.
  ``install()`` wraps ``add``, ``update_and_write`` and
  ``restart_if_needed`` in place, timing each call on the host clock (and,
  in a traced run, naming it in the profiler's trace); ``uninstall()`` puts
  the originals back.  The wrapped calls still do all of their work;
  nothing stands in for them.
* The program's own event trace (``core/trace.py``, the recorder behind
  ``CRAFT_TRACE``), armed for the run: ``tier_write`` (a version landed on
  a tier: when, and its seconds), ``restore`` (a version read back) and
  ``decision`` (the step loop's checkpoint decision, with the writer's
  backlog).  Its timestamps are put on the host clock of the spans.
"""
from __future__ import annotations

import functools
import json
import threading
import time
from pathlib import Path
from typing import Callable, Optional

import jax

from repro.core import trace as craft_trace
from repro.core.checkpoint import Checkpoint

CLOCK_EVENT = "bench_clock"


class Probes:
    def __init__(self, trace_path: Path, annotate: bool = False):
        self.trace_path = Path(trace_path)
        self.annotate = annotate
        self.spans = []                 # dicts: name, t0, t1, + fields
        self.cp: Optional[Checkpoint] = None
        self.state_box = None
        self.after_update: Optional[Callable[[dict], None]] = None
        self._lock = threading.Lock()
        self._saved = {}

    # -------------------------------------------------------------- spans
    def _span(self, name: str, fn, fields: dict):
        t0 = time.perf_counter()
        if self.annotate:
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                out = fn()
        else:
            out = fn()
        rec = {"name": name, "t0": t0, "t1": time.perf_counter(), **fields}
        with self._lock:
            self.spans.append(rec)
        return out, rec

    def select(self, name: str, t0: float = float("-inf"),
               t1: float = float("inf")) -> list:
        with self._lock:
            return [s for s in self.spans
                    if s["name"] == name and s["t0"] >= t0 and s["t0"] < t1]

    # ------------------------------------------------- the program's trace
    def events(self, kind: str) -> list:
        """The program's trace events of ``kind``, each with ``t_host``:
        its time on the host clock of the spans."""
        lines = [json.loads(x) for x in
                 self.trace_path.read_text().splitlines() if x.strip()]
        marks = [e for e in lines if e["kind"] == CLOCK_EVENT]
        if not marks:
            raise RuntimeError(f"no {CLOCK_EVENT} event in {self.trace_path}")
        offset = marks[0]["host"] - marks[0]["t"]
        return [dict(e, t_host=e["t"] + offset)
                for e in lines if e["kind"] == kind]

    # ------------------------------------------------------------ install
    def install(self) -> "Probes":
        probes = self
        self.trace_path.parent.mkdir(parents=True, exist_ok=True)
        craft_trace.install(str(self.trace_path))
        # the recorder's clock is the spans' (CLOCK_MONOTONIC) from another
        # origin: one event stamped with both places every event
        craft_trace.emit(CLOCK_EVENT, host=time.perf_counter())
        orig = {name: getattr(Checkpoint, name) for name in (
            "add", "update_and_write", "restart_if_needed")}
        self._saved = orig

        @functools.wraps(orig["add"])
        def add(cp, key, obj, **kw):
            probes.cp = cp
            if key == "state":
                probes.state_box = obj
            return probes._span("cp_add", lambda: orig["add"](
                cp, key, obj, **kw), {"key": key})[0]

        @functools.wraps(orig["update_and_write"])
        def update_and_write(cp, iteration=None, cp_freq=1):
            wrote, rec = probes._span(
                "update_and_write",
                lambda: orig["update_and_write"](cp, iteration, cp_freq),
                {"step": iteration})
            rec["wrote"] = bool(wrote)
            rec["version"] = cp.version if wrote else None
            if probes.after_update is not None:
                probes.after_update(rec)
            return wrote

        @functools.wraps(orig["restart_if_needed"])
        def restart_if_needed(cp, *args, **kw):
            return probes._span(
                "restart", lambda: orig["restart_if_needed"](cp, *args, **kw),
                {})[0]

        Checkpoint.add = add
        Checkpoint.update_and_write = update_and_write
        Checkpoint.restart_if_needed = restart_if_needed
        return self

    def uninstall(self) -> None:
        for name, fn in self._saved.items():
            setattr(Checkpoint, name, fn)
        if self._saved:
            craft_trace.uninstall()
        self._saved = {}
        self.cp = None
        self.state_box = None

    def __enter__(self) -> "Probes":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()
