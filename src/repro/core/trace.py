"""Low-overhead JSONL run tracing (``CRAFT_TRACE``) — the *record* third of
the record → replay → tune loop (paper §V measures CR overhead by hand; we
measure it by instrumenting the real code paths).

Every load-bearing event on the CR path emits one JSON line:

=================  =======================================================
kind               fields (beyond ``t``, seconds since trace start)
=================  =======================================================
``config``         snapshot of the scheduling-relevant ``CRAFT_*`` knobs +
                   the checkpoint's payload size (emitted at ``commit()``)
``decision``       the policy verdict for one step: ``it``, ``pending``
                   (writer backpressure seen), ``write``, ``tiers``,
                   ``full``, ``sync``, ``reason``, plus the caller's
                   ``cp_freq``/``next_version`` gate inputs
``scheduled``      ``record_written`` fired for ``version`` (cadence state
                   advanced — on async runs this precedes the tier writes)
``step``           one measured application step (``seconds``)
``tier_write``     a tier write *landed*: ``slot``, ``version``,
                   ``seconds``, ``nbytes`` (logical payload),
                   ``phys_bytes``/``chunks``/``ref_chunks`` (codec IO),
                   ``full`` (self-contained vs delta)
``degraded``       a scheduled write did not land on ``slot`` (fault or
                   open breaker) and was routed down the chain
``breaker``        a circuit breaker tripped: ``slot``
``restore``        a restore completed: ``version``, ``tier`` (label),
                   ``slot``, ``seconds``, ``read_bytes``
``failure``        the collective engine observed one fail-stop
``kill``           a fault injector killed ``rank`` (SimWorld)
``recovery``       an AFT recovery reset live policies (epoch bump)
``retune``         online re-tuning replaced cadences: ``cadence`` map
``span``           one timed region (:func:`span`): ``name``, ``t0_ns``/
                   ``t1_ns`` (``time.perf_counter_ns``), ``id``,
                   ``parent``, ``thread``, plus its fields.  Written on
                   :func:`flush`/close, with ``t`` the time of writing
=================  =======================================================

Spans time the work inside a save or a restore.  ``span(name, **fields)``
is a context manager; an armed recorder keeps each finished span in
memory as a tuple (:func:`spans`) with its parent, the innermost span
open on the same thread.  Work handed to another thread keeps its parent
through :func:`carry`.  ``version`` and ``slot`` pass from a span to its
children, so every span of one save or restore carries the save's
identifier.  With ``annotate`` each span also enters a
``jax.profiler.TraceAnnotation("craft.<name>")``, so it lands on the
profiler's host plane on the device ops' clock.  The recorder also listens
to ``jax.monitoring`` and records ``jit.trace``, ``jit.lower``,
``jit.compile`` and ``jit.cache_load`` spans (their ends are when JAX
reports them).

Overhead contract: when ``CRAFT_TRACE`` is unset the module-level
:data:`TRACER` stays the no-op :class:`_NullTracer` — every hook is a
single dynamic call that immediately returns, no branching, no string
formatting, no clock reads; ``span()`` returns one shared no-op context.
Hooks must therefore pass only cheap, already-computed values.  What the
armed recorder costs on the chip is measured in ``PERF.md`` (tracing cost
of the benchmark's cells).

The recorder is process-global (one trace file interleaves every
checkpoint, scheduler and communicator in the process — a total order of
events is exactly what the replayer needs) and append-only, so a
restarted job extends its predecessor's trace.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Optional

__all__ = [
    "TRACER", "emit", "span", "carry", "spans", "flush", "enabled",
    "install", "uninstall", "env_snapshot",
]

# jax.monitoring duration events recorded as spans
JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jit.cache_load",
}
# fields a span passes on to its children: the request's identifier
_INHERITED = ("version", "slot")


class _NullSpan:
    """The span of a recorder that records none: one shared instance."""

    __slots__ = ()
    id = None

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **fields) -> None:
        return None


_NULL_SPAN = _NullSpan()


class _NullTracer:
    """The ``CRAFT_TRACE``-unset tracer: every emit is a no-op."""

    enabled = False
    path = None

    def emit(self, kind: str, **fields) -> None:  # pragma: no cover - trivial
        return None

    def span(self, name: str, **fields) -> _NullSpan:
        return _NULL_SPAN

    def carry(self, fn):
        return fn

    def spans(self) -> list:
        return []

    def flush(self) -> None:
        return None

    def close(self) -> None:  # pragma: no cover - trivial
        return None


class _Span:
    """One armed span; records itself into its tracer on exit."""

    __slots__ = ("_tr", "name", "fields", "id", "parent", "t0", "_ann")

    def __init__(self, tracer: "JsonlTracer", name: str, fields: dict):
        self._tr = tracer
        self.name = name
        self.fields = fields
        self._ann = None

    def __enter__(self) -> "_Span":
        tr = self._tr
        stack = tr._stack()
        if stack:
            self.parent, ctx = stack[-1]
            for k in _INHERITED:
                if k in ctx and k not in self.fields:
                    self.fields[k] = ctx[k]
        else:
            self.parent = None
        self.id = next(tr._ids)
        stack.append((self.id, self.fields))
        if tr.annotate:
            import jax

            self._ann = jax.profiler.TraceAnnotation(f"craft.{self.name}")
            self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr = self._tr
        tr._stack().pop()
        tr._record((self.name, self.t0, t1, self.id, self.parent,
                    threading.current_thread().name, self.fields))
        return False

    def set(self, **fields) -> None:
        """Add fields known only inside the span (rows fetched, ...)."""
        self.fields.update(fields)


class JsonlTracer:
    """Append-only JSONL writer; thread-safe, line-at-a-time.

    ``t`` is seconds since the tracer was installed on the shared monotonic
    clock, so events from every thread (main loop, async writer, sim ranks)
    land on one comparable timeline.  Spans are held in memory and written
    on :meth:`flush` (and on close, or when ``SPAN_BUFFER`` of them wait).
    """

    enabled = True
    SPAN_BUFFER = 1 << 16

    def __init__(self, path: str, clock=time.monotonic, spans: bool = True,
                 annotate: bool = False):
        self.path = str(path)
        self._clock = clock
        self._t0 = clock()
        self._lock = threading.Lock()
        self._closed = False
        self._local = threading.local()
        self._ids = itertools.count(1)
        self._done: list = []
        self._listening = False
        self.spans_on = False
        self.annotate = False
        parent = os.path.dirname(self.path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        self._fh = open(self.path, "a", buffering=1, encoding="utf-8")
        self.configure(spans, annotate)

    def configure(self, spans: bool, annotate: bool) -> None:
        """Turn spans (and their profiler annotations) on or off."""
        self.spans_on = bool(spans)
        self.annotate = bool(spans and annotate)
        if self.spans_on != self._listening:
            try:
                from jax import monitoring
            except ImportError:     # pragma: no cover - jax is a dependency
                return
            if self.spans_on:
                monitoring.register_event_duration_secs_listener(
                    self._on_duration)
            else:
                monitoring.unregister_event_duration_listener(
                    self._on_duration)
            self._listening = self.spans_on

    def emit(self, kind: str, **fields) -> None:
        rec = {"t": round(self._clock() - self._t0, 6), "kind": kind}
        rec.update(fields)
        line = json.dumps(rec, separators=(",", ":"), default=str)
        with self._lock:
            # Re-check liveness *under the lock*: a concurrent uninstall()
            # (telemetry shutdown hook, test teardown) may have closed the
            # writer between the module-level TRACER read and here — without
            # this a mid-emit close could tear the final line or raise on a
            # closed file.
            if self._closed or self._fh.closed:
                return
            try:
                self._fh.write(line + "\n")
            except ValueError:       # closed out from under us (interp exit)
                self._closed = True

    # ------------------------------------------------------------- spans
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, rec: tuple) -> None:
        self._done.append(rec)
        if len(self._done) >= self.SPAN_BUFFER:
            self.flush()

    def span(self, name: str, **fields):
        if not self.spans_on:
            return _NULL_SPAN
        return _Span(self, name, fields)

    def carry(self, fn):
        """``fn`` run on any thread as a child of the span open here."""
        if not self.spans_on:
            return fn
        stack = self._stack()
        if not stack:
            return fn
        top = stack[-1]

        def carried(*args, **kw):
            inner = self._stack()
            inner.append(top)
            try:
                return fn(*args, **kw)
            finally:
                inner.pop()

        return carried

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        name = JIT_EVENTS.get(event)
        if name is None:
            return
        t1 = time.perf_counter_ns()
        stack = self._stack()
        parent, ctx = stack[-1] if stack else (None, {})
        fields = {k: ctx[k] for k in _INHERITED if k in ctx}
        if "fun_name" in kw:
            fields["fun"] = str(kw["fun_name"])
        self._record((name, t1 - int(duration * 1e9), t1, next(self._ids),
                      parent, threading.current_thread().name, fields))

    def spans(self) -> list:
        """Finished spans not yet flushed, as dicts, oldest end first."""
        return [_span_dict(r) for r in list(self._done)]

    def flush(self) -> None:
        with self._lock:
            done, self._done = self._done, []
            if self._closed or self._fh.closed or not done:
                return
            # ``t`` is when the line is written, so the file's events stay
            # in time order; the span's own times are t0_ns and t1_ns
            t = round(self._clock() - self._t0, 6)
            try:
                for r in done:
                    line = {"t": t, "kind": "span", **_span_dict(r)}
                    self._fh.write(json.dumps(
                        line, separators=(",", ":"), default=str) + "\n")
            except ValueError:       # closed out from under us (interp exit)
                self._closed = True

    def close(self) -> None:
        # Idempotent and thread-safe: emit() holds the same lock, so a close
        # always lands between whole lines, never inside one.
        self.configure(False, False)
        self.flush()
        with self._lock:
            if self._closed:
                return
            self._closed = True
            if not self._fh.closed:
                self._fh.flush()
                self._fh.close()


def _span_dict(rec: tuple) -> dict:
    name, t0, t1, sid, parent, thread, fields = rec
    return {**fields, "name": name, "t0_ns": t0, "t1_ns": t1, "id": sid,
            "parent": parent, "thread": thread}


#: The process-wide tracer.  Hooks call ``trace.TRACER.emit(...)`` (or the
#: module-level :func:`emit` alias); both stay no-ops until :func:`install`.
TRACER = _NullTracer()


def emit(kind: str, **fields) -> None:
    """Module-level emit alias (reads :data:`TRACER` at call time, so hooks
    that imported the function still see a later install)."""
    TRACER.emit(kind, **fields)


def span(name: str, **fields):
    """A context manager timing one region: ``with trace.span("x", k=v):``.
    The no-op recorder returns one shared object and reads no clock."""
    return TRACER.span(name, **fields)


def carry(fn):
    """``fn`` wrapped so that, run on another thread, its spans keep the
    span open at this call as their parent (``fn`` itself when none)."""
    return TRACER.carry(fn)


def spans() -> list:
    """The armed recorder's finished spans not yet flushed to the file."""
    return TRACER.spans()


def flush() -> None:
    """Write the finished spans to the trace file."""
    TRACER.flush()


def enabled() -> bool:
    return TRACER.enabled


def install(path: str, spans: bool = True, annotate: bool = False) -> None:
    """Arm the recorder (idempotent for the same path: the existing writer
    keeps appending with the new span settings; a different path swaps
    writers).  ``spans=False`` records events only; ``annotate=True`` also
    names every span in the profiler's trace."""
    global TRACER
    if TRACER.enabled and TRACER.path == str(path):
        TRACER.configure(spans, annotate)
        return
    old, TRACER = TRACER, JsonlTracer(path, spans=spans, annotate=annotate)
    old.close()


def uninstall() -> None:
    """Back to the no-op recorder (tests; end of a traced benchmark)."""
    global TRACER
    old, TRACER = TRACER, _NullTracer()
    old.close()


def maybe_install_from_env(env) -> None:
    """Arm the recorder when the captured env names a trace file
    (``Checkpoint.commit()`` calls this — the paper's read-once contract)."""
    if getattr(env, "trace_path", None):
        install(env.trace_path)


def env_snapshot(env, payload_bytes: int = 0,
                 comm_size: Optional[int] = None) -> dict:
    """The scheduling-relevant knobs as a re-capturable ``{CRAFT_*: str}``
    map — what the replayer feeds back into ``CraftEnv.capture`` so the
    simulated policy is configured exactly like the recorded one."""
    tier_every = ",".join(
        f"{slot}:{spec}" if slot != "*" else str(spec)
        for slot, spec in env.tier_every
    )
    snap = {
        "CRAFT_TIER_CHAIN": ",".join(env.tier_chain),
        "CRAFT_TIER_EVERY": tier_every,
        "CRAFT_PFS_EVERY": str(env.pfs_every),
        "CRAFT_MTBF_SECONDS": repr(env.mtbf_seconds),
        "CRAFT_DELTA": "1" if env.delta else "0",
        "CRAFT_DELTA_MAX_CHAIN": str(env.delta_max_chain),
        "CRAFT_KEEP_VERSIONS": str(env.keep_versions),
        "CRAFT_NODE_REDUNDANCY": env.node_redundancy,
        "CRAFT_XOR_GROUP_SIZE": str(env.xor_group_size),
        "CRAFT_RS_PARITY": str(env.rs_parity),
        "CRAFT_MEM_REPLICAS": str(env.mem_replicas),
        "CRAFT_WALLTIME_SECONDS": repr(env.walltime_seconds),
        "CRAFT_WALLTIME_MARGIN_SECONDS": repr(env.walltime_margin_seconds),
        "CRAFT_WRITE_ASYNC": "1" if env.write_async else "0",
        "CRAFT_CODEC_VERSION": str(env.codec_version),
    }
    out = {"env": snap, "payload_bytes": int(payload_bytes)}
    if comm_size is not None:
        out["comm_size"] = int(comm_size)
    return out
