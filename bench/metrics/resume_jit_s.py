"""Seconds JAX spent tracing, lowering, compiling or loading compiled
programs from its cache, per resume of the window: the union of the
``jit.*`` spans the recorder takes from ``jax.monitoring`` (a cache load
happens inside a compile, so the two overlap)."""
from bench import spans

JIT = ("jit.trace", "jit.lower", "jit.compile", "jit.cache_load")


def read(run):
    got = spans.window_spans(run, *JIT)
    return spans.per_resume(run, spans.union_seconds(got), got)
