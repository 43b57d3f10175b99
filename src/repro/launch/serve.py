"""Serving driver: batched prefill + decode with a restartable decode loop.

The CRAFT angle on serving: a long decode (the assigned ``long_500k`` shape
decodes against a 524k-token context) is exactly the kind of hours-long,
loses-everything-on-failure loop the paper targets.  The KV/SSM cache, the
position counter and the generated tokens are all CRAFT-checkpointable, so
``serve`` periodically checkpoints the decode state and a restarted run
resumes mid-generation instead of re-prefilling.

    PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
        --batch 4 --prompt-len 32 --gen 64 --cp-freq 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import Box, Checkpoint
from repro.launch.compile_cache import setup_compile_cache
from repro.models import model as M
from repro.train.steps import make_decode_step, make_prefill


@dataclasses.dataclass
class ServeConfig:
    arch: str = "h2o-danube-1.8b"
    tiny: bool = True
    batch: int = 4
    prompt_len: int = 32
    gen_tokens: int = 64
    cp_freq: int = 0            # 0 = no decode checkpointing
    cp_name: str = "serve"
    seed: int = 0
    temperature: float = 0.0    # 0 = greedy


def run(sc: ServeConfig, comm=None, env=None, params=None,
        fail_at_token: Optional[int] = None) -> Dict:
    """Prefill a synthetic prompt batch, decode ``gen_tokens`` greedily.

    Returns {"tokens": (B, gen) np.ndarray, "prefill_s", "decode_s",
    "resumed_at": int}.  ``fail_at_token`` raises after that many generated
    tokens (restartability tests re-call ``run`` and assert resumption).
    """
    cfg = get_config(sc.arch, tiny=sc.tiny)
    if params is None:
        params = M.init_params(jax.random.PRNGKey(sc.seed), cfg)
    max_len = sc.prompt_len + sc.gen_tokens + (
        cfg.n_patches if cfg.frontend else 0)
    rng = np.random.default_rng(sc.seed)
    prompts = rng.integers(0, cfg.vocab, (sc.batch, sc.prompt_len),
                           dtype=np.int32)
    embeds = None
    if cfg.frontend:
        stub = np.random.default_rng(sc.seed + 1).standard_normal(
            (sc.batch, cfg.n_patches, cfg.d_model)).astype(np.float32)
        embeds = jnp.asarray(stub, cfg.dtype)

    prefill = jax.jit(make_prefill(cfg, sc.batch, max_len))
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))

    t0 = time.perf_counter()
    if embeds is not None:
        cache, logits = prefill(params, jnp.asarray(prompts), embeds)
        pos0 = sc.prompt_len + cfg.n_patches
    else:
        cache, logits = prefill(params, jnp.asarray(prompts))
        pos0 = sc.prompt_len
    jax.block_until_ready(logits)
    prefill_s = time.perf_counter() - t0

    cache_box = Box(cache)
    tok_box = Box(np.zeros((sc.batch, sc.gen_tokens), np.int32))
    i_box = Box(0)

    cp = None
    resumed_at = 0
    if sc.cp_freq:
        cp = Checkpoint(sc.cp_name, comm, env=env)
        cp.add("cache", cache_box)
        cp.add("generated", tok_box)
        cp.add("i", i_box)
        cp.commit()
        if cp.restart_if_needed():
            resumed_at = i_box.value

    def sample(lg, i) -> jnp.ndarray:
        if sc.temperature <= 0.0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            jax.random.fold_in(jax.random.PRNGKey(sc.seed), i),
            lg / sc.temperature).astype(jnp.int32)

    if resumed_at > 0:
        next_tok = jnp.asarray(tok_box.value[:, resumed_at - 1])
    else:
        next_tok = sample(logits, 0)

    t0 = time.perf_counter()
    i = i_box.value
    while i < sc.gen_tokens:
        cache_box.value, logits = decode(
            params, cache_box.value, next_tok[:, None], jnp.int32(pos0 + i))
        next_tok = sample(logits, i + 1)
        tok_box.value[:, i] = np.asarray(next_tok)
        i += 1
        i_box.value = i
        if cp is not None:
            cp.update_and_write(i, sc.cp_freq)
        if fail_at_token is not None and i == fail_at_token:
            if cp is not None:
                cp.wait()
                cp.close()
            raise RuntimeError(f"injected failure at token {i}")
    decode_s = time.perf_counter() - t0
    if cp is not None:
        cp.wait()
        cp.close()
    return {"tokens": tok_box.value, "prefill_s": prefill_s,
            "decode_s": decode_s, "resumed_at": resumed_at}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    ap.add_argument("--tiny", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=64)
    ap.add_argument("--cp-freq", type=int, default=0)
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics + /healthz on this port (k8s "
                         "liveness probe; same as CRAFT_METRICS_PORT)")
    args = ap.parse_args()
    setup_compile_cache()
    if args.metrics_port is not None:
        # Start the exporter up front so the replica answers its liveness
        # probe during prefill, before any Checkpoint commits.
        from repro.core import metrics, telemetry

        metrics.install()
        port = telemetry.start(args.metrics_port)
        print(f"telemetry: /metrics + /healthz on port {port}")
    sc = ServeConfig(arch=args.arch, tiny=args.tiny, batch=args.batch,
                     prompt_len=args.prompt_len, gen_tokens=args.gen,
                     cp_freq=args.cp_freq)
    out = run(sc)
    print(f"prefill {out['prefill_s']:.2f}s, decode {out['decode_s']:.2f}s "
          f"({sc.gen_tokens} tokens), resumed_at={out['resumed_at']}")
    print("first sequence:", out["tokens"][0][:16], "...")


if __name__ == "__main__":
    main()
