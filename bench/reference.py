"""Plain reference of a dense GQA decoder (RMSNorm, RoPE, causal attention
with an optional sliding window, SwiGLU), the architecture that the
configurations naming ``"reference": "bench/reference.py"`` state.  It
keeps the contract of ``bench/ref_training.py``: ``init_params``,
``nll_sum``, ``model_flops_per_token`` and ``TINY``; the training step,
the batches and the comparison live there.

It imports nothing of the program.  It builds its own weights from the
seed with the same random streams the configuration names
(``jax.random`` truncated normals scaled by 1/sqrt(fan-in), cast to
bfloat16).  The counts below are of the same model: model FLOPs count the
matrix multiplications of the forward and backward passes (3x the
forward), with causal attention counted over the keys each query attends
to; recomputation under remat does not count.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.ref_training import mm, rms, rope

# the model keys a CPU test shrinks; a null window stays null
TINY = dict(n_layers=2, d_model=64, vocab=512, n_heads=4, n_kv_heads=2,
            head_dim=16, d_ff=128, window=16)


# ----------------------------------------------------------------- inputs
def init_params(seed: int, m: dict):
    """bf16 weights from the seed, in the configuration's layout."""
    d, h, kv, hd, f, v = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                          m["head_dim"], m["d_ff"], m["vocab"])
    bf16 = jnp.bfloat16

    def tn(key, shape, fan_in):
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
        return (x * (1.0 / math.sqrt(max(1, fan_in)))).astype(bf16)

    def layer(key):
        k_attn, k_ffn = jax.random.split(key)
        a = jax.random.split(k_attn, 4)
        g = jax.random.split(k_ffn, 3)
        return {
            "ln1": jnp.ones((d,), bf16),
            "attn": {"wq": tn(a[0], (d, h, hd), d),
                     "wk": tn(a[1], (d, kv, hd), d),
                     "wv": tn(a[2], (d, kv, hd), d),
                     "wo": tn(a[3], (h, hd, d), h * hd)},
            "ln2": jnp.ones((d,), bf16),
            "ffn": {"w_gate": tn(g[0], (d, f), d),
                    "w_up": tn(g[1], (d, f), d),
                    "w_down": tn(g[2], (f, d), f)},
        }

    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    layers = [layer(k) for k in jax.random.split(ks[1], m["n_layers"])]
    params = {
        "embed": {"embedding": tn(ks[0], (v, d), d)},
        "final_ln": jnp.ones((d,), bf16),
        "blocks": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers),
    }
    if not m["tie_embeddings"]:
        params["lm_head"] = tn(ks[3], (d, v), d)
    return params


# ---------------------------------------------------------------- forward
def _layer(p, x, m, quant):
    eps, group = m["norm_eps"], m["n_heads"] // m["n_kv_heads"]
    h = rms(x, p["ln1"], eps)
    q = rope(mm("bld,dhk->bhlk", h, p["attn"]["wq"], quant), m["rope_theta"])
    k = rope(mm("bld,dhk->bhlk", h, p["attn"]["wk"], quant), m["rope_theta"])
    v = mm("bld,dhk->bhlk", h, p["attn"]["wv"], quant)
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = mm("bhqd,bhkd->bhqk", q, k, quant) * (m["head_dim"] ** -0.5)
    n = x.shape[1]
    qpos, kpos = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    mask = kpos <= qpos
    if m.get("window"):
        mask &= kpos > qpos - m["window"]
    s = jnp.where(mask, s, -jnp.inf)
    y = mm("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v, quant)
    x = x + mm("bhlk,hkd->bld", y, p["attn"]["wo"], quant)
    h = rms(x, p["ln2"], eps)
    g = mm("bld,df->blf", h, p["ffn"]["w_gate"], quant)
    u = mm("bld,df->blf", h, p["ffn"]["w_up"], quant)
    return x + mm("blf,fd->bld", jax.nn.silu(g) * u, p["ffn"]["w_down"],
                  quant)


def nll_sum(params, tokens, labels, m, quant=None):
    """Sum of the next-token negative log-likelihood over all positions."""
    x = params["embed"]["embedding"][tokens]
    layer = jax.checkpoint(functools.partial(_layer, m=m, quant=quant))
    for i in range(m["n_layers"]):
        x = layer(jax.tree_util.tree_map(lambda a: a[i], params["blocks"]),
                  x)
    x = rms(x, params["final_ln"], m["norm_eps"])
    if m["tie_embeddings"]:
        logits = mm("bld,vd->blv", x, params["embed"]["embedding"], quant)
    else:
        logits = mm("bld,dv->blv", x, params["lm_head"], quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


# ----------------------------------------------------------------- counts
def matmul_params(m: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    every projection of every layer and the output head (tied or not);
    the embedding gather and the norms do no multiplication."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * m["n_heads"] * hd * 2 + d * m["n_kv_heads"] * hd * 2
    mlp = 3 * d * m["d_ff"]
    return m["n_layers"] * (attn + mlp) + m["vocab"] * d


def param_count(m: dict, norms: bool = True) -> int:
    """All parameters of a dense GQA decoder: embedding, untied head when
    present, every layer's projections, and (``norms``) its RMSNorm gains."""
    d = m["d_model"]
    n = matmul_params(m) + (0 if m["tie_embeddings"] else m["vocab"] * d)
    if norms:
        n += m["n_layers"] * 2 * d + d
    return n


def attended_keys(m: dict, seq_len: int) -> float:
    """Mean number of keys a query attends to under the causal (and
    sliding-window) mask."""
    w = m.get("window") or seq_len
    total = sum(min(i + 1, w) for i in range(seq_len))
    return total / seq_len


def model_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per token (3x forward; no recompute)."""
    dense = 2 * matmul_params(m)
    attn = 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * attended_keys(
        m, seq_len)
    return 3 * (dense + attn)


def leaf_bytes(m: dict, moments_bytes: int = 4) -> list:
    """Bytes of every leaf of the training state: bf16 params, fp32 Adam
    moments m and v, and the int32 step count."""
    d, hd, f, v = m["d_model"], m["head_dim"], m["d_ff"], m["vocab"]
    layer = [d, d, d * m["n_heads"] * hd, d * m["n_kv_heads"] * hd,
             d * m["n_kv_heads"] * hd, m["n_heads"] * hd * d, d * f, d * f,
             f * d]
    params = [v * d, d] + [m["n_layers"] * x for x in layer]
    if not m["tie_embeddings"]:
        params.append(d * v)
    out = [2 * p for p in params]
    out += [moments_bytes * p for p in params] * 2
    out.append(4)
    return out
