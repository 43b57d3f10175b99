"""The shared half of the plain reference: what every architecture's
training comparison uses, with the architecture passed in.

An architecture is a module that a configuration names by its
``"reference"`` key (a path from the checkout's root, such as
``bench/reference.py``).  It imports nothing of the program and defines:

* ``init_params(seed, m)`` -- bf16 weights from the seed, in the program's
  layout, with the input embedding at ``params["embed"]["embedding"]``;
* ``nll_sum(params, tokens, labels, m, quant=None)`` -- the sum of the
  next-token negative log-likelihood over all positions, every matrix
  product through ``mm`` so that ``quant="fp8"`` reaches each of them;
* ``model_flops_per_token(m, seq_len)`` -- forward plus backward FLOPs per
  token (3x the forward, causal attention over the keys each query
  attends to, no recompute);
* ``TINY`` -- the model keys a CPU test shrinks, with their tiny values; a
  key the configuration leaves null stays null.

This module builds the token batches from the seed (Philox zipf tokens),
computes the loss and its gradient in float32 at the highest matmul
precision, in blocks of rows, and takes the AdamW step the configuration
states (bf16 parameters between steps, fp32 moments, no master copy).
``quant="fp8"`` computes every matrix product on operands rounded to
float8 e4m3 with a per-tensor scale: the control that has to come out as
not correct.  ``rows`` restricts the loss to a subset of each batch's rows
(the half-batch fault).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


# ----------------------------------------------------------------- inputs
def token_batch(seed: int, step: int, vocab: int, seq_len: int, rows: int,
                zipf_a: float):
    """The batch of step ``step`` (0-based): zipf ids folded into the
    vocabulary, labels the tokens shifted left."""
    rng = np.random.Generator(np.random.Philox(
        key=[(seed << 32) | (step & 0xFFFFFFFF), 0xC0FFEE]))
    raw = rng.zipf(zipf_a, size=(rows, seq_len + 1))
    ids = (raw - 1) % vocab
    return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)


# ------------------------------------------------------ forward helpers
def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(eq, a, b, quant):
    """A matrix product at the highest precision; ``quant="fp8"`` rounds
    both operands to float8 first."""
    if quant == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, theta):
    d, n = x.shape[-1], x.shape[-2]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("arch", "mkey", "quant"))
def _block_grad(params, tokens, labels, arch, mkey, quant):
    m = dict(mkey)
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    return jax.value_and_grad(arch.nll_sum)(f32, tokens, labels, m, quant)


def loss_and_grad(arch, params, tokens, labels, m, quant=None, block_rows=1):
    """Mean loss and its fp32 gradient, accumulated over blocks of rows so
    that the attention scores of one block at a time fit the device."""
    mkey = tuple(sorted(m.items()))
    total, grads = 0.0, None
    for r in range(0, tokens.shape[0], block_rows):
        l, g = _block_grad(params, tokens[r:r + block_rows],
                           labels[r:r + block_rows], arch, mkey, quant)
        total += float(l)
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    n = tokens.size
    return total / n, jax.tree_util.tree_map(lambda g: g / n, grads)


# -------------------------------------------------------------- optimizer
def learning_rate(opt: dict, count: int) -> float:
    """Linear warm-up, then cosine decay to ``total_steps``."""
    warm = min(1.0, (count + 1) / max(1, opt["warmup_steps"]))
    frac = (count - opt["warmup_steps"]) / max(
        1, opt["total_steps"] - opt["warmup_steps"])
    frac = min(max(frac, 0.0), 1.0)
    return opt["lr"] * warm * 0.5 * (1.0 + math.cos(math.pi * frac))


@functools.partial(jax.jit, static_argnames=("okey",))
def _adamw(params, grads, mom, vel, count, lr, okey):
    opt = dict(okey)
    b1, b2 = opt["beta1"], opt["beta2"]
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.where(gnorm > opt["clip_norm"], opt["clip_norm"] / gnorm, 1.0)
    c = (count + 1).astype(jnp.float32)
    bc1, bc2 = 1.0 - b1 ** c, 1.0 - b2 ** c
    clipped = jax.tree_util.tree_map(lambda g: g * scale, grads)
    mom = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                 mom, clipped)
    vel = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                 vel, clipped)

    def step(p, m, v):
        w = p.astype(jnp.float32)
        upd = (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
        return (w - lr * (upd + opt["weight_decay"] * w)).astype(p.dtype)

    return jax.tree_util.tree_map(step, params, mom, vel), mom, vel, clipped


@jax.jit
def zero_embed_rows(grads):
    """Rows of the input embedding's gradient that are exactly zero."""
    return jnp.all(grads["embed"]["embedding"] == 0, axis=1)


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


@jax.jit
def diff_norms(a, b):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))])


def train_readings(arch, seed: int, m: dict, opt: dict, data: dict,
                   steps: int, quant: Optional[str] = None, rows=None,
                   block_rows: int = 1) -> dict:
    """The reference's losses of the first ``steps`` steps, the per-leaf
    norms of the first step's clipped gradient and which rows of its input
    embedding are zero (tokens the batch does not hold), and the per-leaf
    norms of the parameters' change after ``steps`` steps."""
    params = arch.init_params(seed, m)
    first = params
    mom = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    vel = mom
    okey = tuple(sorted((k, v) for k, v in opt.items()
                        if isinstance(v, (int, float))))
    losses, grad_norms, zero_rows = [], None, None
    for step in range(steps):
        tokens, labels = token_batch(seed, step, m["vocab"], data["seq_len"],
                                     data["global_batch"], data["zipf_a"])
        if rows is not None:
            tokens, labels = tokens[rows], labels[rows]
        loss, grads = loss_and_grad(arch, params, jnp.asarray(tokens),
                                    jnp.asarray(labels), m, quant, block_rows)
        losses.append(loss)
        lr = learning_rate(opt, step)
        params, mom, vel, clipped = _adamw(params, grads, mom, vel,
                                           jnp.int32(step), jnp.float32(lr),
                                           okey)
        if step == 0:
            grad_norms = np.asarray(leaf_norms(clipped), np.float64)
            zero_rows = np.asarray(zero_embed_rows(clipped))
        del grads, clipped
    change = np.asarray(diff_norms(params, first), np.float64)
    return {"losses": losses, "grad_norms": grad_norms,
            "embed_zero_rows": zero_rows, "change_norms": change}


# ------------------------------------------------------------- comparison
def worst_leaf_gap(prog, ref, include=None) -> float:
    """Largest gap between the program's and the reference's norm of one
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = float(np.median(ref))
    gaps = np.abs(prog - ref) / np.maximum(ref, floor)
    if include is not None:
        gaps = gaps[np.asarray(include, bool)]
    return float(gaps.max())


def moving_leaves(ref_grad_norms) -> np.ndarray:
    """Leaves whose reference gradient is above a thousandth of the median
    leaf's; the rest move under Adam by round-off alone."""
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= 1e-3 * float(np.median(g))


def loss_gap(prog_losses, ref_losses) -> float:
    """Largest relative gap between the program's and the reference's loss
    over the compared steps."""
    p = np.asarray(prog_losses, np.float64)
    r = np.asarray(ref_losses, np.float64)
    return float(np.max(np.abs(p - r) / np.abs(r)))
