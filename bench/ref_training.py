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

Three optional hooks serve a model that trains more than its parameters
under AdamW on the NLL alone, such as an MoE with a routing correction
bias and a sequence-wise balance loss (DeepSeek-V3, arXiv:2412.19437
§2.1.2).  A module that defines none of them runs the plain program: the
same jitted functions, the same operations in the same order.

* ``EXTRA_KEY`` and ``init_extra(seed, m)`` -- ``EXTRA_KEY`` names the
  subtree of the program's training state (``state[EXTRA_KEY]``) that
  holds state which is neither a parameter nor the optimizer's;
  ``init_extra`` builds that state at step 0 from the seed: a pytree of
  float32 arrays in the program's layout (the same tree, shapes and
  dtype).  It is carried through the steps, returned under ``"extra"``
  and compared with the program's as ``extra_gap``.
* ``objective(params, extra, tokens, labels, m, quant=None)`` ->
  ``(total, (nll, stats))`` -- ``total``, a float32 scalar, is what the
  gradient is taken of; ``nll``, summed as ``nll_sum`` sums it, is the
  loss reading; ``stats`` is a pytree of float32 arrays for
  ``update_extra`` (``extra`` is None where ``EXTRA_KEY`` is not
  defined).  Each is a sum over the rows (sequences) it is given, so that
  the blocks of rows below change nothing but rounding: every part must
  add up over rows.  A sequence-wise balance loss does; a batch-wise one,
  a function of the whole batch's loads, does not and cannot be written
  here.  The program's loss is ``total / tokens.size``, so a term that
  the program averages over sequences enters ``total`` times the
  sequence length.  Without this hook ``total`` and ``nll`` are both
  ``nll_sum`` and there are no stats.
* ``update_extra(extra, stats, m)`` -> ``extra`` -- the state's update,
  which is not by the gradient: it runs once per step, after the AdamW
  step, on ``stats`` summed over all of that step's row blocks, and
  returns the new state in the same layout.  Without it the state stays
  as ``init_extra`` built it.

This module builds the token batches from the seed (Philox zipf tokens),
computes the loss and its gradient in float32 at the highest matmul
precision, in blocks of rows, and takes the AdamW step the configuration
states (bf16 parameters between steps, fp32 moments, no master copy).
``quant="fp8"`` computes every matrix product on operands rounded to
float8 e4m3 with a per-tensor scale: the control that has to come out as
not correct.  ``rows`` restricts the loss to a subset of each batch's rows
(the half-batch fault).  Both reach the hooks as they reach ``nll_sum``.
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


@functools.partial(jax.jit, static_argnames=("arch", "mkey", "quant"))
def _block_objective_grad(params, extra, tokens, labels, arch, mkey, quant):
    m = dict(mkey)
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    (_, (nll, stats)), grads = jax.value_and_grad(
        arch.objective, has_aux=True)(f32, extra, tokens, labels, m, quant)
    return nll, grads, stats


def loss_and_grad(arch, params, tokens, labels, m, quant=None, block_rows=1,
                  extra=None):
    """Mean loss and its fp32 gradient, accumulated over blocks of rows so
    that the attention scores of one block at a time fit the device, and
    the ``objective`` hook's stats summed over the blocks (None without
    the hook, whose gradient is then of the NLL)."""
    mkey = tuple(sorted(m.items()))
    total, grads, stats = 0.0, None, None
    for r in range(0, tokens.shape[0], block_rows):
        block = tokens[r:r + block_rows], labels[r:r + block_rows]
        if hasattr(arch, "objective"):
            l, g, s = _block_objective_grad(params, extra, *block, arch,
                                            mkey, quant)
            stats = s if stats is None else jax.tree_util.tree_map(
                jnp.add, stats, s)
        else:
            l, g = _block_grad(params, *block, arch, mkey, quant)
        total += float(l)
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    n = tokens.size
    return total / n, jax.tree_util.tree_map(lambda g: g / n, grads), stats


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
    embedding are zero (tokens the batch does not hold), the per-leaf
    norms of the parameters' change after ``steps`` steps, and, where the
    architecture defines ``EXTRA_KEY``, its state beside the parameters
    after ``steps`` steps (``"extra"``)."""
    params = arch.init_params(seed, m)
    first = params
    extra = arch.init_extra(seed, m) if hasattr(arch, "EXTRA_KEY") else None
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
        loss, grads, stats = loss_and_grad(
            arch, params, jnp.asarray(tokens), jnp.asarray(labels), m, quant,
            block_rows, extra)
        losses.append(loss)
        lr = learning_rate(opt, step)
        params, mom, vel, clipped = _adamw(params, grads, mom, vel,
                                           jnp.int32(step), jnp.float32(lr),
                                           okey)
        if hasattr(arch, "update_extra"):
            extra = arch.update_extra(extra, stats, m)
        if step == 0:
            grad_norms = np.asarray(leaf_norms(clipped), np.float64)
            zero_rows = np.asarray(zero_embed_rows(clipped))
        del grads, clipped
    change = np.asarray(diff_norms(params, first), np.float64)
    out = {"losses": losses, "grad_norms": grad_norms,
           "embed_zero_rows": zero_rows, "change_norms": change}
    if extra is not None:
        out["extra"] = extra
    return out


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


def extra_gap(prog, ref) -> float:
    """Largest norm of the difference between the program's and the
    reference's state beside the parameters in one leaf, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (the rule of ``worst_leaf_gap``).  The two trees must have one
    layout."""
    p_def, r_def = (jax.tree_util.tree_structure(t) for t in (prog, ref))
    p_shapes, r_shapes = ([np.shape(x) for x in jax.tree_util.tree_leaves(t)]
                          for t in (prog, ref))
    if p_def != r_def or p_shapes != r_shapes:
        raise ValueError(f"the program's state {p_def}, shapes {p_shapes}, "
                         f"is not in the reference's layout {r_def}, shapes "
                         f"{r_shapes}")
    diff = np.asarray(diff_norms(prog, ref), np.float64)
    norm = np.asarray(leaf_norms(ref), np.float64)
    den = np.maximum(norm, float(np.median(norm)))
    with np.errstate(divide="ignore", invalid="ignore"):
        gaps = np.where(diff == 0, 0.0, diff / den)
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
