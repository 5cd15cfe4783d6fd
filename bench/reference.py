"""Pieces every model module's plain float32 reference shares.

Each model family's reference lives in its module under ``bench/models/``
and builds its blocks from these.  They import nothing of the program.
Every matrix product runs at ``precision="highest"`` (on a TPU a float32
product is otherwise rounded to bfloat16).

``_mm(x, w, control)`` is the product a block uses: at the highest
precision, or with ``control=True`` in float8, e4m3 operands with a scale
per row of activations and per output column of weights, and in the
backward an e5m2 gradient with one scale.  That is the reference at the
next precision below a bfloat16 configuration, used to show that the
benchmark's comparison fails a lower-precision program.

AdamW follows ``training.optimizer.AdamWConfig`` for the training cells.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax


def _f8(a, axis=None, dtype=jnp.float8_e4m3fn):
    """Round ``a`` to float8 with an absmax scale along ``axis`` (the
    whole tensor when None)."""
    top = float(jnp.finfo(dtype).max)
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=axis is not None) / top
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(dtype).astype(jnp.float32) * s


def _hi(a, b):
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


@jax.custom_vjp
def _mm_f8(x, w):
    """x @ w with both operands in float8 e4m3 (a scale per row of x and
    per column of w); the backward products take the gradient in float8
    e5m2 with one scale, the usual float8 training recipe."""
    return _hi(_f8(x, -1), _f8(w, -2))


def _mm_f8_fwd(x, w):
    return _mm_f8(x, w), (x, w)


def _mm_f8_bwd(res, g):
    x, w = res
    gq = _f8(g, None, jnp.float8_e5m2)
    dx = _hi(gq, _f8(w, -1).T)
    x2 = _f8(x, -1).reshape(-1, x.shape[-1])
    dw = _hi(x2.T, gq.reshape(-1, g.shape[-1]))
    return dx, dw


_mm_f8.defvjp(_mm_f8_fwd, _mm_f8_bwd)


def _mm(x, w, control):
    return _mm_f8(x, w) if control else _hi(x, w)


def _rmsnorm(x, scale):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale


# ---------------------------------------------------------------------------
# AdamW, as ``training.optimizer.AdamWConfig`` states it: global-norm
# clipping, warmup then cosine decay of the rate, bias-corrected moments,
# decoupled weight decay on every leaf but norm scales and biases.
# ---------------------------------------------------------------------------

def adamw_lr(opt: dict, step: int) -> float:
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    frac = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    floor = opt["min_lr_ratio"]
    decay = floor + (1.0 - floor) * 0.5 * (1.0 + math.cos(math.pi * frac))
    return opt["lr"] * warm * decay


def decay_mask(params):
    """1.0 where weight decay applies: every leaf but norm scales and
    biases (stacked over layers, those have two dimensions too)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, p: 0.0 if p.ndim <= 1 or str(
            getattr(path[-1], "key", "")) in ("scale", "bias") else 1.0,
        params)


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "clip"))
def _adamw_update(params, grads, mu, nu, mask, lr, bc1, bc2, wd, *, b1, b2,
                  eps, clip):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-9)) if clip > 0 else 1.0
    grads = jax.tree.map(lambda g: g * scale, grads)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def upd(p, m, v, decay):
        return p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)
                         + wd * decay * p)

    return jax.tree.map(upd, params, mu, nu, mask), mu, nu, grads


def adamw(opt: dict, params, grads, mu, nu, step: int, store_dtype):
    """One AdamW step (``step`` counts from 1).  Parameters are kept in
    ``store_dtype`` between steps, as the configuration stores them.
    Returns (params, mu, nu, clipped grads)."""
    lr = adamw_lr(opt, step)
    new, mu, nu, clipped = _adamw_update(
        params, grads, mu, nu, decay_mask(params), lr,
        1.0 - opt["b1"] ** step, 1.0 - opt["b2"] ** step,
        opt["weight_decay"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        clip=opt["grad_clip"])
    new = jax.tree.map(lambda p: p.astype(store_dtype).astype(jnp.float32),
                       new)
    return new, mu, nu, clipped
