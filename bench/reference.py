"""Plain float32 reference of the paper's minGRU and minLSTM language models.

Written from Feng et al. 2024, "Were RNNs All We Needed?" (arXiv:2410.01201,
sections 3.1, 3.2 and App. C) and the residual block layout of
``core/blocks.py``; it imports nothing of the program.  Every matrix
product runs at ``precision="highest"`` (on a TPU a float32 product is
otherwise rounded to bfloat16).

Per layer, for input x (B, T, d):

    y  = RMSNorm(x) * scale                          eps 1e-6
    y  = causal depthwise conv, 4 taps, plus bias    zero left padding
    minGRU:  z = sigmoid(y Wz + bz),  h~ = g(y Wh + bh)
             h_t = (1 - z_t) h_{t-1} + z_t h~_t
    minLSTM: f = sigmoid(y Wf + bf), i = sigmoid(y Wi + bi), h~ = g(y Wh + bh)
             h_t = f/(f+i) h_{t-1} + i/(f+i) h~_t
    x  = x + h Wdown
    x  = x + gelu(RMSNorm(x) Win + bin) Wout + bout  (tanh-form gelu)

with h_{-1} = 0 and g(v) = v + 1/2 for v >= 0, sigmoid(v) otherwise.  The
logits are RMSNorm(x) times the tied embedding table.

Departures, each equal in exact arithmetic:
  * the paper's App. B scans in log space; this one scans (a, b) linearly
    with ``lax.associative_scan`` in float32, where the gates in (0, 1)
    keep it stable;
  * minLSTM's f/(f+i) is taken as written, not through Algorithm 8's
    softplus form.

``control=True`` computes every matrix product in float8: e4m3 operands
with a scale per row of activations and per output column of weights,
and in the backward an e5m2 gradient with one scale.  That is the
reference at the next precision below the configuration's bfloat16, used
to show that the benchmark's comparison fails a lower-precision program.
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


def _g(v):
    return jnp.where(v >= 0, v + 0.5, jax.nn.sigmoid(v))


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _linear_scan(a, b):
    """h_t = a_t h_{t-1} + b_t along axis 1, h_{-1} = 0."""
    def combine(l, r):
        return l[0] * r[0], r[0] * l[1] + r[1]
    return lax.associative_scan(combine, (a, b), axis=1)[1]


def _block(p, x, cell, control):
    y = _rmsnorm(x, p["norm_rnn"]["scale"])
    k = p["conv"]["kernel"]                          # (taps, d)
    taps = k.shape[0]
    yp = jnp.pad(y, ((0, 0), (taps - 1, 0), (0, 0)))
    t = y.shape[1]
    y = sum(yp[:, i:i + t] * k[i] for i in range(taps)) + p["conv"]["bias"]
    r = p["rnn"]

    def proj(name):
        return _mm(y, r[name]["kernel"], control) + r[name]["bias"]

    h_tilde = _g(proj("wh"))
    if cell == "mingru":
        z = jax.nn.sigmoid(proj("wz"))
        a, b = 1.0 - z, z * h_tilde
    else:
        f = jax.nn.sigmoid(proj("wf"))
        i = jax.nn.sigmoid(proj("wi"))
        a, b = f / (f + i), i / (f + i) * h_tilde
    h = _linear_scan(a, b)
    x = x + _mm(h, p["down"]["kernel"], control)
    y = _rmsnorm(x, p["norm_mlp"]["scale"])
    y = _gelu(_mm(y, p["mlp_in"]["kernel"], control) + p["mlp_in"]["bias"])
    return x + _mm(y, p["mlp_out"]["kernel"], control) + p["mlp_out"]["bias"]


def forward(params, tokens, *, cell: str, vocab: int, control: bool = False):
    """tokens (B, T) int -> logits (B, T, vocab) float32."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    table = params["embed"]["table"]
    x = table[tokens]

    def body(x, p):
        return jax.checkpoint(functools.partial(
            _block, cell=cell, control=control))(p, x), None

    x, _ = lax.scan(body, x, params["layers"]["blocks"])
    x = _rmsnorm(x, params["final_norm"]["scale"])
    return _mm(x, table.T, control)[..., :vocab]


def nll_sum(params, batch, *, cell: str, vocab: int, control: bool = False):
    """Summed next-token negative log-likelihood over labels >= 0."""
    logits = forward(params, batch["tokens"], cell=cell, vocab=vocab,
                     control=control)
    labels = batch["labels"]
    mask = labels >= 0
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               -1)[..., 0]
    return jnp.sum(jnp.where(mask, logz - gold, 0.0))


@functools.partial(jax.jit, static_argnames=("cell", "vocab", "control"))
def nll_sum_and_grad(params, batch, *, cell, vocab, control=False):
    """(summed NLL, its float32 gradient) for one block of rows."""
    return jax.value_and_grad(nll_sum)(params, batch, cell=cell, vocab=vocab,
                                       control=control)


def loss_and_grad(params, batch, *, cell: str, vocab: int, rows: int,
                  control: bool = False):
    """Mean NLL over the batch and its gradient, ``rows`` rows at a time
    so that the float32 activations fit beside the program's memory."""
    n = batch["tokens"].shape[0]
    total, grads = 0.0, None
    for r in range(0, n, rows):
        part = {k: v[r:r + rows] for k, v in batch.items()}
        s, g = nll_sum_and_grad(params, part, cell=cell, vocab=vocab,
                                control=control)
        total = total + s
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    count = jnp.sum(batch["labels"] >= 0).astype(jnp.float32)
    return total / count, jax.tree.map(lambda g: g / count, grads)


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
