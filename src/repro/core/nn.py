"""Minimal functional NN building blocks (no flax dependency).

Parameters are plain nested dicts of jax arrays.  Every layer is a pair of
functions: ``*_init(key, ...) -> params`` and ``*_apply(params, x) -> y``.
Sharding is attached later by path-based rules (repro.distributed.sharding).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

Array = jax.Array


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def lecun_normal(key, shape, dtype=jnp.float32, in_axis: int = 0):
    fan_in = shape[in_axis]
    std = math.sqrt(1.0 / max(1, fan_in))
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape)
            ).astype(dtype)


def normal_init(key, shape, std, dtype=jnp.float32):
    return (std * jax.random.normal(key, shape)).astype(dtype)


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, *, use_bias: bool = True,
               dtype=jnp.float32, bias_init: float = 0.0):
    p = {"kernel": lecun_normal(key, (in_dim, out_dim), dtype)}
    if use_bias:
        p["bias"] = jnp.full((out_dim,), bias_init, dtype)
    return p


def dense_apply(p, x: Array, compute_dtype=None) -> Array:
    k = p["kernel"]
    if compute_dtype is not None:
        k = k.astype(compute_dtype)
        x = x.astype(compute_dtype)
    y = x @ k
    if "bias" in p:
        b = p["bias"]
        if compute_dtype is not None:
            b = b.astype(compute_dtype)
        y = y + b
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm_apply(p, x: Array, eps: float = 1e-6,
                  zero_centered: bool = False) -> Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    scale = p["scale"].astype(jnp.float32)
    if zero_centered:          # gemma-style (1 + scale)
        scale = 1.0 + scale
    return (y * scale).astype(dtype)


def layernorm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm_apply(p, x: Array, eps: float = 1e-5) -> Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(dtype)


def norm_init(kind: str, dim: int, dtype=jnp.float32):
    if kind == "rmsnorm":
        return rmsnorm_init(dim, dtype)
    if kind == "layernorm":
        return layernorm_init(dim, dtype)
    raise ValueError(kind)


def norm_apply(kind: str, p, x: Array, **kw) -> Array:
    if kind == "rmsnorm":
        return rmsnorm_apply(p, x, **kw)
    if kind == "layernorm":
        return layernorm_apply(p, x, **kw)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Causal depthwise conv (the paper's / mamba's "Conv4" temporal mixer)
# ---------------------------------------------------------------------------

def causal_conv_init(key, dim: int, kernel_size: int = 4, dtype=jnp.float32):
    std = math.sqrt(1.0 / (kernel_size))
    return {"kernel": normal_init(key, (kernel_size, dim), std, dtype),
            "bias": jnp.zeros((dim,), dtype)}


def causal_conv_apply(p, x: Array, prefix: Optional[Array] = None) -> Array:
    """x: (..., T, D) depthwise causal conv along T.

    ``prefix`` (default zeros) is the (..., K-1, D) window of inputs that
    precede ``x`` -- passing the carried conv state here makes chunked
    prefill bit-exact with an unchunked pass (same slide-multiply-add
    schedule, only the left pad values change).
    """
    k = p["kernel"].astype(x.dtype)          # (K, D)
    ksize = k.shape[0]
    if prefix is None:
        pad = [(0, 0)] * (x.ndim - 2) + [(ksize - 1, 0), (0, 0)]
        xp = jnp.pad(x, pad)
    else:
        xp = jnp.concatenate([prefix.astype(x.dtype), x], axis=-2)
    # sum_k x[t - (K-1) + k] * k[k]  -- small K: unrolled adds (fuses well)
    y = jnp.zeros_like(x)
    t = x.shape[-2]
    for i in range(ksize):
        y = y + jax.lax.slice_in_dim(xp, i, i + t, axis=-2) * k[i]
    return y + p["bias"].astype(x.dtype)


def conv_taps(taps, k):
    """sum_i taps[i] * k[i], oldest tap first: the depthwise conv of one
    position as elementwise multiply-adds.  The decode step and the fused
    block kernel share it, so both round identically."""
    y = taps[0] * k[0]
    for tap, w in zip(taps[1:], k[1:]):
        y = y + tap * w
    return y


def causal_conv_step(p, x_t: Array, conv_state: Array):
    """Single decode step. conv_state: (..., K-1, D) trailing inputs."""
    k = p["kernel"].astype(x_t.dtype)
    window = jnp.concatenate([conv_state, x_t[..., None, :]], axis=-2)
    taps = [window[..., i, :] for i in range(window.shape[-2])]
    y = conv_taps(taps, k) + p["bias"].astype(x_t.dtype)
    return y, window[..., 1:, :]


# ---------------------------------------------------------------------------
# Variable-length (right-padded batch) state gathers
#
# Batched prefill right-pads prompts to a shared T.  Because every sequence
# mixer in the zoo is causal, positions < length are bit-identical to an
# unpadded run, so the decode state of request b is simply the state *at
# position lengths[b]-1* -- these helpers extract it.
# ---------------------------------------------------------------------------

def gather_last(x: Array, lengths: Array) -> Array:
    """x: (B, T, ...) -> (B, ...), row b taken at position lengths[b]-1."""
    idx = (lengths - 1).reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.take_along_axis(x, idx.astype(jnp.int32), axis=1)[:, 0]


def gather_conv_window(x: Array, lengths: Array, width: int,
                       prefix: Optional[Array] = None) -> Array:
    """Trailing ``width`` inputs after consuming ``lengths[b]`` tokens.

    x: (B, T, D); returns (B, width, D) = rows [len-width, len-1] of
    ``concat(prefix, x)`` where ``prefix`` (default zeros) holds the
    ``width`` inputs that preceded ``x`` (carried conv state on resume).
    """
    bsz = x.shape[0]
    if prefix is None:
        prefix = jnp.zeros((bsz, width) + x.shape[2:], x.dtype)
    ext = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)
    idx = lengths[:, None].astype(jnp.int32) + jnp.arange(width)[None, :]
    idx = idx.reshape(idx.shape + (1,) * (x.ndim - 2))
    return jnp.take_along_axis(ext, idx, axis=1)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def gelu(x):
    return jax.nn.gelu(x, approximate=True)


ACTIVATIONS = {
    "gelu": gelu,
    "silu": jax.nn.silu,
    "relu": jax.nn.relu,
    "tanh": jnp.tanh,
}


# ---------------------------------------------------------------------------
# The paper's g() positivity transform (Appendix B, Listing 6)
# ---------------------------------------------------------------------------

def g(x: Array) -> Array:
    """g(x) = x + 0.5 if x >= 0 else sigmoid(x); ensures h_tilde > 0."""
    return jnp.where(x >= 0, x + 0.5, jax.nn.sigmoid(x))


def log_g(x: Array) -> Array:
    """log g(x), computed stably: log(x+0.5) / -softplus(-x)."""
    return jnp.where(x >= 0,
                     jnp.log(jax.nn.relu(x) + 0.5),
                     -jax.nn.softplus(-x))


def log_sigmoid(x: Array) -> Array:
    """log sigma(x) = -softplus(-x)."""
    return -jax.nn.softplus(-x)
