"""Parallel-scan primitives for first-order linear recurrences.

The paper's central algorithmic device: every minGRU/minLSTM (and the SSD
special case used by mamba2/zamba2) reduces to

    h_t = a_t * h_{t-1} + b_t                (elementwise over features)

which is associative under the combine

    (a_i, b_i) o (a_j, b_j) = (a_i * a_j, a_j * b_i + b_j)   (i before j)

and therefore computable in O(log T) depth.  This module provides every
execution strategy the framework uses:

  * ``scan_sequential``     -- lax.scan reference / serving-step oracle
  * ``scan_associative``    -- jax.lax.associative_scan (training default)
  * ``scan_log_space``      -- Heinsen (2023) log-space scan for stability
  * ``scan_chunked``        -- two-level chunked scan (structure mirrors the
                               Pallas kernel; used for very long sequences)
  * ``scan_sequence_parallel`` -- shard_map body: sequence-sharded scan with
                               a single tiny carry-exchange collective

Array convention: time axis is ``axis`` (default -2), i.e. shapes are
``(..., T, D)``; ``h0`` has shape ``(..., D)``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array


# ---------------------------------------------------------------------------
# Combine rule
# ---------------------------------------------------------------------------

def combine(left: Tuple[Array, Array], right: Tuple[Array, Array]):
    """Associative combine for h_t = a_t h_{t-1} + b_t segments."""
    a_l, b_l = left
    a_r, b_r = right
    return a_l * a_r, a_r * b_l + b_r


# ---------------------------------------------------------------------------
# Sequential reference (also the serving step)
# ---------------------------------------------------------------------------

def scan_sequential(a: Array, b: Array, h0: Optional[Array] = None,
                    axis: int = -2) -> Array:
    """O(T) lax.scan reference. Ground truth for every other strategy."""
    a = jnp.moveaxis(a, axis, 0)
    b = jnp.moveaxis(b, axis, 0)
    if h0 is None:
        h0 = jnp.zeros_like(b[0])

    def step(h, ab):
        a_t, b_t = ab
        h = a_t * h + b_t
        return h, h

    _, hs = lax.scan(step, h0, (a, b))
    return jnp.moveaxis(hs, 0, axis)


def scan_step(a_t: Array, b_t: Array, h_prev: Array) -> Array:
    """Single recurrence step (decode path)."""
    return a_t * h_prev + b_t


# ---------------------------------------------------------------------------
# Associative scan (training default)
# ---------------------------------------------------------------------------

def scan_associative(a: Array, b: Array, h0: Optional[Array] = None,
                     axis: int = -2) -> Array:
    """Work-efficient parallel scan via jax.lax.associative_scan."""
    a_cum, b_cum = lax.associative_scan(combine, (a, b), axis=axis)
    if h0 is None:
        return b_cum
    return b_cum + a_cum * jnp.expand_dims(h0, axis)


def scan_associative_with_aggregate(a: Array, b: Array, axis: int = -2):
    """As scan_associative but also returns the cumulative coefficients.

    Needed by the chunked / sequence-parallel strategies, which must combine
    an incoming carry: h_t = B_t + A_t * h_in.
    """
    return lax.associative_scan(combine, (a, b), axis=axis)


# ---------------------------------------------------------------------------
# Log-space scan (Heinsen 2023) -- the paper's Appendix B implementation
# ---------------------------------------------------------------------------

def logcumsumexp(x: Array, axis: int = -2) -> Array:
    """Numerically-stable cumulative logsumexp via associative logaddexp."""
    return lax.associative_scan(jnp.logaddexp, x, axis=axis)


def scan_log_space(log_a: Array, log_b: Array,
                   log_h0: Optional[Array] = None, axis: int = -2,
                   strategy: str = "associative") -> Array:
    """Heinsen scan: inputs are log coefficients / log values, output is h.

    h_t = exp(a*_t + logcumsumexp(log_b - a*)_t)  with a*_t = cumsum(log_a).
    Requires b_t > 0 (the paper guarantees this via the g() transform).
    If ``log_h0`` is given it is prepended exactly as in the paper's
    ``torch.cat([log_h0, ...])``.

    ``strategy="pallas"`` routes to the in-kernel logaddexp ladder
    (``repro.kernels.scan.ops.log_space_scan``): same math, chunked in
    VMEM with a log-space cross-chunk carry; any other value runs the
    ``lax.associative_scan`` formulation below.
    """
    if strategy == "pallas":
        from repro.kernels.scan import ops as scan_kernel_ops
        if axis not in (-2, log_a.ndim - 2):
            raise ValueError("pallas log scan requires time axis -2")
        return scan_kernel_ops.log_space_scan_auto(log_a, log_b, log_h0)
    if log_h0 is not None:
        zero = jnp.zeros_like(jnp.take(log_a, jnp.array([0]), axis=axis))
        log_a_ext = jnp.concatenate([zero, log_a], axis=axis)
        log_b_ext = jnp.concatenate(
            [jnp.expand_dims(log_h0, axis), log_b], axis=axis)
        h = scan_log_space(log_a_ext, log_b_ext, None, axis=axis)
        # drop the h0 position
        t = h.shape[axis]
        return lax.slice_in_dim(h, 1, t, axis=axis)
    a_star = jnp.cumsum(log_a, axis=axis)
    log_h = a_star + logcumsumexp(log_b - a_star, axis=axis)
    return jnp.exp(log_h)


# ---------------------------------------------------------------------------
# Chunked two-level scan (mirrors the Pallas kernel's structure)
# ---------------------------------------------------------------------------

def scan_chunked(a: Array, b: Array, h0: Optional[Array] = None,
                 chunk: int = 256, axis: int = -2) -> Array:
    """Two-level scan: intra-chunk parallel, inter-chunk sequential.

    This is the HBM->VMEM blocking the Pallas kernel uses: per-chunk state
    stays on-chip, and only the O(T/chunk) chunk carries are sequential.
    """
    a = jnp.moveaxis(a, axis, -2)
    b = jnp.moveaxis(b, axis, -2)
    batch_shape = a.shape[:-2]
    t, d = a.shape[-2], a.shape[-1]
    if t % chunk != 0:
        pad = chunk - t % chunk
        # pad with identity elements (a=1, b=0)
        a = jnp.concatenate(
            [a, jnp.ones(batch_shape + (pad, d), a.dtype)], axis=-2)
        b = jnp.concatenate(
            [b, jnp.zeros(batch_shape + (pad, d), b.dtype)], axis=-2)
    nc = a.shape[-2] // chunk
    a_c = a.reshape(batch_shape + (nc, chunk, d))
    b_c = b.reshape(batch_shape + (nc, chunk, d))

    # level 1: intra-chunk inclusive scan (parallel over chunks)
    a_cum, b_cum = scan_associative_with_aggregate(a_c, b_c, axis=-2)

    # level 2: exclusive scan over chunk aggregates (sequential, nc steps)
    agg_a = a_cum[..., -1, :]   # (..., nc, d)
    agg_b = b_cum[..., -1, :]
    carry0 = (jnp.zeros(batch_shape + (d,), b.dtype) if h0 is None
              else h0.astype(b.dtype))

    def step(h, ab):
        a_k, b_k = ab
        return a_k * h + b_k, h   # emit carry *before* applying this chunk

    agg_a_t = jnp.moveaxis(agg_a, -2, 0)
    agg_b_t = jnp.moveaxis(agg_b, -2, 0)
    _, carries = lax.scan(step, carry0, (agg_a_t, agg_b_t))
    carries = jnp.moveaxis(carries, 0, -2)          # (..., nc, d)

    h = b_cum + a_cum * carries[..., :, None, :]
    h = h.reshape(batch_shape + (nc * chunk, d))[..., :t, :]
    return jnp.moveaxis(h, -2, axis)


# ---------------------------------------------------------------------------
# Sequence-parallel scan (shard_map body)
# ---------------------------------------------------------------------------

def scan_sequence_parallel(a: Array, b: Array, axis_name: str,
                           h0: Optional[Array] = None,
                           axis: int = -2) -> Array:
    """Scan whose time axis is sharded across mesh axis ``axis_name``.

    Must be called inside shard_map with ``a``/``b`` carrying the *local*
    sequence shard.  Strategy:

      1. local inclusive scan  -> (A_loc, B_loc)
      2. all-gather each device's aggregate (last element) -- 2*D floats
         per device, the only collective
      3. every device combines the aggregates of the devices before it to
         obtain its incoming carry (exclusive prefix over n_dev elements)
      4. fix-up: h = B_loc + A_loc * carry_in
    """
    a_cum, b_cum = scan_associative_with_aggregate(a, b, axis=axis)
    agg_a = jnp.take(a_cum, jnp.array([-1]), axis=axis)
    agg_b = jnp.take(b_cum, jnp.array([-1]), axis=axis)
    # gather aggregates from every device: leading axis n_dev
    all_a = lax.all_gather(agg_a, axis_name)     # (n_dev, ..., 1, D)
    all_b = lax.all_gather(agg_b, axis_name)
    n_dev = all_a.shape[0]
    idx = lax.axis_index(axis_name)

    # derive the zero carry from varying data so shard_map's VMA typing
    # sees a consistent carry type through the scan
    carry0 = agg_b * 0
    if h0 is not None:
        carry0 = carry0 + jnp.expand_dims(h0, axis).astype(b.dtype)

    def step(h, ab):
        a_k, b_k = ab
        return a_k * h + b_k, h

    _, carries = lax.scan(step, carry0, (all_a, all_b))   # (n_dev, ..., 1, D)
    carry_in = jnp.take(carries, idx, axis=0)
    return b_cum + a_cum * carry_in


# ---------------------------------------------------------------------------
# Strategy dispatch
# ---------------------------------------------------------------------------

# "fused" = the Pallas fused projection+scan kernels (minGRU/minLSTM layers
# only; resolved by the cell's ``parallel``, not by ``scan_linear``).
# "auto" = the fused Pallas path: compiled on a TPU, run by the Pallas
# interpreter on the CPU (same semantics), decided when each kernel is
# called (``repro.kernels.resolve_interpret``).
STRATEGIES = ("associative", "sequential", "chunked", "pallas", "fused",
              "auto")


def resolve_strategy(strategy: str) -> str:
    """Resolve the config-level ``scan_strategy`` to a concrete strategy."""
    if strategy == "auto":
        return "fused"
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown scan strategy {strategy!r}")
    return strategy


def scan_linear(a: Array, b: Array, h0: Optional[Array] = None,
                axis: int = -2, strategy: str = "associative",
                chunk: int = 256) -> Array:
    """Unified entry point used by the model layers."""
    if strategy == "associative":
        return scan_associative(a, b, h0, axis=axis)
    if strategy == "sequential":
        return scan_sequential(a, b, h0, axis=axis)
    if strategy == "chunked":
        return scan_chunked(a, b, h0, chunk=chunk, axis=axis)
    if strategy == "pallas":
        # the TPU kernel path (interpret mode on CPU); time axis must be -2
        from repro.kernels.scan import ops as scan_kernel_ops
        if axis not in (-2, a.ndim - 2):
            raise ValueError("pallas scan requires time axis -2")
        return scan_kernel_ops.linear_scan_auto(a, b, h0)
    raise ValueError(f"unknown scan strategy {strategy!r}")
