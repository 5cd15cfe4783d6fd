"""Pallas TPU kernel: chunked first-order linear scan.

TPU-native adaptation of the paper's parallel scan (DESIGN.md §3):

  * grid = (batch, feature_tiles, time_chunks); the time dimension is the
    LAST grid axis so it executes sequentially on a core ("arbitrary"
    dimension semantics), giving us a legal cross-chunk carry;
  * each (chunk, feature_tile) block of a/b lives in VMEM -- (bt, bd) with
    bt a multiple of 8 (sublanes) and bd a multiple of 128 (lanes);
  * the in-chunk inclusive prefix is a Kogge-Stone doubling ladder of
    elementwise VPU ops (log2(bt) steps), never touching the MXU;
  * the carry h between chunks is a (1, bd) fp32 VMEM scratch accumulator.

HBM traffic: reads a,b once, writes h once -- the roofline optimum for an
elementwise scan (arithmetic intensity ~ log2(bt)/6 flops/byte).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kogge_stone(a: jax.Array, b: jax.Array):
    """Inclusive scan of (a, b) segments along axis 0 of a (bt, bd) tile.

    combine((A_l,B_l),(A_r,B_r)) = (A_l*A_r, A_r*B_l + B_r); log2(bt) steps,
    each a full-tile shift + multiply-add (vectorizes on 8x128 VPU lanes).
    """
    bt = a.shape[0]
    A, B = a, b
    shift = 1
    while shift < bt:
        A_prev = jnp.concatenate(
            [jnp.ones((shift,) + A.shape[1:], A.dtype), A[:-shift]], axis=0)
        B_prev = jnp.concatenate(
            [jnp.zeros((shift,) + B.shape[1:], B.dtype), B[:-shift]], axis=0)
        B = A * B_prev + B
        A = A * A_prev
        shift *= 2
    return A, B


def _log_kogge_stone(la: jax.Array, lb: jax.Array):
    """Inclusive scan of log-space (log_a, log_b) segments along axis 0.

    Same doubling ladder as :func:`_kogge_stone` but with the combine done
    entirely in log space,

        combine((La_l, Lb_l), (La_r, Lb_r))
            = (La_l + La_r, logaddexp(La_r + Lb_l, Lb_r)),

    so no cumulative product/sum is ever materialised in linear space --
    this is the in-kernel equivalent of the Heinsen (2023) scan.  Identity
    element: (log_a, log_b) = (0, -inf).
    """
    bt = la.shape[0]
    A, B = la, lb
    shift = 1
    while shift < bt:
        A_prev = jnp.concatenate(
            [jnp.zeros((shift,) + A.shape[1:], A.dtype), A[:-shift]], axis=0)
        B_prev = jnp.concatenate(
            [jnp.full((shift,) + B.shape[1:], -jnp.inf, B.dtype),
             B[:-shift]], axis=0)
        B = jnp.logaddexp(A + B_prev, B)
        A = A + A_prev
        shift *= 2
    return A, B


def _scan_kernel(a_ref, b_ref, h0_ref, o_ref, carry_ref):
    """One (batch row, feature tile, time chunk) block."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        carry_ref[...] = h0_ref[0].astype(carry_ref.dtype)

    a = a_ref[0].astype(jnp.float32)          # (bt, bd)
    b = b_ref[0].astype(jnp.float32)
    A, B = _kogge_stone(a, b)
    h = B + A * carry_ref[...]                # carry broadcasts (1, bd)
    o_ref[0, ...] = h.astype(o_ref.dtype)
    carry_ref[...] = h[-1:]


@functools.partial(jax.jit, static_argnames=("block_t", "block_d",
                                             "interpret"))
def linear_scan_kernel(a: jax.Array, b: jax.Array, h0: jax.Array,
                       *, block_t: int = 256, block_d: int = 128,
                       interpret: bool = True) -> jax.Array:
    """h_t = a_t * h_{t-1} + b_t via the Pallas chunked-scan kernel.

    a, b: (B, T, D); h0: (B, D).  T % block_t == 0 and D % block_d == 0
    (ops.py pads).  interpret=True executes the kernel body on CPU; on a
    real TPU pass interpret=False.
    """
    bsz, t, d = a.shape
    assert t % block_t == 0 and d % block_d == 0, (t, d, block_t, block_d)
    grid = (bsz, d // block_d, t // block_t)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    return pl.pallas_call(
        _scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_d), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, block_t, block_d), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, 1, block_d), lambda i, j, k: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_d),
                               lambda i, j, k: (i, k, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, t, d), b.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(a, b, h0[:, None, :])


def _log_scan_kernel(la_ref, lb_ref, lh0_ref, o_ref, carry_ref):
    """One (batch row, feature tile, time chunk) block of the log-space scan.

    Inputs are log coefficients / log values; the cross-chunk carry stays in
    LOG space (the per-chunk logaddexp ladder is the rescaling: nothing is
    exponentiated until the final write), so arbitrarily long products of
    a_t in (0, 1) never underflow.  Output is h = exp(log_h), linear space.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        carry_ref[...] = lh0_ref[0].astype(carry_ref.dtype)

    la = la_ref[0].astype(jnp.float32)        # (bt, bd) cumulative log a
    lb = lb_ref[0].astype(jnp.float32)
    A, B = _log_kogge_stone(la, lb)
    log_h = jnp.logaddexp(B, A + carry_ref[...])   # carry: (1, bd) log h
    o_ref[0, ...] = jnp.exp(log_h).astype(o_ref.dtype)
    carry_ref[...] = log_h[-1:]


@functools.partial(jax.jit, static_argnames=("block_t", "block_d",
                                             "interpret"))
def log_scan_kernel(log_a: jax.Array, log_b: jax.Array, log_h0: jax.Array,
                    *, block_t: int = 256, block_d: int = 128,
                    interpret: bool = True) -> jax.Array:
    """h_t = exp(log_a_t) * h_{t-1} + exp(log_b_t) via the log-space kernel.

    log_a, log_b: (B, T, D); log_h0: (B, D), -inf encodes h0 = 0.  Output is
    h in linear space; all intermediate state (cumulative coefficients and
    the cross-chunk carry) stays in log space.  T % block_t == 0 and
    D % block_d == 0 (ops.py pads with the identity (0, -inf)).
    """
    bsz, t, d = log_a.shape
    assert t % block_t == 0 and d % block_d == 0, (t, d, block_t, block_d)
    grid = (bsz, d // block_d, t // block_t)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    return pl.pallas_call(
        _log_scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_d), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, block_t, block_d), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, 1, block_d), lambda i, j, k: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_d),
                               lambda i, j, k: (i, k, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, t, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(log_a, log_b, log_h0[:, None, :])
