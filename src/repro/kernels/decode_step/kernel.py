"""Pallas TPU kernel: fused minGRU/minLSTM single-token decode step.

Decode rolls the O(1) recurrence one token at a time, so the per-step
compute is a *batched GEMV*: x_t (B, Dx) against the gate projections
(Dx, Dh) followed by a handful of elementwise VPU ops.  Unfused, XLA
materialises the gate pre-activations k/v (B, Dh) in HBM between the
matmul and the state update and launches one fusion per projection; at
decode batch sizes the step is weight-bound, so every extra HBM
round-trip and launch is pure latency on the serving hot path.

This kernel runs the whole cell step in ONE pallas_call per layer:

  * both (minGRU) / all three (minLSTM) projections on the MXU from a
    single resident (B, Dx) input tile;
  * the sigmoid / g() gate transforms, the numerically stable minLSTM
    f/(f+i) normalisation (Algorithm 8 exponentiated -- naive division
    NaNs at saturated gates), and the convex state update
    h = a * h_prev + b on the VPU;
  * only the new h (B, Dh) is written back.

Grid = (Dh tiles,): the x tile is pinned by its index_map so Mosaic
keeps it resident across feature tiles, and the weight tiles stream
through VMEM once per step.  The layer stack is dispatched as ONE
lax.scan over stacked weights by ``models/lm.decode_step`` (the weights
stay device-resident across the whole multi-token decode loop -- the
weight-stationary serving regime), and ``lm.superstep`` wraps that step
in a second on-device scan so K rounds -- prefilling and decoding slots
alike -- cost one host round-trip.

All arithmetic is fp32 in-kernel regardless of input dtype (matching
the fused parallel kernels, so prefill -> decode handoff is consistent);
bf16 inputs are upcast on load and the output is cast back.

The ``*_chunk_kernel`` variants amortise the weight stream over a packed
prompt chunk: one pallas_call keeps the gate weight tiles VMEM-resident
while a ``fori_loop`` replays up to C per-token step updates with
per-row ``valid``-length freezing -- the serving superstep's prompt
*packing* path (C prompt tokens per weight stream instead of 1 in the
weight-bound regime), bit-identical to C sequential step-kernel calls.
The SAME chunk variants are the speculative-decoding *verify* primitive
(``lm.decode_verify``): they emit the recurrent state after every
position, so accepting a leading run of drafts and rolling back to the
first rejection is one O(d_hidden) gather per slot -- no extra kernel,
no recompute.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import min_lstm, nn


def _mingru_step_kernel(x_ref, wz_ref, bz_ref, wh_ref, bh_ref, h_ref,
                        o_ref, *, mode: str):
    x = x_ref[...].astype(jnp.float32)                    # (B, Dx)
    wz = wz_ref[...].astype(jnp.float32)                  # (Dx, bdh)
    wh = wh_ref[...].astype(jnp.float32)
    bz = bz_ref[...].astype(jnp.float32)
    bh = bh_ref[...].astype(jnp.float32)
    k = jnp.dot(x, wz, preferred_element_type=jnp.float32) + bz
    v = jnp.dot(x, wh, preferred_element_type=jnp.float32) + bh
    z = jax.nn.sigmoid(k)
    h_tilde = nn.g(v) if mode == "log" else v
    h_prev = h_ref[...].astype(jnp.float32)               # (B, bdh)
    o_ref[...] = ((1.0 - z) * h_prev + z * h_tilde).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_dh", "mode", "interpret"))
def mingru_step_kernel(x: jax.Array, wz: jax.Array, bz: jax.Array,
                       wh: jax.Array, bh: jax.Array, h_prev: jax.Array,
                       *, block_dh: int = 128, mode: str = "log",
                       interpret: bool = True) -> jax.Array:
    """x: (B, Dx), h_prev: (B, Dh) -> h_t: (B, Dh).  Dh % block_dh == 0
    and Dx % 128 == 0 (ops.py pads); B padded to a sublane multiple."""
    bsz, dx = x.shape
    dh = wz.shape[1]
    assert dh % block_dh == 0, (dh, block_dh)
    grid = (dh // block_dh,)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))

    return pl.pallas_call(
        functools.partial(_mingru_step_kernel, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bsz, dx), lambda j: (0, 0)),
            pl.BlockSpec((dx, block_dh), lambda j: (0, j)),
            pl.BlockSpec((1, block_dh), lambda j: (0, j)),
            pl.BlockSpec((dx, block_dh), lambda j: (0, j)),
            pl.BlockSpec((1, block_dh), lambda j: (0, j)),
            pl.BlockSpec((bsz, block_dh), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bsz, block_dh), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, dh), x.dtype),
        interpret=interpret,
        **kwargs,
    )(x, wz, bz.reshape(1, dh), wh, bh.reshape(1, dh), h_prev)


def _mingru_chunk_kernel(x_ref, wz_ref, bz_ref, wh_ref, bh_ref, h_ref,
                         valid_ref, o_ref, *, mode: str, chunk: int):
    """Variable-length C-token chunk: the weight tiles stay VMEM-resident
    while a ``fori_loop`` replays the *exact* per-token arithmetic of
    ``_mingru_step_kernel`` (same (B, Dx) @ (Dx, bdh) dot per token, same
    gate ops, same per-token cast to the output dtype), so a packed chunk
    is bit-identical to ``chunk`` sequential step-kernel calls -- while
    streaming the gate weights from HBM once instead of ``chunk`` times.
    Bit-exactness holds per feature tile on every backend: real TPU runs
    both kernels' grids tile-sequentially, and under interpret mode
    ops.py forces a single-tile grid (``_tile``), so step and chunk
    always execute the identical dot -- multi-tile configs included.
    Rows freeze once ``t >= valid[b]``: the update is masked and the
    frozen h is re-written, so ``o[valid[b]-1:]`` all hold the row's
    final state (the caller reads position ``valid[b]-1``)."""
    wz = wz_ref[...].astype(jnp.float32)                  # (Dx, bdh)
    wh = wh_ref[...].astype(jnp.float32)
    bz = bz_ref[...].astype(jnp.float32)
    bh = bh_ref[...].astype(jnp.float32)
    valid = valid_ref[...]                                # (B, 1) int32

    def body(t, h):
        x = x_ref[t].astype(jnp.float32)                  # (B, Dx)
        k = jnp.dot(x, wz, preferred_element_type=jnp.float32) + bz
        v = jnp.dot(x, wh, preferred_element_type=jnp.float32) + bh
        z = jax.nn.sigmoid(k)
        h_tilde = nn.g(v) if mode == "log" else v
        h_new = (1.0 - z) * h + z * h_tilde
        # per-token round-trip through the output dtype: sequential steps
        # re-read h from a cdtype cache, so the packed carry must quantize
        # identically for bf16 bit-exactness
        h_new = h_new.astype(o_ref.dtype).astype(jnp.float32)
        h = jnp.where(t < valid, h_new, h)
        o_ref[t] = h.astype(o_ref.dtype)
        return h

    jax.lax.fori_loop(0, chunk, body,
                      h_ref[...].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("block_dh", "mode", "interpret"))
def mingru_chunk_kernel(x: jax.Array, wz: jax.Array, bz: jax.Array,
                        wh: jax.Array, bh: jax.Array, h_prev: jax.Array,
                        valid: jax.Array, *, block_dh: int = 128,
                        mode: str = "log", interpret: bool = True
                        ) -> jax.Array:
    """x: (C, B, Dx) time-major, h_prev: (B, Dh), valid: (B, 1) int32 ->
    hs: (C, B, Dh).  Same tiling contract as :func:`mingru_step_kernel`;
    C rides the untiled leading axis so the in-kernel time index is a
    cheap leading-dim dynamic slice."""
    chunk, bsz, dx = x.shape
    dh = wz.shape[1]
    assert dh % block_dh == 0, (dh, block_dh)
    grid = (dh // block_dh,)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))

    return pl.pallas_call(
        functools.partial(_mingru_chunk_kernel, mode=mode, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, bsz, dx), lambda j: (0, 0, 0)),
            pl.BlockSpec((dx, block_dh), lambda j: (0, j)),
            pl.BlockSpec((1, block_dh), lambda j: (0, j)),
            pl.BlockSpec((dx, block_dh), lambda j: (0, j)),
            pl.BlockSpec((1, block_dh), lambda j: (0, j)),
            pl.BlockSpec((bsz, block_dh), lambda j: (0, j)),
            pl.BlockSpec((bsz, 1), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((chunk, bsz, block_dh), lambda j: (0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((chunk, bsz, dh), x.dtype),
        interpret=interpret,
        **kwargs,
    )(x, wz, bz.reshape(1, dh), wh, bh.reshape(1, dh), h_prev, valid)


def _minlstm_step_kernel(x_ref, wf_ref, bf_ref, wi_ref, bi_ref, wh_ref,
                         bh_ref, h_ref, o_ref, *, mode: str,
                         normalize: bool):
    x = x_ref[...].astype(jnp.float32)                    # (B, Dx)
    wf = wf_ref[...].astype(jnp.float32)
    wi = wi_ref[...].astype(jnp.float32)
    wh = wh_ref[...].astype(jnp.float32)
    kf = jnp.dot(x, wf, preferred_element_type=jnp.float32) \
        + bf_ref[...].astype(jnp.float32)
    ki = jnp.dot(x, wi, preferred_element_type=jnp.float32) \
        + bi_ref[...].astype(jnp.float32)
    v = jnp.dot(x, wh, preferred_element_type=jnp.float32) \
        + bh_ref[...].astype(jnp.float32)
    if normalize:
        # stable f/(f+i) -- the naive quotient is 0/0 = NaN at saturated
        # gates; same in-kernel call as kernels/fused_minlstm
        f, i = min_lstm.normalized_gates(kf, ki)
    else:
        f, i = jax.nn.sigmoid(kf), jax.nn.sigmoid(ki)
    h_tilde = nn.g(v) if mode == "log" else v
    h_prev = h_ref[...].astype(jnp.float32)
    o_ref[...] = (f * h_prev + i * h_tilde).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_dh", "mode", "normalize",
                                             "interpret"))
def minlstm_step_kernel(x: jax.Array, wf: jax.Array, bf: jax.Array,
                        wi: jax.Array, bi: jax.Array, wh: jax.Array,
                        bh: jax.Array, h_prev: jax.Array,
                        *, block_dh: int = 128, mode: str = "log",
                        normalize: bool = True,
                        interpret: bool = True) -> jax.Array:
    """x: (B, Dx), h_prev: (B, Dh) -> h_t: (B, Dh).  Same tiling contract
    as :func:`mingru_step_kernel`."""
    bsz, dx = x.shape
    dh = wf.shape[1]
    assert dh % block_dh == 0, (dh, block_dh)
    grid = (dh // block_dh,)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))

    return pl.pallas_call(
        functools.partial(_minlstm_step_kernel, mode=mode,
                          normalize=normalize),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bsz, dx), lambda j: (0, 0)),
            pl.BlockSpec((dx, block_dh), lambda j: (0, j)),
            pl.BlockSpec((1, block_dh), lambda j: (0, j)),
            pl.BlockSpec((dx, block_dh), lambda j: (0, j)),
            pl.BlockSpec((1, block_dh), lambda j: (0, j)),
            pl.BlockSpec((dx, block_dh), lambda j: (0, j)),
            pl.BlockSpec((1, block_dh), lambda j: (0, j)),
            pl.BlockSpec((bsz, block_dh), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bsz, block_dh), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, dh), x.dtype),
        interpret=interpret,
        **kwargs,
    )(x, wf, bf.reshape(1, dh), wi, bi.reshape(1, dh), wh,
      bh.reshape(1, dh), h_prev)


def _minlstm_chunk_kernel(x_ref, wf_ref, bf_ref, wi_ref, bi_ref, wh_ref,
                          bh_ref, h_ref, valid_ref, o_ref, *, mode: str,
                          normalize: bool, chunk: int):
    """minLSTM sibling of ``_mingru_chunk_kernel``: weights resident, one
    ``fori_loop`` of bit-exact ``_minlstm_step_kernel`` token updates with
    per-row ``valid`` freezing."""
    wf = wf_ref[...].astype(jnp.float32)
    wi = wi_ref[...].astype(jnp.float32)
    wh = wh_ref[...].astype(jnp.float32)
    bf = bf_ref[...].astype(jnp.float32)
    bi = bi_ref[...].astype(jnp.float32)
    bh = bh_ref[...].astype(jnp.float32)
    valid = valid_ref[...]                                # (B, 1) int32

    def body(t, h):
        x = x_ref[t].astype(jnp.float32)                  # (B, Dx)
        kf = jnp.dot(x, wf, preferred_element_type=jnp.float32) + bf
        ki = jnp.dot(x, wi, preferred_element_type=jnp.float32) + bi
        v = jnp.dot(x, wh, preferred_element_type=jnp.float32) + bh
        if normalize:
            f, i = min_lstm.normalized_gates(kf, ki)
        else:
            f, i = jax.nn.sigmoid(kf), jax.nn.sigmoid(ki)
        h_tilde = nn.g(v) if mode == "log" else v
        h_new = (f * h + i * h_tilde).astype(o_ref.dtype).astype(jnp.float32)
        h = jnp.where(t < valid, h_new, h)
        o_ref[t] = h.astype(o_ref.dtype)
        return h

    jax.lax.fori_loop(0, chunk, body,
                      h_ref[...].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("block_dh", "mode", "normalize",
                                             "interpret"))
def minlstm_chunk_kernel(x: jax.Array, wf: jax.Array, bf: jax.Array,
                         wi: jax.Array, bi: jax.Array, wh: jax.Array,
                         bh: jax.Array, h_prev: jax.Array, valid: jax.Array,
                         *, block_dh: int = 128, mode: str = "log",
                         normalize: bool = True,
                         interpret: bool = True) -> jax.Array:
    """x: (C, B, Dx) time-major, h_prev: (B, Dh), valid: (B, 1) int32 ->
    hs: (C, B, Dh).  Same contract as :func:`mingru_chunk_kernel`."""
    chunk, bsz, dx = x.shape
    dh = wf.shape[1]
    assert dh % block_dh == 0, (dh, block_dh)
    grid = (dh // block_dh,)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))

    return pl.pallas_call(
        functools.partial(_minlstm_chunk_kernel, mode=mode,
                          normalize=normalize, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, bsz, dx), lambda j: (0, 0, 0)),
            pl.BlockSpec((dx, block_dh), lambda j: (0, j)),
            pl.BlockSpec((1, block_dh), lambda j: (0, j)),
            pl.BlockSpec((dx, block_dh), lambda j: (0, j)),
            pl.BlockSpec((1, block_dh), lambda j: (0, j)),
            pl.BlockSpec((dx, block_dh), lambda j: (0, j)),
            pl.BlockSpec((1, block_dh), lambda j: (0, j)),
            pl.BlockSpec((bsz, block_dh), lambda j: (0, j)),
            pl.BlockSpec((bsz, 1), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((chunk, bsz, block_dh), lambda j: (0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((chunk, bsz, dh), x.dtype),
        interpret=interpret,
        **kwargs,
    )(x, wf, bf.reshape(1, dh), wi, bi.reshape(1, dh), wh,
      bh.reshape(1, dh), h_prev, valid)
