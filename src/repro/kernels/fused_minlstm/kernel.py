"""Pallas TPU kernel: fused minLSTM (three gate projections + scan).

Sibling of ``kernels/fused_mingru``: unfused, XLA materialises the gate
activations kf, ki, v: (B, T, 3*Dh) in HBM between the matmuls and the
scan.  This kernel keeps the (bt, Dx) input tile and the three (Dx, bdh)
weight tiles in VMEM, runs the projections on the MXU, applies the
sigmoid / normalisation / g() gates and the Kogge-Stone scan on the VPU,
and writes only h.

The paper's length-independence normalisation (Section 3.2) is computed
in-kernel: f' = f/(f+i), i' = i/(f+i), then h_t = f' h_{t-1} + i' h~_t.
VMEM: one more weight tile than the minGRU kernel (see there), still
inside Mosaic's default 16 MiB scope at the paper LMs' widths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import min_lstm, nn
from repro.kernels.scan.kernel import _kogge_stone


def _fused_kernel(x_ref, wf_ref, bf_ref, wi_ref, bi_ref, wh_ref, bh_ref,
                  h0_ref, o_ref, carry_ref, *, mode: str, normalize: bool):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        carry_ref[...] = h0_ref[0].astype(carry_ref.dtype)

    x = x_ref[0].astype(jnp.float32)                      # (bt, Dx)
    wf = wf_ref[...].astype(jnp.float32)                  # (Dx, bdh)
    wi = wi_ref[...].astype(jnp.float32)
    wh = wh_ref[...].astype(jnp.float32)
    kf = (jnp.dot(x, wf, preferred_element_type=jnp.float32)
          + bf_ref[...].astype(jnp.float32))
    ki = (jnp.dot(x, wi, preferred_element_type=jnp.float32)
          + bi_ref[...].astype(jnp.float32))
    v = (jnp.dot(x, wh, preferred_element_type=jnp.float32)
         + bh_ref[...].astype(jnp.float32))
    if normalize:
        f, i = min_lstm.normalized_gates(kf, ki)
    else:
        f, i = jax.nn.sigmoid(kf), jax.nn.sigmoid(ki)
    if mode == "log":
        h_tilde = nn.g(v)
    else:
        h_tilde = v
    A, B = _kogge_stone(f, i * h_tilde)
    h = B + A * carry_ref[...]
    o_ref[0, ...] = h.astype(o_ref.dtype)
    carry_ref[...] = h[-1:]


@functools.partial(jax.jit, static_argnames=("block_t", "block_dh", "mode",
                                             "normalize", "interpret"))
def fused_minlstm_kernel(x: jax.Array, wf: jax.Array, bf: jax.Array,
                         wi: jax.Array, bi: jax.Array,
                         wh: jax.Array, bh: jax.Array, h0: jax.Array,
                         *, block_t: int = 256, block_dh: int = 128,
                         mode: str = "log", normalize: bool = True,
                         interpret: bool = True):
    """x: (B, T, Dx) -> h: (B, T, Dh).  T % block_t == 0, Dh % block_dh == 0."""
    bsz, t, dx = x.shape
    dh = wf.shape[1]
    assert t % block_t == 0 and dh % block_dh == 0, (t, dh)
    grid = (bsz, dh // block_dh, t // block_t)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    return pl.pallas_call(
        functools.partial(_fused_kernel, mode=mode, normalize=normalize),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, dx), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((dx, block_dh), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_dh), lambda i, j, k: (0, j)),
            pl.BlockSpec((dx, block_dh), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_dh), lambda i, j, k: (0, j)),
            pl.BlockSpec((dx, block_dh), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_dh), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, 1, block_dh), lambda i, j, k: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_dh),
                               lambda i, j, k: (i, k, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, t, dh), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_dh), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(x, wf, bf.reshape(1, dh), wi, bi.reshape(1, dh), wh,
      bh.reshape(1, dh), h0[:, None, :])
