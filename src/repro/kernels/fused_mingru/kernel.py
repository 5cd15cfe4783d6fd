"""Pallas TPU kernel: fused minGRU (gate projections + scan).

Why fuse (DESIGN.md §3): unfused, XLA materializes the gate activations
k, v: (B, T, 2*Dh) in HBM between the matmul and the scan -- for the paper's
LM block that is 2x the layer's activation traffic.  This kernel keeps a
(bt, Dx) input tile and the (Dx, bdh) weight tiles in VMEM, runs both
projections on the MXU, applies the sigmoid/g gates and the Kogge-Stone
scan on the VPU, and writes only h.  Per-block HBM traffic drops from
reading x + writing k,v + reading k,v + writing h  to  reading x + weights
+ writing h.

VMEM per grid step, at the paper LMs' published widths (Dx = 768, bf16,
bt = 256, bdh = 128): the x tile 256 x 768 x 2 B = 384 KiB and two
768 x 128 weight tiles of 192 KiB, each double-buffered, plus their fp32
copies and a few (bt, bdh) fp32 gate tiles -- about 3 MiB, inside
Mosaic's default 16 MiB scope (v5e has 128 MiB of VMEM).  Forward and
forward+backward compile for a v5e at B = 8, T = 2048
(``tests/test_tpu_compile.py``).  The weight blocks' index_map ignores
the sequential time axis, so Mosaic fetches them once per feature tile.
Vectors ride as (1, n) rows and h0 as (B, 1, Dh): the TPU tiling needs
the last two block dims to be multiples of (8, 128) or the full array
dims.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import nn
from repro.kernels.scan.kernel import _kogge_stone


def _fused_kernel(x_ref, wz_ref, bz_ref, wh_ref, bh_ref, h0_ref,
                  o_ref, carry_ref, *, mode: str):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        carry_ref[...] = h0_ref[0].astype(carry_ref.dtype)

    x = x_ref[0].astype(jnp.float32)                      # (bt, Dx)
    wz = wz_ref[...].astype(jnp.float32)                  # (Dx, bdh)
    wh = wh_ref[...].astype(jnp.float32)
    bz = bz_ref[...].astype(jnp.float32)
    bh = bh_ref[...].astype(jnp.float32)
    k = jnp.dot(x, wz, preferred_element_type=jnp.float32) + bz
    v = jnp.dot(x, wh, preferred_element_type=jnp.float32) + bh
    z = jax.nn.sigmoid(k)
    if mode == "log":
        h_tilde = nn.g(v)
    else:
        h_tilde = v
    A, B = _kogge_stone(1.0 - z, z * h_tilde)
    h = B + A * carry_ref[...]
    o_ref[0, ...] = h.astype(o_ref.dtype)
    carry_ref[...] = h[-1:]


@functools.partial(jax.jit, static_argnames=("block_t", "block_dh", "mode",
                                             "interpret"))
def fused_mingru_kernel(x: jax.Array, wz: jax.Array, bz: jax.Array,
                        wh: jax.Array, bh: jax.Array, h0: jax.Array,
                        *, block_t: int = 256, block_dh: int = 128,
                        mode: str = "log", interpret: bool = True):
    """x: (B, T, Dx) -> h: (B, T, Dh).  T % block_t == 0, Dh % block_dh == 0."""
    bsz, t, dx = x.shape
    dh = wz.shape[1]
    assert t % block_t == 0 and dh % block_dh == 0, (t, dh)
    grid = (bsz, dh // block_dh, t // block_t)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    return pl.pallas_call(
        functools.partial(_fused_kernel, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, dx), lambda i, j, k: (i, k, 0)),
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
    )(x, wz, bz.reshape(1, dh), wh, bh.reshape(1, dh), h0[:, None, :])
