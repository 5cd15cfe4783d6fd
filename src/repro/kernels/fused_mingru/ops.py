"""Jitted wrapper for the fused minGRU kernel, with a custom VJP.

Forward (training, prefill, serving): one Pallas call runs both gate
projections on the MXU and the chunked scan on the VPU, writing only h --
the k, v: (B, T, Dh) gate activations never round-trip through HBM.

Backward: ``custom_vjp`` whose heavy sequential piece is the *same* Pallas
chunked-scan kernel reversed,

    g_t = dL/dh_t + (1 - z_{t+1}) g_{t+1}       (reverse linear scan)
    dL/da_t = g_t * h_{t-1},  dL/db_t = g_t      with (a, b) = (1-z, z*h~)

followed by the transposed projection matmuls (dWz/dWh/dx/db*), which XLA
derives from the rematerialised gate computation -- so forward AND backward
of the default training hot path run through Pallas (compiled on a TPU,
interpreted on the CPU).  The gate pre-activations are recomputed from x in the backward
(two matmuls, standard rematerialisation) rather than saved, keeping the
forward's HBM win.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import nn
from repro.kernels import resolve_interpret
from repro.kernels.fused_mingru import kernel as _kernel
from repro.kernels.scan import ops as scan_ops


def _run(x, wz, bz, wh, bh, h0, mode, block_t, block_dh, interpret):
    """Pad T to the time tile and Dh to the feature tile, run, slice."""
    interpret = resolve_interpret(interpret)
    t, dh = x.shape[1], wz.shape[1]
    bt = scan_ops.round_block_t(block_t, t)
    x, _ = scan_ops.pad_to(x, bt, 1)
    wz, _ = scan_ops.pad_to(wz, block_dh, 1)
    wh, _ = scan_ops.pad_to(wh, block_dh, 1)
    bz, _ = scan_ops.pad_to(bz, block_dh, 0)
    bh, _ = scan_ops.pad_to(bh, block_dh, 0)
    h0, _ = scan_ops.pad_to(h0, block_dh, 1)
    out = _kernel.fused_mingru_kernel(x, wz, bz, wh, bh, h0, block_t=bt,
                                      block_dh=block_dh, mode=mode,
                                      interpret=interpret)
    return out[:, :t, :dh]


def _gates_fp32(x, wz, bz, wh, bh, mode):
    """Rematerialised (a, b) scan inputs, fp32 (matches the kernel's
    internal compute dtype so backward residuals agree with forward)."""
    x32 = x.astype(jnp.float32)
    k = x32 @ wz.astype(jnp.float32) + bz.astype(jnp.float32)
    v = x32 @ wh.astype(jnp.float32) + bh.astype(jnp.float32)
    z = jax.nn.sigmoid(k)
    if mode == "log":
        h_tilde = nn.g(v)
    else:
        h_tilde = v
    return 1.0 - z, z * h_tilde


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _fused_mingru(x, wz, bz, wh, bh, h0, mode, block_t, block_dh, interpret):
    return _run(x, wz, bz, wh, bh, h0, mode, block_t, block_dh, interpret)


def _fwd(x, wz, bz, wh, bh, h0, mode, block_t, block_dh, interpret):
    h = _run(x, wz, bz, wh, bh, h0, mode, block_t, block_dh, interpret)
    return h, (x, wz, bz, wh, bh, h0, h)


def _bwd(mode, block_t, block_dh, interpret, res, dh):
    x, wz, bz, wh, bh, h0, h = res
    gates = functools.partial(_gates_fp32, mode=mode)
    (a, _), pull = jax.vjp(gates, x, wz, bz, wh, bh)
    g, h_prev, dh0 = scan_ops.reverse_scan_grads(
        a, dh.astype(jnp.float32), h.astype(jnp.float32),
        h0.astype(jnp.float32), block_t, block_dh, interpret)
    dx, dwz, dbz, dwh, dbh = pull((g * h_prev, g))
    return dx, dwz, dbz, dwh, dbh, dh0.astype(h0.dtype)


_fused_mingru.defvjp(_fwd, _bwd)


def fused_mingru(x: jax.Array, wz: jax.Array, bz: Optional[jax.Array],
                 wh: jax.Array, bh: Optional[jax.Array],
                 h0: Optional[jax.Array] = None, *, mode: str = "log",
                 block_t: int = 256, block_dh: int = 128,
                 interpret: Optional[bool] = None) -> jax.Array:
    """minGRU layer forward (projections + recurrence) in one Pallas call.

    Differentiable in x, wz, bz, wh, bh and h0 (carried state, so chunked
    prefill / TBPTT can backprop into the incoming carry).
    """
    bsz, _, _ = x.shape
    dh = wz.shape[1]
    if bz is None:
        bz = jnp.zeros((dh,), x.dtype)
    if bh is None:
        bh = jnp.zeros((dh,), x.dtype)
    if h0 is None:
        h0 = jnp.zeros((bsz, dh), x.dtype)
    return _fused_mingru(x, wz, bz, wh, bh, h0, mode, block_t, block_dh,
                         interpret)
