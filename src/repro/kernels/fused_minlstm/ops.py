"""Jitted wrapper for the fused minLSTM kernel, with a custom VJP.

Mirrors ``kernels/fused_mingru/ops.py``: the forward is one Pallas call
(three MXU projections + VPU gates + chunked scan, only h leaves VMEM);
the backward's sequential piece is the reversed Pallas linear-scan kernel

    g_t = dL/dh_t + f'_{t+1} g_{t+1}

and the gate/projection gradients (dWf/dWi/dWh/dx/db*, including the
f' = f/(f+i) normalisation jacobian) come from XLA's vjp of the
rematerialised fp32 gate computation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import min_lstm, nn
from repro.kernels import resolve_interpret
from repro.kernels.fused_minlstm import kernel as _kernel
from repro.kernels.scan import ops as scan_ops


def _run(x, wf, bf, wi, bi, wh, bh, h0, mode, normalize, block_t, block_dh,
         interpret):
    """Pad T to the time tile and Dh to the feature tile, run, slice."""
    interpret = resolve_interpret(interpret)
    t, dh = x.shape[1], wf.shape[1]
    bt = scan_ops.round_block_t(block_t, t)
    x, _ = scan_ops.pad_to(x, bt, 1)
    wf, _ = scan_ops.pad_to(wf, block_dh, 1)
    wi, _ = scan_ops.pad_to(wi, block_dh, 1)
    wh, _ = scan_ops.pad_to(wh, block_dh, 1)
    bf, _ = scan_ops.pad_to(bf, block_dh, 0)
    bi, _ = scan_ops.pad_to(bi, block_dh, 0)
    bh, _ = scan_ops.pad_to(bh, block_dh, 0)
    h0, _ = scan_ops.pad_to(h0, block_dh, 1)
    out = _kernel.fused_minlstm_kernel(x, wf, bf, wi, bi, wh, bh, h0,
                                       block_t=bt, block_dh=block_dh,
                                       mode=mode, normalize=normalize,
                                       interpret=interpret)
    return out[:, :t, :dh]


def _gates_fp32(x, wf, bf, wi, bi, wh, bh, mode, normalize):
    """Rematerialised (a, b) scan inputs, fp32 (kernel-internal dtype)."""
    x32 = x.astype(jnp.float32)
    kf = x32 @ wf.astype(jnp.float32) + bf.astype(jnp.float32)
    ki = x32 @ wi.astype(jnp.float32) + bi.astype(jnp.float32)
    v = x32 @ wh.astype(jnp.float32) + bh.astype(jnp.float32)
    if normalize:
        f, i = min_lstm.normalized_gates(kf, ki)
    else:
        f, i = jax.nn.sigmoid(kf), jax.nn.sigmoid(ki)
    if mode == "log":
        h_tilde = nn.g(v)
    else:
        h_tilde = v
    return f, i * h_tilde


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11, 12))
def _fused_minlstm(x, wf, bf, wi, bi, wh, bh, h0, mode, normalize, block_t,
                   block_dh, interpret):
    return _run(x, wf, bf, wi, bi, wh, bh, h0, mode, normalize, block_t,
                block_dh, interpret)


def _fwd(x, wf, bf, wi, bi, wh, bh, h0, mode, normalize, block_t, block_dh,
         interpret):
    h = _run(x, wf, bf, wi, bi, wh, bh, h0, mode, normalize, block_t,
             block_dh, interpret)
    return h, (x, wf, bf, wi, bi, wh, bh, h0, h)


def _bwd(mode, normalize, block_t, block_dh, interpret, res, dh):
    x, wf, bf, wi, bi, wh, bh, h0, h = res
    gates = functools.partial(_gates_fp32, mode=mode, normalize=normalize)
    (a, _), pull = jax.vjp(gates, x, wf, bf, wi, bi, wh, bh)
    g, h_prev, dh0 = scan_ops.reverse_scan_grads(
        a, dh.astype(jnp.float32), h.astype(jnp.float32),
        h0.astype(jnp.float32), block_t, block_dh, interpret)
    dx, dwf, dbf, dwi, dbi, dwh, dbh = pull((g * h_prev, g))
    return dx, dwf, dbf, dwi, dbi, dwh, dbh, dh0.astype(h0.dtype)


_fused_minlstm.defvjp(_fwd, _bwd)


def fused_minlstm(x: jax.Array, wf: jax.Array, bf: Optional[jax.Array],
                  wi: jax.Array, bi: Optional[jax.Array],
                  wh: jax.Array, bh: Optional[jax.Array],
                  h0: Optional[jax.Array] = None, *, mode: str = "log",
                  normalize: bool = True, block_t: int = 256,
                  block_dh: int = 128,
                  interpret: Optional[bool] = None) -> jax.Array:
    """minLSTM layer forward (projections + recurrence) in one Pallas call.

    Differentiable in x, the three weight/bias pairs and h0.
    """
    bsz = x.shape[0]
    dh = wf.shape[1]
    if bf is None:
        bf = jnp.zeros((dh,), x.dtype)
    if bi is None:
        bi = jnp.zeros((dh,), x.dtype)
    if bh is None:
        bh = jnp.zeros((dh,), x.dtype)
    if h0 is None:
        h0 = jnp.zeros((bsz, dh), x.dtype)
    return _fused_minlstm(x, wf, bf, wi, bi, wh, bh, h0, mode, normalize,
                          block_t, block_dh, interpret)
