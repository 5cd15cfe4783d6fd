"""Padded public wrappers for the fused whole-block decode kernel.

``fused_block_step`` / ``fused_block_chunk`` take a minRNN residual
block's own param dict (``blocks.init`` layout) plus its carried decode
state and run the ENTIRE block -- norm, conv step, cell, down-proj,
MLP -- in one ``pallas_call``.  Dispatch: ``blocks.step`` /
``blocks.step_chunk`` route here when ``scan_strategy`` resolves to
``"fused"`` and the block's ``fuse_block`` knob allows it (rmsnorm
blocks, layer not sliced by tensor-parallel serving -- the TP psum must
stay outside the kernel, so sharded layers fall back to the cell-fused
tier).

Dtype contract: the compute-dtype cast points inside the kernel body
replicate the unfused composition exactly -- norm scales are passed
UNCAST (``rmsnorm_apply`` reads them in fp32 from the param dtype),
conv weights are passed uncast (``causal_conv_step`` casts to the
activation dtype in place), gate / down / MLP weights are pre-cast here
exactly where ``_fused_step_args`` / ``dense_apply`` cast them.

Padding: batch pads to the fp32 sublane multiple (padded rows carry
zeros; chunk rows get valid=0 and freeze).  Under interpret mode the
feature dims are NOT padded and the grid is forced to a single tile --
every op in the kernel body is then the identical jnp op on identical
values, which is the bit-exactness contract the tier-1 parity tests
pin (same single-tile policy as ``kernels/decode_step``).  On a real
TPU backend the feature dims pad to the lane/tile grid (zero pad
columns are inert through the whole residual chain: zero norm-scale,
conv, gate and projection pads keep them zero) and ``block_dh`` tiles
the Dh axis -- exact per feature tile, autotuned via
``benchmarks/autotune.py``.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.block_step import kernel as _kernel
from repro.kernels.scan.ops import pad_to

_SUBLANES = 8     # fp32 sublane multiple; bf16 inputs are upcast in-kernel
_LANES = 128
_MAX_BLOCK_DH = 512   # default Dh tile ceiling on real backends


_GATES = {"mingru": ("wz", "wh"), "minlstm": ("wf", "wi", "wh")}


def _cast(a, cd):
    return a if cd is None else a.astype(cd)


def _gate_operands(params, cd, x_dtype, cell):
    """(w, b) per gate with ``_fused_step_args``'s compute-dtype cast;
    missing biases become zeros (cell wrappers do the same)."""
    out = []
    for name in _GATES[cell]:
        p = params["rnn"][name]
        w = _cast(p["kernel"], cd)
        b = p.get("bias")
        b = jnp.zeros((w.shape[1],), cd or x_dtype) if b is None \
            else _cast(b, cd)
        out.append((w, b))
    return out


def _tile_plan(dx, dh, dm, block_dh, interpret):
    """(dx_pad, dh_pad, dm_pad, block_dh).  Interpret mode: unpadded
    features, single tile (bit-exactness).  Real backend: lane-aligned
    pads, Dh tiled."""
    if interpret:
        return dx, dh, dm, dh
    rnd = lambda v: -(-v // _LANES) * _LANES if v else 0
    dxp, dmp = rnd(dx), rnd(dm)
    bdh = rnd(block_dh) if block_dh else min(rnd(dh), _MAX_BLOCK_DH)
    dhp = -(-dh // bdh) * bdh
    return dxp, dhp, dmp, bdh


def _row(v, n):
    """A bias / scale vector as the (1, n) row the kernel reads."""
    return pad_to(v, n, 0)[0][None, :]


def _pack(params, x, h, win, valid, *, cell, use_conv, use_mlp, cd,
          block_dh, interpret):
    """Pad everything to the kernel grid and build the flat operand
    tuple in ``kernel._specs`` order.  Returns (operands, dims)."""
    dx = x.shape[-1]
    dh = h.shape[-1]
    dm = params["mlp_in"]["kernel"].shape[1] if use_mlp else 0
    dxp, dhp, dmp, bdh = _tile_plan(dx, dh, dm, block_dh, interpret)

    xp, _ = pad_to(x, _SUBLANES, 0)
    bsz = x.shape[0]
    xp, _ = pad_to(xp, dxp, -1)
    ops = [xp, _row(params["norm_rnn"]["scale"], dxp)]
    if use_conv:
        win = jnp.swapaxes(pad_to(win, _SUBLANES, 0)[0], 0, 1)
        ops += [pad_to(params["conv"]["kernel"], dxp, 1)[0],
                _row(params["conv"]["bias"], dxp),
                pad_to(win, dxp, -1)[0]]
    for w, b in _gate_operands(params, cd, x.dtype, cell):
        ops += [pad_to(pad_to(w, dxp, 0)[0], dhp, 1)[0], _row(b, dhp)]
    ops.append(pad_to(pad_to(h, _SUBLANES, 0)[0], dhp, -1)[0])
    ops.append(pad_to(pad_to(_cast(params["down"]["kernel"], cd),
                             dhp, 0)[0], dxp, 1)[0])
    if use_mlp:
        ops += [_row(params["norm_mlp"]["scale"], dxp),
                pad_to(pad_to(_cast(params["mlp_in"]["kernel"], cd),
                              dxp, 0)[0], dmp, 1)[0],
                _row(_cast(params["mlp_in"]["bias"], cd), dmp),
                pad_to(pad_to(_cast(params["mlp_out"]["kernel"], cd),
                              dmp, 0)[0], dxp, 1)[0],
                _row(_cast(params["mlp_out"]["bias"], cd), dxp)]
    if valid is not None:
        ops.append(pad_to(valid.astype(jnp.int32)[:, None],
                          _SUBLANES, 0)[0])
    return tuple(ops), (bsz, dx, dh, bdh)


def _flat_lead(arrs, n_trail):
    """Collapse leading dims to one batch dim; returns (flats, lead)."""
    lead = arrs[0].shape[:-n_trail[0]]
    if len(lead) == 1:
        return list(arrs), None
    n = math.prod(lead)
    return [a.reshape((n,) + a.shape[len(lead):])
            for a in arrs], lead


def fused_block_step(params, x_t: jax.Array, state: dict, *,
                     cell: str = "mingru", mode: str = "log",
                     use_conv: bool = False, use_mlp: bool = False,
                     compute_dtype=None, block_dh: int = 0,
                     interpret: Optional[bool] = None):
    """One whole-block decode step in one Pallas call.  x_t: (..., D),
    state: {"h": (..., Dh)[, "conv": (..., K-1, D)]} -> (y, new_state),
    bit-identical to ``blocks.step`` on the cell-fused path (single
    feature tile)."""
    interpret = resolve_interpret(interpret)
    win = state.get("conv") if use_conv else None
    arrs = [x_t, state["h"]] + ([win] if use_conv else [])
    trails = [1, 1] + ([2] if use_conv else [])
    (x_f, h_f, *rest), lead = _flat_lead(arrs, trails)
    win_f = rest[0] if use_conv else None

    operands, (bsz, dx, dh, bdh) = _pack(
        params, x_f, h_f, win_f, None, cell=cell, use_conv=use_conv,
        use_mlp=use_mlp, cd=compute_dtype, block_dh=block_dh,
        interpret=interpret)
    outs = _kernel.block_step_kernel(
        operands, cell=cell, mode=mode, use_conv=use_conv,
        use_mlp=use_mlp, block_dh=bdh, dx_true=dx, interpret=interpret)
    y, h = outs[0][:bsz, :dx], outs[1][:bsz, :dh]
    new_state = dict(state)
    new_state["h"] = h
    if use_conv:
        new_state["conv"] = jnp.swapaxes(outs[2], 0, 1)[:bsz, :, :dx]
    if lead is not None:
        y = y.reshape(lead + y.shape[1:])
        new_state = {k: v.reshape(lead + v.shape[1:])
                     for k, v in new_state.items()}
    return y, new_state


def fused_block_chunk(params, x: jax.Array, state: dict,
                      valid: jax.Array, *, cell: str = "mingru",
                      mode: str = "log", use_conv: bool = False,
                      use_mlp: bool = False, compute_dtype=None,
                      block_dh: int = 0, return_positions: bool = False,
                      interpret: Optional[bool] = None):
    """Varlen C-token whole-block chunk in one Pallas call (the packed
    prefill / speculative-verify form).  x: (B, C, D), valid: (B,) int32
    in [1, C] -> (ys, new_state[, per-position states]), matching
    ``blocks.step_chunk`` with ``return_positions``."""
    chunk = x.shape[1]
    interpret = resolve_interpret(interpret)
    win = state.get("conv") if use_conv else None

    # weight/state operands from a (B, D) probe, then swap in the padded
    # time-major chunk (the kernel's fori_loop wants (C, B, D))
    operands, (bsz, dx, dh, bdh) = _pack(
        params, x[:, 0], state["h"], win, valid, cell=cell,
        use_conv=use_conv, use_mlp=use_mlp, cd=compute_dtype,
        block_dh=block_dh, interpret=interpret)
    xp, _ = pad_to(x, _SUBLANES, 0)
    xp, _ = pad_to(xp, operands[0].shape[-1], -1)
    operands = (jnp.swapaxes(xp, 0, 1),) + operands[1:]

    outs = _kernel.block_chunk_kernel(
        operands, cell=cell, mode=mode, use_conv=use_conv,
        use_mlp=use_mlp, block_dh=bdh, dx_true=dx, interpret=interpret)
    ys = jnp.swapaxes(outs[0], 0, 1)[:bsz, :chunk, :dx]
    hs = jnp.swapaxes(outs[1], 0, 1)[:bsz, :chunk, :dh]
    new_state = dict(state)
    new_state["h"] = hs[:, -1]          # frozen rows: == hs[:, valid-1]
    pos_states = {"h": hs}
    if use_conv:
        wins = jnp.moveaxis(outs[2], 2, 0)[:bsz, :chunk, :, :dx]
        new_state["conv"] = wins[:, -1]
        pos_states["conv"] = wins
    if return_positions:
        return ys, new_state, pos_states
    return ys, new_state
