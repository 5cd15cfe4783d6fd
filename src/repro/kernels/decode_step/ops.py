"""Padded public wrappers for the fused decode-step kernels.

``fused_mingru_step`` / ``fused_minlstm_step`` accept arbitrary batch
leading dims, any Dx/Dh (padded up to the kernel tile grid with zeros --
zero-padded contraction columns contribute nothing to the GEMVs, and
padded feature columns are sliced off the output), and optional biases.
No custom VJP: decode is inference-only; training/prefill differentiate
through the fused *parallel* kernels instead.

Dispatch: ``core.min_gru.step`` / ``core.min_lstm.step`` route here when
their ``scan_strategy`` resolves to ``"fused"`` (the config default
``"auto"``), which is how ``blocks.step`` -> ``lm.decode_step`` ->
``lm.superstep`` put the whole serving hot path on Pallas: the engine's
unified device loop drives prefilling (teacher-forced prompt tokens) and
decoding rows through this same kernel in the same round -- compiled on
a TPU, interpreted on the CPU (``repro.kernels.resolve_interpret``).

The ``*_chunk`` wrappers serve double duty: packed prefill
(``lm.decode_chunk``) and speculative-decode verification
(``lm.decode_verify``) are the same masked varlen replay -- the chunk's
per-position states ARE the rollback table, so both callers share one
kernel and one parity contract.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.decode_step import kernel as _kernel
from repro.kernels.scan.ops import call_with_flat_lead, pad_to

_SUBLANES = 8     # fp32 sublane multiple; bf16 inputs are upcast in-kernel
_LANES = 128


def _pad_batch(x, h_prev):
    x, b = pad_to(x, _SUBLANES, 0)
    h_prev, _ = pad_to(h_prev, _SUBLANES, 0)
    return x, h_prev, b


def _tile(dh: int, block_dh: int, interpret: bool) -> int:
    """Force a SINGLE-tile grid under interpret mode: there the grid is
    a traced loop, so a multi-tile step kernel unrolls into straight-line
    per-tile dots that XLA merges into one fused dot -- an accumulation
    order the chunk kernels' ``fori_loop`` body cannot reproduce (the
    historical "~1 ulp on multi-tile interpret grids" caveat).  One tile
    makes step and chunk execute the identical dot on every config, so
    the step==chunk bit-exactness contract holds unconditionally.  Real
    TPU backends keep the requested ``block_dh`` streaming tile (both
    kernels run the grid tile-sequentially there, already exact)."""
    if interpret:
        return -(-dh // _LANES) * _LANES
    return block_dh


def fused_mingru_step(x: jax.Array, wz: jax.Array, bz: Optional[jax.Array],
                      wh: jax.Array, bh: Optional[jax.Array],
                      h_prev: jax.Array, *, mode: str = "log",
                      block_dh: int = 128,
                      interpret: Optional[bool] = None) -> jax.Array:
    """minGRU cell step (projections + gates + state update), one Pallas
    call.  x: (..., Dx), h_prev: (..., Dh) -> h_t: (..., Dh)."""
    dh = wz.shape[1]
    interpret = resolve_interpret(interpret)
    block_dh = _tile(dh, block_dh, interpret)
    if bz is None:
        bz = jnp.zeros((dh,), x.dtype)
    if bh is None:
        bh = jnp.zeros((dh,), x.dtype)

    def run(xf, hf):
        xp, hp, b = _pad_batch(xf, hf)
        xp, _ = pad_to(xp, _LANES, 1)
        wzp, _ = pad_to(pad_to(wz, _LANES, 0)[0], block_dh, 1)
        whp, _ = pad_to(pad_to(wh, _LANES, 0)[0], block_dh, 1)
        bzp, _ = pad_to(bz, block_dh, 0)
        bhp, _ = pad_to(bh, block_dh, 0)
        hp, _ = pad_to(hp, block_dh, 1)
        out = _kernel.mingru_step_kernel(xp, wzp, bzp, whp, bhp, hp,
                                         block_dh=block_dh, mode=mode,
                                         interpret=interpret)
        return out[:b, :dh]

    return call_with_flat_lead(run, (x, 1), (h_prev, 1))


def fused_minlstm_step(x: jax.Array, wf: jax.Array, bf: Optional[jax.Array],
                       wi: jax.Array, bi: Optional[jax.Array],
                       wh: jax.Array, bh: Optional[jax.Array],
                       h_prev: jax.Array, *, mode: str = "log",
                       normalize: bool = True, block_dh: int = 128,
                       interpret: Optional[bool] = None) -> jax.Array:
    """minLSTM cell step (three projections + stable f/(f+i) normalisation
    + state update), one Pallas call.  Shapes as fused_mingru_step."""
    dh = wf.shape[1]
    interpret = resolve_interpret(interpret)
    block_dh = _tile(dh, block_dh, interpret)
    if bf is None:
        bf = jnp.zeros((dh,), x.dtype)
    if bi is None:
        bi = jnp.zeros((dh,), x.dtype)
    if bh is None:
        bh = jnp.zeros((dh,), x.dtype)

    def run(xf, hf):
        xp, hp, b = _pad_batch(xf, hf)
        xp, _ = pad_to(xp, _LANES, 1)
        ws = [pad_to(pad_to(w, _LANES, 0)[0], block_dh, 1)[0]
              for w in (wf, wi, wh)]
        bs = [pad_to(b_, block_dh, 0)[0] for b_ in (bf, bi, bh)]
        hp, _ = pad_to(hp, block_dh, 1)
        out = _kernel.minlstm_step_kernel(
            xp, ws[0], bs[0], ws[1], bs[1], ws[2], bs[2], hp,
            block_dh=block_dh, mode=mode, normalize=normalize,
            interpret=interpret)
        return out[:b, :dh]

    return call_with_flat_lead(run, (x, 1), (h_prev, 1))


# ---------------------------------------------------------------------------
# Variable-length packed-prefill chunks (the superstep prompt-packing path)
# ---------------------------------------------------------------------------

def _chunk_pad(xf, hf, valid):
    """Shared chunk-wrapper padding: (B, C, Dx) -> time-major (C, B8,
    Dx128) plus padded h/valid (padded rows get valid=0, freezing them at
    their zero h0 -- sliced off on the way out)."""
    xp, b = pad_to(xf, _SUBLANES, 0)
    xp, _ = pad_to(xp, _LANES, 2)
    hp, _ = pad_to(hf, _SUBLANES, 0)
    vp, _ = pad_to(valid.astype(jnp.int32)[:, None], _SUBLANES, 0)
    return jnp.swapaxes(xp, 0, 1), hp, vp, b


def fused_mingru_chunk(x: jax.Array, wz: jax.Array, bz: Optional[jax.Array],
                       wh: jax.Array, bh: Optional[jax.Array],
                       h_prev: jax.Array, valid: jax.Array, *,
                       mode: str = "log", block_dh: int = 128,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Packed varlen minGRU chunk in one Pallas call: weights stream from
    HBM once for up to C prompt tokens.  x: (..., C, Dx), h_prev:
    (..., Dh), valid: (...,) int32 in [1, C] -> hs: (..., C, Dh); row b
    freezes at ``valid[b]`` so ``hs[..., valid-1, :]`` onward is its final
    state.  Bit-identical to ``valid[b]`` sequential ``fused_mingru_step``
    calls (the packed superstep's C=1 parity contract rides on this)."""
    dh = wz.shape[1]
    interpret = resolve_interpret(interpret)
    block_dh = _tile(dh, block_dh, interpret)
    if bz is None:
        bz = jnp.zeros((dh,), x.dtype)
    if bh is None:
        bh = jnp.zeros((dh,), x.dtype)

    def run(xf, hf, vf):
        chunk = xf.shape[1]
        xp, hp, vp, b = _chunk_pad(xf, hf, vf)
        wzp, _ = pad_to(pad_to(wz, _LANES, 0)[0], block_dh, 1)
        whp, _ = pad_to(pad_to(wh, _LANES, 0)[0], block_dh, 1)
        bzp, _ = pad_to(bz, block_dh, 0)
        bhp, _ = pad_to(bh, block_dh, 0)
        hp, _ = pad_to(hp, block_dh, 1)
        out = _kernel.mingru_chunk_kernel(xp, wzp, bzp, whp, bhp, hp, vp,
                                          block_dh=block_dh, mode=mode,
                                          interpret=interpret)
        return jnp.swapaxes(out, 0, 1)[:b, :chunk, :dh]

    return call_with_flat_lead(run, (x, 2), (h_prev, 1), (valid, 0))


def fused_minlstm_chunk(x: jax.Array, wf: jax.Array, bf: Optional[jax.Array],
                        wi: jax.Array, bi: Optional[jax.Array],
                        wh: jax.Array, bh: Optional[jax.Array],
                        h_prev: jax.Array, valid: jax.Array, *,
                        mode: str = "log", normalize: bool = True,
                        block_dh: int = 128,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Packed varlen minLSTM chunk; contract as :func:`fused_mingru_chunk`
    (bit-identical to sequential ``fused_minlstm_step`` calls)."""
    dh = wf.shape[1]
    interpret = resolve_interpret(interpret)
    block_dh = _tile(dh, block_dh, interpret)
    if bf is None:
        bf = jnp.zeros((dh,), x.dtype)
    if bi is None:
        bi = jnp.zeros((dh,), x.dtype)
    if bh is None:
        bh = jnp.zeros((dh,), x.dtype)

    def run(xf, hf, vf):
        chunk = xf.shape[1]
        xp, hp, vp, b = _chunk_pad(xf, hf, vf)
        ws = [pad_to(pad_to(w, _LANES, 0)[0], block_dh, 1)[0]
              for w in (wf, wi, wh)]
        bs = [pad_to(b_, block_dh, 0)[0] for b_ in (bf, bi, bh)]
        hp, _ = pad_to(hp, block_dh, 1)
        out = _kernel.minlstm_chunk_kernel(
            xp, ws[0], bs[0], ws[1], bs[1], ws[2], bs[2], hp, vp,
            block_dh=block_dh, mode=mode, normalize=normalize,
            interpret=interpret)
        return jnp.swapaxes(out, 0, 1)[:b, :chunk, :dh]

    return call_with_flat_lead(run, (x, 2), (h_prev, 1), (valid, 0))
