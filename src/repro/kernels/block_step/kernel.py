"""Pallas TPU kernel: fused whole-block minRNN decode step.

``kernels/decode_step`` fuses the *cell* (gate GEMVs + state update);
every other op of the residual block -- RMSNorm, the causal-conv step,
the down-projection and the MLP -- still runs as separate XLA fusions,
re-streaming (B, D) activations through HBM and paying a kernel launch
per op, per layer, per decode round.  At serving batch sizes the round
is weight-bound, so that overhead is pure latency on the hot path.

This kernel runs the ENTIRE block step in ONE pallas_call per layer:

    y  = RMSNorm(x) ; y = ConvStep(y)                 [optional conv]
    h  = cell(y, h_prev)          minGRU / minLSTM (stable f/(f+i))
    x  = x + Down(h)
    x  = x + MLPout(gelu(MLPin(RMSNorm(x))))          [optional MLP]

carrying (h, conv window) through VMEM and emitting the residual output
plus the updated state.  The arithmetic mirrors ``core.blocks.step``
op-for-op -- fp32 inside the norm and the cell (matching
``nn.rmsnorm_apply`` and the decode-step cell kernels), compute-dtype
dots for down/MLP (matching ``nn.dense_apply``) -- so with a single
feature tile the fused block is bit-identical to the cell-fused
composition.

Grid = (Dh tiles,), sequential: each tile computes its slice of the
gate projections and the new h, and accumulates its partial
down-projection product into a VMEM scratch; the final tile adds the
residual and runs the MLP.  With ``n_tiles == 1`` (every interpret-mode
config -- ops.py forces it, see the decode_step single-tile policy) the
body collapses to plain unsplit dots and the scratch disappears, which
is the bit-exactness contract.  Multi-tile grids (the compiled TPU path,
``block_dh`` 512 by default) split the down contraction per tile, exact
per feature tile only.  Every dot takes its inputs in the weight dtype
and accumulates in fp32 on the MXU.

VMEM budget (``_VMEM_LIMIT_BYTES`` = 64 MiB of v5e's 128 MiB), at the
paper LMs' published widths -- d_model 768, d_hidden 1536 in three
512-wide tiles, d_ff 3072, bf16, B = 64, C = 16:

  * pinned, single-buffered (``pl.Buffered(1)``), fetched once per call:
    MLP in + out weights 2 x 768 x 3072 x 2 B = 9.0 MiB; x 96 KiB (chunk
    1.5 MiB); conv window 288 KiB; norm / bias rows under 16 KiB;
  * streamed per tile, double-buffered: gate weights 768 x 512 x 2 B =
    768 KiB each, x 2 buffers -- 3.0 MiB for minGRU's two gates, 4.5 MiB
    for minLSTM's three; down rows 1.5 MiB; the h tile 128 KiB;
  * outputs: step y / h / window under 0.5 MiB; chunk ys 1.5 MiB, hs
    1.0 MiB, per-position windows 4.5 MiB; the fp32 down accumulator
    192 KiB (chunk 3.0 MiB).

Mosaic's default 16 MiB scope refused both forms of both cells (double
buffering the MLP weights alone takes 18 MiB); with the operands above
all four compile at 24 MiB (``tests/test_tpu_compile.py``).  The 64 MiB
limit leaves room for larger batches and chunks.

The ``*_chunk`` variants replay up to C per-token block steps per call
with per-row ``valid`` freezing -- the packed-prefill and
speculative-verify form.  They emit the per-position residual stream,
per-position h and per-position conv windows, so ``lm.decode_chunk``
(reads position ``valid-1``) and ``lm.decode_verify`` (needs the whole
rollback table) ride the same kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import min_lstm, nn


def _rmsnorm(x, scale, dx_true: int):
    """``nn.rmsnorm_apply`` arithmetic; when the feature axis is padded
    (real-TPU lane alignment) the mean divides by the TRUE d_model --
    zero pad columns add nothing to the sum."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if x.shape[-1] == dx_true:
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    else:
        var = jnp.sum(jnp.square(x32), axis=-1, keepdims=True) / dx_true
    y = x32 * jax.lax.rsqrt(var + 1e-6)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def _cell_update(cell: str, mode: str, y, gates, h32):
    """One cell state update with fp32 gates -- the op sequence of the
    ``decode_step`` kernels.  The gate dots take their inputs in the
    weight dtype with fp32 accumulation; y arrives rounded to that dtype
    (``_fused_step_args``'s compute-dtype cast), so every product equals
    the decode_step kernels' fp32-upcast product."""
    def pre(w_ref, b_ref):
        w = w_ref[...]
        return jnp.dot(y.astype(w.dtype), w,
                       preferred_element_type=jnp.float32) \
            + b_ref[...].astype(jnp.float32)

    if cell == "mingru":
        k, v = (pre(w, b) for (w, b) in gates)
        z = jax.nn.sigmoid(k)
        h_tilde = nn.g(v) if mode == "log" else v
        return (1.0 - z) * h32 + z * h_tilde
    kf, ki, v = (pre(w, b) for (w, b) in gates)
    f, i = min_lstm.normalized_gates(kf, ki)   # stable f/(f+i)
    h_tilde = nn.g(v) if mode == "log" else v
    return f * h32 + i * h_tilde


def _unpack(refs, *, cell: str, use_conv: bool, use_mlp: bool):
    """Split the flat pallas ref list into named groups (input order of
    ``_in_specs``)."""
    it = iter(refs)
    x = next(it)
    gamma = next(it)
    conv = (next(it), next(it), next(it)) if use_conv else None
    n_gates = 2 if cell == "mingru" else 3
    gates = [(next(it), next(it)) for _ in range(n_gates)]
    h = next(it)
    down = next(it)
    mlp = (next(it), next(it), next(it), next(it), next(it)) \
        if use_mlp else None
    return x, gamma, conv, gates, h, down, mlp, list(it)


def _conv_step(conv, taps_prev, y):
    """``nn.causal_conv_step`` on a window held as K-1 (B, D) taps, oldest
    first: returns (conv output, the K-1 taps of the next window)."""
    ck_ref, cb_ref, _ = conv
    ck = ck_ref[...].astype(y.dtype)                      # (K, D)
    taps = list(taps_prev) + [y]
    out = nn.conv_taps(taps, [ck[i:i + 1] for i in range(len(taps))]) \
        + cb_ref[...].astype(y.dtype)
    return out, taps[1:]


def _dot(a, w_ref):
    """Compute-dtype dot with fp32 accumulation, rounded back to the
    weight dtype -- ``nn.dense_apply``'s ``x @ k`` on the MXU."""
    w = w_ref[...]
    return jnp.dot(a.astype(w.dtype), w,
                   preferred_element_type=jnp.float32).astype(w.dtype)


def _mlp(mlp, x, dx_true: int):
    """Pre-norm gelu MLP sub-block on the residual stream.  The casts
    into the weight dtype replicate ``nn.dense_apply``'s compute-dtype
    cast (ops.py pre-casts the weights)."""
    gamma2_ref, wi_ref, bi_ref, wo_ref, bo_ref = mlp
    y = _rmsnorm(x, gamma2_ref[...], dx_true)
    m = _dot(y, wi_ref) + bi_ref[...]
    m = jax.nn.gelu(m, approximate=True)
    return _dot(m, wo_ref) + bo_ref[...]


def _block_step_body(*refs, cell: str, mode: str, use_conv: bool,
                     use_mlp: bool, n_tiles: int, dx_true: int):
    x_ref, gamma_ref, conv, gates, h_ref, down_ref, mlp, rest = _unpack(
        refs, cell=cell, use_conv=use_conv, use_mlp=use_mlp)
    y_out_ref, h_out_ref = rest[0], rest[1]
    win_out_ref = rest[2] if use_conv else None
    acc_ref = rest[-1] if n_tiles > 1 else None

    x = x_ref[...]                                        # (B, Dx)
    y = _rmsnorm(x, gamma_ref[...], dx_true)
    if use_conv:
        win_ref = conv[2]                                 # (K-1, B, Dx)
        y, taps = _conv_step(conv, [win_ref[i] for i in
                                    range(win_ref.shape[0])], y)
    h32 = _cell_update(cell, mode, y, gates,
                       h_ref[...].astype(jnp.float32))
    h = h32.astype(h_out_ref.dtype)
    h_out_ref[...] = h

    def write_window():
        for i, tap in enumerate(taps):
            win_out_ref[i] = tap.astype(win_out_ref.dtype)

    if n_tiles == 1:
        # the bit-exact tier: plain compute-dtype down dot, exactly
        # ``nn.dense_apply`` on the full feature dim
        if use_conv:
            write_window()
        xr = x + _dot(h, down_ref)
        if use_mlp:
            xr = xr + _mlp(mlp, xr, dx_true)
        y_out_ref[...] = xr
        return

    # multi-tile (real-TPU streaming) tier: sequential grid over Dh
    # tiles, partial down products accumulated in fp32 scratch; the
    # last tile finishes the residual + MLP.  Exact per feature tile.
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        if use_conv:
            write_window()
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(h.astype(down_ref.dtype), down_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_tiles - 1)
    def _():
        xr = x + acc_ref[...].astype(x.dtype)
        if use_mlp:
            xr = xr + _mlp(mlp, xr, dx_true)
        y_out_ref[...] = xr


def _block_chunk_body(*refs, cell: str, mode: str, use_conv: bool,
                      use_mlp: bool, n_tiles: int, dx_true: int,
                      chunk: int):
    """Varlen C-token chunk: weights VMEM-resident, one ``fori_loop``
    replaying the exact per-token arithmetic of ``_block_step_body``
    with per-row ``valid`` freezing of (h, conv window) -- bit-identical
    to ``chunk`` sequential block-step calls."""
    x_ref, gamma_ref, conv, gates, h_ref, down_ref, mlp, rest = _unpack(
        refs, cell=cell, use_conv=use_conv, use_mlp=use_mlp)
    valid_ref = rest[0]                                   # (B, 1) int32
    y_out_ref, hs_ref = rest[1], rest[2]
    win_pos_ref = rest[3] if use_conv else None
    acc_ref = rest[-1] if n_tiles > 1 else None

    valid = valid_ref[...]
    j = pl.program_id(0) if n_tiles > 1 else 0

    def body(t, carry):
        h32, taps = carry
        live = t < valid                                  # (B, 1)
        x_t = x_ref[t]                                    # (B, Dx)
        y = _rmsnorm(x_t, gamma_ref[...], dx_true)
        if use_conv:
            y, new_taps = _conv_step(conv, taps, y)
            taps = tuple(jnp.where(live, n, o)
                         for n, o in zip(new_taps, taps))
            for i, tap in enumerate(taps):
                win_pos_ref[t, i] = tap.astype(win_pos_ref.dtype)
        h_new32 = _cell_update(cell, mode, y, gates, h32)
        # per-token round-trip through the cache dtype -- sequential
        # steps re-read h from a cdtype cache, so the packed carry must
        # quantize identically (same contract as the decode_step chunks)
        h_new32 = h_new32.astype(hs_ref.dtype).astype(jnp.float32)
        h32 = jnp.where(live, h_new32, h32)
        h = h32.astype(hs_ref.dtype)
        hs_ref[t] = h
        if n_tiles == 1:
            xr = x_t + _dot(h, down_ref)
            if use_mlp:
                xr = xr + _mlp(mlp, xr, dx_true)
            y_out_ref[t] = xr
        else:
            prev = jnp.where(j == 0, jnp.zeros_like(acc_ref[t]),
                             acc_ref[t])
            part = prev + jnp.dot(h.astype(down_ref.dtype), down_ref[...],
                                  preferred_element_type=jnp.float32)
            acc_ref[t] = part
            # complete only on the last tile; earlier tiles' writes are
            # overwritten (sequential grid, pinned output block)
            xr = x_t + part.astype(x_t.dtype)
            if use_mlp:
                xr = xr + _mlp(mlp, xr, dx_true)
            y_out_ref[t] = xr
        return h32, taps

    if use_conv:
        win_ref = conv[2]
        taps0 = tuple(win_ref[i] for i in range(win_ref.shape[0]))
    else:
        taps0 = ()
    jax.lax.fori_loop(0, chunk, body,
                      (h_ref[...].astype(jnp.float32), taps0))


# Scoped-VMEM budget of both block kernels.  v5e has 128 MiB of VMEM per
# core and Mosaic's default scope is 16 MiB, which the resident MLP
# weights alone overflow at the paper LMs' widths; see the module
# docstring for the arithmetic.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _specs(bsz, dxp, dhp, dmp, conv_k, block_dh, *, cell, use_conv,
           use_mlp, chunk=0):
    """(in_specs, out_specs) for the step (chunk=0) / chunk forms.  The
    x / norm / conv / MLP operands are pinned (index_map constant) and
    single-buffered: Mosaic fetches them once for the whole grid.  Gate
    weights, biases, h and the down rows stream per Dh tile.  Vectors
    ride as (1, n) rows and the conv window as K-1 time-major (B, Dx)
    taps, the layouts the TPU tiling accepts."""
    def pinned(*shape):
        return pl.BlockSpec(shape, lambda j: (0,) * len(shape),
                            pipeline_mode=pl.Buffered(1))

    vec = pinned(1, dxp)
    gate_w = pl.BlockSpec((dxp, block_dh), lambda j: (0, j))
    gate_b = pl.BlockSpec((1, block_dh), lambda j: (0, j))
    n_gates = 2 if cell == "mingru" else 3

    in_specs = [pinned(chunk, bsz, dxp) if chunk else pinned(bsz, dxp),
                vec]
    if use_conv:
        in_specs += [pinned(conv_k, dxp), vec,
                     pinned(conv_k - 1, bsz, dxp)]
    in_specs += [gate_w, gate_b] * n_gates
    in_specs += [pl.BlockSpec((bsz, block_dh), lambda j: (0, j)),
                 pl.BlockSpec((block_dh, dxp), lambda j: (j, 0))]
    if use_mlp:
        in_specs += [vec, pinned(dxp, dmp), pinned(1, dmp),
                     pinned(dmp, dxp), vec]
    if chunk:
        in_specs.append(pinned(bsz, 1))

    def out(*shape):
        return pl.BlockSpec(shape, lambda j: (0,) * len(shape))

    if chunk:
        out_specs = [out(chunk, bsz, dxp),
                     pl.BlockSpec((chunk, bsz, block_dh),
                                  lambda j: (0, 0, j))]
        if use_conv:
            out_specs.append(out(chunk, conv_k - 1, bsz, dxp))
    else:
        out_specs = [out(bsz, dxp),
                     pl.BlockSpec((bsz, block_dh), lambda j: (0, j))]
        if use_conv:
            out_specs.append(out(conv_k - 1, bsz, dxp))
    return in_specs, out_specs


def _dims(operands, *, cell, use_conv, use_mlp):
    """(dhp, conv_k, dmp, h_prev) read off the flat operand tuple."""
    n_gates = 2 if cell == "mingru" else 3
    i_gate = 2 + (3 if use_conv else 0)
    dhp = operands[i_gate].shape[1]
    h_prev = operands[i_gate + 2 * n_gates]
    conv_k = operands[2].shape[0] if use_conv else 0
    dmp = operands[i_gate + 2 * n_gates + 3].shape[1] if use_mlp else 0
    return dhp, conv_k, dmp, h_prev


def _call(body, operands, in_specs, out_specs, out_shape, scratch,
          n_tiles, interpret):
    kwargs = {}
    if scratch:
        kwargs["scratch_shapes"] = scratch
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),   # sequential: down acc
            vmem_limit_bytes=_VMEM_LIMIT_BYTES)
    return pl.pallas_call(
        body, grid=(n_tiles,), in_specs=in_specs,
        out_specs=tuple(out_specs), out_shape=tuple(out_shape),
        interpret=interpret, **kwargs)(*operands)


@functools.partial(jax.jit, static_argnames=(
    "cell", "mode", "use_conv", "use_mlp", "block_dh", "dx_true",
    "interpret"))
def block_step_kernel(operands, *, cell: str, mode: str, use_conv: bool,
                      use_mlp: bool, block_dh: int, dx_true: int,
                      interpret: bool = True):
    """operands: flat tuple in ``_specs`` input order -- x (B, Dxp),
    norm scale (1, Dxp), [conv kernel (K, Dxp) / bias (1, Dxp) / window
    (K-1, B, Dxp)], gate (w, b (1, Dhp)) pairs, h_prev (B, Dhp), down
    kernel, [mlp norm scale / in w / in b / out w / out b, vectors as
    (1, n) rows].  Returns (y (B, Dxp), h (B, Dhp)[, window (K-1, B,
    Dxp)]).  Dhp % block_dh == 0 (ops.py pads; forces a single tile
    under interpret for bit-exactness)."""
    x = operands[0]
    bsz, dxp = x.shape
    dhp, conv_k, dmp, h_prev = _dims(operands, cell=cell,
                                     use_conv=use_conv, use_mlp=use_mlp)
    assert dhp % block_dh == 0, (dhp, block_dh)
    n_tiles = dhp // block_dh

    in_specs, out_specs = _specs(bsz, dxp, dhp, dmp, conv_k, block_dh,
                                 cell=cell, use_conv=use_conv,
                                 use_mlp=use_mlp)
    out_shape = [jax.ShapeDtypeStruct((bsz, dxp), x.dtype),
                 jax.ShapeDtypeStruct((bsz, dhp), h_prev.dtype)]
    if use_conv:
        out_shape.append(jax.ShapeDtypeStruct((conv_k - 1, bsz, dxp),
                                              x.dtype))
    scratch = [pltpu.VMEM((bsz, dxp), jnp.float32)] if n_tiles > 1 else []
    body = functools.partial(_block_step_body, cell=cell, mode=mode,
                             use_conv=use_conv, use_mlp=use_mlp,
                             n_tiles=n_tiles, dx_true=dx_true)
    return _call(body, operands, in_specs, out_specs, out_shape, scratch,
                 n_tiles, interpret)


@functools.partial(jax.jit, static_argnames=(
    "cell", "mode", "use_conv", "use_mlp", "block_dh", "dx_true",
    "interpret"))
def block_chunk_kernel(operands, *, cell: str, mode: str, use_conv: bool,
                       use_mlp: bool, block_dh: int, dx_true: int,
                       interpret: bool = True):
    """Chunk form: operands as :func:`block_step_kernel` with x time-major
    (C, B, Dxp) and a trailing valid (B, 1) int32.  Returns per-position
    (ys (C, B, Dxp), hs (C, B, Dhp)[, windows (C, K-1, B, Dxp)]); frozen
    rows re-emit their final state from position ``valid-1`` on."""
    x = operands[0]
    chunk, bsz, dxp = x.shape
    dhp, conv_k, dmp, h_prev = _dims(operands, cell=cell,
                                     use_conv=use_conv, use_mlp=use_mlp)
    assert dhp % block_dh == 0, (dhp, block_dh)
    n_tiles = dhp // block_dh

    in_specs, out_specs = _specs(bsz, dxp, dhp, dmp, conv_k, block_dh,
                                 cell=cell, use_conv=use_conv,
                                 use_mlp=use_mlp, chunk=chunk)
    out_shape = [jax.ShapeDtypeStruct((chunk, bsz, dxp), x.dtype),
                 jax.ShapeDtypeStruct((chunk, bsz, dhp), h_prev.dtype)]
    if use_conv:
        out_shape.append(jax.ShapeDtypeStruct(
            (chunk, conv_k - 1, bsz, dxp), x.dtype))
    scratch = [pltpu.VMEM((chunk, bsz, dxp), jnp.float32)] \
        if n_tiles > 1 else []
    body = functools.partial(_block_chunk_body, cell=cell, mode=mode,
                             use_conv=use_conv, use_mlp=use_mlp,
                             n_tiles=n_tiles, dx_true=dx_true, chunk=chunk)
    return _call(body, operands, in_specs, out_specs, out_shape, scratch,
                 n_tiles, interpret)
