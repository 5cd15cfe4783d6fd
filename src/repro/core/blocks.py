"""The paper's minRNN residual block (Appendix C.2).

Pre-norm residual structure with the paper's task-dependent components:

    x = x + Down( minRNN( [Conv4]( Norm(x) ) ) )          # mixer sub-block
    x = x + MLP( Norm(x) )                                # optional

``expansion`` is the paper's state-expansion factor alpha (d_h = alpha*d_x)
with a down-projection back to d_model.  A sequential ``step`` form carries
(conv window, h) state for decode.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import min_gru, min_lstm, nn
from repro.core import scan as scan_lib
from repro.distributed import context as mesh_ctx

Array = jax.Array


def fuse_block_tier(cfg: "MinRNNBlockConfig", params=None,
                    scan_strategy: Optional[str] = None) -> str:
    """Which decode kernel tier this block will actually run.

    Returns ``"block-fused"`` (whole block in one pallas_call,
    kernels/block_step), ``"cell-fused"`` (cell-only kernel,
    kernels/decode_step) or ``"unfused"`` (pure-jnp cell step).  The
    block tier requires: ``fuse_block`` not "off", an rmsnorm block (the
    kernel pins the rmsnorm arithmetic), and -- when ``params`` are
    given inside a ``serving_tp`` shard_map -- unsliced row-parallel
    kernels: a TP-sharded layer's down/mlp_out products need a psum
    over the model axis, which must stay outside the kernel, so sharded
    layers fall back to the cell tier.  The serving engine surfaces
    this in its stats line."""
    strategy = scan_strategy if scan_strategy is not None \
        else cfg.scan_strategy
    if scan_lib.resolve_strategy(strategy) != "fused":
        return "unfused"
    if cfg.fuse_block == "off" or cfg.norm != "rmsnorm":
        return "cell-fused"
    if params is not None and mesh_ctx.serving_tp_axis() is not None:
        if params["down"]["kernel"].shape[0] != cfg.d_hidden:
            return "cell-fused"
        if cfg.use_mlp and \
                params["mlp_out"]["kernel"].shape[0] != cfg.d_mlp:
            return "cell-fused"
    return "block-fused"


def _row_parallel_apply(p, x: Array, compute_dtype, full_in_dim: int
                        ) -> Array:
    """``dense_apply`` that understands tensor-parallel serving.

    Inside a ``serving_tp`` shard_map the col-parallel projections
    (gates, ``mlp_in``) hand each model shard a ``d_hidden/model`` (resp.
    ``d_ff/model``) column block, so the row-parallel projections that
    contract over that dim (``down``, ``mlp_out``) see a *sliced* kernel:
    ``kernel.shape[0] < full_in_dim``.  Their local product is then a
    partial sum that must be ``psum``'d over the model axis BEFORE the
    (replicated) bias is added -- ``dense_apply`` would add the bias into
    every partial.  Outside a shard_map, or when the kernel is unsliced
    (pure DP; a replicated draft model riding a TP trace; non-divisible
    dims that ``sharding.spec_for_param`` left replicated), this is
    exactly ``dense_apply`` -- the shape check keeps partially sharded
    layouts self-consistent without any configuration plumbing."""
    axis = mesh_ctx.serving_tp_axis()
    k = p["kernel"]
    if axis is None or k.shape[0] == full_in_dim:
        return nn.dense_apply(p, x, compute_dtype)
    if compute_dtype is not None:
        k = k.astype(compute_dtype)
        x = x.astype(compute_dtype)
    y = jax.lax.psum(x @ k, axis)
    if "bias" in p:
        b = p["bias"]
        if compute_dtype is not None:
            b = b.astype(compute_dtype)
        y = y + b
    return y


@dataclass(frozen=True)
class MinRNNBlockConfig:
    d_model: int
    cell: str = "mingru"            # mingru | minlstm
    expansion: float = 1.0          # alpha
    use_conv: bool = False
    conv_kernel: int = 4
    use_mlp: bool = False
    mlp_factor: float = 4.0
    mode: str = "log"               # log | linear scan parameterization
    norm: str = "rmsnorm"
    dropout: float = 0.0
    # core.scan.STRATEGIES; "auto" = fused Pallas kernels (compiled on a
    # TPU, interpreted on the CPU).  Callers of ``apply`` may override.
    scan_strategy: str = "auto"
    # whole-block decode fusion (kernels/block_step): "auto"/"on" run
    # norm -> conv -> cell -> down -> MLP as ONE pallas_call per step /
    # chunk when the scan strategy resolves to "fused"; "off" keeps the
    # cell-only kernel.  Falls back to the cell tier for non-rmsnorm
    # blocks and for tensor-parallel-sliced layers (the TP psum must
    # stay outside the kernel).  ``block_dh`` = Dh feature tile on real
    # backends (0 = kernel default; autotune plans set it).
    fuse_block: str = "auto"        # auto | on | off
    block_dh: int = 0

    @property
    def d_hidden(self) -> int:
        return int(self.d_model * self.expansion)

    @property
    def d_mlp(self) -> int:
        return int(self.d_model * self.mlp_factor)


_CELLS = {"mingru": min_gru, "minlstm": min_lstm}


def init(key, cfg: MinRNNBlockConfig, *, dtype=jnp.float32):
    keys = jax.random.split(key, 5)
    cell = _CELLS[cfg.cell]
    p = {
        "norm_rnn": nn.norm_init(cfg.norm, cfg.d_model, dtype),
        "rnn": cell.init(keys[0], cfg.d_model, cfg.d_hidden, dtype=dtype),
        "down": nn.dense_init(keys[1], cfg.d_hidden, cfg.d_model,
                              use_bias=False, dtype=dtype),
    }
    if cfg.use_conv:
        p["conv"] = nn.causal_conv_init(keys[2], cfg.d_model,
                                        cfg.conv_kernel, dtype)
    if cfg.use_mlp:
        p["norm_mlp"] = nn.norm_init(cfg.norm, cfg.d_model, dtype)
        p["mlp_in"] = nn.dense_init(keys[3], cfg.d_model, cfg.d_mlp,
                                    dtype=dtype)
        p["mlp_out"] = nn.dense_init(keys[4], cfg.d_mlp, cfg.d_model,
                                     dtype=dtype)
    return p


def apply(params, cfg: MinRNNBlockConfig, x: Array, *,
          h0: Optional[Array] = None, state0: Optional[dict] = None,
          lengths: Optional[Array] = None, compute_dtype=None,
          scan_strategy: Optional[str] = None, dropout_rng=None,
          deterministic: bool = True, return_state: bool = False):
    """x: (..., T, d_model) parallel (training / prefill) form.

    With ``return_state`` also returns the decode-ready state (final h and
    conv window) so prefill can hand off to sequential decoding.

    ``lengths`` (B,) supports right-padded variable-length batches: the
    returned state is taken at each row's true terminal position (the
    recurrence is causal, so padded positions never influence it).
    ``state0`` (a previous ``return_state`` dict) resumes the block from a
    carried (h, conv window) -- the chunked-prefill path.

    ``scan_strategy`` overrides ``cfg.scan_strategy`` (default ``None`` =
    use the config's; "auto" = fused Pallas kernels, with carried h0 /
    lengths composing exactly because the fused scan is causal and
    chunk-associative) and is forwarded to the cell (see
    min_gru.parallel) -- so the classifier/DT heads and every other
    trunk over these blocks hit the fused path by default too.
    """
    if scan_strategy is None:
        scan_strategy = cfg.scan_strategy
    cell = _CELLS[cfg.cell]
    y = nn.norm_apply(cfg.norm, params["norm_rnn"], x)
    state = {}
    if state0 is not None:
        h0 = state0["h"]
    conv0 = state0.get("conv") if (state0 is not None and cfg.use_conv) \
        else None
    if cfg.use_conv:
        if return_state:
            width = cfg.conv_kernel - 1
            if lengths is not None or conv0 is not None:
                lens = lengths if lengths is not None \
                    else jnp.full(y.shape[:1], y.shape[-2], jnp.int32)
                state["conv"] = nn.gather_conv_window(y, lens, width,
                                                      prefix=conv0)
            else:
                pad = max(width - y.shape[-2], 0)
                win = y[..., -width:, :]
                if pad:
                    win = jnp.concatenate(
                        [jnp.zeros(y.shape[:-2] + (pad, y.shape[-1]),
                                   y.dtype), win], axis=-2)
                state["conv"] = win
        y = nn.causal_conv_apply(params["conv"], y, prefix=conv0)
    h = cell.parallel(params["rnn"], y, h0, mode=cfg.mode,
                      scan_strategy=scan_strategy,
                      compute_dtype=compute_dtype)
    if return_state:
        state["h"] = nn.gather_last(h, lengths) if lengths is not None \
            else h[..., -1, :]
    y = nn.dense_apply(params["down"], h, compute_dtype)
    y = _dropout(y, cfg.dropout, dropout_rng, deterministic)
    x = x + y
    if cfg.use_mlp:
        y = nn.norm_apply(cfg.norm, params["norm_mlp"], x)
        y = nn.gelu(nn.dense_apply(params["mlp_in"], y, compute_dtype))
        y = nn.dense_apply(params["mlp_out"], y, compute_dtype)
        y = _dropout(y, cfg.dropout, dropout_rng, deterministic)
        x = x + y
    if return_state:
        return x, state
    return x


def init_state(cfg: MinRNNBlockConfig, batch_shape: Tuple[int, ...],
               dtype=jnp.float32):
    """Decode-time carried state for one block."""
    state = {"h": jnp.zeros(batch_shape + (cfg.d_hidden,), dtype)}
    if cfg.use_conv:
        state["conv"] = jnp.zeros(
            batch_shape + (cfg.conv_kernel - 1, cfg.d_model), dtype)
    return state


def step(params, cfg: MinRNNBlockConfig, x_t: Array, state, *,
         compute_dtype=None, scan_strategy: Optional[str] = None):
    """Single-token decode. x_t: (..., d_model).

    ``scan_strategy`` defaults to ``cfg.scan_strategy`` (``"auto"`` = the
    fused Pallas decode-step kernel for the cell, ``kernels/decode_step``;
    compiled on a TPU, interpreted on the CPU).  Pass e.g.
    ``"sequential"`` to force the pure-jnp cell step (the parity oracle).
    Norm / conv window / down-projection / MLP stay in XLA either way.

    This is the serving engine's only model entry point: ``lm.superstep``
    drives both prompt consumption (teacher-forced) and decode (sampled)
    through this step for every slot in the batch, so prefill and decode
    share one code path and one kernel.
    """
    if scan_strategy is None:
        scan_strategy = cfg.scan_strategy
    if fuse_block_tier(cfg, params, scan_strategy) == "block-fused":
        from repro.kernels.block_step import ops as block_ops
        return block_ops.fused_block_step(
            params, x_t, state, cell=cfg.cell, mode=cfg.mode,
            use_conv=cfg.use_conv, use_mlp=cfg.use_mlp,
            compute_dtype=compute_dtype, block_dh=cfg.block_dh)
    cell = _CELLS[cfg.cell]
    y = nn.norm_apply(cfg.norm, params["norm_rnn"], x_t)
    new_state = dict(state)
    if cfg.use_conv:
        y, new_state["conv"] = nn.causal_conv_step(params["conv"], y,
                                                   state["conv"])
    h = cell.step(params["rnn"], y, state["h"], mode=cfg.mode,
                  compute_dtype=compute_dtype, scan_strategy=scan_strategy)
    new_state["h"] = h
    y = _row_parallel_apply(params["down"], h, compute_dtype, cfg.d_hidden)
    x_t = x_t + y
    if cfg.use_mlp:
        y = nn.norm_apply(cfg.norm, params["norm_mlp"], x_t)
        y = nn.gelu(nn.dense_apply(params["mlp_in"], y, compute_dtype))
        y = _row_parallel_apply(params["mlp_out"], y, compute_dtype,
                                cfg.d_mlp)
        x_t = x_t + y
    return x_t, new_state


def _conv_chunk(p, y, window, valid, *, return_windows: bool = False):
    """Varlen chunked causal conv: a ``lax.scan`` of ``causal_conv_step``
    over the chunk axis -- the same per-token einsum as single-token
    decode (bit-exact where ``causal_conv_apply``'s unrolled slide-add
    schedule is not), with row b's carried window frozen once ``t >=
    valid[b]``.  y: (B, C, D), window: (B, K-1, D), valid: (B,) int32.

    ``return_windows`` additionally stacks the carried window *after*
    every position -- (B, C, K-1, D), frozen rows re-emitting their
    final window -- so speculative verify can roll the conv state back
    to any committed position with one gather (no recompute)."""

    def body(win, inp):
        y_t, t = inp
        out, win_new = nn.causal_conv_step(p, y_t, win)
        win = jnp.where((t < valid)[:, None, None], win_new, win)
        return win, (out, win if return_windows else None)

    win, (outs, wins) = jax.lax.scan(
        body, window, (jnp.moveaxis(y, 1, 0), jnp.arange(y.shape[1])))
    outs = jnp.moveaxis(outs, 0, 1)
    if return_windows:
        return outs, win, jnp.moveaxis(wins, 0, 1)
    return outs, win


def step_chunk(params, cfg: MinRNNBlockConfig, x: Array, state, valid, *,
               compute_dtype=None, scan_strategy: Optional[str] = None,
               return_positions: bool = False):
    """Packed varlen decode chunk of one block.  x: (B, C, d_model),
    valid: (B,) int32 in [1, C] -> ((B, C, d_model), new state).

    The serving superstep's prompt-packing form of :func:`step`: row b
    consumes its first ``valid[b]`` positions with per-token arithmetic
    identical to ``valid[b]`` sequential ``step`` calls (norm / conv /
    down / MLP are causal or positionwise, and the cell rides
    ``step_chunk``'s masked sequential recurrence -- one weight stream
    per chunk under the fused strategy), and its carried (conv window,
    h) state freezes at ``valid[b]``.  Positions >= ``valid[b]`` hold
    garbage the caller must mask (the superstep reads position
    ``valid[b]-1`` only).

    ``return_positions`` also returns the carried state after EVERY
    position -- ``{"h": (B, C, d_hidden)[, "conv": (B, C, K-1,
    d_model)]}`` -- the speculative-decoding rollback primitive: the
    cell chunk already emits its per-position states (that is what the
    varlen chunk kernels compute), so restoring the prefix state at the
    first rejected draft is a single O(d_hidden) gather per slot."""
    if scan_strategy is None:
        scan_strategy = cfg.scan_strategy
    if fuse_block_tier(cfg, params, scan_strategy) == "block-fused":
        from repro.kernels.block_step import ops as block_ops
        return block_ops.fused_block_chunk(
            params, x, state, valid, cell=cfg.cell, mode=cfg.mode,
            use_conv=cfg.use_conv, use_mlp=cfg.use_mlp,
            compute_dtype=compute_dtype, block_dh=cfg.block_dh,
            return_positions=return_positions)
    cell = _CELLS[cfg.cell]
    y = nn.norm_apply(cfg.norm, params["norm_rnn"], x)
    new_state = dict(state)
    pos_states = {}
    if cfg.use_conv:
        if return_positions:
            y, new_state["conv"], pos_states["conv"] = _conv_chunk(
                params["conv"], y, state["conv"], valid,
                return_windows=True)
        else:
            y, new_state["conv"] = _conv_chunk(params["conv"], y,
                                               state["conv"], valid)
    hs = cell.step_chunk(params["rnn"], y, state["h"], valid,
                         mode=cfg.mode, compute_dtype=compute_dtype,
                         scan_strategy=scan_strategy)
    new_state["h"] = hs[:, -1]          # frozen rows: == hs[:, valid-1]
    pos_states["h"] = hs
    y = _row_parallel_apply(params["down"], hs, compute_dtype, cfg.d_hidden)
    x = x + y
    if cfg.use_mlp:
        y = nn.norm_apply(cfg.norm, params["norm_mlp"], x)
        y = nn.gelu(nn.dense_apply(params["mlp_in"], y, compute_dtype))
        y = _row_parallel_apply(params["mlp_out"], y, compute_dtype,
                                cfg.d_mlp)
        x = x + y
    if return_positions:
        return x, new_state, pos_states
    return x, new_state


def _dropout(x, rate, rng, deterministic):
    if deterministic or rate == 0.0 or rng is None:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)
