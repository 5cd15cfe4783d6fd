"""Decoder-only LM assembly for the whole architecture zoo.

One model builder covers: dense GQA transformers (starcoder2, gemma,
deepseek-67b, pixtral backbone), MLA+MoE (deepseek-v3), fine-grained MoE
(deepseek-moe), SSD (mamba2), hybrid SSD+shared-attention (zamba2), and the
paper's own minGRU/minLSTM LMs.  ``cfg.seq_mixer`` swaps any attention
mixer for the paper's minRNN (DESIGN.md §5).

Layers run under ``lax.scan`` over stacked parameters (cfg.scan_layers) so
HLO size -- and dry-run compile time -- is O(1) in depth.  Every block kind
provides a parallel form (train / batch-eval ``prefill``, returning
per-layer caches) and a step form (decode, carrying caches).  Serving
drives the step form exclusively: ``superstep`` scans K rounds of
token-select -> ``decode_step`` -> sample-or-teacher-force -> retire ->
re-admission over device-resident per-slot state (``init_slot_state``),
so prefilling and decoding requests share one code path and one kernel.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import blocks as minrnn_blocks
from repro.core import min_gru, min_lstm, nn
from repro.distributed.act_sharding import constrain
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssd as ssd_lib
from repro.models.mlp import mlp_apply, mlp_init

Array = jax.Array

_MIN_CELLS = {"mingru": min_gru, "minlstm": min_lstm}


# ===========================================================================
# Parameter init
# ===========================================================================

def init_params(key, cfg) -> Dict[str, Any]:
    dtype = cfg.pdtype
    k_embed, k_layers, k_out, k_front = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "embed": {"table": nn.normal_init(
            k_embed, (cfg.padded_vocab, cfg.d_model), 0.02, dtype)},
        "final_norm": nn.norm_init(cfg.norm, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = nn.dense_init(k_out, cfg.d_model,
                                          cfg.padded_vocab,
                                          use_bias=False, dtype=dtype)
    if cfg.frontend == "patches":
        params["patch_proj"] = nn.dense_init(
            k_front, cfg.frontend_dim, cfg.d_model, use_bias=False,
            dtype=dtype)
    params["layers"] = _init_trunk(k_layers, cfg, dtype)
    return params


def _stack_init(init_one, keys):
    return jax.vmap(init_one)(keys)


def _init_trunk(key, cfg, dtype):
    if cfg.block_kind == "hybrid":
        return _init_hybrid(key, cfg, dtype)
    if cfg.block_kind == "minrnn":
        bc = _minrnn_block_cfg(cfg)
        keys = jax.random.split(key, cfg.n_layers)
        return {"blocks": _stack_init(
            lambda k: minrnn_blocks.init(k, bc, dtype=dtype), keys)}
    if cfg.block_kind == "ssm":
        keys = jax.random.split(key, cfg.n_layers)
        return {"blocks": _stack_init(
            lambda k: _ssm_layer_init(k, cfg, dtype), keys)}
    # attention trunk, possibly with a leading dense segment before MoE
    n_dense_first = cfg.moe.first_dense_layers if cfg.moe else 0
    n_main = cfg.n_layers - n_dense_first
    out = {}
    if n_dense_first:
        keys = jax.random.split(jax.random.fold_in(key, 1), n_dense_first)
        out["dense_blocks"] = _stack_init(
            lambda k: _attn_layer_init(k, cfg, dtype, force_dense=True), keys)
    keys = jax.random.split(jax.random.fold_in(key, 2), n_main)
    out["blocks"] = _stack_init(
        lambda k: _attn_layer_init(k, cfg, dtype), keys)
    return out


def _minrnn_block_cfg(cfg):
    mr = cfg.minrnn
    return minrnn_blocks.MinRNNBlockConfig(
        d_model=cfg.d_model, cell=mr.cell, expansion=mr.expansion,
        use_conv=mr.use_conv, conv_kernel=mr.conv_kernel,
        use_mlp=mr.use_mlp, mlp_factor=cfg.d_ff / cfg.d_model,
        mode=mr.mode, norm=cfg.norm, scan_strategy=cfg.scan_strategy,
        fuse_block=cfg.fuse_block, block_dh=cfg.block_dh)


def _mixer_init(key, cfg, dtype):
    """The sequence mixer of an attention-style block."""
    if cfg.seq_mixer in _MIN_CELLS:
        cell = _MIN_CELLS[cfg.seq_mixer]
        exp = cfg.minrnn.expansion if cfg.minrnn else 1.0
        dh = int(cfg.d_model * exp)
        k1, k2 = jax.random.split(key)
        return {"rnn": cell.init(k1, cfg.d_model, dh, dtype=dtype),
                "down": nn.dense_init(k2, dh, cfg.d_model, use_bias=False,
                                      dtype=dtype)}
    if cfg.attn_kind == "mla":
        return attn.mla_init(key, cfg, dtype=dtype)
    return attn.gqa_init(key, cfg, dtype=dtype)


def _attn_layer_init(key, cfg, dtype, force_dense: bool = False):
    ks = jax.random.split(key, 3)
    p = {
        "norm1": nn.norm_init(cfg.norm, cfg.d_model, dtype),
        "mixer": _mixer_init(ks[0], cfg, dtype),
        "norm2": nn.norm_init(cfg.norm, cfg.d_model, dtype),
    }
    if cfg.moe and not force_dense:
        p["moe"] = moe_lib.moe_init(ks[1], cfg, dtype=dtype)
    else:
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                            gated=cfg.gated_mlp, bias=cfg.mlp_bias,
                            dtype=dtype)
    return p


def _ssm_layer_init(key, cfg, dtype):
    return {
        "norm": nn.norm_init(cfg.norm, cfg.d_model, dtype),
        "mixer": ssd_lib.ssd_init(key, cfg, dtype=dtype),
    }


def _init_hybrid(key, cfg, dtype):
    """zamba2: n_layers SSD blocks + ONE shared attention block applied
    every ``hybrid_attn_every`` layers (params shared, activations not)."""
    k1, k2 = jax.random.split(key)
    keys = jax.random.split(k1, cfg.n_layers)
    return {
        "blocks": _stack_init(lambda k: _ssm_layer_init(k, cfg, dtype), keys),
        "shared_attn": _attn_layer_init(k2, cfg, dtype, force_dense=True),
    }


# ===========================================================================
# Block bodies (parallel form)
# ===========================================================================

def _remat(cfg, fn):
    if cfg.remat == "full":
        return jax.checkpoint(fn, policy=None)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def _mixer_apply(p, cfg, x, positions):
    if cfg.seq_mixer in _MIN_CELLS:
        cell = _MIN_CELLS[cfg.seq_mixer]
        mode = cfg.minrnn.mode if cfg.minrnn else "log"
        h = cell.parallel(p["rnn"], x, mode=mode, compute_dtype=cfg.cdtype,
                          scan_strategy=cfg.scan_strategy)
        return nn.dense_apply(p["down"], h, cfg.cdtype)
    if cfg.attn_kind == "mla":
        return attn.mla_apply(p, cfg, x, positions=positions, causal=True)
    return attn.gqa_apply(p, cfg, x, positions=positions, causal=True)


def _attn_block_apply(p, cfg, x, positions, *, has_moe):
    nk = dict(zero_centered=True) if cfg.norm_zero_centered else {}
    y = nn.norm_apply(cfg.norm, p["norm1"], x, **nk)
    x = x + _mixer_apply(p["mixer"], cfg, y, positions)
    y = nn.norm_apply(cfg.norm, p["norm2"], x, **nk)
    if has_moe:
        out, aux = moe_lib.moe_apply(p["moe"], cfg, y,
                                     activation=cfg.mlp_activation)
        return x + out, aux
    out = mlp_apply(p["mlp"], y, activation=cfg.mlp_activation,
                    compute_dtype=cfg.cdtype)
    return x + out, jnp.zeros((), jnp.float32)


def _ssm_block_apply(p, cfg, x):
    nk = dict(zero_centered=True) if cfg.norm_zero_centered else {}
    y = nn.norm_apply(cfg.norm, p["norm"], x, **nk)
    return x + ssd_lib.ssd_block_apply(p["mixer"], cfg, y)


# ===========================================================================
# Trunk (parallel): scan over stacked layer params
# ===========================================================================

def _trunk_apply(params, cfg, x, positions) -> Tuple[Array, Array]:
    """Returns (x, aux_loss_sum)."""
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.block_kind == "minrnn":
        bc = _minrnn_block_cfg(cfg)

        def body(carry, p_l):
            h = minrnn_blocks.apply(p_l, bc, carry, compute_dtype=cfg.cdtype,
                                    scan_strategy=cfg.scan_strategy)
            return h, None

        x, _ = _scan_layers(cfg, body, x, params["layers"]["blocks"])
        return x, aux_total

    if cfg.block_kind == "ssm":
        def body(carry, p_l):
            return _ssm_block_apply(p_l, cfg, carry), None

        x, _ = _scan_layers(cfg, body, x, params["layers"]["blocks"])
        return x, aux_total

    if cfg.block_kind == "hybrid":
        return _hybrid_apply(params, cfg, x, positions), aux_total

    # attention trunk
    layers = params["layers"]
    if "dense_blocks" in layers:
        def body_d(carry, p_l):
            h, _ = _attn_block_apply(p_l, cfg, carry, positions,
                                     has_moe=False)
            return h, None

        x, _ = _scan_layers(cfg, body_d, x, layers["dense_blocks"])

    has_moe = cfg.moe is not None

    def body(carry, p_l):
        h, aux = _attn_block_apply(p_l, cfg, carry, positions,
                                   has_moe=has_moe)
        return h, aux

    x, auxs = _scan_layers(cfg, body, x, layers["blocks"])
    if auxs is not None:
        aux_total = aux_total + jnp.sum(auxs)
    return x, aux_total


def _iterate(cfg, body, x, scanned):
    """lax.scan over stacked leaves, or an unrolled python loop when
    cfg.scan_layers=False (the dry-run uses unrolled so cost_analysis
    counts every layer -- XLA tallies a while-loop body only once)."""
    if cfg.scan_layers:
        return lax.scan(body, x, scanned)
    n = jax.tree.leaves(scanned)[0].shape[0]
    ys = []
    for i in range(n):
        sl = jax.tree.map(lambda a: a[i], scanned)
        x, y = body(x, sl)
        ys.append(y)
    if ys and jax.tree.leaves(ys[0]):
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return x, ys


def _scan_layers(cfg, body, x, stacked):
    return _iterate(cfg, _remat(cfg, body), x, stacked)


def _hybrid_apply(params, cfg, x, positions):
    """zamba2 trunk: scan over groups of (every k SSD layers + shared attn)."""
    every = cfg.hybrid_attn_every
    n_groups = cfg.n_layers // every
    blocks = params["layers"]["blocks"]
    shared = params["layers"]["shared_attn"]
    grouped = jax.tree.map(
        lambda a: a.reshape((n_groups, every) + a.shape[1:]), blocks)

    def group_body(carry, p_group):
        def inner(c, p_l):
            return _ssm_block_apply(p_l, cfg, c), None

        h, _ = _iterate(cfg, inner, carry, p_group)
        h, _ = _attn_block_apply(shared, cfg, h, positions, has_moe=False)
        return h, None

    x, _ = _iterate(cfg, _remat(cfg, group_body), x, grouped)
    return x


# ===========================================================================
# Embedding / logits / forward / loss
# ===========================================================================

def _embed(params, cfg, tokens, patch_embeds=None):
    x = params["embed"]["table"].astype(cfg.cdtype)[tokens]
    x = constrain(x, "dp", None, None)
    if cfg.embedding_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype)
    if cfg.frontend == "patches" and patch_embeds is not None:
        pe = nn.dense_apply(params["patch_proj"], patch_embeds, cfg.cdtype)
        x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)
    return x


def _logits(params, cfg, x):
    if cfg.tie_embeddings:
        table = params["embed"]["table"].astype(cfg.cdtype)
        logits = x @ table.T
    else:
        logits = nn.dense_apply(params["unembed"], x, cfg.cdtype)
    if cfg.logits_softcap:
        logits = jnp.tanh(logits / cfg.logits_softcap) * cfg.logits_softcap
    if cfg.padded_vocab != cfg.vocab_size:   # mask the pad columns
        col = jnp.arange(cfg.padded_vocab)
        logits = jnp.where(col < cfg.vocab_size, logits, -1e30)
    return logits


def forward(params, cfg, tokens: Array, *, patch_embeds: Optional[Array] = None
            ) -> Tuple[Array, Array]:
    """tokens: (B, S) -> (logits (B, S*, V), aux_loss).  S* includes any
    frontend prefix tokens."""
    x = _embed(params, cfg, tokens, patch_embeds)
    positions = jnp.arange(x.shape[1])[None, :]
    x, aux = _trunk_apply(params, cfg, x, positions)
    nk = dict(zero_centered=True) if cfg.norm_zero_centered else {}
    x = nn.norm_apply(cfg.norm, params["final_norm"], x, **nk)
    return _logits(params, cfg, x), aux


def loss_fn(params, cfg, batch: Dict[str, Array]) -> Tuple[Array, Dict]:
    """batch: tokens (B, S), labels (B, S) with -1 = ignore, optional
    patch_embeds."""
    tokens = batch["tokens"]
    labels = batch["labels"]
    logits, aux = forward(params, cfg, tokens,
                          patch_embeds=batch.get("patch_embeds"))
    if logits.shape[1] != labels.shape[1]:      # frontend prefix: drop it
        logits = logits[:, logits.shape[1] - labels.shape[1]:]
    logits = constrain(logits.astype(jnp.float32), "dp", None, "tp")
    mask = (labels >= 0).astype(jnp.float32)
    safe_labels = jnp.maximum(labels, 0)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    # gold logit via one-hot contraction: shards cleanly over a
    # vocab-parallel logits tensor (take_along_axis would all-gather it)
    col = jnp.arange(logits.shape[-1])
    gold = jnp.sum(jnp.where(col == safe_labels[..., None], logits, 0.0),
                   axis=-1)
    nll = (logz - gold) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll) / denom
    metrics = {"nll": loss, "ntokens": jnp.sum(mask)}
    if cfg.z_loss:
        zl = cfg.z_loss * jnp.sum((logz ** 2) * mask) / denom
        loss = loss + zl
        metrics["z_loss"] = zl
    if cfg.moe:
        loss = loss + cfg.moe.router_aux_weight * aux
        metrics["moe_aux"] = aux
    metrics["loss"] = loss
    return loss, metrics


# ===========================================================================
# Decode: cache init / prefill / step
# ===========================================================================

def init_cache(cfg, batch: int, max_len: int) -> Dict[str, Any]:
    """Stacked per-layer caches + shared position counter."""
    dt = cfg.cdtype
    cache: Dict[str, Any] = {"pos": jnp.zeros((batch,), jnp.int32)}
    L = cfg.n_layers

    if cfg.block_kind == "minrnn":
        bc = _minrnn_block_cfg(cfg)
        cache["h"] = jnp.zeros((L, batch, bc.d_hidden), dt)
        if bc.use_conv:
            cache["conv"] = jnp.zeros(
                (L, batch, bc.conv_kernel - 1, cfg.d_model), dt)
        return cache

    if cfg.block_kind == "ssm":
        s = cfg.ssm
        cache["conv"] = jnp.zeros(
            (L, batch, s.conv_kernel - 1,
             s.d_inner(cfg.d_model) + 2 * s.n_groups * s.d_state), dt)
        cache["ssm"] = jnp.zeros(
            (L, batch, s.n_heads(cfg.d_model), s.head_dim, s.d_state),
            jnp.float32)
        return cache

    if cfg.block_kind == "hybrid":
        s = cfg.ssm
        n_groups = cfg.n_layers // cfg.hybrid_attn_every
        cache["conv"] = jnp.zeros(
            (L, batch, s.conv_kernel - 1,
             s.d_inner(cfg.d_model) + 2 * s.n_groups * s.d_state), dt)
        cache["ssm"] = jnp.zeros(
            (L, batch, s.n_heads(cfg.d_model), s.head_dim, s.d_state),
            jnp.float32)
        cache["k"] = jnp.zeros(
            (n_groups, batch, max_len, cfg.n_kv_heads, cfg.head_dim_), dt)
        cache["v"] = jnp.zeros_like(cache["k"])
        return cache

    # attention trunk
    if cfg.seq_mixer in _MIN_CELLS:
        exp = cfg.minrnn.expansion if cfg.minrnn else 1.0
        cache["h"] = jnp.zeros((L, batch, int(cfg.d_model * exp)), dt)
    elif cfg.attn_kind == "mla":
        cache["ckv"] = jnp.zeros((L, batch, max_len, cfg.mla_kv_lora), dt)
        cache["krope"] = jnp.zeros((L, batch, max_len, cfg.mla_rope_dim), dt)
    else:
        cache["k"] = jnp.zeros(
            (L, batch, max_len, cfg.n_kv_heads, cfg.head_dim_), dt)
        cache["v"] = jnp.zeros_like(cache["k"])
    return cache


def decode_step(params, cfg, token: Array, cache: Dict[str, Any]
                ) -> Tuple[Array, Dict[str, Any]]:
    """token: (B,) -> (logits (B, V), new cache).  One step for every arch.

    Every trunk kind dispatches its layer stack as ONE ``lax.scan`` over
    stacked weights (``_iterate``), so the per-step HLO is O(1) in depth;
    the minRNN step body additionally runs its cell in the fused Pallas
    decode kernel under the default ``scan_strategy="auto"`` (see
    ``_minrnn_decode``).  This is the single model entry point of the
    serving engine: ``superstep`` wraps it in a second on-device scan
    that drives prefill (teacher-forced prompt tokens) and decode
    (sampled tokens) through the same step, K rounds per host call.
    """
    pos = cache["pos"]
    x = params["embed"]["table"].astype(cfg.cdtype)[token]
    if cfg.embedding_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype)

    new_cache = dict(cache)

    if cfg.block_kind == "minrnn":
        x, outs = _minrnn_decode(params, cfg, x, cache)
        new_cache.update(outs)

    elif cfg.block_kind == "ssm":
        def body(carry, scanned):
            p_l, cache_l = scanned
            y = nn.norm_apply(cfg.norm, p_l["norm"], carry)
            out, state = ssd_lib.ssd_block_step(
                p_l["mixer"], cfg, y,
                {"conv": cache_l["conv"], "ssm": cache_l["ssm"]})
            return carry + out, state

        scanned = {"conv": cache["conv"], "ssm": cache["ssm"]}
        x, outs = _iterate(cfg, body, x,
                           (params["layers"]["blocks"], scanned))
        new_cache.update(outs)

    elif cfg.block_kind == "hybrid":
        x, outs = _hybrid_decode(params, cfg, x, cache)
        new_cache.update(outs)

    else:
        x, outs = _attn_decode(params, cfg, x, cache)
        new_cache.update(outs)

    nk = dict(zero_centered=True) if cfg.norm_zero_centered else {}
    x = nn.norm_apply(cfg.norm, params["final_norm"], x, **nk)
    logits = _logits(params, cfg, x)
    new_cache["pos"] = pos + 1
    return logits, new_cache


def _minrnn_decode(params, cfg, x, cache):
    """minRNN trunk single-token step: one stacked-weight ``lax.scan``
    whose body is ``blocks.step`` -- the cell GEMVs + gates + state update
    run in the fused Pallas decode kernel when ``cfg.scan_strategy``
    resolves to ``"fused"`` (the ``"auto"`` default)."""
    bc = _minrnn_block_cfg(cfg)

    def body(carry, scanned):
        p_l, cache_l = scanned
        state = {"h": cache_l["h"]}
        if bc.use_conv:
            state["conv"] = cache_l["conv"]
        y, state = minrnn_blocks.step(p_l, bc, carry, state,
                                      compute_dtype=cfg.cdtype)
        out_c = {"h": state["h"]}
        if bc.use_conv:
            out_c["conv"] = state["conv"]
        return y, out_c

    scanned = {"h": cache["h"]}
    if bc.use_conv:
        scanned["conv"] = cache["conv"]
    return _iterate(cfg, body, x, (params["layers"]["blocks"], scanned))


def supports_prompt_packing(cfg) -> bool:
    """True when the superstep can consume C > 1 prompt tokens per round
    (``decode_chunk``): requires the whole decode state to be a constant-
    size recurrence, i.e. the paper's minRNN family -- same condition as
    chunked prefill."""
    return supports_chunked_prefill(cfg)


def decode_chunk(params, cfg, tokens: Array, valid: Array,
                 cache: Dict[str, Any]) -> Tuple[Array, Dict[str, Any]]:
    """Packed varlen step: tokens (B, C), valid (B,) int32 in [1, C] ->
    (logits (B, V) at each row's position ``valid[b]-1``, new cache).

    The prompt-packing core: row b consumes its first ``valid[b]`` tokens
    in one device round -- per-token arithmetic identical to ``valid[b]``
    sequential ``decode_step`` calls (the cell rides the fused Pallas
    chunk kernels under ``scan_strategy="auto"``, streaming each layer's
    weights from HBM once per chunk instead of once per token), with the
    recurrent state frozen per-row at ``valid[b]``.  Logits (final norm +
    unembed) are computed once per row at its last valid position, not C
    times.  Only recurrence-cached archs can do this
    (``supports_prompt_packing``); KV/SSD caches would need per-position
    cache scatter."""
    if cfg.block_kind != "minrnn":
        raise NotImplementedError(
            f"packed decode_chunk requires a constant-size recurrent "
            f"state (block_kind='minrnn'), got {cfg.block_kind!r}")
    bc = _minrnn_block_cfg(cfg)
    x = params["embed"]["table"].astype(cfg.cdtype)[tokens]   # (B, C, D)
    if cfg.embedding_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype)

    def body(carry, scanned):
        p_l, cache_l = scanned
        state = {"h": cache_l["h"]}
        if bc.use_conv:
            state["conv"] = cache_l["conv"]
        y, state = minrnn_blocks.step_chunk(p_l, bc, carry, state, valid,
                                            compute_dtype=cfg.cdtype)
        out_c = {"h": state["h"]}
        if bc.use_conv:
            out_c["conv"] = state["conv"]
        return y, out_c

    scanned = {"h": cache["h"]}
    if bc.use_conv:
        scanned["conv"] = cache["conv"]
    x, outs = _iterate(cfg, body, x, (params["layers"]["blocks"], scanned))

    new_cache = dict(cache)
    new_cache.update(outs)
    x_last = nn.gather_last(x, valid)                 # (B, D) at valid-1
    nk = dict(zero_centered=True) if cfg.norm_zero_centered else {}
    x_last = nn.norm_apply(cfg.norm, params["final_norm"], x_last, **nk)
    logits = _logits(params, cfg, x_last)
    new_cache["pos"] = cache["pos"] + valid.astype(jnp.int32)
    return logits, new_cache


def decode_verify(params, cfg, tokens: Array, valid: Array,
                  cache: Dict[str, Any]):
    """Speculative-verify pass: tokens (B, W), valid (B,) int32 in
    [1, W] -> (logits (B, W, V), per-position states).

    ``decode_chunk``'s sibling for speculative decoding: the same masked
    varlen replay through the fused chunk kernels (one weight stream per
    round, per-token arithmetic identical to sequential ``decode_step``
    calls, rows frozen at ``valid[b]``), but keeping what verification
    needs and ``decode_chunk`` throws away -- the logits at EVERY
    position (to judge each draft token) and the carried recurrent
    state after every position: ``{"h": (L, B, W, d_hidden)[, "conv":
    (L, B, W, K-1, d_model)]}``.  The caller commits a per-row prefix of
    ``valid_eff[b] <= valid[b]`` positions by gathering the state at
    ``valid_eff[b] - 1`` and advancing ``pos`` by ``valid_eff`` -- the
    recompute-free O(d_hidden)-per-slot rollback the paper's constant-
    size state makes trivial (a Transformer would instead truncate and
    re-page its KV cache).  The returned cache is untouched; positions
    ``>= valid[b]`` re-emit the frozen state so any gather index in
    ``[valid_eff-1, W)`` is safe."""
    if cfg.block_kind != "minrnn":
        raise NotImplementedError(
            f"decode_verify requires a constant-size recurrent state "
            f"(block_kind='minrnn'), got {cfg.block_kind!r}")
    bc = _minrnn_block_cfg(cfg)
    x = params["embed"]["table"].astype(cfg.cdtype)[tokens]   # (B, W, D)
    if cfg.embedding_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype)

    def body(carry, scanned):
        p_l, cache_l = scanned
        state = {"h": cache_l["h"]}
        if bc.use_conv:
            state["conv"] = cache_l["conv"]
        y, _, pos_states = minrnn_blocks.step_chunk(
            p_l, bc, carry, state, valid, compute_dtype=cfg.cdtype,
            return_positions=True)
        return y, pos_states

    scanned = {"h": cache["h"]}
    if bc.use_conv:
        scanned["conv"] = cache["conv"]
    x, states = _iterate(cfg, body, x, (params["layers"]["blocks"], scanned))

    nk = dict(zero_centered=True) if cfg.norm_zero_centered else {}
    x = nn.norm_apply(cfg.norm, params["final_norm"], x, **nk)
    return _logits(params, cfg, x), states


# ===========================================================================
# Superstep: unified prefill + decode + sampling + re-admission on device
# ===========================================================================

# cache leaves that are *read back* by the recurrence and must be zeroed
# when a slot is re-armed for a new request.  KV-style leaves (k / v /
# ckv / krope) are deliberately NOT reset: decode masks attention by the
# per-slot ``pos`` counter and overwrites position p before attending to
# it, so stale rows beyond ``pos`` are never visible (the same invariant
# batched padded prefill already relies on).
_RECURRENT_CACHE_KEYS = ("h", "conv", "ssm")


def init_slot_state(cfg, batch: int, max_len: int, *, seed: int = 0,
                    draft=None) -> Dict[str, Any]:
    """Device-resident per-slot serving state for ``superstep``.

    One fixed-shape pytree holds everything the device loop needs to run
    admission, prefill, decode and sampling without host intervention:

      * ``cache``       -- the decode cache (``init_cache``);
      * active request: ``tok`` (last sampled token), ``prompt`` (B,
        max_len) staged prompt tokens, ``prompt_len`` / ``prompt_pos``,
        ``rid`` (host request tag riding along so the (B, n) output
        buffer can be demuxed even when one slot serves two requests in
        a single superstep), ``remaining`` / ``eos`` and the per-slot
        sampling controls ``temperature`` / ``top_k`` / ``top_p``;
      * ``alive``       -- slot has a request in flight (prefilling or
        decoding);
      * ``keys``        -- per-*slot* PRNG keys (slot-persistent and
        emission-aligned: a row's key advances only on rounds it emits,
        so a request's k-th output token uses the k-th key in its
        slot's chain regardless of ``prompt_chunk`` or how many
        teacher-forced rounds its prompt took);
      * staging buffer  -- ``s_*`` mirrors of the request fields plus
        ``s_valid``: the host parks the next queued request here and the
        scan body arms it into the row the moment the row goes dead.

    ``draft`` (a ``serving.draft`` source) adds the speculative-decoding
    state: ``n_out`` (emitted tokens appended to the prompt buffer as
    drafting history) plus whatever the source itself carries per slot
    (``draft.extra_state`` -- e.g. the draft model's decode cache).
    """
    # lazy import: models/ stays importable without the serving package
    # in minimal deployments; sampling itself only depends on jax
    from repro.serving import sampling

    i32 = jnp.int32

    def iv(fill=0):
        return jnp.full((batch,), fill, i32)

    state: Dict[str, Any] = {
        "cache": init_cache(cfg, batch, max_len),
        "tok": iv(), "alive": jnp.zeros((batch,), bool),
        "keys": sampling.make_keys(seed, batch),
        "prompt": jnp.zeros((batch, max_len), i32),
        "prompt_len": iv(), "prompt_pos": iv(),
        "rid": iv(-1), "remaining": iv(), "eos": iv(-1),
        "temperature": jnp.zeros((batch,), jnp.float32),
        "top_k": iv(), "top_p": jnp.ones((batch,), jnp.float32),
        "s_valid": jnp.zeros((batch,), bool),
        "s_prompt": jnp.zeros((batch, max_len), i32),
        "s_prompt_len": iv(), "s_rid": iv(-1), "s_remaining": iv(),
        "s_eos": iv(-1),
        "s_temperature": jnp.zeros((batch,), jnp.float32),
        "s_top_k": iv(), "s_top_p": jnp.ones((batch,), jnp.float32),
    }
    if draft is not None:
        state["n_out"] = iv()
        state.update(draft.extra_state(batch, max_len))
    return state


def _reset_slot_rows(cache: Dict[str, Any], mask: Array) -> Dict[str, Any]:
    """Re-arm rows ``mask``: zero the recurrent state and position counter
    so the row starts a fresh request (see _RECURRENT_CACHE_KEYS for why
    KV leaves are left in place)."""
    out = dict(cache)
    out["pos"] = jnp.where(mask, 0, cache["pos"])
    for name in _RECURRENT_CACHE_KEYS:
        if name in cache:
            leaf = cache[name]
            m = mask.reshape((1, -1) + (1,) * (leaf.ndim - 2))
            out[name] = jnp.where(m, jnp.zeros((), leaf.dtype), leaf)
    return out


# request fields swapped wholesale from the staging buffer when a row arms
_ARM_FIELDS = ("prompt_len", "rid", "remaining", "eos", "temperature",
               "top_k", "top_p")


def superstep(params, cfg, state: Dict[str, Any], n: int, *,
              prompt_chunk: int = 1, draft=None, draft_params=None):
    """Run ``n`` rounds of the unified serving loop entirely on device.

    ONE ``lax.scan`` whose body is, for every slot simultaneously:

      1. **re-admission** -- dead rows with a staged request arm it:
         recurrent cache rows zeroed, ``pos``/``prompt_pos`` reset,
         request fields swapped in from the ``s_*`` staging buffer;
      2. **token select** -- prefilling rows (``prompt_pos <
         prompt_len``) consume their next prompt token (the next *C*
         prompt tokens when ``prompt_chunk=C > 1``), decoding rows feed
         back their last sampled token;
      3. **fused block step** -- one ``decode_step`` for the whole
         batch: prefilling and decoding rows ride the same fused Pallas
         cell kernel in the same round.  Under ``prompt_chunk=C > 1``
         this is ``decode_chunk`` instead: prefilling rows advance
         through up to C prompt tokens via the masked varlen chunk
         kernels (one weight stream amortised over C prompt tokens --
         the weight-bound-regime packing win) while decoding and dead
         rows ride the same call with a valid length of 1; emitted
         greedy and seeded streams are bit-exact with the C=1 path;
      4. **sample-or-teacher-force** -- every row samples, but only
         rows whose logits are real output logits emit: decoding rows,
         and prefilling rows whose round reached their *last* prompt
         token (their sample is the request's first output token).
         Keys advance only on rows that emit, so a request's k-th
         output token uses the k-th key in its slot's chain regardless
         of ``prompt_chunk`` -- seeded streams are bit-exact across C
         for a given slot assignment.  Teacher-forced rows discard the
         sample and emit -1;
      5. **EOS / retire** -- emitting rows that hit their stop token or
         length cap go dead; the next round's step 1 re-arms them from
         staging with zero idle rounds.

    Returns ``(tokens, rids, state, counters)``: ``tokens`` (B, n) int32
    with -1 at non-emitting positions, ``rids`` (B, n) int32 tagging
    each emitted token with its request id (one row may emit for two
    requests within a single call), the advanced slot state, and
    ``counters`` with ``prefill_steps`` (prompt tokens consumed -- up to
    C per slot-round when packing), ``prefill_rounds`` (slot-rounds
    spent prefilling; equals ``prefill_steps`` at C=1),
    ``wasted_slot_steps`` (rows stepped while dead with nothing staged
    -- the idle waste this loop exists to eliminate; rows keep stepping
    regardless so the batch stays dense and shapes stay static), and
    ``packed_rounds`` / ``packed_tokens``: the rounds that took the
    packed branch (some row prefilling, C > 1) and the positions in them
    that carried a real token -- each prefilling row's prompt tokens
    plus one per live decoding row, of ``B * C`` computed; both 0 at
    C=1.

    **Numerical health guard**: every round, each row's fresh logits
    (and recurrent state, for recurrent-cache archs) are reduced to a
    per-slot finite/non-finite bit.  A row that goes non-finite is
    killed THAT round -- its emission is suppressed so garbage never
    reaches the output buffers, and the next round's re-admission
    re-arms it through the same state-zeroing path a normal retirement
    uses.  ``counters['nonfinite']`` is the per-slot-per-round flag
    plane (B, n) the host uses to attribute the kill to a request, and
    ``counters['nonfinite_decode_rounds']`` counts suppressed rounds on
    non-prefilling rows (the slot-step identity's correction term: such
    a round is neither a prefill round nor an emitted token).  On a
    healthy batch the guard is the identity -- every select masks with
    an all-False flag -- so fault-free streams stay bit-exact.

    ``n`` and ``prompt_chunk`` must be static (the engine jits one
    program per block size); ``prompt_chunk > 1`` requires
    ``supports_prompt_packing(cfg)``.

    ``draft`` (a ``serving.draft`` source, with its weights -- if any --
    passed as ``draft_params`` so they stay traced) switches the loop to
    **speculative decoding**: decoding rows propose up to
    ``draft.draft_len`` draft tokens per round and verify them in ONE
    pass through the varlen chunk kernels (``decode_verify``), emitting
    every accepted token plus the verifier's own next token -- up to
    ``draft_len + 1`` tokens per slot-round, so the emit buffers grow a
    per-round plane: ``tokens``/``rids`` become (B, n, draft_len + 1).
    Rejection rolls the slot state back to the last accepted position
    with one O(d_hidden) gather of the chunk's per-position states (no
    recompute, no host round-trip).  Emission stays EXACT: every token
    is computed precisely as the non-speculative path would (greedy
    argmax, or categorical under the same emission-aligned key chain --
    position i of a round uses the slot's i-th chained key), so greedy
    AND seeded streams are bit-identical to ``draft=None`` and drafting
    only ever changes latency.  ``counters`` gains ``draft_proposed`` /
    ``draft_accepted`` (sum of drafts offered / accepted on decoding
    rows) and ``emit_rounds`` (emitting slot-rounds == tokens the non-
    speculative path contributes: ``decode_tokens == draft_accepted +
    emit_rounds`` exactly); in ``packed_tokens`` a decoding row counts
    its 1 + drafts verified positions.  Requires
    ``supports_prompt_packing(cfg)``.
    """
    from repro.serving import sampling

    if prompt_chunk > 1 and not supports_prompt_packing(cfg):
        raise NotImplementedError(
            f"prompt_chunk={prompt_chunk} requires a recurrent-state arch "
            f"(block_kind='minrnn'), got block_kind={cfg.block_kind!r}")
    if draft is not None:
        if not supports_prompt_packing(cfg):
            raise NotImplementedError(
                f"speculative decoding requires a recurrent-state arch "
                f"(block_kind='minrnn'), got block_kind={cfg.block_kind!r}")
        return _superstep_spec(params, cfg, state, n,
                               prompt_chunk=prompt_chunk, draft=draft,
                               draft_params=draft_params)

    batch = state["tok"].shape[0]
    p_cap = state["prompt"].shape[1]
    chunk = int(prompt_chunk)

    def body(carry, _):
        st, prefill_ct, round_ct, waste_ct, nf_ct, pk_rounds, pk_toks = carry
        st = dict(st)

        # 1. re-admission from the staging buffer
        arm = jnp.logical_not(st["alive"]) & st["s_valid"]
        for f in _ARM_FIELDS:
            st[f] = jnp.where(arm, st["s_" + f], st[f])
        st["prompt"] = jnp.where(arm[:, None], st["s_prompt"], st["prompt"])
        st["prompt_pos"] = jnp.where(arm, 0, st["prompt_pos"])
        st["alive"] = st["alive"] | arm
        st["s_valid"] = st["s_valid"] & jnp.logical_not(arm)
        st["cache"] = _reset_slot_rows(st["cache"], arm)

        alive = st["alive"]
        waste_ct = waste_ct + jnp.sum(
            jnp.logical_not(alive).astype(jnp.int32))
        prefilling = alive & (st["prompt_pos"] < st["prompt_len"])
        round_ct = round_ct + jnp.sum(prefilling.astype(jnp.int32))

        if chunk == 1:
            take = prefilling.astype(jnp.int32)
            prefill_ct = prefill_ct + jnp.sum(take)

            # 2. per-slot token select
            nxt = st["prompt"][jnp.arange(batch),
                               jnp.clip(st["prompt_pos"], 0, p_cap - 1)]
            in_tok = jnp.where(prefilling, nxt, st["tok"])

            # 3. fused block step, all rows in one batch
            logits, st["cache"] = decode_step(params, cfg, in_tok,
                                              st["cache"])
        else:
            # 2. packed token select: up to C prompt tokens per
            # prefilling row, the fed-back sample for decoding rows
            left = st["prompt_len"] - st["prompt_pos"]
            take = jnp.where(prefilling,
                             jnp.minimum(left, chunk), 0).astype(jnp.int32)
            prefill_ct = prefill_ct + jnp.sum(take)
            valid = jnp.maximum(take, 1)        # non-prefilling rows: 1
            packed = jnp.any(prefilling)
            pk_rounds = pk_rounds + packed.astype(jnp.int32)
            pk_toks = pk_toks + jnp.where(packed, jnp.sum(jnp.where(
                alive, valid, 0)), 0)

            # 3. packed varlen block step, all rows in one batch -- but
            # only when some row is actually prefilling: steady-state
            # decode-only rounds take the plain single-token step (the
            # exact C=1 program) instead of paying the C-wide chunk
            # compute for 1 useful token per row
            def packed_step(cache):
                idx = st["prompt_pos"][:, None] + jnp.arange(chunk)[None]
                gathered = jnp.take_along_axis(
                    st["prompt"], jnp.clip(idx, 0, p_cap - 1), axis=1)
                tok_blk = jnp.where(prefilling[:, None], gathered,
                                    st["tok"][:, None])
                return decode_chunk(params, cfg, tok_blk, valid, cache)

            def plain_step(cache):
                # no prefilling rows: valid == 1 everywhere, so this is
                # bit-identical state-wise (pos + 1, one token per row)
                return decode_step(params, cfg, st["tok"], cache)

            logits, st["cache"] = lax.cond(jnp.any(prefilling),
                                           packed_step, plain_step,
                                           st["cache"])

        # 3b. numerical health guard: reduce this round's logits (and
        # the recurrent state, when the arch carries one) to a per-slot
        # finite bit.  Poisoned rows are killed this round with their
        # emission suppressed; re-admission re-zeroes their state.  On a
        # healthy batch ``bad`` is all-False and every masked op below
        # is the identity, so fault-free streams are bit-exact.
        ok = jnp.all(jnp.isfinite(logits), axis=-1)
        if "h" in st["cache"]:
            h = st["cache"]["h"]
            ok = ok & jnp.all(jnp.isfinite(h), axis=tuple(
                a for a in range(h.ndim) if a != 1))
        bad = alive & jnp.logical_not(ok)
        nf_ct = nf_ct + jnp.sum(
            (bad & jnp.logical_not(prefilling)).astype(jnp.int32))

        # 4. sample-or-teacher-force
        toks, new_keys = sampling.sample_tokens(
            logits, st["keys"], st["temperature"], st["top_k"], st["top_p"])
        pos_next = st["prompt_pos"] + take
        emitting = alive & jnp.logical_not(bad) \
            & (pos_next >= st["prompt_len"])
        st["keys"] = jnp.where(emitting[:, None], new_keys, st["keys"])
        emit = jnp.where(emitting, toks, jnp.int32(-1))
        emit_rid = jnp.where(emitting, st["rid"], jnp.int32(-1))

        # 5. EOS / length-cap retire (a non-finite row dies too)
        st["remaining"] = st["remaining"] - emitting.astype(jnp.int32)
        hit_eos = emitting & (st["eos"] >= 0) & (toks == st["eos"])
        died = hit_eos | (emitting & (st["remaining"] <= 0))
        st["alive"] = alive & jnp.logical_not(died | bad)
        st["tok"] = jnp.where(emitting, toks, st["tok"])
        st["prompt_pos"] = pos_next
        return (st, prefill_ct, round_ct, waste_ct, nf_ct, pk_rounds,
                pk_toks), (emit, emit_rid, bad)

    zero = jnp.zeros((), jnp.int32)
    (state, prefill_ct, round_ct, waste_ct, nf_ct, pk_rounds, pk_toks), \
        (emitted, rids, nonfinite) = lax.scan(
            body, (state,) + (zero,) * 6, None, length=n)
    counters = {"prefill_steps": prefill_ct,
                "prefill_rounds": round_ct,
                "wasted_slot_steps": waste_ct,
                "nonfinite_decode_rounds": nf_ct,
                "packed_rounds": pk_rounds,
                "packed_tokens": pk_toks,
                "nonfinite": jnp.swapaxes(nonfinite, 0, 1)}
    return (jnp.swapaxes(emitted, 0, 1), jnp.swapaxes(rids, 0, 1),
            state, counters)


def _superstep_spec(params, cfg, state: Dict[str, Any], n: int, *,
                    prompt_chunk: int, draft, draft_params):
    """The speculative form of :func:`superstep` (see its docstring for
    the contract).  Per round, for every slot simultaneously:

      1. **re-admission** as in the plain loop, additionally resetting
         the drafting history (``n_out``) and the draft source's own
         per-slot state;
      2. **propose** -- the draft source offers up to S continuation
         tokens per row; only decoding rows keep theirs (capped at
         ``remaining - 1``: the round's guaranteed token covers the
         rest);
      3. **verify** -- ONE ``decode_verify`` chunk pass over
         ``[tok, d_1..d_S]`` for decoding rows (prefilling rows ride the
         same call with their next C prompt tokens, dead rows with
         valid=1), producing per-position logits and per-position
         states;
      4. **accept** -- position i's exact token x_i (greedy argmax or
         categorical under chained key i) is compared to draft d_{i+1}:
         the committed length is e = (leading run of matches) + 1,
         truncated at the first emitted EOS.  Tokens x_0..x_{e-1} emit
         into planes 0..e-1; the slot's key advances e splits, its fed-
         back token becomes x_{e-1};
      5. **rollback / commit** -- the recurrent state is gathered at the
         last committed position (prefilling rows: their packed take;
         dead rows: 1) and ``pos`` advances by exactly the committed
         length -- O(d_hidden) per slot, no recompute;
      6. **EOS / retire** exactly as the plain loop (an EOS can only sit
         at the last emitted plane, by the truncation in 4).
    """
    from repro.serving import sampling

    batch = state["tok"].shape[0]
    p_cap = state["prompt"].shape[1]
    chunk = int(prompt_chunk)
    s_len = int(draft.draft_len)
    n_emit_planes = s_len + 1                   # E: emit planes per round
    width = max(chunk, s_len + 1)               # W: verify chunk width
    b_idx = jnp.arange(batch)
    i32 = jnp.int32

    def body(carry, _):
        st, ct = carry
        st, ct = dict(st), dict(ct)

        # 1. re-admission from the staging buffer
        arm = jnp.logical_not(st["alive"]) & st["s_valid"]
        for f in _ARM_FIELDS:
            st[f] = jnp.where(arm, st["s_" + f], st[f])
        st["prompt"] = jnp.where(arm[:, None], st["s_prompt"], st["prompt"])
        st["prompt_pos"] = jnp.where(arm, 0, st["prompt_pos"])
        st["n_out"] = jnp.where(arm, 0, st["n_out"])
        st["alive"] = st["alive"] | arm
        st["s_valid"] = st["s_valid"] & jnp.logical_not(arm)
        st["cache"] = _reset_slot_rows(st["cache"], arm)
        if "draft_cache" in st:
            st["draft_cache"] = _reset_slot_rows(st["draft_cache"], arm)

        alive = st["alive"]
        ct["wasted_slot_steps"] += jnp.sum(
            jnp.logical_not(alive).astype(i32))
        prefilling = alive & (st["prompt_pos"] < st["prompt_len"])
        decoding = alive & jnp.logical_not(prefilling)
        ct["prefill_rounds"] += jnp.sum(prefilling.astype(i32))

        left = st["prompt_len"] - st["prompt_pos"]
        take = jnp.where(prefilling,
                         jnp.minimum(left, chunk), 0).astype(i32)
        ct["prefill_steps"] += jnp.sum(take)

        # 2. draft proposal; decoding rows only, capped so the proposal
        # never overshoots the length budget (the verify round's own
        # token is always emitted)
        drafts, n_draft = draft.propose(draft_params, st)
        n_draft = jnp.where(
            decoding,
            jnp.clip(jnp.minimum(n_draft, st["remaining"] - 1), 0, s_len),
            0).astype(i32)
        ct["draft_proposed"] += jnp.sum(n_draft)

        # 3. one verify pass for the whole batch: prefilling rows carry
        # their next C prompt tokens, decoding rows [tok, d_1..d_S]
        idx = st["prompt_pos"][:, None] + jnp.arange(width)[None]
        gathered = jnp.take_along_axis(
            st["prompt"], jnp.clip(idx, 0, p_cap - 1), axis=1)
        dec_blk = jnp.concatenate([st["tok"][:, None], drafts], axis=1)
        if width > s_len + 1:
            dec_blk = jnp.concatenate(
                [dec_blk, jnp.zeros((batch, width - s_len - 1), i32)],
                axis=1)
        tok_blk = jnp.where(prefilling[:, None], gathered, dec_blk)
        valid_in = jnp.where(prefilling, jnp.maximum(take, 1),
                             1 + n_draft).astype(i32)
        if chunk > 1:
            packed = jnp.any(prefilling)
            ct["packed_rounds"] += packed.astype(i32)
            ct["packed_tokens"] += jnp.where(
                packed, jnp.sum(jnp.where(alive, valid_in, 0)), 0)
        logits_all, pstates = decode_verify(params, cfg, tok_blk,
                                            valid_in, st["cache"])

        # 3b. numerical health guard (see the plain loop): per-slot
        # finite bit over the verify pass's logits and per-position
        # recurrent states; poisoned rows emit nothing this round and
        # die, all-False on a healthy batch so streams stay bit-exact
        ok = jnp.all(jnp.isfinite(logits_all), axis=(1, 2))
        if "h" in pstates:
            ph = pstates["h"]
            ok = ok & jnp.all(jnp.isfinite(ph), axis=tuple(
                a for a in range(ph.ndim) if a != 1))
        bad = alive & jnp.logical_not(ok)
        ct["nonfinite_decode_rounds"] += jnp.sum(
            (bad & jnp.logical_not(prefilling)).astype(i32))

        # 4a. exact per-position tokens under the chained key schedule
        # (decoding rows); position i IS what the i-th non-speculative
        # round would sample, so acceptance never changes content
        x_toks, keys_chain = sampling.sample_chain(
            logits_all[:, :n_emit_planes], st["keys"], st["temperature"],
            st["top_k"], st["top_p"])
        # prefilling rows emit (at most) their first output token, from
        # the logits at their LAST consumed prompt position with the
        # slot's current key -- exactly the plain packed path
        last_logits = jnp.take_along_axis(
            logits_all, (valid_in - 1)[:, None, None], axis=1)[:, 0]
        tok_first, _ = sampling.sample_tokens(
            last_logits, st["keys"], st["temperature"], st["top_k"],
            st["top_p"])

        # 4b. acceptance: leading run of drafts matching the exact
        # tokens, +1 for the verifier's own token, truncated at EOS
        m = (x_toks[:, :s_len] == tok_blk[:, 1:s_len + 1]) \
            & (jnp.arange(s_len)[None] < n_draft[:, None])
        lead = jnp.sum(jnp.cumprod(m.astype(i32), axis=1), axis=1)
        is_eos = (st["eos"] >= 0)[:, None] & (x_toks == st["eos"][:, None])
        first_eos = jnp.min(
            jnp.where(is_eos, jnp.arange(n_emit_planes)[None],
                      n_emit_planes), axis=1)
        e = jnp.minimum(lead + 1, first_eos + 1)
        ct["draft_accepted"] += jnp.sum(
            jnp.where(decoding & jnp.logical_not(bad), e - 1, 0))

        pos_next = st["prompt_pos"] + take
        pf_emit = prefilling & (pos_next >= st["prompt_len"])
        emitting = (pf_emit | decoding) & jnp.logical_not(bad)
        ct["emit_rounds"] += jnp.sum(emitting.astype(i32))
        n_emit = jnp.where(bad, 0,
                           jnp.where(decoding, e, pf_emit.astype(i32)))

        # 4c. multi-emit planes: -1 beyond each row's committed length
        plane = jnp.arange(n_emit_planes)[None]
        emit_tok = jnp.where(decoding[:, None], x_toks,
                             tok_first[:, None])
        live_plane = plane < n_emit[:, None]
        emit = jnp.where(live_plane, emit_tok, jnp.int32(-1))
        emit_rid = jnp.where(live_plane, st["rid"][:, None],
                             jnp.int32(-1))

        # keys advance one split per emitted token (keys_chain[:, 0] is
        # the single-split advance, so pf_emit rows get the plain path's
        # key); tok becomes the last emitted token
        kidx = jnp.clip(n_emit - 1, 0, n_emit_planes - 1)
        keys_adv = jnp.take_along_axis(
            keys_chain, kidx[:, None, None], axis=1)[:, 0]
        st["keys"] = jnp.where(emitting[:, None], keys_adv, st["keys"])
        last_tok = jnp.take_along_axis(emit_tok, kidx[:, None],
                                       axis=1)[:, 0]
        st["tok"] = jnp.where(emitting, last_tok, st["tok"])

        # drafting history: append the emitted tokens to the prompt
        # buffer (the n-gram source self-drafts from it); writes past
        # the buffer (only ever a request's final token) are dropped
        hist = st["prompt_len"] + st["n_out"]
        w_idx = jnp.where(live_plane, hist[:, None] + plane, p_cap)
        st["prompt"] = st["prompt"].at[b_idx[:, None], w_idx].set(
            jnp.maximum(emit, 0), mode="drop")
        st["n_out"] = st["n_out"] + n_emit

        # 5. rollback/commit: gather the recurrent state at each row's
        # last committed position, advance pos by the committed length
        valid_eff = jnp.where(prefilling, jnp.maximum(take, 1),
                              jnp.where(decoding, e, 1)).astype(i32)
        g_idx = (valid_eff - 1).astype(i32)
        new_cache = dict(st["cache"])
        new_cache["h"] = jnp.take_along_axis(
            pstates["h"], g_idx[None, :, None, None], axis=2)[:, :, 0]
        if "conv" in pstates:
            new_cache["conv"] = jnp.take_along_axis(
                pstates["conv"], g_idx[None, :, None, None, None],
                axis=2)[:, :, 0]
        new_cache["pos"] = st["cache"]["pos"] + valid_eff
        st["cache"] = new_cache
        st.update(draft.commit(draft_params, st, tok_blk, valid_eff))

        # 6. EOS / length-cap retire (truncation in 4b guarantees an
        # emitted EOS sits at the last plane)
        st["remaining"] = st["remaining"] - n_emit
        hit_eos = emitting & (st["eos"] >= 0) & (last_tok == st["eos"])
        died = hit_eos | (emitting & (st["remaining"] <= 0))
        st["alive"] = alive & jnp.logical_not(died | bad)
        st["prompt_pos"] = pos_next
        return (st, ct), (emit, emit_rid, bad)

    zero = jnp.zeros((), i32)
    counters0 = {k: zero for k in (
        "prefill_steps", "prefill_rounds", "wasted_slot_steps",
        "draft_proposed", "draft_accepted", "emit_rounds",
        "nonfinite_decode_rounds", "packed_rounds", "packed_tokens")}
    (state, counters), (emitted, rids, nonfinite) = lax.scan(
        body, (state, counters0), None, length=n)
    counters["nonfinite"] = jnp.swapaxes(nonfinite, 0, 1)
    return (jnp.moveaxis(emitted, 0, 1), jnp.moveaxis(rids, 0, 1),
            state, counters)


def _attn_mixer_step(p, cfg, y, cache_l, pos):
    """Single-token mixer with cache. Returns (out, new mixer cache dict)."""
    if cfg.seq_mixer in _MIN_CELLS:
        cell = _MIN_CELLS[cfg.seq_mixer]
        mode = cfg.minrnn.mode if cfg.minrnn else "log"
        h = cell.step(p["rnn"], y, cache_l["h"], mode=mode,
                      compute_dtype=cfg.cdtype,
                      scan_strategy=cfg.scan_strategy)
        return nn.dense_apply(p["down"], h, cfg.cdtype), {"h": h}
    if cfg.attn_kind == "mla":
        out, ckv, krope = attn.mla_decode_step(p, cfg, y, cache_l["ckv"],
                                               cache_l["krope"], pos)
        return out, {"ckv": ckv, "krope": krope}
    out, k, v = attn.gqa_decode_step(p, cfg, y, cache_l["k"], cache_l["v"],
                                     pos)
    return out, {"k": k, "v": v}


def _attn_block_step(p, cfg, x, cache_l, pos, *, has_moe):
    nk = dict(zero_centered=True) if cfg.norm_zero_centered else {}
    y = nn.norm_apply(cfg.norm, p["norm1"], x, **nk)
    out, mix_cache = _attn_mixer_step(p["mixer"], cfg, y, cache_l, pos)
    x = x + out
    y = nn.norm_apply(cfg.norm, p["norm2"], x, **nk)
    if has_moe:
        out, _ = moe_lib.moe_apply(p["moe"], cfg, y[:, None, :],
                                   activation=cfg.mlp_activation)
        out = out[:, 0]
    else:
        out = mlp_apply(p["mlp"], y, activation=cfg.mlp_activation,
                        compute_dtype=cfg.cdtype)
    return x + out, mix_cache


def _attn_decode(params, cfg, x, cache):
    pos = cache["pos"]
    layers = params["layers"]
    mixer_keys = [k for k in ("h", "ckv", "krope", "k", "v") if k in cache]

    n_dense = 0
    if "dense_blocks" in layers:
        n_dense = jax.tree.leaves(layers["dense_blocks"])[0].shape[0]

        def body_d(carry, scanned):
            p_l, cache_l = scanned
            y, mc = _attn_block_step(p_l, cfg, carry, cache_l, pos,
                                     has_moe=False)
            return y, mc

        sub = {k: cache[k][:n_dense] for k in mixer_keys}
        x, outs_d = _iterate(cfg, body_d, x, (layers["dense_blocks"], sub))
    has_moe = cfg.moe is not None

    def body(carry, scanned):
        p_l, cache_l = scanned
        y, mc = _attn_block_step(p_l, cfg, carry, cache_l, pos,
                                 has_moe=has_moe)
        return y, mc

    sub = {k: cache[k][n_dense:] for k in mixer_keys}
    x, outs = _iterate(cfg, body, x, (layers["blocks"], sub))
    if n_dense:
        outs = jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                            outs_d, outs)
    return x, outs


def _hybrid_decode(params, cfg, x, cache):
    pos = cache["pos"]
    every = cfg.hybrid_attn_every
    n_groups = cfg.n_layers // every
    blocks = params["layers"]["blocks"]
    shared = params["layers"]["shared_attn"]
    grouped = jax.tree.map(
        lambda a: a.reshape((n_groups, every) + a.shape[1:]), blocks)
    g_conv = cache["conv"].reshape((n_groups, every) + cache["conv"].shape[1:])
    g_ssm = cache["ssm"].reshape((n_groups, every) + cache["ssm"].shape[1:])

    def group_body(carry, scanned):
        p_group, conv_g, ssm_g, k_g, v_g = scanned

        def inner(c, s):
            p_l, conv_l, ssm_l = s
            y = nn.norm_apply(cfg.norm, p_l["norm"], c)
            out, state = ssd_lib.ssd_block_step(
                p_l["mixer"], cfg, y, {"conv": conv_l, "ssm": ssm_l})
            return c + out, (state["conv"], state["ssm"])

        h, (conv_new, ssm_new) = _iterate(cfg, inner, carry,
                                          (p_group, conv_g, ssm_g))
        h, mc = _attn_block_step(shared, cfg, h, {"k": k_g, "v": v_g}, pos,
                                 has_moe=False)
        return h, (conv_new, ssm_new, mc["k"], mc["v"])

    x, (conv_new, ssm_new, k_new, v_new) = _iterate(
        cfg, group_body, x,
        (grouped, g_conv, g_ssm, cache["k"], cache["v"]))
    return x, {
        "conv": conv_new.reshape(cache["conv"].shape),
        "ssm": ssm_new.reshape(cache["ssm"].shape),
        "k": k_new, "v": v_new,
    }


# ===========================================================================
# Prefill: parallel pass over the prompt that seeds the decode caches
# ===========================================================================

def _attn_block_prefill(p, cfg, x, positions, *, has_moe, lengths=None):
    nk = dict(zero_centered=True) if cfg.norm_zero_centered else {}
    y = nn.norm_apply(cfg.norm, p["norm1"], x, **nk)
    if cfg.seq_mixer in _MIN_CELLS:
        cell = _MIN_CELLS[cfg.seq_mixer]
        mode = cfg.minrnn.mode if cfg.minrnn else "log"
        h = cell.parallel(p["mixer"]["rnn"], y, mode=mode,
                          compute_dtype=cfg.cdtype,
                          scan_strategy=cfg.scan_strategy)
        out = nn.dense_apply(p["mixer"]["down"], h, cfg.cdtype)
        mix_cache = {"h": h[:, -1] if lengths is None
                     else nn.gather_last(h, lengths)}
    elif cfg.attn_kind == "mla":
        out, ckv, krope = attn.mla_prefill(p["mixer"], cfg, y,
                                           positions=positions)
        mix_cache = {"ckv": ckv, "krope": krope}
    else:
        out, k, v = attn.gqa_prefill(p["mixer"], cfg, y, positions=positions)
        mix_cache = {"k": k, "v": v}
    x = x + out
    y = nn.norm_apply(cfg.norm, p["norm2"], x, **nk)
    if has_moe:
        out, _ = moe_lib.moe_apply(p["moe"], cfg, y,
                                   activation=cfg.mlp_activation)
    else:
        out = mlp_apply(p["mlp"], y, activation=cfg.mlp_activation,
                        compute_dtype=cfg.cdtype)
    return x + out, mix_cache


def _seed_kv(full, max_len):
    """(L, B, T, ...) prompt kv -> (L, B, max_len, ...) zero-padded cache."""
    t = full.shape[2]
    pad = [(0, 0)] * full.ndim
    pad[2] = (0, max_len - t)
    return jnp.pad(full, pad)


def supports_chunked_prefill(cfg) -> bool:
    """True when ``prefill`` can resume from a carried cache, i.e. the whole
    decode state is a constant-size recurrence (the paper's minRNN family).
    KV/SSD caches would need offset-aware attention / state-resumed chunk
    scans; those archs prefill whole-prompt instead."""
    return cfg.block_kind == "minrnn"


def prefill(params, cfg, tokens: Array, max_len: int, *,
            patch_embeds: Optional[Array] = None,
            lengths: Optional[Array] = None,
            cache: Optional[Dict[str, Any]] = None
            ) -> Tuple[Array, Dict[str, Any]]:
    """Parallel prompt processing.  Returns (last-token logits (B, V), cache
    ready for decode_step).  This is the paper's headline win: the prompt is
    one parallel scan, not T sequential cell evaluations.

    ``lengths`` (B,) int32 enables *batched* prefill of right-padded
    variable-length prompts: row b's logits/state are taken at its true
    terminal position ``lengths[b]-1``.  Every mixer is causal, so positions
    before the pad are bit-identical to an unpadded run; recurrent states
    are gathered per-row (SSD additionally masks dt so padded steps are
    inert), while KV caches may hold garbage beyond ``lengths[b]`` -- decode
    masks attention by the per-slot ``pos`` and overwrites those positions
    in place before they ever become visible.

    ``cache`` resumes prefill from a previous prefill's cache (chunked
    prefill); only supported for ``supports_chunked_prefill`` configs.
    """
    if cache is not None and not supports_chunked_prefill(cfg):
        raise NotImplementedError(
            f"chunked prefill resume not supported for block_kind="
            f"{cfg.block_kind!r}")
    if lengths is not None and cfg.frontend == "patches":
        raise NotImplementedError("variable-length prefill with a patch "
                                  "frontend prefix is not supported")
    x = _embed(params, cfg, tokens, patch_embeds)
    bsz, t = x.shape[0], x.shape[1]
    positions = jnp.arange(t)[None, :]
    consumed = jnp.full((bsz,), t, jnp.int32) if lengths is None \
        else lengths.astype(jnp.int32)
    pos0 = cache["pos"] if cache is not None else 0
    new_cache: Dict[str, Any] = {"pos": pos0 + consumed}

    if cfg.block_kind == "minrnn":
        bc = _minrnn_block_cfg(cfg)

        if cache is not None:
            state0 = {"h": cache["h"]}
            if bc.use_conv:
                state0["conv"] = cache["conv"]

            def body_r(carry, scanned):
                p_l, st_l = scanned
                h, state = minrnn_blocks.apply(p_l, bc, carry, state0=st_l,
                                               lengths=lengths,
                                               compute_dtype=cfg.cdtype,
                                               scan_strategy=cfg.scan_strategy,
                                               return_state=True)
                return h, state

            x, states = _scan_layers(cfg, body_r, x,
                                     (params["layers"]["blocks"], state0))
        else:
            def body(carry, p_l):
                h, state = minrnn_blocks.apply(p_l, bc, carry,
                                               lengths=lengths,
                                               compute_dtype=cfg.cdtype,
                                               scan_strategy=cfg.scan_strategy,
                                               return_state=True)
                return h, state

            x, states = _scan_layers(cfg, body, x,
                                     params["layers"]["blocks"])
        new_cache["h"] = states["h"]
        if bc.use_conv:
            new_cache["conv"] = states["conv"]

    elif cfg.block_kind == "ssm":
        def body(carry, p_l):
            nk = dict(zero_centered=True) if cfg.norm_zero_centered else {}
            y = nn.norm_apply(cfg.norm, p_l["norm"], carry, **nk)
            out, state = ssd_lib.ssd_block_apply(p_l["mixer"], cfg, y,
                                                 return_state=True,
                                                 lengths=lengths)
            return carry + out, state

        x, states = _scan_layers(cfg, body, x, params["layers"]["blocks"])
        new_cache["conv"] = states["conv"]
        new_cache["ssm"] = states["ssm"]

    elif cfg.block_kind == "hybrid":
        x, cache_h = _hybrid_prefill(params, cfg, x, positions, max_len,
                                     lengths=lengths)
        new_cache.update(cache_h)

    else:
        layers = params["layers"]
        has_moe = cfg.moe is not None
        mix_caches = []

        if "dense_blocks" in layers:
            def body_d(carry, p_l):
                return _attn_block_prefill(p_l, cfg, carry, positions,
                                           has_moe=False, lengths=lengths)

            x, mc_d = _scan_layers(cfg, body_d, x, layers["dense_blocks"])
            mix_caches.append(mc_d)

        def body(carry, p_l):
            return _attn_block_prefill(p_l, cfg, carry, positions,
                                       has_moe=has_moe, lengths=lengths)

        x, mc = _scan_layers(cfg, body, x, layers["blocks"])
        mix_caches.append(mc)
        if len(mix_caches) == 2:
            mc = jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                              mix_caches[0], mix_caches[1])
        else:
            mc = mix_caches[0]
        if "h" in mc:
            new_cache["h"] = mc["h"]
        elif "ckv" in mc:
            new_cache["ckv"] = _seed_kv(mc["ckv"], max_len)
            new_cache["krope"] = _seed_kv(mc["krope"], max_len)
        else:
            new_cache["k"] = _seed_kv(mc["k"], max_len)
            new_cache["v"] = _seed_kv(mc["v"], max_len)

    nk = dict(zero_centered=True) if cfg.norm_zero_centered else {}
    x_last = x[:, -1] if lengths is None else nn.gather_last(x, lengths)
    x_last = nn.norm_apply(cfg.norm, params["final_norm"], x_last, **nk)
    return _logits(params, cfg, x_last), new_cache


def _hybrid_prefill(params, cfg, x, positions, max_len, lengths=None):
    every = cfg.hybrid_attn_every
    n_groups = cfg.n_layers // every
    blocks = params["layers"]["blocks"]
    shared = params["layers"]["shared_attn"]
    grouped = jax.tree.map(
        lambda a: a.reshape((n_groups, every) + a.shape[1:]), blocks)

    def group_body(carry, p_group):
        def inner(c, p_l):
            nk = dict(zero_centered=True) if cfg.norm_zero_centered else {}
            y = nn.norm_apply(cfg.norm, p_l["norm"], c, **nk)
            out, state = ssd_lib.ssd_block_apply(p_l["mixer"], cfg, y,
                                                 return_state=True,
                                                 lengths=lengths)
            return c + out, state

        h, states = _iterate(cfg, inner, carry, p_group)
        h, mc = _attn_block_prefill(shared, cfg, h, positions, has_moe=False,
                                    lengths=lengths)
        return h, (states, mc)

    x, (states, mc) = _iterate(cfg, _remat(cfg, group_body), x, grouped)
    conv = states["conv"].reshape((-1,) + states["conv"].shape[2:])
    ssm = states["ssm"].reshape((-1,) + states["ssm"].shape[2:])
    return x, {"conv": conv, "ssm": ssm,
               "k": _seed_kv(mc["k"], max_len),
               "v": _seed_kv(mc["v"], max_len)}
