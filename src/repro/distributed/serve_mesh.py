"""Mesh-sharded serving: the slot pool over ``data``, gate projections
over ``model``.

The serving superstep (``lm.superstep``) is one jitted scan whose body is
purely per-slot arithmetic, which makes it trivially data-parallel: shard
every batch-leading leaf of the slot state over the ``data`` axis and run
the SAME body per shard under ``shard_map`` -- no collectives, per-row
bit-exact with the single-device engine.  Tensor parallelism composes on
top for the weight-bound regime (full config: the decode round is an HBM
weight stream, see benchmarks/engine_throughput.py): the gate / down /
MLP kernels shard their ``d_hidden`` / ``d_ff`` dim over ``model`` via
the existing ``sharding.PARAM_RULES``, each shard's fused Pallas step
kernels run on their local ``d_hidden/model`` column block, and the
row-parallel projections ``psum`` their partials per layer
(``blocks._row_parallel_apply``) -- Megatron-style, one reduction per
mixer sub-block and one per MLP.  The residual stream, norms, depthwise
conv and the (tiny, vocab=256) embedding/unembedding stay replicated per
model shard, so sampling sees full logits with NO collective at the
readout.  TP streams are argmax-equivalent, not bit-identical, to single
device: splitting the down-projection's contraction reorders the fp32
reduction, perturbing logits by ~1 ulp (documented + tested; pure DP is
bit-exact because per-row arithmetic is untouched).

Per-shard accounting: the superstep's scalar counters are emitted with a
``P("data")`` out-spec (reshaped to (1,) inside the body), so the host
receives one value per data shard and the slot-step identity can be
checked per shard AND globally (``scheduler.ShardStats``).

Caveat: the in-loop non-finite health guard reduces each model shard's
LOCAL ``h`` block; a genuine overflow confined to one shard's block
would desynchronise slot liveness across model shards.  Injected faults
(``serving/faults.py``) poison whole rows so every shard agrees; on a
fault-free trace the guard is the identity.

DP-shard failover (``serving/recovery.py`` + the ``shard_crash`` chaos
point): a "crashed" data shard stays IN the mesh -- the device topology
is fixed at backend init -- but the engine marks its contiguous row
group (:func:`shard_rows`) permanently dead (``alive=False``, never
staged), so the shard's device keeps lock-stepping empty rows (counted
as its own ``wasted_slot_steps``, keeping the per-shard slot-step
identity exact) while its drained requests re-run on the survivors.
This models losing a shard's *state*, the recoverable failure a
fixed-state RNN makes cheap; losing the device itself needs a restart
onto a smaller mesh via the engine snapshot/journal path.
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.distributed import context as mesh_ctx
from repro.distributed import sharding

_FORCE_FLAG = "--xla_force_host_platform_device_count"


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """``data`` x ``model`` serving mesh shape (the ``--mesh dxm`` flag).

    ``data`` shards the slot pool over B (throughput: d independent HBM
    weight streams each serving B/d slots); ``model`` shards ``d_hidden``
    (latency in the weight-bound regime: each chip streams 1/m of the
    gate/down/MLP bytes per round, paying a per-layer psum).
    """
    data: int = 1
    model: int = 1

    def __post_init__(self):
        if self.data < 1 or self.model < 1:
            raise ValueError(f"mesh axes must be >= 1, got "
                             f"{self.data}x{self.model}")

    @classmethod
    def parse(cls, spec) -> Optional["MeshPlan"]:
        """``None`` | ``MeshPlan`` | ``"dxm"`` string -> MeshPlan or None."""
        if spec is None or isinstance(spec, cls):
            return spec
        m = re.fullmatch(r"(\d+)x(\d+)", str(spec).strip())
        if not m:
            raise ValueError(
                f"mesh spec must look like '4x1' or '2x2' "
                f"(data x model), got {spec!r}")
        return cls(int(m.group(1)), int(m.group(2)))

    @property
    def size(self) -> int:
        return self.data * self.model

    def build(self) -> Mesh:
        devs = jax.devices()
        if len(devs) < self.size:
            raise RuntimeError(
                f"mesh {self} needs {self.size} devices but jax sees "
                f"{len(devs)}.  On CPU, force virtual devices BEFORE jax "
                f"initialises: XLA_FLAGS='{_FORCE_FLAG}={self.size}' (the "
                f"launchers do this for you via ensure_host_devices when "
                f"--mesh is passed early enough; under pytest set "
                f"REPRO_FORCE_DEVICES={self.size}).")
        return Mesh(np.asarray(devs[:self.size]).reshape(
            self.data, self.model), ("data", "model"))

    def __str__(self) -> str:
        return f"{self.data}x{self.model}"


def shard_rows(shard: int, rows_per_shard: int) -> range:
    """Contiguous slot rows owned by data shard ``shard`` (ownership is
    ``slot // rows_per_shard`` everywhere: staging placement, per-shard
    counters and the failover drain all agree on this map)."""
    return range(shard * rows_per_shard, (shard + 1) * rows_per_shard)


def ensure_host_devices(n: int) -> None:
    """Make sure jax will see >= ``n`` devices, or fail actionably.

    The host-platform device count is fixed the moment jax initialises
    its backend, so this must run before the first ``jax.devices()`` /
    array op of the process.  If ``XLA_FLAGS`` does not already force a
    count we set it here (idempotent for a fresh process); if the backend
    initialised earlier with fewer devices, the count cannot change and
    we raise with the fix instead of silently serving a 1-device mesh.
    """
    if n <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " " if flags else "") + f"{_FORCE_FLAG}={n}"
    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"requested a {n}-device mesh but jax initialised with "
            f"{have} device(s) before the flag could take effect.  "
            f"Relaunch with XLA_FLAGS='{_FORCE_FLAG}={n}' in the "
            f"environment (or pass --mesh so the launcher sets it before "
            f"any jax use).")


# ---------------------------------------------------------------------------
# PartitionSpecs for the slot state and the serving param layout
# ---------------------------------------------------------------------------

def _tp_shards_hidden(cfg, plan: MeshPlan) -> bool:
    """True when the model axis actually shards ``d_hidden`` -- must
    match ``sharding.spec_for_param``'s divisibility fallback so the h
    cache layout agrees with the gate-kernel layout."""
    if plan.model <= 1 or cfg.block_kind != "minrnn":
        return False
    d_hidden = int(cfg.d_model * (cfg.minrnn.expansion if cfg.minrnn
                                  else 1.0))
    return d_hidden % plan.model == 0


def _cache_pspecs(cache: Dict[str, Any], shard_hidden: bool
                  ) -> Dict[str, Any]:
    """Decode-cache leaves: (L, B, ...) with batch at axis 1 (``pos`` at
    axis 0).  Only the minRNN ``h`` leaf carries a model dim (it IS the
    col-parallel gate output); conv windows / KV / SSM rows stay
    replicated per model shard."""
    specs: Dict[str, Any] = {}
    for k, leaf in cache.items():
        if k == "pos":
            specs[k] = P("data")
        elif k == "h" and shard_hidden:
            specs[k] = P(None, "data", "model")
        else:
            specs[k] = P(None, "data", *([None] * (leaf.ndim - 2)))
    return specs


def slot_state_pspecs(cfg, state: Dict[str, Any], plan: MeshPlan
                      ) -> Dict[str, Any]:
    """PartitionSpecs for every leaf of ``lm.init_slot_state``: the slot
    pool (request fields, sampling keys, staging buffers, prompt matrix)
    shards over ``data`` on its leading B dim; cache leaves shard B at
    axis 1, with ``h`` additionally on ``model`` under TP.  A draft
    model's cache shards over ``data`` only (draft weights are
    replicated -- its per-shard compute is identical everywhere)."""
    shard_hidden = _tp_shards_hidden(cfg, plan)
    specs: Dict[str, Any] = {}
    for k, v in state.items():
        if k == "cache":
            specs[k] = _cache_pspecs(v, shard_hidden)
        elif k == "draft_cache":
            specs[k] = _cache_pspecs(v, False)
        else:
            specs[k] = jax.tree.map(
                lambda leaf: P("data", *([None] * (leaf.ndim - 1))), v)
    return specs


def slot_state_shardings(cfg, state, plan: MeshPlan, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        slot_state_pspecs(cfg, state, plan),
                        is_leaf=lambda x: isinstance(x, P))


# Serving-TP whitelist: ONLY the projections whose d_hidden / d_ff dim
# the decode path actually blocks over (col-parallel gates + mlp_in,
# row-parallel down + mlp_out).  Everything else -- norms, depthwise conv
# (its channels feed the FULL-d_model gate contraction), the tiny
# embedding/unembedding (vocab 256: sampling wants full logits with no
# collective) -- is replicated per model shard even where the training
# PARAM_RULES would shard it.
_SERVE_TP_PARAMS = re.compile(
    r"(rnn/w[zhfi]/(kernel|bias)|down/kernel"
    r"|mlp_in/(kernel|bias)|mlp_out/kernel)$")


def serve_params_pspecs(params, cfg, plan: MeshPlan, mesh: Mesh):
    """Param PartitionSpecs for the sharded superstep: replicated under
    pure DP; under TP the ``sharding.PARAM_RULES`` entries for the gate /
    down / MLP projections apply with ``tp -> ("model",)`` and every
    other logical axis disabled (``fsdp`` etc. are training-time
    layouts -- serving wants whole weights per data shard)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    if plan.model <= 1:
        return jax.tree_util.tree_unflatten(treedef, [P()] * len(flat))
    mapping = {"dp": (), "fsdp": (), "tp": ("model",), "expert": (),
               "sp": ()}
    specs = []
    for path, leaf in flat:
        path_s = sharding._path_str(path)
        if _SERVE_TP_PARAMS.search(path_s):
            specs.append(sharding.spec_for_param(path_s, leaf.shape, mesh,
                                                 mapping))
        else:
            specs.append(P())
    return jax.tree_util.tree_unflatten(treedef, specs)


def serve_params_shardings(params, cfg, plan: MeshPlan, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        serve_params_pspecs(params, cfg, plan, mesh),
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# The shard_map'd superstep
# ---------------------------------------------------------------------------

_PLAIN_COUNTERS = ("prefill_steps", "prefill_rounds", "wasted_slot_steps",
                   "nonfinite_decode_rounds", "packed_rounds",
                   "packed_tokens")
_SPEC_COUNTERS = _PLAIN_COUNTERS + ("draft_proposed", "draft_accepted",
                                    "emit_rounds")


def make_superstep(cfg, plan: MeshPlan, mesh: Mesh, state: Dict[str, Any],
                   params, n: int, *, prompt_chunk: int = 1, draft=None):
    """Build the jitted ``shard_map``'d superstep.

    Returns ``fn(params, draft_params, state) -> (toks, rids, state,
    counters)`` with the same contract as ``lm.superstep`` except that
    the scalar counters come back as (data,) arrays -- one value per
    data shard -- so the host can hold the slot-step identity per shard
    as well as globally.  ``toks``/``rids`` are the global (B, n[, S+1])
    planes (B-sharded on device; ``np.asarray`` gathers them at drain).
    """
    from repro.models import lm      # deferred: keep import cycles away

    state_specs = slot_state_pspecs(cfg, state, plan)
    param_specs = serve_params_pspecs(params, cfg, plan, mesh)
    tp_axis = "model" if plan.model > 1 else None

    ckeys = _SPEC_COUNTERS if draft is not None else _PLAIN_COUNTERS
    counter_specs = {k: P("data") for k in ckeys}
    counter_specs["nonfinite"] = P("data", None)
    emit_spec = P("data", None, None) if draft is not None \
        else P("data", None)

    def body(p, dp, s):
        # the serving_tp context is consulted at TRACE time -- tracing
        # happens inside this body, so row-parallel projections know to
        # psum their d_hidden-block partials over the model axis
        with mesh_ctx.serving_tp(tp_axis):
            toks, rids, st, counters = lm.superstep(
                p, cfg, s, n, prompt_chunk=prompt_chunk, draft=draft,
                draft_params=dp)
        # scalar counters -> (1,) so the P("data") out-spec concatenates
        # one value per data shard
        counters = {k: (v[None] if v.ndim == 0 else v)
                    for k, v in counters.items()}
        return toks, rids, st, counters

    fn = mesh_ctx.shard_map(
        body, mesh=mesh,
        in_specs=(param_specs, P(), state_specs),
        out_specs=(emit_spec, emit_spec, state_specs, counter_specs),
        check_vma=False)
    return jax.jit(fn)
