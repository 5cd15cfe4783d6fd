"""Config schema for every architecture in the zoo.

One frozen dataclass describes any of the 10 assigned architectures plus the
paper's own minRNN LMs.  Block composition is driven by ``block_kind`` and
the optional MoE / SSM / hybrid sub-configs; ``seq_mixer`` swaps the native
attention mixer for the paper's minGRU/minLSTM (DESIGN.md §5).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax.numpy as jnp

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int                 # routed experts
    top_k: int
    d_expert: int                  # per-expert FFN hidden dim
    n_shared: int = 0              # shared (always-on) experts
    d_shared: int = 0              # shared-expert hidden dim (total)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    first_dense_layers: int = 0    # deepseek: leading dense layers
    ep_2d: str = "auto"            # 2D (expert x d) weight sharding:
                                   # auto = on when activation all-to-all
                                   # traffic < weight gather (decode);
                                   # on | off force (EXPERIMENTS.md §Perf D)


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    conv_kernel: int = 4
    chunk: int = 256               # SSD chunk length
    dual_form: str = "masked"      # masked (paper-faithful) | factored
                                   # (beyond-paper, EXPERIMENTS.md §Perf)

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class MinRNNConfig:
    cell: str = "mingru"           # mingru | minlstm
    expansion: float = 2.0         # paper's alpha (LM uses 2)
    mode: str = "log"              # log-space parameterization
    use_conv: bool = True          # Conv4 prefix (paper App. C.2)
    conv_kernel: int = 4
    use_mlp: bool = True


@dataclass(frozen=True)
class ModelConfig:
    # identity ------------------------------------------------------------
    name: str = "unnamed"
    family: str = "lm"             # lm | encdec
    block_kind: str = "attention"  # attention | ssm | minrnn | hybrid
    seq_mixer: str = "native"      # native | mingru | minlstm (DESIGN §5)

    # trunk ---------------------------------------------------------------
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 0              # 0 -> d_model // n_heads
    d_ff: int = 512
    vocab_size: int = 256
    max_seq_len: int = 8192

    # flavor --------------------------------------------------------------
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    norm_zero_centered: bool = False   # gemma (1+scale) RMSNorm
    mlp_activation: str = "silu"   # silu|gelu for the (gated) MLP
    gated_mlp: bool = True         # SwiGLU/GeGLU vs plain MLP
    attn_bias: bool = False        # starcoder2/whisper use biases
    mlp_bias: bool = False
    rope: bool = True
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    embedding_scale: bool = False  # gemma: x *= sqrt(d_model)
    attn_logit_soft_cap: float = 0.0

    # attention variant -----------------------------------------------------
    attn_kind: str = "gqa"         # gqa | mla
    mla_q_lora: int = 1536
    mla_kv_lora: int = 512
    mla_rope_dim: int = 64
    mla_v_dim: int = 128
    mla_qk_nope_dim: int = 128

    # sub-configs -----------------------------------------------------------
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    minrnn: Optional[MinRNNConfig] = None
    hybrid_attn_every: int = 0     # zamba2: shared attn block period

    # modality frontend stubs (assignment: frontends are stubs) -------------
    frontend: Optional[str] = None  # "patches" (vlm) | "frames" (audio)
    n_frontend_tokens: int = 0
    frontend_dim: int = 0           # raw embedding dim of the stub inputs

    # encoder-decoder --------------------------------------------------------
    n_encoder_layers: int = 0

    # numerics / performance -------------------------------------------------
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    # minRNN scan execution (core.scan.STRATEGIES): "auto" resolves to the
    # fused Pallas projection+scan kernels -- compiled on a TPU,
    # interpreted on the CPU.  Set "associative" to force the
    # pure-jnp reference path.
    scan_strategy: str = "auto"
    # minRNN decode block fusion (kernels/block_step): "auto"/"on" run the
    # whole residual block (norm -> conv step -> cell -> down -> MLP) in
    # one pallas_call per layer per decode round when ``scan_strategy``
    # resolves to "fused" (falling back to the cell kernel under
    # tensor-parallel serving or non-rmsnorm blocks); "off" keeps the
    # cell-only fusion.  ``block_dh`` is the kernel's feature tile (0 =
    # kernel default; autotune plans set it via TUNE_<config>.json).
    fuse_block: str = "auto"       # auto | on | off
    block_dh: int = 0
    remat: str = "none"            # none | full | dots
    scan_layers: bool = True       # lax.scan over stacked layer params
    pure_dp: int = 0               # 1: replicate weights, all axes are DP
                                   # (small-model layout; §Perf)
    attn_q_chunk: int = 1024       # blocked-attention tile sizes
    attn_kv_chunk: int = 1024
    logits_softcap: float = 0.0
    # loss partitioning: keep vocab-sharded logits (see §Perf)
    z_loss: float = 0.0

    # ------------------------------------------------------------------
    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 128 (TPU lane width) so the
        embedding/unembedding shard over the model axis; pad columns are
        masked to -1e30 in the logits (DESIGN.md §8)."""
        return -(-self.vocab_size // 128) * 128

    @property
    def pdtype(self):
        return DTYPES[self.param_dtype]

    @property
    def cdtype(self):
        return DTYPES[self.compute_dtype]

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeConfig:
    """One assigned (input-shape) cell."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

# archs whose native mixer is sub-quadratic (long_500k runs for these)
SUBQUADRATIC_KINDS = ("ssm", "minrnn", "hybrid")


def long_context_ok(cfg: ModelConfig) -> bool:
    if cfg.block_kind in SUBQUADRATIC_KINDS:
        return True
    return cfg.seq_mixer in ("mingru", "minlstm")
