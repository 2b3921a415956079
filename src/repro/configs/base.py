"""Config system: dataclass model/arch configs + input-shape registry.

Every assigned architecture gets one module in this package exporting
``CONFIG`` (full-size, used only by the dry-run via ShapeDtypeStruct) and
``smoke_config()`` (reduced same-family config instantiable on CPU).
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field, replace
from typing import Optional, Sequence


# ---------------------------------------------------------------------------
# Model configs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MoEConfig:
    """A mixture-of-experts layer, or this chip's share of one.

    The router scores all `num_routed_experts`; the layer holds
    `num_experts` of them, from global index `first_expert` on, and returns
    only their part of the routed output (plus the shared experts). Under a
    mesh the held experts are further split over the model axis."""
    num_experts: int              # routed experts held here
    top_k: int
    num_shared_experts: int = 0   # always-on experts (deepseek/llama4 style)
    # not read: dispatch is dropless; kept so configuration files that name
    # it still load
    capacity_factor: float = 1.25
    # which layers are MoE: "all", "every_2", "all_but_first"
    layout: str = "all"
    num_routed_experts: int = 0   # the router's width; 0: num_experts
    first_expert: int = 0         # global index of the first held expert
    # "softmax": top-k of softmax(x Wr), renormalised, switch-style batch
    # balance loss; "sigmoid" (DeepSeek-V3): top-k of sigmoid(x Wr) + bias,
    # weights renormalised over the top-k, sequence-wise balance loss
    scoring: str = "softmax"
    routed_scaling: float = 1.0   # factor on the routed experts' weights
    dense_d_ff: int = 0           # the leading dense layer's width; 0: 8 d_ff
    aux_weight: float = 0.01      # balance loss weight
    z_weight: float = 1e-3        # router z-loss weight

    @property
    def routed(self) -> int:
        return self.num_routed_experts or self.num_experts


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-1 block config (jamba)."""
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2


@dataclass(frozen=True)
class RWKVConfig:
    head_dim: int = 64


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | hybrid | ssm | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int                # query heads (0 for attn-free)
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0             # 0 -> d_model // num_heads
    mlp_kind: str = "swiglu"      # swiglu | sq_relu | gelu
    norm_kind: str = "rmsnorm"    # rmsnorm | layernorm
    rope_theta: float = 1e6
    # attention pattern: "full" | "local:global:<L>:<G>" (L local then 1 global
    # per period) with sliding window below
    attn_pattern: str = "full"
    sliding_window: int = 0
    # hybrid interleave: attention every `attn_every` layers (jamba: 8), rest SSM
    attn_every: int = 0
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rwkv: Optional[RWKVConfig] = None
    tie_embeddings: bool = False
    # modality frontend stub: model takes precomputed embeddings instead of ids
    embed_inputs: bool = False
    # M-RoPE (qwen2-vl): rope over 3 position coordinates
    mrope: bool = False
    # multi-head latent attention (DeepSeek-V2/V3), on when kv_lora_rank > 0:
    # q = x Wq; [c_kv | k_pe] = x Wkv_a, c_kv RMS-normed; [k_nope | v] =
    # c_kv Wkv_b per head; k_pe one RoPE head shared by all heads
    kv_lora_rank: int = 0
    q_lora_rank: Optional[int] = None   # a query LoRA is not built
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    # -- derived ---------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        assert self.num_heads > 0
        return self.d_model // self.num_heads

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def attn_free(self) -> bool:
        return self.family == "ssm"

    def param_count(self) -> int:
        """Analytic parameter count (matches init exactly; used for memory
        accounting)."""
        from repro.models.registry import param_count  # lazy, avoids cycle
        return param_count(self)

    def active_param_count(self) -> int:
        from repro.models.registry import param_count
        return param_count(self, active_only=True)


# ---------------------------------------------------------------------------
# Input shapes (assigned; seq_len x global_batch)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k":    ShapeConfig("train_4k",    4_096,   256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768,  32,  "prefill"),
    "decode_32k":  ShapeConfig("decode_32k",  32_768,  128, "decode"),
    "long_500k":   ShapeConfig("long_500k",   524_288, 1,   "decode"),
}

# archs allowed to run long_500k (sub-quadratic path exists)
LONG_CONTEXT_ARCHS = ("rwkv6-3b", "jamba-1.5-large-398b", "gemma3-4b")

ARCH_IDS = (
    "musicgen-medium",
    "command-r-35b",
    "llama3-8b",
    "nemotron-4-15b",
    "gemma3-4b",
    "deepseek-moe-16b",
    "llama4-scout-17b-a16e",
    "jamba-1.5-large-398b",
    "qwen2-vl-7b",
    "rwkv6-3b",
    "moonlight-16b-a3b",
)

_MODULES = {
    "musicgen-medium": "musicgen_medium",
    "command-r-35b": "command_r_35b",
    "llama3-8b": "llama3_8b",
    "nemotron-4-15b": "nemotron_4_15b",
    "gemma3-4b": "gemma3_4b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "llama4-scout-17b-a16e": "llama4_scout_17b_a16e",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "qwen2-vl-7b": "qwen2_vl_7b",
    "rwkv6-3b": "rwkv6_3b",
    "mobilenetv2-cifar": "mobilenetv2_cifar",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}


def get_config(arch_id: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")
    return mod.CONFIG


def get_smoke_config(arch_id: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")
    return mod.smoke_config()


def cell_is_skipped(arch_id: str, shape_name: str) -> Optional[str]:
    """Return a skip-reason string if (arch, shape) is not runnable."""
    if shape_name == "long_500k" and arch_id not in LONG_CONTEXT_ARCHS:
        return "pure full-attention arch: no sub-quadratic path for 500k decode"
    if SHAPES[shape_name].kind != "train" and get_config(arch_id).mla:
        return ("latent attention (MLA) has no KV cache in this program: "
                "prefill and decode are not built for it")
    return None


def all_cells() -> list[tuple[str, str]]:
    return [(a, s) for a in ARCH_IDS for s in SHAPES]


# ---------------------------------------------------------------------------
# Training / sparse-update config (the paper's knobs)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SparseUpdateConfig:
    """Algorithm 1 knobs + TPU-block granularity."""
    enabled: bool = True
    update_ratio: float = 0.2          # r: fraction of channel blocks per layer
    num_update_layers: int = 0         # K: last-K blocks trainable (0 = solve from budget)
    memory_budget_bytes: int = 0       # M: per-device budget (0 = no constraint)
    channel_block: int = 128           # TPU adaptation: selection granularity
    phase_fixed_early: int = 10        # j (in steps or epochs; trainer decides)
    phase_dynamic: int = 20            # k
    phase_fixed_late: int = 20         # l
    seed: int = 0
    update_embeddings: bool = False    # embeddings/lm_head frozen by default
    update_norms: bool = False         # paper freezes GN; we freeze norms


@dataclass(frozen=True)
class OptimizerConfig:
    kind: str = "sgd"                  # sgd | momentum | adamw  (paper: sgd m=0)
    learning_rate: float = 0.1
    momentum: float = 0.0
    weight_decay: float = 0.0
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    warmup_steps: int = 0
    decay_steps: int = 0               # cosine decay horizon (0 = constant)
    grad_clip: float = 0.0


@dataclass(frozen=True)
class TrainConfig:
    model: ModelConfig
    shape: ShapeConfig
    sparse: SparseUpdateConfig = field(default_factory=SparseUpdateConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    steps: int = 100
    log_every: int = 10
    checkpoint_every: int = 50
    checkpoint_dir: str = ""
    keep_checkpoints: int = 3
    remat: str = "selected"            # none | selected | full
    # compact-gradient path: thread the compact per-block dW through
    # clipping/optimizer/update without ever scattering a full-shape dW
    # (core.sparse_update docstring has the equivalence guarantees)
    compact_grads: bool = False
    seed: int = 0


def with_overrides(cfg, **kw):
    return replace(cfg, **kw)
