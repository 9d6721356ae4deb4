"""Configuration schema for models, input shapes and DEP cluster layout.

Every assigned architecture gets one module in ``repro.configs`` exporting a
``CONFIG`` ModelConfig built from the exact hyper-parameters in its source
paper / model card (cited in the module docstring), plus a ``smoke()``
reduced variant (<=2 layers, d_model<=512, <=4 experts) used by CPU tests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

ARCH_FAMILIES = (
    "dense",    # decoder-only transformer, (GQA) softmax attention
    "moe",      # decoder-only transformer with routed experts
    "ssm",      # xLSTM-style recurrent blocks (sLSTM + mLSTM)
    "hybrid",   # RG-LRU recurrence + local attention (RecurrentGemma)
    "vlm",      # vision-language: stub ViT frontend + dense LM backbone
    "audio",    # encoder-decoder (Seamless-M4T style); stub audio frontend
)

ATTENTION_KINDS = ("full", "sliding", "mla", "local", "none")


@dataclass(frozen=True)
class MoEConfig:
    """Routed-expert configuration (paper notation: E, top_k, N_shared, H)."""

    num_experts: int                 # E — global routed experts
    top_k: int                       # experts activated per token
    expert_ffn_dim: int              # H — hidden dim of each routed expert
    num_shared_experts: int = 0      # N_shared — dense experts on every token
    shared_ffn_dim: int = 0          # hidden dim of each shared expert
    router_aux_loss_coef: float = 0.001
    capacity_factor: float = 1.25    # per-expert capacity = cf * tokens*topk/E
    moe_layer_start: int = 0         # first layer index that is MoE
    moe_layer_every: int = 1         # 1 => every layer from start is MoE
    #: rescale the top-k router weights to sum to one (False: the softmax
    #: probabilities of the chosen experts, as DeepSeek-V2 publishes)
    norm_topk_prob: bool = True


@dataclass(frozen=True)
class YarnScaling:
    """YaRN RoPE scaling as DeepSeek-V2's ``rope_scaling`` states it: the
    frequencies blend interpolated (``factor``) and original ones over a
    ramp set by ``beta_fast``/``beta_slow`` rotations within
    ``original_max_position`` positions, and attention logits scale by
    ``mscale(factor, mscale_all_dim)**2``."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class RecurrentConfig:
    """SSM / hybrid recurrence parameters."""

    kind: str = "rg_lru"             # "rg_lru" | "slstm" | "mlstm"
    lru_width: int = 0               # recurrence state width (0 -> d_model)
    conv1d_width: int = 4
    block_pattern: Tuple[str, ...] = ()   # e.g. ("rec", "rec", "attn") 1:2


@dataclass(frozen=True)
class ModelConfig:
    """A single architecture. Field names follow the paper where possible
    (M = d_model, H = ffn hidden, E/top_k in MoEConfig, T = num_layers)."""

    name: str
    family: str                      # one of ARCH_FAMILIES
    num_layers: int                  # T
    d_model: int                     # M
    num_heads: int
    num_kv_heads: int                # GQA KV heads
    ffn_dim: int                     # dense FFN hidden (0 if pure-MoE/SSM)
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // num_heads
    attention: str = "full"          # ATTENTION_KINDS
    sliding_window: int = 4096       # used when attention == "sliding"/"local"
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnScaling] = None
    qkv_bias: bool = False
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    moe: Optional[MoEConfig] = None
    recurrent: Optional[RecurrentConfig] = None
    # --- enc-dec (audio) ---
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    # --- multimodal stub frontends (vlm/audio carve-out) ---
    frontend_tokens: int = 0         # patch/frame embeddings prepended
    # --- MLA (DeepSeek-V2 latent attention): head_dim is the per-head
    # no-rope query/key width, mla_rope_dim the rope part (one rope key
    # shared by every head), mla_v_dim the value width (0 -> head_dim) ---
    mla_kv_lora_rank: int = 0
    mla_q_lora_rank: int = 0
    mla_rope_dim: int = 0
    mla_v_dim: int = 0
    # citation for the exact config
    source: str = ""

    def __post_init__(self):
        assert self.family in ARCH_FAMILIES, self.family
        assert self.attention in ATTENTION_KINDS, self.attention
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        # YaRN's logit scale is applied by the MLA attention only
        assert self.rope_scaling is None or self.mla_kv_lora_rank
        if self.mla_kv_lora_rank and self.mla_v_dim == 0:
            object.__setattr__(self, "mla_v_dim", self.head_dim)
        assert self.num_heads % max(self.num_kv_heads, 1) == 0

    # ---- derived quantities -------------------------------------------------
    @property
    def is_moe(self) -> bool:
        return self.moe is not None

    @property
    def uses_attention(self) -> bool:
        return self.attention != "none"

    @property
    def subquadratic(self) -> bool:
        """True when the arch natively supports 500k-token decode."""
        return self.family in ("ssm", "hybrid") or self.attention in (
            "sliding", "local")

    def num_params(self) -> int:
        """Approximate parameter count (embeddings + blocks + head)."""
        M, Hd = self.d_model, self.head_dim
        q = self.num_heads * Hd
        kv = self.num_kv_heads * Hd
        attn = M * q + 2 * M * kv + q * M
        if self.mla_kv_lora_rank:
            r, dr, dv = (self.mla_kv_lora_rank, self.mla_rope_dim,
                         self.mla_v_dim)
            H = self.num_heads
            attn = (M * H * (Hd + dr) + M * (r + dr) + r
                    + r * H * (Hd + dv) + H * dv * M)
        dense_ffn = 3 * M * self.ffn_dim if self.ffn_dim else 0
        per_layer = attn + dense_ffn
        n = self.num_layers * per_layer
        if self.moe is not None:
            moe_ffn = 3 * M * self.moe.expert_ffn_dim * self.moe.num_experts
            moe_ffn += 3 * M * self.moe.shared_ffn_dim * self.moe.num_shared_experts
            moe_ffn += M * self.moe.num_experts  # router
            n_moe_layers = len(self.moe_layer_indices())
            n += n_moe_layers * moe_ffn
            n -= n_moe_layers * dense_ffn  # MoE layers replace dense FFN
        if self.recurrent is not None:
            w = self.recurrent.lru_width or M
            n += self.num_layers * (2 * M * w + 2 * w)
        emb = self.vocab_size * M * (1 if self.tie_embeddings else 2)
        if self.is_encoder_decoder:
            n += self.num_encoder_layers * per_layer
        return n + emb

    def active_params(self) -> int:
        """Activated parameters per token (MoE: top_k + shared only)."""
        if self.moe is None:
            return self.num_params()
        m = self.moe
        full = self.num_params()
        n_moe_layers = len(self.moe_layer_indices())
        routed_all = n_moe_layers * 3 * self.d_model * m.expert_ffn_dim * m.num_experts
        routed_act = n_moe_layers * 3 * self.d_model * m.expert_ffn_dim * m.top_k
        return full - routed_all + routed_act

    def moe_layer_indices(self):
        if self.moe is None:
            return []
        m = self.moe
        return [i for i in range(self.num_layers)
                if i >= m.moe_layer_start
                and (i - m.moe_layer_start) % m.moe_layer_every == 0]

    def reduced(self, **overrides) -> "ModelConfig":
        """Smoke-test variant: same family/topology, tiny dims."""
        kw = dict(
            name=self.name + "-smoke",
            num_layers=min(self.num_layers, 2),
            d_model=min(self.d_model, 256),
            num_heads=min(self.num_heads, 4),
            num_kv_heads=min(self.num_kv_heads, 2),
            ffn_dim=min(self.ffn_dim, 512) if self.ffn_dim else 0,
            vocab_size=min(self.vocab_size, 1024),
            head_dim=0,
            sliding_window=min(self.sliding_window, 64),
            num_encoder_layers=min(self.num_encoder_layers, 2),
            frontend_tokens=min(self.frontend_tokens, 16),
            mla_kv_lora_rank=min(self.mla_kv_lora_rank, 64),
            mla_q_lora_rank=min(self.mla_q_lora_rank, 64),
            mla_rope_dim=min(self.mla_rope_dim, 32),
            mla_v_dim=0,
        )
        if self.moe is not None:
            kw["moe"] = dataclasses.replace(
                self.moe,
                num_experts=min(self.moe.num_experts, 4),
                top_k=min(self.moe.top_k, 2),
                expert_ffn_dim=min(self.moe.expert_ffn_dim, 128),
                shared_ffn_dim=min(self.moe.shared_ffn_dim, 128),
            )
        if self.recurrent is not None:
            kw["recurrent"] = dataclasses.replace(
                self.recurrent,
                lru_width=min(self.recurrent.lru_width, 256)
                if self.recurrent.lru_width else 0,
            )
        kw.update(overrides)
        cfg = dataclasses.replace(self, **kw)
        return cfg


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    mode: str                 # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k":    ShapeConfig("train_4k",    4_096,   256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768,  32,  "prefill"),
    "decode_32k":  ShapeConfig("decode_32k",  32_768,  128, "decode"),
    "long_500k":   ShapeConfig("long_500k",   524_288, 1,   "decode"),
}


# ---------------------------------------------------------------------------
# DEP cluster layout (paper Table 1: ag / eg)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DepClusterConfig:
    """Disaggregated-expert-parallel group sizes and link characteristics."""

    num_devices: int          # P
    ag: int                   # attention-group size
    eg: int                   # expert-group size
    dtype_bytes: int = 2      # bf16 activations

    def __post_init__(self):
        assert self.ag + self.eg <= self.num_devices
        assert self.ag >= 1 and self.eg >= 1
