"""Unified model configuration for every architecture family in the pool.

Each assigned architecture gets a ``ModelConfig`` in ``repro.configs``; the
SpecRouter pool holds several ModelConfigs sharing a tokenizer/vocab.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_expert: int                  # hidden width of each expert FFN
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    num_shared_experts: int = 0    # kimi-k2 style shared expert(s)
    d_shared: int = 0
    # expert parallelism seen from one chip: the router scores all
    # ``num_experts`` and this chip computes the part of the result that
    # experts [held_start, held_start + held) give (0 = all of them)
    held_start: int = 0
    held: int = 0

    @property
    def num_held(self) -> int:
        return self.held or self.num_experts


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Covers mamba-style heads (hymba) and xLSTM blocks."""
    state_size: int = 16           # N (mamba) — per-channel state
    num_ssm_heads: int = 0         # parallel SSM heads (hymba)
    conv_size: int = 4
    expand: int = 2
    # xLSTM specifics
    slstm_every: int = 0           # every k-th block is sLSTM (0 = none)
    mlstm_proj_factor: float = 2.0
    slstm_proj_factor: float = 1.334


@dataclasses.dataclass(frozen=True)
class LinearAttnConfig:
    """Gated DeltaNet token mixer (Qwen3-Next): every
    ``full_attention_interval``-th layer is full attention, the others
    are linear attention with a per-row delta state."""
    num_key_heads: int
    num_value_heads: int
    key_head_dim: int
    value_head_dim: int
    conv_kernel: int = 4
    full_attention_interval: int = 4


@dataclasses.dataclass(frozen=True)
class EncDecConfig:
    """Whisper-style encoder-decoder: the encoder is a STUB that provides
    precomputed frame embeddings; the decoder cross-attends to them."""
    num_encoder_positions: int = 1500
    d_encoder: int = 384


@dataclasses.dataclass(frozen=True)
class VLMConfig:
    """Qwen2-VL style: stub patch embeddings prepended, M-RoPE positions."""
    num_patch_tokens: int = 256
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)  # t, h, w (pairs)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                 # dense | moe | ssm | hybrid | audio | vlm
    #                                | qwen3_next
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads
    qkv_bias: bool = False         # qwen1.5
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    tie_embeddings: bool = True
    max_position: int = 131072
    # sliding-window / local:global pattern (gemma3: 5 local per 1 global)
    sliding_window: int = 0        # 0 = full attention everywhere
    local_global_ratio: int = 0    # k -> k local layers then 1 global
    learned_positions: bool = False  # whisper decoder
    logit_softcap: float = 0.0     # gemma-style final logit softcap
    attn_softcap: float = 0.0
    embed_scale: bool = False      # gemma: scale embeddings by sqrt(d)
    sandwich_norm: bool = False    # gemma3: pre+post norms around attn/mlp
    qk_norm: bool = False          # gemma3: rmsnorm on q,k heads
    kv_quant: bool = False         # int8 KV cache (beyond-paper, §Perf G2)
    rope_theta_global: float = 0.0  # gemma3: different theta on global layers
    partial_rotary: float = 1.0    # share of head_dim that RoPE rotates
    moe: Optional[MoEConfig] = None
    linear_attn: Optional[LinearAttnConfig] = None
    ssm: Optional[SSMConfig] = None
    encdec: Optional[EncDecConfig] = None
    vlm: Optional[VLMConfig] = None
    dtype: jnp.dtype = jnp.bfloat16
    source: str = ""               # citation bracket from the assignment

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        assert self.num_heads % max(self.num_kv_heads, 1) == 0, (
            f"{self.name}: num_heads={self.num_heads} not a multiple of "
            f"num_kv_heads={self.num_kv_heads}")

    # ---- derived -----------------------------------------------------
    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    def is_global_layer(self, layer_idx: int) -> bool:
        """Layer attention pattern under local:global interleave."""
        if self.local_global_ratio <= 0 or self.sliding_window <= 0:
            return True
        # k local layers then 1 global, repeating (gemma3 = 5:1)
        return (layer_idx + 1) % (self.local_global_ratio + 1) == 0

    @property
    def recurrent(self) -> bool:
        """Carries per-row recurrent state beside (or instead of) a
        per-position cache: rollback restores it, and a token tree cannot
        branch it."""
        return self.arch_type in ("ssm", "hybrid") \
            or self.linear_attn is not None

    def supports_long_context(self) -> bool:
        """Sub-quadratic-capable: recurrent, or sliding-window dense."""
        return self.recurrent or self.sliding_window > 0

    def has_decode_step(self) -> bool:
        return True  # all assigned archs are decoder-bearing

    @property
    def supports_tree(self) -> bool:
        """Tree-structured speculation needs per-position KV that can mask
        dead branches; recurrent carries cannot branch."""
        return not self.recurrent

    @property
    def supports_paged(self) -> bool:
        """Paged KV needs a per-position cache.  The linear-attention
        mixer keeps its recurrent state as per-row leaves of the paged
        state; SSM/hybrid carries keep the contiguous state + snapshot
        rings."""
        return not self.recurrent or self.linear_attn is not None

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS roofline term)."""
        d, L, V = self.d_model, self.num_layers, self.vocab_size
        hd, nh, nkv = self.head_dim, self.num_heads, self.num_kv_heads
        emb = V * d * (1 if self.tie_embeddings else 2)
        per_layer = 0
        if self.arch_type == "ssm":
            s = self.ssm or SSMConfig()
            # mLSTM block: up-proj 2*pf*d, qkv over inner dim, down-proj
            inner = int(d * s.mlstm_proj_factor)
            per_layer = d * inner * 2 + 3 * inner * inner // max(1, 1) // 1
            per_layer = d * inner * 2 + 3 * inner * (inner // max(self.num_heads, 1)) * self.num_heads + inner * d
        else:
            attn = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
            if self.arch_type == "hybrid" and self.ssm:
                inner = d * self.ssm.expand
                attn += d * inner * 2 + inner * d + inner * self.ssm.state_size * 2
            if self.moe is not None:
                m = self.moe
                ffn = m.num_experts * 3 * d * m.d_expert + d * m.num_experts
                ffn += m.num_shared_experts * 3 * d * max(m.d_shared, m.d_expert)
            else:
                ffn = 3 * d * self.d_ff
            per_layer = attn + ffn
        if self.encdec is not None:
            per_layer += d * nh * hd * 2 + 2 * self.encdec.d_encoder * nkv * hd
        return emb + L * per_layer + d  # + final norm

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed experts)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        d, L = self.d_model, self.num_layers
        total = self.param_count()
        all_expert = L * m.num_experts * 3 * d * m.d_expert
        active_expert = L * m.top_k * 3 * d * m.d_expert
        return total - all_expert + active_expert


# ---------------------------------------------------------------------------
# Input shapes assigned to this paper (public pool)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k":    InputShape("train_4k",    4_096,   256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768,  32,  "prefill"),
    "decode_32k":  InputShape("decode_32k",  32_768,  128, "decode"),
    "long_500k":   InputShape("long_500k",   524_288, 1,   "decode"),
}
