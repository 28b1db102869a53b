"""qwen3-next-80b-a3b [qwen3_next] — 48 layers, Gated DeltaNet : gated
attention 3 : 1, 512 experts top-10 plus a sigmoid-gated shared expert
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json).

``from_hf`` reads the published ``config.json`` keys.  A configuration that
holds one chip's share of the experts gives the number held as
``num_experts`` and the router's width as ``router_experts``."""
from typing import Dict

import jax.numpy as jnp

from ..models.config import LinearAttnConfig, ModelConfig, MoEConfig

ARCH_ID = "qwen3-next-80b-a3b"
SOURCE = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/"
          "main/config.json")

PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "moe_intermediate_size": 512,
    "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "vocab_size": 151936,
}


def from_hf(hf: Dict, name: str = ARCH_ID,
            source: str = SOURCE) -> ModelConfig:
    """The program's configuration of a Qwen3-Next ``config.json``.  Every
    FFN is sparse (``decoder_sparse_step`` 1, no ``mlp_only_layers``).
    The program's RMSNorms scale by their stored weight: a checkpoint's
    zero-centred norms (``x * (1 + w)``) are stored as ``1 + w``."""
    assert not hf["mlp_only_layers"] and hf["decoder_sparse_step"] == 1
    assert hf["norm_topk_prob"]
    held = hf["num_experts"]
    return ModelConfig(
        name=name, arch_type="qwen3_next",
        num_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        rope_theta=float(hf["rope_theta"]), rms_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"],
        max_position=hf["max_position_embeddings"],
        partial_rotary=hf["partial_rotary_factor"],
        moe=MoEConfig(num_experts=hf.get("router_experts", held),
                      top_k=hf["num_experts_per_tok"],
                      d_expert=hf["moe_intermediate_size"],
                      num_shared_experts=1,
                      d_shared=hf["shared_expert_intermediate_size"],
                      held_start=0, held=held),
        linear_attn=LinearAttnConfig(
            num_key_heads=hf["linear_num_key_heads"],
            num_value_heads=hf["linear_num_value_heads"],
            key_head_dim=hf["linear_key_head_dim"],
            value_head_dim=hf["linear_value_head_dim"],
            conv_kernel=hf["linear_conv_kernel_dim"],
            full_attention_interval=hf["full_attention_interval"]),
        dtype=jnp.bfloat16, source=source)
