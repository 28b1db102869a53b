"""Shared arithmetic of the benchmark's metrics."""
from __future__ import annotations

from typing import Dict


def forward_flops_per_token(hf: Dict, context: float) -> float:
    """Forward FLOPs of one token through a dense Qwen2-style model: two
    per weight of every matrix product (output head included, embedding
    lookup not) plus the attention products over ``context`` keys."""
    d, L = hf["hidden_size"], hf["num_hidden_layers"]
    H, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd, ff, V = d // H, hf["intermediate_size"], hf["vocab_size"]
    per_layer = d * (H + 2 * Hkv) * hd + H * hd * d + 3 * d * ff
    return 2.0 * (L * per_layer + d * V) + 4.0 * L * H * hd * context
