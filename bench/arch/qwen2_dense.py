"""The Qwen2 architecture (Qwen1.5 models): dense decoder layers with
biased q/k/v projections, rotary positions, RMSNorm and a SwiGLU MLP
(``repro.models.transformer`` in the program)."""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench import weights as wt
from bench.reference import qwen2_dense as REFERENCE  # noqa: F401

# per-layer leaves of the benchmark's own layout, stacked over layers
LAYER_LEAVES = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
                "ln2", "wg", "wu", "wd")


def program_config(member: Dict):
    from repro.models.config import ModelConfig
    hf = member["config"]
    return ModelConfig(
        name=member["name"], arch_type="dense",
        num_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["hidden_size"] // hf["num_attention_heads"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        qkv_bias=True, rope_theta=hf["rope_theta"],
        rms_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"],
        max_position=hf["max_position_embeddings"], dtype=jnp.bfloat16,
        source=member["source"])


def dims(hf: Dict) -> Dict[str, int]:
    """Shape numbers of one member, from its published config keys."""
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    return dict(d=d, L=hf["num_hidden_layers"], H=h,
                Hkv=hf["num_key_value_heads"], hd=d // h,
                ff=hf["intermediate_size"], V=hf["vocab_size"],
                tied=bool(hf["tie_word_embeddings"]))


def _layers(key, n: Dict[str, int], p: Dict, dt):
    L, d, H, Hkv, hd, ff = (n[k] for k in ("L", "d", "H", "Hkv", "hd", "ff"))
    shapes = {
        "ln1": (L, d), "ln2": (L, d),
        "wq": (L, d, H * hd), "wk": (L, d, Hkv * hd), "wv": (L, d, Hkv * hd),
        "bq": (L, H * hd), "bk": (L, Hkv * hd), "bv": (L, Hkv * hd),
        "wo": (L, H * hd, d), "wg": (L, d, ff), "wu": (L, d, ff),
        "wd": (L, ff, d),
    }
    keys = dict(zip(LAYER_LEAVES, jax.random.split(key, len(LAYER_LEAVES))))
    out = {}
    for name, shape in shapes.items():
        z = jax.random.normal(keys[name], shape, dt)
        if name.startswith("ln"):
            out[name] = (1.0 + p["norm_jitter"] * z).astype(dt)
        elif name.startswith("b"):
            out[name] = (p["bias_scale"] * z).astype(dt)
        else:                       # fan-in scaled, as a fresh init
            out[name] = (z / math.sqrt(shape[1])).astype(dt)
    return out


def make_weights(hf: Dict, planting: Dict, planted: Dict, key) -> Dict:
    """One member's bf16 weights in the benchmark's own layout: the
    stacked ``LAYER_LEAVES``, ``embed``, ``head`` (untied) and
    ``final_norm``."""
    return wt.make(_layers, dims(hf), planting, planted, key)


def to_program(w: Dict) -> Dict:
    """The same arrays, nested as the serving program's parameter tree
    (``repro.models.transformer``); nothing is copied."""
    tree = {
        "embed": w["embed"],
        "blocks": {
            "ln1": {"scale": w["ln1"]}, "ln2": {"scale": w["ln2"]},
            "attn": {"q": {"w": w["wq"], "b": w["bq"]},
                     "k": {"w": w["wk"], "b": w["bk"]},
                     "v": {"w": w["wv"], "b": w["bv"]},
                     "o": {"w": w["wo"]}},
            "mlp": {"gate": {"w": w["wg"]}, "up": {"w": w["wu"]},
                    "down": {"w": w["wd"]}},
        },
        "final_norm": {"scale": w["final_norm"]},
    }
    if "head" in w:
        tree["lm_head"] = {"w": w["head"]}
    return tree


def flops_per_token(hf: Dict, context: float) -> float:
    """Forward FLOPs of one token: two per weight of every matrix product
    (output head included, embedding lookup not) plus the attention
    products over ``context`` keys."""
    d, L = hf["hidden_size"], hf["num_hidden_layers"]
    H, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd, ff, V = d // H, hf["intermediate_size"], hf["vocab_size"]
    per_layer = d * (H + 2 * Hkv) * hd + H * hd * d + 3 * d * ff
    return 2.0 * (L * per_layer + d * V) + 4.0 * L * H * hd * context


def published_params(hf: Dict) -> int:
    """Parameters of the published model: per layer two norms, biased
    q/k/v, the output projection and the three MLP matrices; the
    embedding, an untied head and the final norm."""
    n = dims(hf)
    d, H, Hkv, hd, ff, V = (n[k] for k in ("d", "H", "Hkv", "hd", "ff",
                                            "V"))
    qkv = (H + 2 * Hkv) * hd
    per_layer = 2 * d + d * qkv + qkv + H * hd * d + 3 * d * ff
    return n["L"] * per_layer + V * d * (1 if n["tied"] else 2) + d
