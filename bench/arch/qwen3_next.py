"""The Qwen3-Next architecture: Gated DeltaNet and gated full attention in a
3:1 pattern, a sparse-expert FFN in every layer (``repro.models.qwen3_next``
in the program).

A configuration may hold a share of each layer's experts, as one chip of an
expert-parallel deployment does: ``num_experts`` is then the number held
(experts ``0 .. num_experts - 1``) and ``router_experts`` the router's
width, the published expert count.  Zero-centred norms (``x̂ (1 + w)``) are
stored as their effective scale ``1 + w``.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

# the program reads this architecture's config.json keys: an import error
# here ends a run of a program that lacks it before any weights are made
from repro.configs.qwen3_next_80b_a3b import from_hf
from repro.models.config import ModelConfig

from bench import weights as wt
from bench.reference import qwen3_next as REFERENCE  # noqa: F401
from bench.reference.qwen3_next import ATTN_LEAVES, GDN_LEAVES, MOE_LEAVES

NORMS = ("g_ln", "g_norm", "a_ln", "a_qn", "a_kn", "m_ln")


def program_config(member: Dict) -> ModelConfig:
    return from_hf(member["config"], member["name"], member["source"])


def dims(hf: Dict) -> Dict[str, int]:
    """Shape numbers of one member, from the program's reading of its
    config."""
    c = from_hf(hf)
    la, m = c.linear_attn, c.moe
    L, La = c.num_layers, c.num_layers // la.full_attention_interval
    return dict(
        d=c.d_model, V=c.vocab_size, tied=c.tie_embeddings, L=L, La=La,
        Lg=L - La, H=c.num_heads, Hkv=c.num_kv_heads, hd=c.head_dim,
        hk=la.num_key_heads, hv=la.num_value_heads, dk=la.key_head_dim,
        dv=la.value_head_dim, kc=la.conv_kernel, E=m.num_experts,
        held=m.num_held, top_k=m.top_k, F=m.d_expert, Fs=m.d_shared)


def _shapes(n: Dict[str, int]) -> Dict[str, tuple]:
    d, Lg, La, L = n["d"], n["Lg"], n["La"], n["L"]
    hk, hv, dk, dv = n["hk"], n["hv"], n["dk"], n["dv"]
    H, Hkv, hd = n["H"], n["Hkv"], n["hd"]
    cc = 2 * hk * dk + hv * dv
    return {
        "g_ln": (Lg, d), "g_qkvz": (Lg, d, cc + hv * dv),
        "g_ba": (Lg, d, 2 * hv), "g_conv": (Lg, n["kc"], cc),
        "g_A_log": (Lg, hv), "g_dt_bias": (Lg, hv), "g_norm": (Lg, dv),
        "g_out": (Lg, hv * dv, d),
        "a_ln": (La, d), "a_q": (La, d, 2 * H * hd), "a_k": (La, d, Hkv * hd),
        "a_v": (La, d, Hkv * hd), "a_o": (La, H * hd, d), "a_qn": (La, hd),
        "a_kn": (La, hd),
        "m_ln": (L, d), "m_router": (L, d, n["E"]),
        "m_wg": (L, n["held"], d, n["F"]), "m_wu": (L, n["held"], d, n["F"]),
        "m_wd": (L, n["held"], n["F"], d), "m_sg": (L, d, n["Fs"]),
        "m_su": (L, d, n["Fs"]), "m_sd": (L, n["Fs"], d),
        "m_sgate": (L, d, 1),
    }


def _layers(key, n: Dict[str, int], p: Dict, dt):
    shapes = _shapes(n)
    names = GDN_LEAVES + ATTN_LEAVES + MOE_LEAVES
    keys = dict(zip(names, jax.random.split(key, len(names))))
    out = {}
    for name in names:
        shape, k = shapes[name], keys[name]
        if name in NORMS:                    # effective scales, 1 + w
            z = jax.random.normal(k, shape)
            out[name] = (1.0 + p["norm_jitter"] * z).astype(dt)
        elif name == "g_A_log":              # A ~ U(0, 16), as published
            out[name] = jnp.log(jax.random.uniform(
                k, shape, minval=0.0, maxval=16.0)).astype(dt)
        elif name == "g_dt_bias":
            out[name] = jnp.ones(shape, dt)
        else:                                # fan-in scaled, as a fresh init
            z = jax.random.normal(k, shape, dt)
            out[name] = (z / math.sqrt(shape[-2])).astype(dt)
    return out


def make_weights(hf: Dict, planting: Dict, planted: Dict, key) -> Dict:
    """One member's bf16 weights in the benchmark's own layout: the
    stacked GDN (``g_*``), attention (``a_*``) and FFN (``m_*``) leaves,
    ``embed``, ``head`` and ``final_norm``."""
    return wt.make(_layers, dims(hf), planting, planted, key)


def to_program(w: Dict) -> Dict:
    """The same arrays, nested as the serving program's parameter tree
    (``repro.models.qwen3_next``); nothing is copied."""
    return {
        "embed": w["embed"],
        "lm_head": {"w": w["head"]},
        "final_norm": {"scale": w["final_norm"]},
        "gdn": {"ln": {"scale": w["g_ln"]}, "qkvz": {"w": w["g_qkvz"]},
                "ba": {"w": w["g_ba"]}, "conv": w["g_conv"],
                "A_log": w["g_A_log"], "dt_bias": w["g_dt_bias"],
                "norm": {"scale": w["g_norm"]}, "out": {"w": w["g_out"]}},
        "attn": {"ln": {"scale": w["a_ln"]}, "q": {"w": w["a_q"]},
                 "k": {"w": w["a_k"]}, "v": {"w": w["a_v"]},
                 "o": {"w": w["a_o"]}, "q_norm": {"scale": w["a_qn"]},
                 "k_norm": {"scale": w["a_kn"]}},
        "moe": {"ln": {"scale": w["m_ln"]}, "router": w["m_router"],
                "w_gate": w["m_wg"], "w_up": w["m_wu"], "w_down": w["m_wd"],
                "shared": {"gate": {"w": w["m_sg"]}, "up": {"w": w["m_su"]},
                           "down": {"w": w["m_sd"]}},
                "shared_gate": w["m_sgate"]},
    }


def flops_per_token(hf: Dict, context: float) -> float:
    """Forward FLOPs of one token: two per weight of every matrix product
    (the head included, the embedding lookup not), counting of the routed
    experts only the expected number that are held here, ``top_k x held /
    router_experts``; the delta rule's state products (7 per state entry:
    decay, ``S^T k``, the rank-one update and ``S^T q``); and the attention
    products over ``context`` keys."""
    n = dims(hf)
    d, hv, dk, dv = n["d"], n["hv"], n["dk"], n["dv"]
    H, Hkv, hd = n["H"], n["Hkv"], n["hd"]
    cc = 2 * n["hk"] * dk + hv * dv
    gdn = d * (cc + hv * dv) + d * 2 * hv + hv * dv * d
    attn = d * (2 * H + 2 * Hkv) * hd + H * hd * d
    routed = n["top_k"] * n["held"] / n["E"]
    ffn = d * n["E"] + routed * 3 * d * n["F"] + 3 * d * n["Fs"] + d
    weights = n["Lg"] * gdn + n["La"] * attn + n["L"] * ffn + d * n["V"]
    state = n["Lg"] * 7 * hv * dk * dv
    return 2.0 * weights + state + 4.0 * n["La"] * H * hd * context


def published_params(hf: Dict) -> int:
    """Parameters of the model: per GDN layer its norm, the two input
    projections, the conv, ``A_log``, ``dt_bias``, the output norm and
    projection; per attention layer its norm, q (with gate), k, v, o and
    the q/k norms; per layer the FFN norm, the router, the experts held,
    the shared expert and its gate; the embedding, the head and the final
    norm."""
    n = dims(hf)
    size = sum(math.prod(s) for s in _shapes(n).values())
    return size + n["V"] * n["d"] * (1 if n["tied"] else 2) + n["d"]
