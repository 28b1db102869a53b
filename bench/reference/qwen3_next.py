"""Plain float32 reference for the Qwen3-Next architecture.

Written from the published ``modeling_qwen3_next`` description, independent
of the program under test.  Layer ``i`` is full attention where ``(i + 1) %
full_attention_interval == 0`` and Gated DeltaNet otherwise; every layer then
has a sparse-expert FFN.  Each sublayer is pre-norm and added to the
residual; a final RMSNorm and the untied head give the logits.

* Gated attention: ``q_proj`` gives ``[q_h | gate_h]`` per head; per-head
  RMSNorm on q and k; rotate-half RoPE (``rope_theta``) on the first
  ``partial_rotary_factor`` of each head; causal softmax attention scaled by
  ``head_dim ** -0.5`` with each KV head serving its group of query heads;
  ``attn * sigmoid(gate)``, then ``o_proj``.
* Gated DeltaNet: ``in_proj_qkvz`` laid out per key head as ``[q | k | v (r
  heads) | z (r heads)]``, ``in_proj_ba`` as ``[b (r) | a (r)]``, ``r`` value
  heads per key head; causal depthwise conv (no bias) over the q‖k‖v
  channels, then SiLU; q and k L2-normalised (eps 1e-6), q scaled by
  ``dk ** -0.5``; each key head repeated for its ``r`` value heads; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; per position and
  value head ``S <- S exp(g)``, ``S <- S + k beta (v - S^T k)^T``, ``o = S^T
  q``, one position at a time from a zero state; ``w * rmsnorm(o) *
  silu(z)``; ``out_proj``.
* Experts: a router over all the published experts (``router_experts``),
  softmax, the top ``num_experts_per_tok`` renormalised over themselves; the
  held experts ``0 .. num_experts - 1`` (SwiGLUs), each weighted by its
  gate, zero where not routed: a routed expert that is not held adds
  nothing, as on the chip of the stated deployment that holds this share.
  Plus one SwiGLU shared expert scaled by ``sigmoid(x w_sg)``.

Departures from the published code: every norm weight is read as its
effective scale (the published zero-centred norms compute ``x̂ (1 + w)``;
the benchmark's layout stores ``1 + w``); the MTP head is left out, as the
published forward does not run it; the routing softmax and the delta rule
run in float32, as published, and so does everything else here.  Every
matrix product runs in float32 at ``HIGHEST`` precision.

The model runs one layer at a time over a block of sequences, with that
layer's bfloat16 weights raised to float32 inside the step.  ``quant``
computes every weight product in a lower precision for the control run
(``bench/reference/qwen2_dense.py``'s ``matmul``); the delta rule, the conv
and the attention products stay in float32.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from bench.reference.qwen2_dense import HIGHEST, matmul, rmsnorm

GDN_LEAVES = ("g_ln", "g_qkvz", "g_ba", "g_conv", "g_A_log", "g_dt_bias",
              "g_norm", "g_out")
ATTN_LEAVES = ("a_ln", "a_q", "a_k", "a_v", "a_o", "a_qn", "a_kn")
MOE_LEAVES = ("m_ln", "m_router", "m_wg", "m_wu", "m_wd", "m_sg", "m_su",
              "m_sd", "m_sgate")


def _pick(w: Dict, names, i):
    return {k: jax.lax.dynamic_index_in_dim(w[k], i, keepdims=False)
            .astype(jnp.float32) for k in names}


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


@partial(jax.jit, static_argnames=("hf_items", "quant"))
def _gdn(w: Dict, i, x, hf_items, quant):
    hf = dict(hf_items)
    hk, hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    dk, dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    K, eps = hf["linear_conv_kernel_dim"], hf["rms_norm_eps"]
    r = hv // hk
    p = _pick(w, GDN_LEAVES, i)
    B, S, _ = x.shape
    h = rmsnorm(x, p["g_ln"], eps)
    qkvz = matmul(h, p["g_qkvz"], quant).reshape(B, S, hk, 2 * dk + 2 * r * dv)
    q = qkvz[..., :dk].reshape(B, S, hk * dk)
    k = qkvz[..., dk:2 * dk].reshape(B, S, hk * dk)
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(B, S, hv * dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(B, S, hv, dv)
    ba = matmul(h, p["g_ba"], quant).reshape(B, S, hk, 2 * r)
    b = ba[..., :r].reshape(B, S, hv)
    a = ba[..., r:].reshape(B, S, hv)
    mixed = jnp.concatenate([q, k, v], axis=-1)
    padded = jnp.pad(mixed, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + S] * p["g_conv"][j] for j in range(K))
    conv = jax.nn.silu(conv)
    q = _l2norm(conv[..., :hk * dk].reshape(B, S, hk, dk)) * dk ** -0.5
    k = _l2norm(conv[..., hk * dk:2 * hk * dk].reshape(B, S, hk, dk))
    v = conv[..., 2 * hk * dk:].reshape(B, S, hv, dv)
    q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["g_A_log"]) * jax.nn.softplus(a + p["g_dt_bias"])

    def step(state, t):
        q_t, k_t, v_t, g_t, b_t = t
        state = state * jnp.exp(g_t)[..., None, None]
        kv = jnp.sum(state * k_t[..., :, None], axis=-2)
        state = state + k_t[..., :, None] * (
            (v_t - kv) * b_t[..., None])[..., None, :]
        return state, jnp.sum(state * q_t[..., :, None], axis=-2)

    s0 = jnp.zeros((B, hv, dk, dv), jnp.float32)
    seq = tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, s0, seq)
    o = jnp.swapaxes(o, 0, 1)                                # (B, S, hv, dv)
    o = rmsnorm(o, p["g_norm"], eps) * jax.nn.silu(z)
    return x + matmul(o.reshape(B, S, hv * dv), p["g_out"], quant)


def _rope(x, theta: float, rot: int):
    """Rotate-half RoPE on the first ``rot`` dims of x (B, S, H, hd)."""
    S = x.shape[1]
    half = rot // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / rot))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


@partial(jax.jit, static_argnames=("hf_items", "quant"))
def _attention(w: Dict, i, x, hf_items, quant):
    hf = dict(hf_items)
    H, Hkv, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                  hf["head_dim"])
    eps = hf["rms_norm_eps"]
    p = _pick(w, ATTN_LEAVES, i)
    B, S, _ = x.shape
    h = rmsnorm(x, p["a_ln"], eps)
    qg = matmul(h, p["a_q"], quant).reshape(B, S, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(B, S, H * hd)
    k = matmul(h, p["a_k"], quant).reshape(B, S, Hkv, hd)
    v = matmul(h, p["a_v"], quant).reshape(B, S, Hkv, hd)
    q, k = rmsnorm(q, p["a_qn"], eps), rmsnorm(k, p["a_kn"], eps)
    rot = int(hd * hf["partial_rotary_factor"])
    q, k = _rope(q, hf["rope_theta"], rot), _rope(k, hf["rope_theta"], rot)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST).reshape(B, S, H * hd)
    return x + matmul(o * jax.nn.sigmoid(gate), p["a_o"], quant)


def _swiglu(x, wg, wu, wd, quant):
    return matmul(jax.nn.silu(matmul(x, wg, quant)) * matmul(x, wu, quant),
                  wd, quant)


@partial(jax.jit, static_argnames=("hf_items", "quant"))
def _experts(w: Dict, i, x, hf_items, quant):
    hf = dict(hf_items)
    p = _pick(w, MOE_LEAVES, i)
    held = p["m_wg"].shape[0]
    h = rmsnorm(x, p["m_ln"], hf["rms_norm_eps"])
    probs = jax.nn.softmax(matmul(h, p["m_router"], quant), axis=-1)
    top_v, top_i = jax.lax.top_k(probs, hf["num_experts_per_tok"])
    if hf["norm_topk_prob"]:
        top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(held):
        gate = jnp.sum(jnp.where(top_i == e, top_v, 0.0), axis=-1)
        out = out + gate[..., None] * _swiglu(
            h, p["m_wg"][e], p["m_wu"][e], p["m_wd"][e], quant)
    shared = _swiglu(h, p["m_sg"], p["m_su"], p["m_sd"], quant)
    return x + out + jax.nn.sigmoid(matmul(h, p["m_sgate"], quant)) * shared


@partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, scale, eps):
    return rmsnorm(x, scale, eps)


def hidden(w: Dict, hf: Dict, tokens: jnp.ndarray,
           quant: Optional[str] = None) -> jnp.ndarray:
    """Final normed hidden states (B, S, d), float32, for right-padded
    token rows (causal, so padding never reaches an earlier position)."""
    hf_items = tuple(sorted((k, v) for k, v in hf.items()
                            if isinstance(v, (int, float))))
    gdn = {k: w[k] for k in GDN_LEAVES}
    attn = {k: w[k] for k in ATTN_LEAVES}
    ffn = {k: w[k] for k in MOE_LEAVES}
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    n_gdn = n_attn = 0
    for i in range(w["m_ln"].shape[0]):
        if (i + 1) % hf["full_attention_interval"] == 0:
            x = _attention(attn, n_attn, x, hf_items=hf_items, quant=quant)
            n_attn += 1
        else:
            x = _gdn(gdn, n_gdn, x, hf_items=hf_items, quant=quant)
            n_gdn += 1
        x = _experts(ffn, i, x, hf_items=hf_items, quant=quant)
    return _final_norm(x, w["final_norm"], eps=hf["rms_norm_eps"])


@partial(jax.jit, static_argnames=("tied", "quant"))
def logits(w_out: jnp.ndarray, h: jnp.ndarray, tied: bool,
           quant: Optional[str] = None) -> jnp.ndarray:
    """(N, V) float32 logits of hidden rows h (N, d).  ``w_out`` is the
    head (d, V), or the embedding (V, d) of a tied model."""
    w = w_out.T if tied else w_out
    return matmul(h, w, quant)
