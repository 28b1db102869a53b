"""Plain float32 reference for the Qwen2 architecture (Qwen1.5 models).

Written from the published description of the architecture, independent of
the program under test: token embedding; per layer a pre-norm (RMSNorm)
multi-head attention with biased q/k/v projections, rotary position
embeddings (rotate-half form, ``rope_theta``) and a causal softmax, then a
pre-norm SwiGLU MLP (``down(silu(gate x) * up x)``), each added to the
residual; a final RMSNorm and the output head (the embedding, transposed,
where ``tie_word_embeddings``).  Every matrix product runs in float32 at
``HIGHEST`` precision: on a TPU a float32 product runs in bfloat16 passes
unless told otherwise.

The model runs one layer at a time over a block of sequences, with the
bfloat16 weights of that layer raised to float32 inside the step, so it fits
beside the served weights.

``quant`` computes every weight product in a lower precision instead, for the
control run: ``"int8"`` quantizes weights per output channel and activations
per token to int8 (symmetric, round to nearest) and multiplies in int32;
``"fp8"`` does the same in float8 e4m3.  Attention products stay in float32.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_FP8 = jnp.float8_e4m3fn
_FP8_MAX = 448.0


def _quantize(a: jnp.ndarray, axis: int, quant: str):
    """Symmetric per-slice quantization along ``axis``: (values, scale)."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    if quant == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(a / s), -127, 127).astype(jnp.int8), s
    if quant == "fp8":
        s = jnp.maximum(amax, 1e-30) / _FP8_MAX
        return (a / s).astype(_FP8), s
    raise ValueError(f"unknown quant {quant!r}")


def matmul(x: jnp.ndarray, w: jnp.ndarray, quant: Optional[str]):
    """x (..., k) float32 times w (k, n), in float32 or ``quant``."""
    w = w.astype(jnp.float32)
    if quant is None:
        return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)
    xq, xs = _quantize(x, -1, quant)
    wq, ws = _quantize(w, 0, quant)
    if quant == "int8":
        y = jnp.einsum("...k,kn->...n", xq, wq,
                       preferred_element_type=jnp.int32).astype(jnp.float32)
    else:
        y = jnp.einsum("...k,kn->...n", xq.astype(jnp.float32),
                       wq.astype(jnp.float32), precision=HIGHEST)
    return y * xs * ws


def rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def rope(x, theta: float):
    """x (B, S, H, hd); positions 0..S-1; rotate-half form."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("hf_items", "quant"))
def _layer(layers: Dict, i, x, hf_items, quant):
    hf = dict(hf_items)
    d, H = hf["hidden_size"], hf["num_attention_heads"]
    Hkv, hd = hf["num_key_value_heads"], d // hf["num_attention_heads"]
    eps = hf["rms_norm_eps"]
    p = {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
         .astype(jnp.float32) for k, v in layers.items()}
    B, S, _ = x.shape
    h = rmsnorm(x, p["ln1"], eps)
    q = (matmul(h, p["wq"], quant) + p["bq"]).reshape(B, S, H, hd)
    k = (matmul(h, p["wk"], quant) + p["bk"]).reshape(B, S, Hkv, hd)
    v = (matmul(h, p["wv"], quant) + p["bv"]).reshape(B, S, Hkv, hd)
    q, k = rope(q, hf["rope_theta"]), rope(k, hf["rope_theta"])
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST).reshape(B, S, H * hd)
    x = x + matmul(a, p["wo"], quant)
    h = rmsnorm(x, p["ln2"], eps)
    g = jax.nn.silu(matmul(h, p["wg"], quant)) * matmul(h, p["wu"], quant)
    return x + matmul(g, p["wd"], quant)


@partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, scale, eps):
    return rmsnorm(x, scale, eps)


def hidden(w: Dict, hf: Dict, tokens: jnp.ndarray,
           quant: Optional[str] = None) -> jnp.ndarray:
    """Final normed hidden states (B, S, d), float32, for right-padded
    token rows (causal, so padding never reaches an earlier position)."""
    layers = {k: w[k] for k in ("ln1", "wq", "bq", "wk", "bk", "wv", "bv",
                                "wo", "ln2", "wg", "wu", "wd")}
    hf_items = tuple(sorted((k, v) for k, v in hf.items()
                            if isinstance(v, (int, float))))
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    for i in range(hf["num_hidden_layers"]):
        x = _layer(layers, i, x, hf_items=hf_items, quant=quant)
    return _final_norm(x, w["final_norm"], eps=hf["rms_norm_eps"])


@partial(jax.jit, static_argnames=("tied", "quant"))
def logits(w_out: jnp.ndarray, h: jnp.ndarray, tied: bool,
           quant: Optional[str] = None) -> jnp.ndarray:
    """(N, V) float32 logits of hidden rows h (N, d).  ``w_out`` is the
    head (d, V), or the embedding (V, d) of a tied model."""
    w = w_out.T if tied else w_out
    return matmul(h, w, quant)
