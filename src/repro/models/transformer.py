"""Dense decoder transformer.

Covers: gemma3 (local:global SWA interleave, qk-norm, sandwich norms,
logit softcap), qwen1.5 (QKV bias), minitron, granite (MQA), whisper decoder
(cross-attention + learned positions), qwen2-vl (M-RoPE, patch embeds).

Layers are stacked along a leading L axis and executed with ``lax.scan``
(compile-time O(1) in depth — essential for 62-layer dry-runs on this host).
Per-layer heterogeneity (local vs global attention, rope theta) rides along
as scanned flag arrays.  The stacked KV cache is the scan's carry: each
layer writes into, and reads from, its own layer of it in place.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import kv_cache as kvc
from . import layers as nn
from .config import ModelConfig


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_layer_params(key, cfg: ModelConfig):
    dt = cfg.dtype
    ks = jax.random.split(key, 8)
    p = {}
    p["ln1"], _ = nn.init_rmsnorm(cfg.d_model, dt)
    p["attn"], _ = nn.init_attention(ks[0], cfg, dt)
    p["ln2"], _ = nn.init_rmsnorm(cfg.d_model, dt)
    p["mlp"], _ = nn.init_swiglu(ks[1], cfg.d_model, cfg.d_ff, dt)
    if cfg.sandwich_norm:
        p["post_attn_ln"], _ = nn.init_rmsnorm(cfg.d_model, dt)
        p["post_mlp_ln"], _ = nn.init_rmsnorm(cfg.d_model, dt)
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((cfg.head_dim,), dt)}
        p["k_norm"] = {"scale": jnp.ones((cfg.head_dim,), dt)}
    if cfg.encdec is not None:
        p["ln_cross"], _ = nn.init_rmsnorm(cfg.d_model, dt)
        p["cross"], _ = nn.init_attention(
            ks[2], cfg, dt, kv_input_dim=cfg.encdec.d_encoder)
    return p


def _layer_axes(cfg: ModelConfig):
    L = ("layers",)
    ax: Dict[str, Any] = {
        "ln1": {"scale": L + ("embed",)},
        "ln2": {"scale": L + ("embed",)},
        "attn": {
            "q": {"w": L + ("embed", "heads")},
            "k": {"w": L + ("embed", "kv_heads")},
            "v": {"w": L + ("embed", "kv_heads")},
            "o": {"w": L + ("heads", "embed")},
        },
        "mlp": {
            "gate": {"w": L + ("embed", "mlp")},
            "up": {"w": L + ("embed", "mlp")},
            "down": {"w": L + ("mlp", "embed")},
        },
    }
    if cfg.qkv_bias:
        for n in ("q", "k", "v"):
            tgt = "heads" if n == "q" else "kv_heads"
            ax["attn"][n]["b"] = L + (tgt,)
    if cfg.sandwich_norm:
        ax["post_attn_ln"] = {"scale": L + ("embed",)}
        ax["post_mlp_ln"] = {"scale": L + ("embed",)}
    if cfg.qk_norm:
        ax["q_norm"] = {"scale": L + ("head_dim",)}
        ax["k_norm"] = {"scale": L + ("head_dim",)}
    if cfg.encdec is not None:
        ax["ln_cross"] = {"scale": L + ("embed",)}
        ax["cross"] = {
            "q": {"w": L + ("embed", "heads")},
            "k": {"w": L + ("enc_embed", "kv_heads")},
            "v": {"w": L + ("enc_embed", "kv_heads")},
            "o": {"w": L + ("heads", "embed")},
        }
    return ax


def param_axes(cfg: ModelConfig):
    axes: Dict[str, Any] = {
        "embed": ("vocab", "embed"),
        "blocks": _layer_axes(cfg),
        "final_norm": {"scale": ("embed",)},
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = {"w": ("embed", "vocab")}
    if cfg.learned_positions:
        axes["pos_embed"] = ("seq", "embed")
    return axes


def init(key, cfg: ModelConfig):
    dt = cfg.dtype
    k_emb, k_layers, k_head, k_pos = jax.random.split(key, 4)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model))
                  * 0.02).astype(dt),
        "blocks": jax.vmap(partial(_init_layer_params, cfg=cfg))(layer_keys),
        "final_norm": nn.init_rmsnorm(cfg.d_model, dt)[0],
    }
    if not cfg.tie_embeddings:
        params["lm_head"], _ = nn.init_linear(
            k_head, cfg.d_model, cfg.vocab_size, "embed", "vocab", dt)
    if cfg.learned_positions:
        params["pos_embed"] = (jax.random.normal(
            k_pos, (cfg.max_position, cfg.d_model)) * 0.02).astype(dt)
    return params, param_axes(cfg)


def layer_flags(cfg: ModelConfig):
    """Per-layer scanned metadata: (is_global (L,), rope_theta (L,))."""
    L = cfg.num_layers
    is_global = jnp.array(
        [cfg.is_global_layer(i) for i in range(L)], jnp.bool_)
    theta_g = cfg.rope_theta_global or cfg.rope_theta
    thetas = jnp.where(is_global, theta_g, cfg.rope_theta).astype(jnp.float32)
    return is_global, thetas


# ---------------------------------------------------------------------------
# Shared block computation
# ---------------------------------------------------------------------------
def _embed(params, cfg: ModelConfig, tokens):
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _unembed(params, cfg: ModelConfig, x):
    x = nn.rmsnorm(params["final_norm"], x, cfg.rms_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, params["embed"])
    else:
        logits = nn.linear(params["lm_head"], x)
    return nn.softcap(logits.astype(jnp.float32), cfg.logit_softcap)


def _qk_normed(pl, cfg, q, k):
    if cfg.qk_norm:
        q = nn.rmsnorm(pl["q_norm"], q, cfg.rms_eps)
        k = nn.rmsnorm(pl["k_norm"], k, cfg.rms_eps)
    return q, k


def _block(pl, cfg: ModelConfig, x, *, attend, q_pos3, theta,
           cross_kv=None):
    """One transformer block.

    ``attend(q, k_new, v_new) -> (attn_out, kv)`` is the block's
    self-attention.  The trainer attends within the block and returns no
    cache; the cached forward (``_cached_attention``) writes the block's
    new K/V into its layer of the stacked cache, attends over that layer,
    and returns the updated stack.  Returns ``(x, kv)``.
    """
    h = nn.rmsnorm(pl["ln1"], x, cfg.rms_eps)
    q, k_new, v_new = nn.attention_qkv(pl["attn"], h, cfg)
    q, k_new = _qk_normed(pl, cfg, q, k_new)
    if cfg.vlm is not None:
        q = nn.apply_mrope(q, q_pos3, cfg.vlm.mrope_sections, theta)
        k_new = nn.apply_mrope(k_new, q_pos3, cfg.vlm.mrope_sections, theta)
    else:
        qp = q_pos3[..., 0]
        q = _rope_traced(q, qp, theta, cfg.head_dim)
        k_new = _rope_traced(k_new, qp, theta, cfg.head_dim)

    attn_out, kv = attend(q, k_new, v_new)
    a = nn.attention_out(pl["attn"], attn_out)
    if cfg.sandwich_norm:
        a = nn.rmsnorm(pl["post_attn_ln"], a, cfg.rms_eps)
    x = x + a

    if cross_kv is not None:  # whisper decoder cross-attention
        hc = nn.rmsnorm(pl["ln_cross"], x, cfg.rms_eps)
        B, T, _ = hc.shape
        qc = nn.linear(pl["cross"]["q"], hc).reshape(
            B, T, cfg.num_heads, cfg.head_dim)
        ck_, cv_ = cross_kv  # (B, S_enc, Hkv, hd) — precomputed at prefill
        cm = jnp.ones((B, T, ck_.shape[1]), jnp.bool_)
        co = nn.gqa_attention(qc, ck_, cv_, cm)
        x = x + nn.attention_out(pl["cross"], co)

    h2 = nn.rmsnorm(pl["ln2"], x, cfg.rms_eps)
    m = nn.swiglu(pl["mlp"], h2)
    if cfg.sandwich_norm:
        m = nn.rmsnorm(pl["post_mlp_ln"], m, cfg.rms_eps)
    return x + m, kv


def _cached_attention(cfg: ModelConfig, kv, layer, q, k_new, v_new, *,
                      mask, write_slot=None, paged_idx=None):
    """Write layer ``layer``'s new K/V into the stacked cache ``kv``
    ({"k", "v"}, plus {"k_scale", "v_scale"} under ``kv_quant``; the layer
    scan's carry, so every write lands in place) and attend over that layer.

    Paged (``paged_idx`` = (phys_new (B, T), view_idx (B, S))): the arrays
    are flat pools (L, P·bs, ...); new entries scatter to ``phys_new`` and
    attention reads the per-row view ``view_idx`` from the layer's slice of
    the pool after the write.  Contiguous: (L, B, S, ...), written at
    ``write_slot``.
    Returns ``(attn_out, kv)``.
    """
    new = {"k": k_new, "v": v_new}
    if cfg.kv_quant:
        new["k"], new["k_scale"] = kvc.kv_quantize(k_new)
        new["v"], new["v_scale"] = kvc.kv_quantize(v_new)
    if paged_idx is not None:
        phys_new, view_idx = paged_idx
        # device scopes: the pool write and the per-row gathered view are
        # the paged cache's own traffic in a profile
        with jax.named_scope("kv_write"):
            kv = {n: kvc.paged_scatter_layer(c, layer, new[n], phys_new)
                  for n, c in kv.items()}
        with jax.named_scope("kv_gather"):
            view = {n: kvc.paged_gather(c[layer], view_idx)
                    for n, c in kv.items()}
    else:
        kv = {n: kvc.write_layer(c, layer, new[n], write_slot)
              for n, c in kv.items()}
        view = {n: c[layer] for n, c in kv.items()}
    if cfg.kv_quant:
        out = nn.gqa_attention_quant(q, view["k"], view["k_scale"],
                                     view["v"], view["v_scale"], mask,
                                     cfg.attn_softcap)
    else:
        out = nn.gqa_attention(q, view["k"], view["v"], mask,
                               cfg.attn_softcap)
    return out, kv


def _rope_traced(x, positions, theta, head_dim):
    """RoPE with a *traced* theta (per-layer scanned scalar)."""
    half = head_dim // 2
    exponent = jnp.arange(half, dtype=jnp.float32) / half
    freqs = 1.0 / (theta ** exponent)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Cached forward (prefill + decode): scan over layers
# ---------------------------------------------------------------------------
def make_cache(cfg: ModelConfig, batch: int, max_len: int):
    layers = kvc.make_attn_cache(cfg.num_layers, batch, max_len,
                                 cfg.num_kv_heads, cfg.head_dim, cfg.dtype,
                                 quant=cfg.kv_quant)
    axes = kvc.attn_cache_axes(quant=cfg.kv_quant)
    if cfg.encdec is not None:
        e = cfg.encdec
        shape = (cfg.num_layers, batch, e.num_encoder_positions,
                 cfg.num_kv_heads, cfg.head_dim)
        layers["cross_k"] = jnp.zeros(shape, cfg.dtype)
        layers["cross_v"] = jnp.zeros(shape, cfg.dtype)
        axes["cross_k"] = ("layers", "batch", "enc_seq", "kv_heads", "head_dim")
        axes["cross_v"] = ("layers", "batch", "enc_seq", "kv_heads", "head_dim")
    return layers, axes


def make_paged_cache(cfg: ModelConfig, batch: int, max_len: int,
                     block_size: int = kvc.PAGE_BLOCK,
                     pool_blocks: int | None = None):
    """Pool-shaped attention KV for a paged state.  Cross-attention KV
    (whisper) stays per-row: the encoder context is fixed-length and never
    appended to, so paging it buys nothing."""
    R = kvc._ceil_div(max_len, block_size)
    P = pool_blocks if pool_blocks is not None else batch * R
    layers = kvc.make_paged_attn_cache(cfg.num_layers, P, block_size,
                                       cfg.num_kv_heads, cfg.head_dim,
                                       cfg.dtype, quant=cfg.kv_quant)
    axes = kvc.paged_attn_cache_axes(quant=cfg.kv_quant)
    if cfg.encdec is not None:
        e = cfg.encdec
        shape = (cfg.num_layers, batch, e.num_encoder_positions,
                 cfg.num_kv_heads, cfg.head_dim)
        layers["cross_k"] = jnp.zeros(shape, cfg.dtype)
        layers["cross_v"] = jnp.zeros(shape, cfg.dtype)
        axes["cross_k"] = ("layers", "batch", "enc_seq", "kv_heads", "head_dim")
        axes["cross_v"] = ("layers", "batch", "enc_seq", "kv_heads", "head_dim")
    return layers, axes


def precompute_cross_kv(params, cfg: ModelConfig, enc_states):
    """Whisper: compute per-layer cross K/V from encoder output once."""
    def one(pl):
        B, S, _ = enc_states.shape
        k = nn.linear(pl["cross"]["k"], enc_states).reshape(
            B, S, cfg.num_kv_heads, cfg.head_dim)
        v = nn.linear(pl["cross"]["v"], enc_states).reshape(
            B, S, cfg.num_kv_heads, cfg.head_dim)
        return k, v
    return jax.vmap(one)(params["blocks"])  # over stacked L axis


def forward_cached(params, cfg: ModelConfig, state: kvc.ModelState,
                   tokens: jnp.ndarray,
                   valid: Optional[jnp.ndarray] = None,
                   input_embeds: Optional[jnp.ndarray] = None,
                   mrope_positions: Optional[jnp.ndarray] = None,
                   logits_mode: str = "all",
                   spec_depth: Optional[jnp.ndarray] = None,
                   spec_attend: Optional[jnp.ndarray] = None):
    """Append T tokens, run all layers, return (logits, new_state).

    logits_mode: 'all' -> (B,T,V); 'last' -> (B,V) at each row's last valid.

    Tree-structured speculation: ``spec_depth`` (T,) marks tree entries of
    the block (-1 = committed-stream token; d >= 0 = tree node at depth d,
    positioned at post-linear length + d) and ``spec_attend`` (T, R) is the
    static ancestor mask overriding the attention columns of the cycle's
    tree region — the LAST R physical slots after this append (earlier
    draft levels of the same cycle sit contiguously before this block).
    The override also applies to sliding-window layers: tree depths are
    tiny relative to any real window, so ancestors are never out-of-window.
    """
    state, q_pos, slot = kvc.append_tokens(state, tokens, valid,
                                           spec_depth=spec_depth)
    B, T = tokens.shape
    paged = isinstance(state, kvc.PagedModelState)
    x = input_embeds if input_embeds is not None else _embed(params, cfg, tokens)
    if cfg.learned_positions:
        safe = jnp.clip(q_pos, 0, cfg.max_position - 1)
        x = x + params["pos_embed"][safe]

    kv_pos = state.pos_buf
    m_full = nn.build_attention_mask(state.mask, kv_pos, q_pos, window=0)
    m_win = (nn.build_attention_mask(state.mask, kv_pos, q_pos,
                                     window=cfg.sliding_window)
             if cfg.sliding_window > 0 else m_full)
    if spec_attend is not None:
        spec_attend = jnp.asarray(spec_attend)
        if paged:
            appended = (valid.any(axis=1) if valid is not None
                        else jnp.ones((B,), jnp.bool_))
            cols = kvc.tree_region_cols(state, spec_attend.shape[1],
                                        appended)
            m_full = nn.overlay_block_mask_at(m_full, state.mask,
                                              spec_attend, cols)
            if cfg.sliding_window > 0:
                m_win = nn.overlay_block_mask_at(m_win, state.mask,
                                                 spec_attend, cols)
        else:
            region_start = slot + T - spec_attend.shape[1]
            m_full = nn.overlay_block_mask(m_full, state.mask,
                                           spec_attend, region_start)
            if cfg.sliding_window > 0:
                m_win = nn.overlay_block_mask(m_win, state.mask,
                                              spec_attend, region_start)
    paged_idx = ((kvc.physical_slots(state, slot),
                  kvc.physical_view_index(state)) if paged else None)
    if mrope_positions is None:
        q_pos3 = jnp.repeat(q_pos[..., None], 3, axis=-1)
    else:
        q_pos3 = mrope_positions

    is_global, thetas = layer_flags(cfg)
    has_cross = cfg.encdec is not None
    # what each layer writes rides in the carry and is updated in place;
    # only what layers read is scanned over
    kv_names = ("k", "v", "k_scale", "v_scale") if cfg.kv_quant \
        else ("k", "v")
    kv = {n: state.layers[n] for n in kv_names}
    xs = {"pl": params["blocks"], "g": is_global, "theta": thetas,
          "layer": jnp.arange(cfg.num_layers, dtype=jnp.int32)}
    if has_cross:
        xs["xk"] = state.layers["cross_k"]
        xs["xv"] = state.layers["cross_v"]

    def body(carry, s):
        x, kv = carry
        mask = jnp.where(s["g"], m_full, m_win) if cfg.sliding_window > 0 \
            else m_full
        cross = (s["xk"], s["xv"]) if has_cross else None
        attend = partial(_cached_attention, cfg, kv, s["layer"], mask=mask,
                         write_slot=None if paged else slot,
                         paged_idx=paged_idx)
        x, kv = _block(s["pl"], cfg, x, attend=attend, q_pos3=q_pos3,
                       theta=s["theta"], cross_kv=cross)
        return (x, kv), None

    (x, kv), _ = jax.lax.scan(body, (x, kv), xs)
    state = dataclasses.replace(state, layers={**state.layers, **kv})

    if logits_mode == "none":
        return None, state
    if logits_mode == "last":
        if valid is None:
            x_last = x[:, -1]
        else:
            idx = jnp.maximum(jnp.sum(valid, axis=1) - 1, 0)
            x_last = jnp.take_along_axis(
                x, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return _unembed(params, cfg, x_last), state
    return _unembed(params, cfg, x), state


# ---------------------------------------------------------------------------
# Trainer forward (no cache, full causal)
# ---------------------------------------------------------------------------
def forward_train(params, cfg: ModelConfig, tokens: jnp.ndarray,
                  input_embeds: Optional[jnp.ndarray] = None,
                  mrope_positions: Optional[jnp.ndarray] = None,
                  enc_states: Optional[jnp.ndarray] = None,
                  remat: bool = True):
    B, S = tokens.shape
    x = input_embeds if input_embeds is not None else _embed(params, cfg, tokens)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    if cfg.learned_positions:
        x = x + params["pos_embed"][pos]
    ar = jnp.arange(S, dtype=jnp.int32)
    causal = ar[None, :, None] >= ar[None, None, :]
    m_full = jnp.broadcast_to(causal, (B, S, S))
    if cfg.sliding_window > 0:
        m_win = m_full & (ar[None, None, :] > ar[None, :, None] - cfg.sliding_window)
    else:
        m_win = m_full
    q_pos3 = (jnp.repeat(pos[..., None], 3, axis=-1)
              if mrope_positions is None else mrope_positions)
    is_global, thetas = layer_flags(cfg)
    has_cross = cfg.encdec is not None
    cross_kv_all = (precompute_cross_kv(params, cfg, enc_states)
                    if has_cross else None)

    xs = {"pl": params["blocks"], "g": is_global, "theta": thetas}
    if has_cross:
        xs["xk"], xs["xv"] = cross_kv_all

    def body(x, s):
        mask = jnp.where(s["g"], m_full, m_win) if cfg.sliding_window > 0 \
            else m_full
        cross = (s["xk"], s["xv"]) if has_cross else None
        attend = lambda q, k, v: (
            nn.gqa_attention(q, k, v, mask, cfg.attn_softcap), None)
        x, _ = _block(s["pl"], cfg, x, attend=attend, q_pos3=q_pos3,
                      theta=s["theta"], cross_kv=cross)
        return x, None

    fn = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable) \
        if remat else body
    x, _ = jax.lax.scan(fn, x, xs)
    return _unembed(params, cfg, x)
