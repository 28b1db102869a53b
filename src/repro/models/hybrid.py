"""Hymba-style hybrid block (arXiv:2411.13676): attention heads and mamba
(selective-SSM) heads run in PARALLEL on the same block input; their normed
outputs are averaged, then a standard SwiGLU MLP follows.

Attention uses sliding-window everywhere except the first/middle/last layers
(global), per the Hymba recipe.  The SSM branch carries (conv window, ssm
state) caches with snapshot-ring rollback like ssm.py; the attention branch
rolls back via the logical cache_mask — both stay in sync through the shared
ModelState buffers (the paper's §4.4 requirement for heterogeneous chains).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from . import kv_cache as kvc
from . import layers as nn
from .config import ModelConfig
from . import transformer as tf
from .ssm import SNAP_SLOTS


def _inner(cfg):
    return cfg.d_model * (cfg.ssm.expand if cfg.ssm else 2)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_layer_params(key, cfg: ModelConfig):
    dt = cfg.dtype
    d = cfg.d_model
    inner = _inner(cfg)
    N = cfg.ssm.state_size
    ks = jax.random.split(key, 10)
    s = 1.0 / math.sqrt(d)
    si = 1.0 / math.sqrt(inner)
    p = {
        "ln1": nn.init_rmsnorm(d, dt)[0],
        "attn": nn.init_attention(ks[0], cfg, dt)[0],
        "attn_norm": nn.init_rmsnorm(d, dt)[0],
        "ssm_norm": nn.init_rmsnorm(d, dt)[0],
        "in_proj": (jax.random.normal(ks[1], (d, 2 * inner)) * s).astype(dt),
        "conv_w": (jax.random.normal(ks[2], (4, inner)) * 0.5).astype(dt),
        "w_dt": (jax.random.normal(ks[3], (inner, inner)) * si * 0.1
                 ).astype(jnp.float32),
        "b_dt": jnp.log(jnp.expm1(jnp.full((inner,), 0.01))).astype(jnp.float32),
        "w_B": (jax.random.normal(ks[4], (inner, N)) * si).astype(jnp.float32),
        "w_C": (jax.random.normal(ks[5], (inner, N)) * si).astype(jnp.float32),
        "A_log": jnp.log(jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32),
                                  (inner, 1))),
        "D": jnp.ones((inner,), jnp.float32),
        "out_proj": (jax.random.normal(ks[6], (inner, d)) * si).astype(dt),
        "beta_attn": jnp.ones((), jnp.float32),
        "beta_ssm": jnp.ones((), jnp.float32),
        "ln2": nn.init_rmsnorm(d, dt)[0],
        "mlp": nn.init_swiglu(ks[7], d, cfg.d_ff, dt)[0],
    }
    return p


def _layer_axes(cfg: ModelConfig):
    L = ("layers",)
    return {
        "ln1": {"scale": L + ("embed",)},
        "attn": {
            "q": {"w": L + ("embed", "heads")},
            "k": {"w": L + ("embed", "kv_heads")},
            "v": {"w": L + ("embed", "kv_heads")},
            "o": {"w": L + ("heads", "embed")},
        },
        "attn_norm": {"scale": L + ("embed",)},
        "ssm_norm": {"scale": L + ("embed",)},
        "in_proj": L + ("embed", "ssm_inner"),
        "conv_w": L + ("conv", "ssm_inner"),
        "w_dt": L + ("ssm_inner", "ssm_inner"),
        "b_dt": L + ("ssm_inner",),
        "w_B": L + ("ssm_inner", "ssm_state"),
        "w_C": L + ("ssm_inner", "ssm_state"),
        "A_log": L + ("ssm_inner", "ssm_state"),
        "D": L + ("ssm_inner",),
        "out_proj": L + ("ssm_inner", "embed"),
        "beta_attn": L, "beta_ssm": L,
        "ln2": {"scale": L + ("embed",)},
        "mlp": {"gate": {"w": L + ("embed", "mlp")},
                "up": {"w": L + ("embed", "mlp")},
                "down": {"w": L + ("mlp", "embed")}},
    }


def param_axes(cfg: ModelConfig):
    return {
        "embed": ("vocab", "embed"),
        "blocks": _layer_axes(cfg),
        "final_norm": {"scale": ("embed",)},
    }


def init(key, cfg: ModelConfig):
    dt = cfg.dtype
    k_emb, k_layers = jax.random.split(key)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    params = {
        "embed": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model))
                  * 0.02).astype(dt),
        "blocks": jax.vmap(partial(_init_layer_params, cfg=cfg))(layer_keys),
        "final_norm": nn.init_rmsnorm(cfg.d_model, dt)[0],
    }
    return params, param_axes(cfg)


def layer_flags(cfg: ModelConfig):
    """Hymba: global attention on first, middle, last layer; SWA elsewhere."""
    L = cfg.num_layers
    glb = {0, L // 2, L - 1}
    return jnp.array([i in glb for i in range(L)], jnp.bool_)


def make_cache(cfg: ModelConfig, batch: int, max_len: int, span: int = 0):
    """``span > 0`` keeps the snapshot ring (``SNAP_SLOTS`` states, what
    rollback restores from)."""
    inner = _inner(cfg)
    N = cfg.ssm.state_size
    L = cfg.num_layers
    layers = kvc.make_attn_cache(L, batch, max_len, cfg.num_kv_heads,
                                 cfg.head_dim, cfg.dtype)
    layers["ssm_h"] = jnp.zeros((L, batch, inner, N), jnp.float32)
    layers["conv"] = jnp.zeros((L, batch, 3, inner), cfg.dtype)
    if span > 0:
        layers["snaps"] = {
            "ssm_h": jnp.zeros((SNAP_SLOTS, L, batch, inner, N), jnp.float32),
            "conv": jnp.zeros((SNAP_SLOTS, L, batch, 3, inner), cfg.dtype),
        }
    axes = kvc.attn_cache_axes()
    axes["ssm_h"] = ("layers", "batch", "ssm_inner", "ssm_state")
    axes["conv"] = ("layers", "batch", None, "ssm_inner")
    if span > 0:
        axes["snaps"] = jax.tree.map(lambda _: None, layers["snaps"])
    return layers, axes


# ---------------------------------------------------------------------------
# Mamba branch (selective SSM), scanned over T inside the layer
# ---------------------------------------------------------------------------
def _mamba_branch(pl, cfg, x_norm, ssm_h, conv_buf, valid, collect=False):
    """x_norm: (B,T,d). Returns (y (B,T,d), ssm_h', conv_buf'[, per-step states])."""
    B, T, d = x_norm.shape
    inner = _inner(cfg)
    xz = jnp.einsum("btd,di->bti", x_norm, pl["in_proj"])
    x_ssm, z = jnp.split(xz, 2, axis=-1)                       # (B,T,inner)

    def step(carry, inp):
        h, cbuf = carry
        xt, vt = inp                                           # (B,inner),(B,)
        win = jnp.concatenate([cbuf, xt[:, None, :]], axis=1)  # (B,4,inner)
        xc = jax.nn.silu(jnp.einsum("bti,ti->bi", win.astype(jnp.float32),
                                    pl["conv_w"].astype(jnp.float32)))
        dt_ = jax.nn.softplus(xc @ pl["w_dt"] + pl["b_dt"])    # (B,inner)
        Bc = xc @ pl["w_B"]                                    # (B,N)
        Cc = xc @ pl["w_C"]
        A = -jnp.exp(pl["A_log"])                              # (inner,N)
        dA = jnp.exp(dt_[..., None] * A[None])                 # (B,inner,N)
        h_new = dA * h + (dt_ * xc)[..., None] * Bc[:, None, :]
        y = jnp.einsum("bin,bn->bi", h_new, Cc) + pl["D"] * xc
        vb = vt[:, None]
        h_out = jnp.where(vt[:, None, None], h_new, h)
        cb_out = jnp.where(vt[:, None, None],
                           jnp.concatenate([cbuf[:, 1:], xt[:, None, :]],
                                           axis=1), cbuf)
        ys = (jnp.where(vb, y, 0.0), h_out, cb_out) if collect \
            else jnp.where(vb, y, 0.0)
        return (h_out, cb_out), ys

    x_tb = jnp.swapaxes(x_ssm, 0, 1)
    v_tb = jnp.swapaxes(valid, 0, 1)
    CK = 64
    if not collect and T % CK == 0 and T >= 2 * CK:
        # chunked-remat time scan (same pathology/fix as xlstm §Perf H1)
        def chunk(carry, inp):
            return jax.lax.scan(step, carry, inp)
        chunked = jax.checkpoint(
            chunk, policy=jax.checkpoint_policies.nothing_saveable)
        (h_fin, cb_fin), ys = jax.lax.scan(
            chunked, (ssm_h, conv_buf),
            (x_tb.reshape(T // CK, CK, *x_tb.shape[1:]),
             v_tb.reshape(T // CK, CK, *v_tb.shape[1:])))
        ys = ys.reshape(T, *ys.shape[2:])
    else:
        (h_fin, cb_fin), ys = jax.lax.scan(step, (ssm_h, conv_buf),
                                           (x_tb, v_tb))
    y_tb, steps = (ys[0], (ys[1], ys[2])) if collect else (ys, None)
    y = jnp.swapaxes(y_tb, 0, 1)                               # (B,T,inner)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    out = jnp.einsum("bti,id->btd", y.astype(x_norm.dtype), pl["out_proj"])
    if collect:
        return out, h_fin, cb_fin, steps  # steps: ((T,B,inner,N),(T,B,3,inner))
    return out, h_fin, cb_fin


def _block(pl, cfg, x, *, k_cached, v_cached, ssm_h, conv_buf, mask,
           q_pos, valid, write_slot=None, collect=False):
    h = nn.rmsnorm(pl["ln1"], x, cfg.rms_eps)
    # attention branch
    q, k_new, v_new = nn.attention_qkv(pl["attn"], h, cfg)
    q = tf._rope_traced(q, q_pos, jnp.float32(cfg.rope_theta), cfg.head_dim)
    k_new = tf._rope_traced(k_new, q_pos, jnp.float32(cfg.rope_theta),
                            cfg.head_dim)
    if k_cached is not None:
        ck, cv = kvc.write_kv(k_cached, v_cached, k_new, v_new, write_slot)
        attn_o = nn.gqa_attention(q, ck, cv, mask)
        new_kv = (ck, cv)
    else:
        attn_o = nn.gqa_attention(q, k_new, v_new, mask)
        new_kv = (None, None)
    attn_y = nn.attention_out(pl["attn"], attn_o)
    # mamba branch (parallel, same input)
    res = _mamba_branch(pl, cfg, h, ssm_h, conv_buf, valid, collect=collect)
    ssm_y, ssm_h2, conv2 = res[0], res[1], res[2]
    steps = res[3] if collect else None
    # normalized average fusion (Hymba)
    fused = (nn.rmsnorm(pl["attn_norm"], attn_y, cfg.rms_eps)
             * pl["beta_attn"].astype(x.dtype)
             + nn.rmsnorm(pl["ssm_norm"], ssm_y, cfg.rms_eps)
             * pl["beta_ssm"].astype(x.dtype)) * 0.5
    x = x + fused
    h2 = nn.rmsnorm(pl["ln2"], x, cfg.rms_eps)
    return x + nn.swiglu(pl["mlp"], h2), new_kv, ssm_h2, conv2, steps


def _forward(params, cfg, state, tokens, valid, m_full, m_win, q_pos,
             slot, with_cache: bool):
    x = tf._embed(params, cfg, tokens)
    is_global = layer_flags(cfg)
    xs = {"pl": params["blocks"], "g": is_global}
    if with_cache:
        xs.update({"ck": state.layers["k"], "cv": state.layers["v"],
                   "h": state.layers["ssm_h"], "cb": state.layers["conv"]})
    else:
        B, T = tokens.shape
        inner, N = _inner(cfg), cfg.ssm.state_size
        L = cfg.num_layers
        xs.update({"h": jnp.zeros((L, B, inner, N), jnp.float32),
                   "cb": jnp.zeros((L, B, 3, inner), cfg.dtype)})

    collect = with_cache and state is not None and "snaps" in state.layers

    def body(x, s):
        mask = jnp.where(s["g"], m_full, m_win)
        x, (ck, cv), h2, cb2, steps = _block(
            s["pl"], cfg, x, k_cached=s.get("ck"), v_cached=s.get("cv"),
            ssm_h=s["h"], conv_buf=s["cb"], mask=mask, q_pos=q_pos,
            valid=valid, write_slot=slot, collect=collect)
        out = {"h": h2, "cb": cb2}
        if ck is not None:
            out.update({"k": ck, "v": cv})
        if collect:
            out["h_steps"], out["cb_steps"] = steps
        return x, out

    # trainer path: remat each layer — the mamba time scan otherwise saves
    # every per-step (B,inner,N) state for backward (EXPERIMENTS §Perf,
    # same pathology as xlstm H1)
    if not with_cache:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    return jax.lax.scan(body, x, xs)


def forward_cached(params, cfg: ModelConfig, state: kvc.ModelState,
                   tokens, valid=None, logits_mode="all", **_ignored):
    B, T = tokens.shape
    if valid is None:
        valid = jnp.ones((B, T), jnp.bool_)
    state, q_pos, slot = kvc.append_tokens(state, tokens, valid)
    m_full = nn.build_attention_mask(state.mask, state.pos_buf, q_pos, 0)
    m_win = nn.build_attention_mask(state.mask, state.pos_buf, q_pos,
                                    cfg.sliding_window)
    x, outs = _forward(params, cfg, state, tokens, valid, m_full, m_win,
                       q_pos, slot, with_cache=True)
    new_layers = {**state.layers, "k": outs["k"], "v": outs["v"],
                  "ssm_h": outs["h"], "conv": outs["cb"]}
    if "snaps" in state.layers:
        # outs["h_steps"]: (L, T, B, inner, N); write each token's full-depth
        # SSM state into the snapshot ring at physical slot (slot + t).
        snaps = state.layers["snaps"]
        for t in range(T):
            snaps = {
                "ssm_h": kvc.snap_write(snaps["ssm_h"],
                                        outs["h_steps"][:, t], slot + t),
                "conv": kvc.snap_write(snaps["conv"],
                                       outs["cb_steps"][:, t], slot + t),
            }
        new_layers["snaps"] = snaps
    state = dataclasses.replace(state, layers=new_layers)
    if logits_mode == "none":
        return None, state
    if logits_mode == "last":
        idx = jnp.maximum(jnp.sum(valid, axis=1) - 1, 0)
        x_last = jnp.take_along_axis(
            x, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return tf._unembed(params, cfg, x_last), state
    return tf._unembed(params, cfg, x), state


def rollback_hybrid(state: kvc.ModelState, r: jnp.ndarray) -> kvc.ModelState:
    """Hybrid rollback: attention KV rolls back via cache_mask (caller uses
    kv_cache.rollback); the SSM branch restores per-row snapshots here."""
    from .ssm import _restore_leaf
    layers = state.layers
    assert "snaps" in layers
    P = state.write_ptr
    slots = ((P - 1 - r.astype(jnp.int32)) % SNAP_SLOTS).astype(jnp.int32)
    new = dict(layers)
    new["ssm_h"] = _restore_leaf(layers["snaps"]["ssm_h"],
                                 layers["ssm_h"], slots, 1 + 1)
    new["conv"] = _restore_leaf(layers["snaps"]["conv"],
                                layers["conv"], slots, 1 + 1)
    return dataclasses.replace(state, layers=new)


def forward_train(params, cfg: ModelConfig, tokens, remat=True, **_ignored):
    B, S = tokens.shape
    ar = jnp.arange(S, dtype=jnp.int32)
    pos = jnp.broadcast_to(ar[None, :], (B, S))
    causal = jnp.broadcast_to(ar[None, :, None] >= ar[None, None, :], (B, S, S))
    m_win = causal & (ar[None, None, :] > ar[None, :, None] - cfg.sliding_window)
    valid = jnp.ones((B, S), jnp.bool_)
    x, _ = _forward(params, cfg, None, tokens, valid, causal, m_win,
                    pos, None, with_cache=False)
    return tf._unembed(params, cfg, x)


def restore(state: kvc.ModelState, r: jnp.ndarray) -> kvc.ModelState:
    """Snapshot restore where the state keeps snapshots (a state made
    without them is never rolled back past its attention KV)."""
    return rollback_hybrid(state, r) if "snaps" in state.layers else state
