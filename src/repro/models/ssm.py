"""xLSTM (arXiv:2405.04517): mLSTM (matrix-memory) + sLSTM (scalar-memory)
blocks, interleaved in groups (xLSTM[k:1] style).

Rollback adaptation (DESIGN §5): recurrent models have no per-position KV
cache, so speculative rollback restores a *state snapshot*.  Every decode
step writes the post-token recurrent state into a small ring buffer
(``snaps``, K slots, K > max draft window); rollback gathers the per-row
snapshot at the accepted length.  Invalid (masked) tokens are processed as
no-ops per row so snapshots stay row-consistent.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import kv_cache as kvc
from . import layers as nn
from .config import ModelConfig
from . import transformer as tf

SNAP_SLOTS = 16  # > any draft window we use


# ---------------------------------------------------------------------------
# mLSTM block
# ---------------------------------------------------------------------------
def _inner(cfg):
    return int(cfg.d_model * (cfg.ssm.mlstm_proj_factor if cfg.ssm else 2.0))


def init_mlstm_block(key, cfg: ModelConfig):
    dt = cfg.dtype
    d, NH = cfg.d_model, cfg.num_heads
    inner = _inner(cfg)
    dh = inner // NH
    ks = jax.random.split(key, 8)
    s = 1.0 / math.sqrt(d)
    si = 1.0 / math.sqrt(inner)
    p = {
        "ln": nn.init_rmsnorm(d, dt)[0],
        "up": (jax.random.normal(ks[0], (d, 2 * inner)) * s).astype(dt),
        "conv_w": (jax.random.normal(ks[1], (4, inner)) * 0.5).astype(dt),
        "wq": (jax.random.normal(ks[2], (inner, inner)) * si).astype(dt),
        "wk": (jax.random.normal(ks[3], (inner, inner)) * si).astype(dt),
        "wv": (jax.random.normal(ks[4], (inner, inner)) * si).astype(dt),
        "w_if": (jax.random.normal(ks[5], (inner, 2 * NH)) * si).astype(jnp.float32),
        "b_if": jnp.concatenate([jnp.zeros((NH,)), 3.0 + jnp.arange(NH) * 0.5]
                                ).astype(jnp.float32),
        "gn": jnp.ones((inner,), dt),
        "down": (jax.random.normal(ks[6], (inner, d)) * si).astype(dt),
    }
    return p


def mlstm_axes(prefix):
    return {
        "ln": {"scale": prefix + ("embed",)},
        "up": prefix + ("embed", "ssm_inner"),
        "conv_w": prefix + ("conv", "ssm_inner"),
        "wq": prefix + ("ssm_inner", "ssm_inner"),
        "wk": prefix + ("ssm_inner", "ssm_inner"),
        "wv": prefix + ("ssm_inner", "ssm_inner"),
        "w_if": prefix + ("ssm_inner", None),
        "b_if": prefix + (None,),
        "gn": prefix + ("ssm_inner",),
        "down": prefix + ("ssm_inner", "embed"),
    }


def mlstm_state0(cfg, batch):
    NH = cfg.num_heads
    dh = _inner(cfg) // NH
    return {
        "c": jnp.zeros((batch, NH, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, NH, dh), jnp.float32),
        "m": jnp.zeros((batch, NH), jnp.float32),
        "conv": jnp.zeros((batch, 3, _inner(cfg)), cfg.dtype),
    }


def _mlstm_step(p, cfg, st, x_t, valid_t):
    """One token. x_t: (B, d); valid_t: (B,) bool. Returns (st, y (B,d))."""
    B = x_t.shape[0]
    NH = cfg.num_heads
    inner = _inner(cfg)
    dh = inner // NH
    h = nn.rmsnorm(p["ln"], x_t[:, None, :], cfg.rms_eps)[:, 0]
    hu = h @ p["up"]                                # (B, 2*inner)
    h_gate, hx = jnp.split(hu, 2, axis=-1)
    # causal depthwise conv over the last 4 inputs (3 cached + current)
    win = jnp.concatenate([st["conv"], hx[:, None, :]], axis=1)  # (B,4,inner)
    h_conv = jax.nn.silu(jnp.einsum("bti,ti->bi", win.astype(jnp.float32),
                                    p["conv_w"].astype(jnp.float32)))
    h_conv = h_conv.astype(hx.dtype)
    q = (h_conv @ p["wq"]).reshape(B, NH, dh).astype(jnp.float32)
    k = ((h_conv @ p["wk"]) / math.sqrt(dh)).reshape(B, NH, dh).astype(jnp.float32)
    v = (hx @ p["wv"]).reshape(B, NH, dh).astype(jnp.float32)
    gates = h_conv.astype(jnp.float32) @ p["w_if"] + p["b_if"]   # (B, 2NH)
    i_t, f_t = jnp.split(gates.reshape(B, 2, NH), 2, axis=1)
    i_t, f_t = i_t[:, 0], f_t[:, 0]                 # (B, NH) pre-activations
    logf = jax.nn.log_sigmoid(f_t)
    m_new = jnp.maximum(logf + st["m"], i_t)
    i_p = jnp.exp(i_t - m_new)[..., None]           # (B,NH,1)
    f_p = jnp.exp(logf + st["m"] - m_new)[..., None]
    c_new = f_p[..., None] * st["c"] + i_p[..., None] * (
        k[..., :, None] * v[..., None, :])          # (B,NH,dk,dv)
    n_new = f_p * st["n"] + i_p * k
    qn = jnp.abs(jnp.einsum("bhk,bhk->bh", n_new, q))
    h_num = jnp.einsum("bhkv,bhk->bhv", c_new, q)
    # exact stabilized normalization: stored (C, n) carry an implicit
    # exp(-m) factor, so the true max(|q·n_true|, 1) lower bound becomes
    # exp(-m) here — keeps recurrent ≡ chunkwise forms bit-comparable
    h_t = h_num / jnp.maximum(qn, jnp.exp(-m_new))[..., None]   # (B,NH,dv)
    h_t = h_t.reshape(B, inner)
    h_t = (h_t * p["gn"].astype(jnp.float32)) * jax.nn.silu(
        h_gate.astype(jnp.float32))
    y = (h_t.astype(x_t.dtype) @ p["down"])

    # mask invalid rows: state unchanged, output zero
    vb = valid_t[:, None]
    new_st = {
        "c": jnp.where(valid_t[:, None, None, None], c_new, st["c"]),
        "n": jnp.where(valid_t[:, None, None], n_new, st["n"]),
        "m": jnp.where(vb, m_new, st["m"]),
        "conv": jnp.where(valid_t[:, None, None],
                          jnp.concatenate([st["conv"][:, 1:], hx[:, None, :]],
                                          axis=1), st["conv"]),
    }
    return new_st, jnp.where(vb, y, 0.0).astype(x_t.dtype)


# ---------------------------------------------------------------------------
# sLSTM block
# ---------------------------------------------------------------------------
def init_slstm_block(key, cfg: ModelConfig):
    dt = cfg.dtype
    d, NH = cfg.d_model, cfg.num_heads
    dh = d // NH
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    pf = cfg.ssm.slstm_proj_factor if cfg.ssm else 1.334
    dff = int(d * pf)  # speclint: disable=host-sync -- static config arithmetic, not a traced value
    p = {
        "ln": nn.init_rmsnorm(d, dt)[0],
        "w": (jax.random.normal(ks[0], (d, 4 * d)) * s).astype(jnp.float32),
        "r": (jax.random.normal(ks[1], (NH, dh, 4 * dh)) / math.sqrt(dh)
              ).astype(jnp.float32),
        "b": jnp.zeros((4 * d,), jnp.float32),
        "gn": jnp.ones((d,), dt),
        "ffn": nn.init_swiglu(ks[2], d, dff, dt)[0],
        "ln2": nn.init_rmsnorm(d, dt)[0],
    }
    return p


def slstm_axes(prefix):
    return {
        "ln": {"scale": prefix + ("embed",)},
        "w": prefix + ("embed", None),
        "r": prefix + ("heads", "head_dim", None),
        "b": prefix + (None,),
        "gn": prefix + ("embed",),
        "ffn": {"gate": {"w": prefix + ("embed", "mlp")},
                "up": {"w": prefix + ("embed", "mlp")},
                "down": {"w": prefix + ("mlp", "embed")}},
        "ln2": {"scale": prefix + ("embed",)},
    }


def slstm_state0(cfg, batch):
    d = cfg.d_model
    return {
        "c": jnp.zeros((batch, d), jnp.float32),
        "n": jnp.ones((batch, d), jnp.float32),
        "h": jnp.zeros((batch, d), jnp.float32),
        "m": jnp.zeros((batch, d), jnp.float32),
    }


def _slstm_step(p, cfg, st, x_t, valid_t):
    B = x_t.shape[0]
    d, NH = cfg.d_model, cfg.num_heads
    dh = d // NH
    h_in = nn.rmsnorm(p["ln"], x_t[:, None, :], cfg.rms_eps)[:, 0]
    zx = h_in.astype(jnp.float32) @ p["w"]                     # (B, 4d)
    h_prev = st["h"].reshape(B, NH, dh)
    zr = jnp.einsum("bhd,hdf->bhf", h_prev, p["r"]).reshape(B, 4 * d)
    z_all = (zx + zr + p["b"]).reshape(B, 4, d)
    zi, zf, zz, zo = z_all[:, 0], z_all[:, 1], z_all[:, 2], z_all[:, 3]
    logf = jax.nn.log_sigmoid(zf)
    m_new = jnp.maximum(logf + st["m"], zi)
    i_p = jnp.exp(zi - m_new)
    f_p = jnp.exp(logf + st["m"] - m_new)
    c_new = f_p * st["c"] + i_p * jnp.tanh(zz)
    n_new = f_p * st["n"] + i_p
    h_new = jax.nn.sigmoid(zo) * c_new / jnp.maximum(n_new, 1e-6)
    y = (h_new * p["gn"].astype(jnp.float32)).astype(x_t.dtype)

    vb = valid_t[:, None]
    new_st = {
        "c": jnp.where(vb, c_new, st["c"]),
        "n": jnp.where(vb, n_new, st["n"]),
        "h": jnp.where(vb, h_new, st["h"]),
        "m": jnp.where(vb, m_new, st["m"]),
    }
    return new_st, jnp.where(vb, y, 0.0).astype(x_t.dtype)


def _slstm_block(p, cfg, st, x_t, valid_t):
    st, y = _slstm_step(p, cfg, st, x_t, valid_t)
    x = x_t + y
    h2 = nn.rmsnorm(p["ln2"], x[:, None, :], cfg.rms_eps)[:, 0]
    return st, x + nn.swiglu(p["ffn"], h2[:, None, :])[:, 0]


def _mlstm_block(p, cfg, st, x_t, valid_t):
    st, y = _mlstm_step(p, cfg, st, x_t, valid_t)
    return st, x_t + y


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------
def _group_shape(cfg: ModelConfig) -> Tuple[int, int]:
    """(num_groups, mlstm_per_group). slstm_every=k -> groups of (k-1) mLSTM
    + 1 sLSTM; slstm_every=0 -> one group of all-mLSTM, no sLSTM."""
    k = cfg.ssm.slstm_every if cfg.ssm else 0
    if k <= 0:
        return 1, cfg.num_layers
    assert cfg.num_layers % k == 0, (cfg.num_layers, k)
    return cfg.num_layers // k, k - 1


def param_axes(cfg: ModelConfig):
    axes = {
        "embed": ("vocab", "embed"),
        "mlstm": mlstm_axes(("layers", "layers2")),
        "final_norm": {"scale": ("embed",)},
    }
    if cfg.ssm and cfg.ssm.slstm_every > 0:
        axes["slstm"] = slstm_axes(("layers",))
    return axes


def init(key, cfg: ModelConfig):
    dt = cfg.dtype
    G, M = _group_shape(cfg)
    k_emb, k_m, k_s = jax.random.split(key, 3)
    mk = jax.random.split(k_m, G * M).reshape(G, M, 2)
    params = {
        "embed": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model))
                  * 0.02).astype(dt),
        "mlstm": jax.vmap(jax.vmap(partial(init_mlstm_block, cfg=cfg)))(mk),
        "final_norm": nn.init_rmsnorm(cfg.d_model, dt)[0],
    }
    if cfg.ssm and cfg.ssm.slstm_every > 0:
        sk = jax.random.split(k_s, G)
        params["slstm"] = jax.vmap(partial(init_slstm_block, cfg=cfg))(sk)
    return params, param_axes(cfg)


def make_cache(cfg: ModelConfig, batch: int, max_len: int, span: int = 0):
    """``span > 0`` keeps the snapshot ring (``SNAP_SLOTS`` states, what
    rollback restores from)."""
    G, M = _group_shape(cfg)
    zeros_like_stack = lambda st, *lead: jax.tree.map(
        lambda x: jnp.zeros(lead + x.shape, x.dtype), st)
    m0 = mlstm_state0(cfg, batch)
    layers: Dict[str, Any] = {"mlstm": zeros_like_stack(m0, G, M)}
    # reset n to ones equivalent handled in state0 (zeros fine for mLSTM n)
    if cfg.ssm and cfg.ssm.slstm_every > 0:
        s0 = slstm_state0(cfg, batch)
        layers["slstm"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (G,) + x.shape).copy(), s0)
    if span > 0:
        layers["snaps"] = jax.tree.map(
            lambda x: jnp.zeros((SNAP_SLOTS,) + x.shape, x.dtype),
            {k: v for k, v in layers.items() if k != "snaps"})
    axes = jax.tree.map(lambda _: None, layers)
    axes["mlstm"] = {
        "c": (None, None, "batch", "heads", "ssm_dk", None),
        "n": (None, None, "batch", "heads", "ssm_dk"),
        "m": (None, None, "batch", "heads"),
        "conv": (None, None, "batch", None, "ssm_inner"),
    }
    if "slstm" in layers:
        axes["slstm"] = {k: (None, "batch", "embed")
                         for k in ("c", "n", "h", "m")}
    return layers, axes


def _run_tokens(params, cfg, layers, x_seq, valid_seq, ptr=None):
    """Scan over T tokens; inside, scan over layer groups.

    x_seq: (B, T, d); valid_seq: (B, T). Returns (layers, y (B,T,d))."""
    G, M = _group_shape(cfg)
    has_s = "slstm" in layers

    def token_step(lay, inp):
        x_t, valid_t = inp

        def group_step(x_t, g):
            def m_step(x_t, mm):
                st, x_t = _mlstm_block(mm["p"], cfg, mm["st"], x_t, valid_t)
                return x_t, st
            x_t, m_new = jax.lax.scan(
                m_step, x_t, {"p": g["mp"], "st": g["mst"]})
            out = {"mst": m_new}
            if has_s:
                s_new, x_t = _slstm_block(g["sp"], cfg, g["sst"], x_t, valid_t)
                out["sst"] = s_new
            return x_t, out

        gxs = {"mp": params["mlstm"], "mst": lay["mlstm"]}
        if has_s:
            gxs["sp"] = params["slstm"]
            gxs["sst"] = lay["slstm"]
        y_t, new = jax.lax.scan(group_step, x_t, gxs)
        new_lay = dict(lay)
        new_lay["mlstm"] = new["mst"]
        if has_s:
            new_lay["slstm"] = new["sst"]
        return new_lay, y_t

    lay = {k: v for k, v in layers.items() if k != "snaps"}
    x_tb = jnp.swapaxes(x_seq, 0, 1)          # (T, B, d)
    v_tb = jnp.swapaxes(valid_seq, 0, 1)

    # §Perf iteration 1 (EXPERIMENTS.md): chunked-remat time scan for long
    # sequences.  The naive scan saves every per-step (B,NH,dk,dv) matrix
    # state for backward (catastrophic at T=4096); checkpointing per
    # CHUNK_T-step chunk trades one recompute forward for ~CHUNK_T× less
    # saved-residual traffic.
    CHUNK_T = 64
    T = x_tb.shape[0]
    if "snaps" not in layers and T % CHUNK_T == 0 and T >= 2 * CHUNK_T:
        def chunk_step(lay, inp):
            x_c, v_c = inp                     # (CHUNK_T, B, …)
            def inner(lay, xv):
                return token_step(lay, xv)
            lay, y_c = jax.lax.scan(inner, lay, (x_c, v_c))
            return lay, y_c
        chunked = jax.checkpoint(
            chunk_step, policy=jax.checkpoint_policies.nothing_saveable)
        x_ck = x_tb.reshape(T // CHUNK_T, CHUNK_T, *x_tb.shape[1:])
        v_ck = v_tb.reshape(T // CHUNK_T, CHUNK_T, *v_tb.shape[1:])
        lay, y_ck = jax.lax.scan(chunked, lay, (x_ck, v_ck))
        return lay, jnp.swapaxes(y_ck.reshape(T, *y_ck.shape[2:]), 0, 1)

    if "snaps" in layers:
        ptr0 = jnp.int32(0) if ptr is None else ptr.astype(jnp.int32)

        def step_with_snap(carry, inp):
            lay, snaps, p = carry
            lay, y = token_step(lay, inp)
            snaps = jax.tree.map(
                lambda s, cur: kvc.snap_write(s, cur, p),
                snaps, {k: lay[k] for k in snaps})
            return (lay, snaps, p + 1), y
        (lay, snaps, _), y_tb = jax.lax.scan(
            step_with_snap, (lay, layers["snaps"], ptr0), (x_tb, v_tb))
        lay = dict(lay)
        lay["snaps"] = snaps
    else:
        lay, y_tb = jax.lax.scan(token_step, lay, (x_tb, v_tb))
    return lay, jnp.swapaxes(y_tb, 0, 1)


def forward_cached(params, cfg: ModelConfig, state: kvc.ModelState,
                   tokens, valid=None, logits_mode="all", **_ignored):
    B, T = tokens.shape
    if valid is None:
        valid = jnp.ones((B, T), jnp.bool_)
    state, q_pos, slot = kvc.append_tokens(state, tokens, valid)
    x = tf._embed(params, cfg, tokens)
    new_layers, y = _run_tokens(params, cfg, state.layers, x, valid, ptr=slot)
    state = dataclasses.replace(state, layers=new_layers)
    if logits_mode == "none":
        return None, state
    if logits_mode == "last":
        idx = jnp.maximum(jnp.sum(valid, axis=1) - 1, 0)
        y_last = jnp.take_along_axis(
            y, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return tf._unembed(params, cfg, y_last), state
    return tf._unembed(params, cfg, y), state


def _restore_leaf(snap, cur, slots, b_ax):
    """Per-row snapshot gather: out[..., b, ...] = snap[slots[b], ..., b, ...].

    snap: (K,) + cur.shape; batch axis of ``cur`` is ``b_ax``."""
    g = jnp.take(snap, slots, axis=0)          # (B,) + cur.shape
    g = jnp.moveaxis(g, b_ax + 1, 1)           # (B, B, rest...)
    B = cur.shape[b_ax]
    idx = jnp.arange(B)
    diag = g[idx, idx]                         # (B, rest...)
    return jnp.moveaxis(diag, 0, b_ax).astype(cur.dtype)


def rollback_ssm(state: kvc.ModelState, r: jnp.ndarray) -> kvc.ModelState:
    """Restore per-row recurrent state from the snapshot ring (DESIGN §5).

    r: (B,) number of tokens to roll back (suffix of the physical block).
    Snapshot slot (P-1-r[b]) holds row b's state after its last surviving
    token (invalid tokens were per-row no-ops, so slots are row-consistent).
    """
    layers = state.layers
    assert "snaps" in layers, "rollback_ssm requires snapshot-enabled cache"
    P = state.write_ptr
    slots = ((P - 1 - r.astype(jnp.int32)) % SNAP_SLOTS).astype(jnp.int32)

    new = dict(layers)
    new["mlstm"] = jax.tree.map(
        lambda s, c: _restore_leaf(s, c, slots, 2),
        layers["snaps"]["mlstm"], layers["mlstm"])
    if "slstm" in layers:
        new["slstm"] = jax.tree.map(
            lambda s, c: _restore_leaf(s, c, slots, 1),
            layers["snaps"]["slstm"], layers["slstm"])
    return dataclasses.replace(state, layers=new)


# ---------------------------------------------------------------------------
# Chunkwise-parallel mLSTM (§Perf iteration 2 — EXPERIMENTS.md):
# the recurrent form reads+writes the (B,NH,dk,dv) matrix memory EVERY
# time step; the chunkwise form (xLSTM paper App. A) carries state once
# per chunk and computes intra-chunk interactions as (L×L) masked matmuls
# — MXU-friendly and ~chunk× less state traffic.  Train path only; decode
# keeps the exact recurrent step.
# ---------------------------------------------------------------------------
MLSTM_CHUNK = 64


def _mlstm_block_chunkwise(p, cfg, x, chunk: int = MLSTM_CHUNK):
    """x: (B, S, d) -> (B, S, d) block output. All-valid sequences."""
    B, S, d = x.shape
    NH = cfg.num_heads
    inner = _inner(cfg)
    dh = inner // NH
    L = chunk
    NC = S // L
    h = nn.rmsnorm(p["ln"], x, cfg.rms_eps)
    hu = jnp.einsum("bsd,di->bsi", h, p["up"])
    h_gate, hx = jnp.split(hu, 2, axis=-1)
    # causal depthwise conv over 4 taps — shifted multiply-adds instead of
    # materializing a (B,S,4,inner) window stack (§Perf H3)
    pad = jnp.pad(hx, ((0, 0), (3, 0), (0, 0)))
    w_taps = p["conv_w"].astype(hx.dtype)
    acc = pad[:, 0:S] * w_taps[0]
    for i in range(1, 4):
        acc = acc + pad[:, i:i + S] * w_taps[i]
    h_conv = jax.nn.silu(acc.astype(jnp.float32)).astype(hx.dtype)
    q = (jnp.einsum("bsi,ij->bsj", h_conv, p["wq"])
         .reshape(B, S, NH, dh).astype(jnp.float32))
    k = (jnp.einsum("bsi,ij->bsj", h_conv, p["wk"]) / (dh ** 0.5)
         ).reshape(B, S, NH, dh).astype(jnp.float32)
    v = (jnp.einsum("bsi,ij->bsj", hx, p["wv"])
         .reshape(B, S, NH, dh).astype(jnp.float32))
    gates = h_conv.astype(jnp.float32) @ p["w_if"] + p["b_if"]   # (B,S,2NH)
    i_pre, f_pre = jnp.split(gates.reshape(B, S, 2, NH), 2, axis=2)
    i_pre, f_pre = i_pre[:, :, 0], f_pre[:, :, 0]                # (B,S,NH)
    logf = jax.nn.log_sigmoid(f_pre)

    # chunked views: (B, NC, L, ...)
    ck = lambda t: t.reshape(B, NC, L, *t.shape[2:])
    qc, kc, vc = ck(q), ck(k), ck(v)
    ic, fc = ck(i_pre), ck(logf)
    b = jnp.cumsum(fc, axis=2)              # (B,NC,L,NH) intra-chunk decay
    Btot = b[:, :, -1]                      # (B,NC,NH) total chunk decay

    def chunk_step(carry, inp):
        C, n, m = carry                     # (B,NH,dk,dv),(B,NH,dk),(B,NH)
        qj, kj, vj, ij, bj, Bj = inp        # (B,L,NH,·)
        # stabilizer for this chunk
        a_local = bj + ij                   # source weight log, (B,L,NH)
        m_intra = jnp.max(a_local, axis=1)  # over L -> (B,NH)
        m_new = jnp.maximum(m + Bj, m_intra)
        # inter-chunk contribution: q_t · C_prev, scaled exp(b_t + m - m_new)
        scale_t = jnp.exp(bj + m[:, None, :] - m_new[:, None, :])  # (B,L,NH)
        h_inter = jnp.einsum("blhk,bhkv->blhv", qj, C) * scale_t[..., None]
        n_inter = jnp.einsum("blhk,bhk->blh", qj, n) * scale_t
        # intra-chunk: D[t,s] = exp(b_t - b_s + i_s - m_new) for s <= t
        logD = (bj[:, :, None] - bj[:, None, :, :] + ij[:, None]
                - m_new[:, None, None, :])           # (B,L,L,NH)
        mask = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None]
        D = jnp.where(mask, jnp.exp(logD), 0.0)
        scores = jnp.einsum("blhk,bshk->blsh", qj, kj) * D
        h_intra = jnp.einsum("blsh,bshv->blhv", scores, vj)
        n_intra = jnp.sum(scores, axis=2)            # (B,L,NH)
        # combine + normalize
        h_num = h_inter + h_intra
        n_tot = n_inter + n_intra
        denom = jnp.maximum(jnp.abs(n_tot), jnp.exp(-m_new[:, None, :]))
        h_out = h_num / denom[..., None]             # (B,L,NH,dv)
        # state update to end of chunk:
        # C_new = exp(B_j + m - m_new) C + Σ_s exp(B_j - b_s + i_s - m_new) k v
        w_s = jnp.exp(Bj[:, None, :] - bj + ij - m_new[:, None, :])  # (B,L,NH)
        C_new = (jnp.exp(Bj + m - m_new)[..., None, None] * C
                 + jnp.einsum("blhk,blhv->bhkv", kj * w_s[..., None], vj))
        n_new = (jnp.exp(Bj + m - m_new)[..., None] * n
                 + jnp.sum(kj * w_s[..., None], axis=1))
        return (C_new, n_new, m_new), h_out

    C0 = jnp.zeros((B, NH, dh, dh), jnp.float32)
    n0 = jnp.zeros((B, NH, dh), jnp.float32)
    m0 = jnp.full((B, NH), -1e30, jnp.float32)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (qc, kc, vc, ic, b, Btot))
    _, h_all = jax.lax.scan(chunk_step, (C0, n0, m0), xs)
    h_all = jnp.moveaxis(h_all, 0, 1).reshape(B, S, NH, dh)
    h_flat = h_all.reshape(B, S, inner)
    h_flat = (h_flat * p["gn"].astype(jnp.float32)) * jax.nn.silu(
        h_gate.astype(jnp.float32))
    return x + (h_flat.astype(x.dtype) @ p["down"])


def forward_train(params, cfg: ModelConfig, tokens, remat=True,
                  chunkwise: bool = True, **_ignored):
    B, S = tokens.shape
    x = tf._embed(params, cfg, tokens)
    if chunkwise and S % MLSTM_CHUNK == 0 and S >= MLSTM_CHUNK \
            and (cfg.ssm is None or cfg.ssm.slstm_every == 0
                 or True):
        # chunkwise mLSTM; sLSTM blocks (strictly sequential by design)
        # keep the recurrent step but are a small minority of layers
        G, M = _group_shape(cfg)
        has_s = "slstm" in params

        def group_step(x, g):
            def m_step(x, mp):
                return _mlstm_block_chunkwise(mp, cfg, x), None
            x, _ = jax.lax.scan(m_step, x, g["mp"])
            if has_s:
                st = slstm_state0(cfg, B)
                def s_tok(carry, x_t):
                    st, = carry
                    st, y = _slstm_block(g["sp"], cfg, st, x_t,
                                         jnp.ones((B,), jnp.bool_))
                    return (st,), y
                def s_chunk(carry, x_c):
                    return jax.lax.scan(s_tok, carry, x_c)
                chunks = jnp.swapaxes(x, 0, 1).reshape(
                    S // MLSTM_CHUNK, MLSTM_CHUNK, B, -1)
                _, y = jax.lax.scan(
                    jax.checkpoint(s_chunk,
                                   policy=jax.checkpoint_policies
                                   .nothing_saveable),
                    (st,), chunks)
                x = jnp.swapaxes(y.reshape(S, B, -1), 0, 1)
            return x, None

        gxs = {"mp": params["mlstm"]}
        if has_s:
            gxs["sp"] = params["slstm"]
        fn = jax.checkpoint(group_step,
                            policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(fn, x, gxs)
        return tf._unembed(params, cfg, x)
    layers, _ = make_cache(cfg, B, 0)
    valid = jnp.ones((B, S), jnp.bool_)
    _, y = _run_tokens(params, cfg, layers, x, valid)
    return tf._unembed(params, cfg, y)


restore = rollback_ssm
