"""Qwen3-Next: Gated DeltaNet and gated full attention in a 3:1 pattern,
with a sparse-expert FFN after every token mixer.

Layer ``i`` is full attention where ``(i + 1) % full_attention_interval ==
0`` and Gated DeltaNet (GDN) otherwise; every layer's FFN is the held-expert
layer of ``moe.held_expert_ffn`` (router over all experts, this chip's
experts computed, a sigmoid-gated shared expert).

* **Gated attention**: ``q_proj`` gives ``[q_h | gate_h]`` per head; q and k
  get a per-head RMSNorm; RoPE rotates the first ``partial_rotary`` share of
  each head (rotate-half); the output is ``attn * sigmoid(gate)``.  The KV
  cache is the paged attention cache of ``transformer._cached_attention``.
* **Gated DeltaNet**: ``in_proj_qkvz`` per key head ``[q | k | v (r heads) |
  z (r heads)]`` and ``in_proj_ba`` per key head ``[b (r) | a (r)]`` with
  ``r = num_value_heads / num_key_heads``; a causal depthwise conv (no bias)
  + SiLU over the q‖k‖v channels; L2-normed q, k (q scaled by dk^-1/2), each
  key head serving ``r`` value heads; ``beta = sigmoid(b)``, ``g =
  -exp(A_log) * softplus(a + dt_bias)``; per value head the delta rule
  ``S <- S exp(g); S <- S + k (beta (v - S^T k))^T; o = S^T q`` on a float32
  state; then ``w * rmsnorm(o) * silu(z)`` and ``out_proj``.

Parameters are stacked per kind (GDN, attention, FFN) and one ``lax.scan``
runs over the periods of ``full_attention_interval`` layers, carrying the
KV pool and the recurrent leaves and updating each layer's slice in place.

Blocks of at most ``STEP_MAX`` positions (decode and verify) run the
delta rule token by token; wider ones (prefill, admission) run its chunked
WY form (``CHUNK`` positions).  Invalid positions of a block (the left pads
of the serving path, the right pads of a prefill) are no-ops: each row's
valid positions are compacted to the front, and the rest get ``g = 0``,
``beta = 0`` and no conv shift.

Rollback: a state made with ``span > 0`` keeps, per row, an *anchor*
(conv window and delta state) and the per-position inputs of the delta
rule for the positions appended since (``span_*``, at most ``span``).
``restore`` replays the kept positions from the anchor.  A forward moves a
row's anchor to its current state (one select, under ``lax.cond``) where
the row appends with nothing pending (``span_n == 0``: after admission or a
rollback) or would overflow the span; other rows keep their anchors and
pending positions.  Admission (``logits_mode='last'``) keeps nothing (it
is never rolled back), and any other block must fit the span.  So a
rollback is exact where it reaches no further back than the row's anchor:
in the serving path every member of a chain is rolled back each cycle
(which empties its kept positions), and a member that decodes without a
rollback (target-only cycles) runs one forward a cycle, so an anchor that
overflow moves is at a committed point.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import kv_cache as kvc
from . import layers as nn
from . import moe
from . import transformer as tf
from .config import ModelConfig

STEP_MAX = 32        # widest block run token by token
CHUNK = 64           # chunk of the WY form
L2_EPS = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Shapes
# ---------------------------------------------------------------------------
def _layout(cfg: ModelConfig):
    """(periods, GDN layers per period, total GDN layers, total attention
    layers)."""
    k = cfg.linear_attn.full_attention_interval
    assert cfg.num_layers % k == 0, (cfg.num_layers, k)
    n = cfg.num_layers // k
    return n, k - 1, n * (k - 1), n


def _dims(cfg: ModelConfig):
    la = cfg.linear_attn
    hk, hv = la.num_key_heads, la.num_value_heads
    dk, dv = la.key_head_dim, la.value_head_dim
    return hk, hv, dk, dv, hv // hk, 2 * hk * dk + hv * dv


# ---------------------------------------------------------------------------
# Init and axes
# ---------------------------------------------------------------------------
def param_axes(cfg: ModelConfig):
    L = ("layers",)
    m = cfg.moe
    ax = {
        "embed": ("vocab", "embed"),
        "lm_head": {"w": ("embed", "vocab")},
        "final_norm": {"scale": ("embed",)},
        "gdn": {
            "ln": {"scale": L + ("embed",)},
            "qkvz": {"w": L + ("embed", None)},
            "ba": {"w": L + ("embed", None)},
            "conv": L + ("conv", None),
            "A_log": L + (None,),
            "dt_bias": L + (None,),
            "norm": {"scale": L + ("head_dim",)},
            "out": {"w": L + (None, "embed")},
        },
        "attn": {
            "ln": {"scale": L + ("embed",)},
            "q": {"w": L + ("embed", "heads")},
            "k": {"w": L + ("embed", "kv_heads")},
            "v": {"w": L + ("embed", "kv_heads")},
            "o": {"w": L + ("heads", "embed")},
            "q_norm": {"scale": L + ("head_dim",)},
            "k_norm": {"scale": L + ("head_dim",)},
        },
        "moe": {
            "ln": {"scale": L + ("embed",)},
            "router": L + ("embed", None),
            "w_gate": L + ("experts", "embed", "expert_mlp"),
            "w_up": L + ("experts", "embed", "expert_mlp"),
            "w_down": L + ("experts", "expert_mlp", "embed"),
        },
    }
    if m.num_shared_experts > 0:
        ax["moe"]["shared"] = {"gate": {"w": L + ("embed", "mlp")},
                               "up": {"w": L + ("embed", "mlp")},
                               "down": {"w": L + ("mlp", "embed")}}
        ax["moe"]["shared_gate"] = L + ("embed", None)
    return ax


def init(key, cfg: ModelConfig):
    """Random parameters in the program's layout (norm scales at their
    identity, 1: every norm scales by its stored weight)."""
    dt = cfg.dtype
    m = cfg.moe
    _, _, Lg, La = _layout(cfg)
    L, d, V = cfg.num_layers, cfg.d_model, cfg.vocab_size
    hk, hv, dk, dv, r, cc = _dims(cfg)
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    E, F = m.num_experts, m.d_expert
    Fs = max(m.d_shared, m.d_expert) * m.num_shared_experts
    ks = iter(jax.random.split(key, 24))

    def w(shape, fan_in):
        return (jax.random.normal(next(ks), shape)
                / math.sqrt(fan_in)).astype(dt)

    def norm(*shape):
        return {"scale": jnp.ones(shape, dt)}

    p = {
        "embed": (jax.random.normal(next(ks), (V, d)) * 0.02).astype(dt),
        "lm_head": {"w": w((d, V), d)},
        "final_norm": norm(d),
        "gdn": {
            "ln": norm(Lg, d),
            "qkvz": {"w": w((Lg, d, cc + hv * dv), d)},
            "ba": {"w": w((Lg, d, 2 * hv), d)},
            "conv": w((Lg, cfg.linear_attn.conv_kernel, cc),
                      cfg.linear_attn.conv_kernel),
            "A_log": jnp.log(jax.random.uniform(
                next(ks), (Lg, hv), minval=1.0, maxval=16.0)).astype(dt),
            "dt_bias": jnp.ones((Lg, hv), dt),
            "norm": {"scale": jnp.ones((Lg, dv), dt)},
            "out": {"w": w((Lg, hv * dv, d), hv * dv)},
        },
        "attn": {
            "ln": norm(La, d),
            "q": {"w": w((La, d, 2 * H * hd), d)},
            "k": {"w": w((La, d, Hkv * hd), d)},
            "v": {"w": w((La, d, Hkv * hd), d)},
            "o": {"w": w((La, H * hd, d), H * hd)},
            "q_norm": norm(La, hd),
            "k_norm": norm(La, hd),
        },
        "moe": {
            "ln": norm(L, d),
            "router": w((L, d, E), d),
            "w_gate": w((L, m.num_held, d, F), d),
            "w_up": w((L, m.num_held, d, F), d),
            "w_down": w((L, m.num_held, F, d), F),
        },
    }
    if m.num_shared_experts > 0:
        p["moe"]["shared"] = {"gate": {"w": w((L, d, Fs), d)},
                              "up": {"w": w((L, d, Fs), d)},
                              "down": {"w": w((L, Fs, d), Fs)}}
        p["moe"]["shared_gate"] = w((L, d, 1), d)
    return p, param_axes(cfg)


# ---------------------------------------------------------------------------
# Recurrent state
# ---------------------------------------------------------------------------
def _recurrent_leaves(cfg: ModelConfig, batch: int, span: int):
    _, _, Lg, _ = _layout(cfg)
    hk, hv, dk, dv, _, cc = _dims(cfg)
    kc = cfg.linear_attn.conv_kernel
    f32 = jnp.float32
    leaves = {"conv": jnp.zeros((Lg, batch, kc - 1, cc), cfg.dtype),
              "delta": jnp.zeros((Lg, batch, hv, dk, dv), f32)}
    B = ("layers", "batch")
    axes = {"conv": B + (None, None), "delta": B + (None, None, None)}
    if span > 0:
        leaves.update(
            conv0=jnp.zeros_like(leaves["conv"]),
            delta0=jnp.zeros_like(leaves["delta"]),
            span_x=jnp.zeros((Lg, batch, span, cc), cfg.dtype),
            span_k=jnp.zeros((Lg, batch, span, hk, dk), f32),
            span_v=jnp.zeros((Lg, batch, span, hv, dv), f32),
            span_gb=jnp.zeros((Lg, batch, span, 2, hv), f32),
            span_n=jnp.zeros((batch,), jnp.int32))
        axes.update(conv0=axes["conv"], delta0=axes["delta"],
                    span_x=B + (None, None), span_k=B + (None,) * 3,
                    span_v=B + (None,) * 3, span_gb=B + (None,) * 3,
                    span_n=("batch",))
    return leaves, axes


def make_cache(cfg: ModelConfig, batch: int, max_len: int, span: int = 0):
    """Contiguous attention KV for the full-attention layers plus the
    GDN leaves; ``span > 0`` keeps what rollback replays for blocks of up
    to ``span`` positions."""
    _, _, _, La = _layout(cfg)
    layers = kvc.make_attn_cache(La, batch, max_len, cfg.num_kv_heads,
                                 cfg.head_dim, cfg.dtype)
    axes = kvc.attn_cache_axes()
    rec, rax = _recurrent_leaves(cfg, batch, span)
    return {**layers, **rec}, {**axes, **rax}


def make_paged_cache(cfg: ModelConfig, batch: int, max_len: int,
                     block_size: int = kvc.PAGE_BLOCK,
                     pool_blocks: Optional[int] = None, span: int = 0):
    """Pool-shaped KV for the attention layers; the GDN leaves are per-row
    leaves of the same paged state (wiped with the row on retirement)."""
    _, _, _, La = _layout(cfg)
    R = kvc._ceil_div(max_len, block_size)
    P = pool_blocks if pool_blocks is not None else batch * R
    layers = kvc.make_paged_attn_cache(La, P, block_size, cfg.num_kv_heads,
                                       cfg.head_dim, cfg.dtype)
    axes = kvc.paged_attn_cache_axes()
    rec, rax = _recurrent_leaves(cfg, batch, span)
    return {**layers, **rec}, {**axes, **rax}


# ---------------------------------------------------------------------------
# Gated delta rule: per token, and chunked (WY form)
# ---------------------------------------------------------------------------
def _delta_update(S, k, v, g, beta):
    """One position's state update for every row and head.  S (B, H, dk,
    dv) f32; k (B, H, dk); v (B, H, dv); g, beta (B, H).  g = beta = 0
    leaves S as it was."""
    S = S * jnp.exp(g)[..., None, None]
    kv = jnp.sum(S * k[..., :, None], axis=-2)
    return S + k[..., :, None] * ((v - kv) * beta[..., None])[..., None, :]


def _delta_step(S, q, k, v, g, beta):
    S = _delta_update(S, k, v, g, beta)
    return S, jnp.sum(S * q[..., :, None], axis=-2)


def delta_recurrent(q, k, v, g, beta, S, steps=None):
    """Token by token over T.  q, k (B, T, H, dk) (q already scaled);
    v (B, T, H, dv); g, beta (B, T, H); S (B, H, dk, dv) f32.  With
    ``steps`` (a traced count) only the first ``steps`` positions run,
    and the outputs after them read 0: a decode block whose pads are
    compacted to its end pays for its valid positions only.
    Returns (o (B, T, H, dv), S)."""
    if steps is None:
        def step(S, xs):
            return _delta_step(S, *xs)
        xs = tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v, g, beta))
        S, o = jax.lax.scan(step, S, xs)
        return jnp.swapaxes(o, 0, 1), S

    def body(t, carry):
        S, o = carry
        S, o_t = _delta_step(S, q[:, t], k[:, t], v[:, t], g[:, t],
                             beta[:, t])
        return S, jax.lax.dynamic_update_index_in_dim(o, o_t, t, axis=1)
    S, o = jax.lax.fori_loop(0, steps, body, (S, jnp.zeros(v.shape, S.dtype)))
    return o, S


def delta_chunked(q, k, v, g, beta, S, chunk: int = CHUNK):
    """The same as ``delta_recurrent`` in the chunked WY form: within a
    chunk the delta rule's updates are solved as one unit-lower-triangular
    system, and the state crosses chunk boundaries once.  The decay
    between two positions is the sum of ``g`` over the segment between
    them, summed over that segment alone: a difference of the chunk's
    running sums would carry the rounding of their size (|g| reaches ~20
    a position), ten times the recurrence's error."""
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    pad = (-T) % chunk
    n = (T + pad) // chunk

    def blocks(a):              # (B, T, H, ...) -> (n, B, H, C, ...)
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        a = a.reshape((B, n, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 3)
    q, k, v, g, beta = (blocks(a) for a in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                             # (n,B,H,C)
    pos = jnp.arange(chunk)
    # tail[j, i]: the sum of g over (j, i]
    tail = jnp.cumsum(jnp.where(pos[None, :] > pos[:, None],
                                g[..., None, :], 0.0), axis=-1)
    low = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(low, jnp.exp(jnp.swapaxes(tail, -1, -2)), 0.0)
    kb = k * beta[..., None]
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    A = jnp.where(strict, jnp.einsum("...ik,...jk->...ij", kb, k,
                                     precision=HIGHEST) * decay, 0.0)
    eye = jnp.eye(chunk, dtype=jnp.float32)
    Tm = jax.lax.linalg.triangular_solve(
        eye + A, jnp.broadcast_to(eye, A.shape), left_side=True,
        lower=True, unit_diagonal=True)
    u = jnp.einsum("...ij,...jd->...id", Tm, v * beta[..., None],
                   precision=HIGHEST)
    w = jnp.einsum("...ij,...jd->...id", Tm, kb * jnp.exp(gc)[..., None],
                   precision=HIGHEST)

    def step(S, xs):
        q_i, k_i, u_i, w_i, gc_i, dec_i, end_i = xs
        att = jnp.einsum("...ik,...jk->...ij", q_i, k_i,
                         precision=HIGHEST) * dec_i
        v_new = u_i - jnp.einsum("...ik,...kd->...id", w_i, S,
                                 precision=HIGHEST)
        o = jnp.einsum("...ik,...kd->...id", q_i * jnp.exp(gc_i)[..., None],
                       S, precision=HIGHEST) \
            + jnp.einsum("...ij,...jd->...id", att, v_new, precision=HIGHEST)
        S = S * jnp.exp(gc_i[..., -1:])[..., None] + jnp.einsum(
            "...ik,...id->...kd", k_i * jnp.exp(end_i)[..., None],
            v_new, precision=HIGHEST)
        return S, o
    S, o = jax.lax.scan(step, S, (q, k, u, w, gc, decay, tail[..., -1]))
    o = jnp.moveaxis(o, 3, 2)                               # (n,B,C,H,dv)
    o = jnp.moveaxis(o, 0, 1).reshape(B, n * chunk, H, dv)
    return o[:, :T], S


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


# ---------------------------------------------------------------------------
# Token mixers and the FFN
# ---------------------------------------------------------------------------
def _proj(p, h):
    """``nn.linear``, its output fenced off: the head-wise slices that
    follow would otherwise make XLA relayout the weight (a copy of the
    whole stack per step on the TPU)."""
    return jax.lax.optimization_barrier(nn.linear(p, h))


def _conv(win, xc, w, n_valid):
    """Causal depthwise conv over [window ++ compacted block] + SiLU.
    win (B, K-1, C); xc (B, T, C); w (K, C).  Returns (y (B, T, C) f32,
    the window after each row's ``n_valid`` positions)."""
    K = w.shape[0]
    T = xc.shape[1]
    full = jnp.concatenate([win, xc.astype(win.dtype)], axis=1)
    f = full.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    y = sum(f[:, j:j + T] * wf[j] for j in range(K))
    idx = n_valid[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    return jax.nn.silu(y), jnp.take_along_axis(full, idx[..., None], axis=1)


def _gates(p, b, a):
    """beta = sigmoid(b), g = -exp(A_log) * softplus(a + dt_bias), f32."""
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    return g, beta


def _gdn(p, cfg: ModelConfig, h, rec, l, blk):
    """One GDN mixer on normed input h (B, T, d); reads and writes layer
    ``l`` of the recurrent leaves ``rec`` in place.  ``blk`` holds the
    block's compaction (``order``, ``inv``, ``n_valid``, ``jvalid``), the
    span write offsets (``base``, or None) and ``stepwise``."""
    hk, hv, dk, dv, r, cc = _dims(cfg)
    B, T, _ = h.shape
    with jax.named_scope("gdn"):
        qkvz = _proj(p["qkvz"], h).reshape(B, T, hk, 2 * dk + 2 * r * dv)
        z = qkvz[..., 2 * dk + r * dv:].reshape(B, T, hv, dv)
        xin = jnp.concatenate(
            [qkvz[..., :dk].reshape(B, T, hk * dk),
             qkvz[..., dk:2 * dk].reshape(B, T, hk * dk),
             qkvz[..., 2 * dk:2 * dk + r * dv].reshape(B, T, hv * dv)], -1)
        ba = _proj(p["ba"], h).reshape(B, T, hk, 2 * r)
        ba = jnp.concatenate([ba[..., :r].reshape(B, T, hv),
                              ba[..., r:].reshape(B, T, hv)], -1)
        order = blk["order"][..., None]
        xc = jnp.take_along_axis(xin, order, axis=1)
        bac = jnp.take_along_axis(ba, order, axis=1)
        with jax.named_scope("gdn.conv"):
            y, win = _conv(rec["conv"][l], xc, p["conv"], blk["n_valid"])
        kq = hk * dk
        q = _l2norm(y[..., :kq].reshape(B, T, hk, dk)) * (dk ** -0.5)
        k = _l2norm(y[..., kq:2 * kq].reshape(B, T, hk, dk))
        v = y[..., 2 * kq:].reshape(B, T, hv, dv)
        g, beta = _gates(p, bac[..., :hv], bac[..., hv:])
        jv = blk["jvalid"][..., None]
        g, beta = jnp.where(jv, g, 0.0), jnp.where(jv, beta, 0.0)
        with jax.named_scope("gdn.delta"):
            args = (jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v,
                    g, beta, rec["delta"][l])
            if blk["stepwise"]:
                o, S = delta_recurrent(*args,
                                       steps=jnp.max(blk["n_valid"]))
            else:
                o, S = delta_chunked(*args)
        rec = dict(rec, conv=rec["conv"].at[l].set(win),
                   delta=rec["delta"].at[l].set(S))
        if blk["base"] is not None:        # keep what rollback replays
            col = jnp.where(blk["jvalid"],
                            blk["base"][:, None] + jnp.arange(T)[None, :],
                            rec["span_x"].shape[2])
            rows = jnp.arange(B)[:, None]
            for name, val in (("span_x", xc), ("span_k", k), ("span_v", v),
                              ("span_gb", jnp.stack([g, beta], 2))):
                rec[name] = rec[name].at[l, rows, col].set(
                    val.astype(rec[name].dtype), mode="drop")
        o = jnp.take_along_axis(o, blk["inv"][..., None, None], axis=1)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.rms_eps)
        o = o * p["norm"]["scale"].astype(jnp.float32) \
            * jax.nn.silu(z.astype(jnp.float32))
        return nn.linear(p["out"], o.reshape(B, T, hv * dv).astype(h.dtype)), \
            rec


def _partial_rope(x, positions, theta, rot: int):
    return jnp.concatenate(
        [tf._rope_traced(x[..., :rot], positions, theta, rot), x[..., rot:]],
        axis=-1)


def _attention(p, cfg: ModelConfig, h, attend, q_pos):
    """Gated attention on normed input h; ``attend(q, k, v) -> (o, kv)``."""
    B, T, _ = h.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qg = _proj(p["q"], h).reshape(B, T, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = _proj(p["k"], h).reshape(B, T, Hkv, hd)
    v = _proj(p["v"], h).reshape(B, T, Hkv, hd)
    q = nn.rmsnorm(p["q_norm"], q, cfg.rms_eps)
    k = nn.rmsnorm(p["k_norm"], k, cfg.rms_eps)
    rot = int(hd * cfg.partial_rotary)
    theta = jnp.float32(cfg.rope_theta)
    q = _partial_rope(q, q_pos, theta, rot)
    k = _partial_rope(k, q_pos, theta, rot)
    o, kv = attend(q, k, v)
    o = (o.astype(jnp.float32)
         * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype)
    return nn.linear(p["o"], o.reshape(B, T, H * hd)), kv


def _ffn(p, cfg: ModelConfig, x):
    with jax.named_scope("moe"):
        h = nn.rmsnorm(p["ln"], x, cfg.rms_eps)
        return moe.held_expert_ffn(p, cfg, h)


def _unembed(params, cfg: ModelConfig, x):
    x = nn.rmsnorm(params["final_norm"], x, cfg.rms_eps)
    return nn.linear(params["lm_head"], x).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Cached forward
# ---------------------------------------------------------------------------
def _refresh_anchor(lay, valid):
    """Move the anchor of each appending row that has nothing pending or
    would overflow the span to its current state; returns (leaves, base),
    ``base`` each row's kept positions before this block."""
    K = lay["span_x"].shape[2]
    n_valid = jnp.sum(valid, axis=1, dtype=jnp.int32)
    span_n = lay["span_n"]
    need = (n_valid > 0) & ((span_n == 0) | (span_n + n_valid > K))

    def fresh(c, d, c0, d0):
        row = lambda a: need.reshape((1, -1) + (1,) * (a.ndim - 2))
        return jnp.where(row(c), c, c0), jnp.where(row(d), d, d0)

    c0, d0 = jax.lax.cond(jnp.any(need), fresh, lambda c, d, c0, d0: (c0, d0),
                          lay["conv"], lay["delta"], lay["conv0"],
                          lay["delta0"])
    return dict(lay, conv0=c0, delta0=d0), jnp.where(need, 0, span_n)


def forward_cached(params, cfg: ModelConfig, state, tokens,
                   valid: Optional[jnp.ndarray] = None,
                   logits_mode: str = "all", **_ignored):
    """Append T tokens, run all layers, return (logits, new_state).

    logits_mode: 'all' -> (B, T, V); 'last' -> (B, V) at each row's last
    valid position; 'none' -> None."""
    B, T = tokens.shape
    if valid is None:
        valid = jnp.ones((B, T), jnp.bool_)
    state, q_pos, slot = kvc.append_tokens(state, tokens, valid)
    paged = isinstance(state, kvc.PagedModelState)
    mask = nn.build_attention_mask(state.mask, state.pos_buf, q_pos, window=0)
    paged_idx = ((kvc.physical_slots(state, slot),
                  kvc.physical_view_index(state)) if paged else None)
    lay = dict(state.layers)
    n_valid = jnp.sum(valid, axis=1, dtype=jnp.int32)
    order = jnp.argsort((~valid).astype(jnp.int32), axis=1,
                        stable=True).astype(jnp.int32)
    # admission ('last': prefill, insert) is never rolled back; any other
    # block keeps its inputs, so it has to fit the span
    kept = "span_x" in lay and logits_mode != "last"
    base = None
    if kept:
        assert T <= lay["span_x"].shape[2], (
            f"a {T}-wide block on a state that keeps "
            f"{lay['span_x'].shape[2]} positions for rollback")
        lay, base = _refresh_anchor(lay, valid)
    blk = {"order": order, "inv": jnp.argsort(order, axis=1),
           "n_valid": n_valid, "stepwise": T <= STEP_MAX, "base": base,
           "jvalid": jnp.arange(T)[None, :] < n_valid[:, None]}

    n_per, G, _, _ = _layout(cfg)
    kv = {n: lay[n] for n in ("k", "v")}
    rec_names = [n for n in ("conv", "delta", "span_x", "span_k", "span_v",
                             "span_gb") if n in lay]
    rec = {n: lay[n] for n in rec_names}

    def layer(tree, i):
        # one layer of a stack, sliced inside the loop where its products
        # read it (a static slice of a per-period block would be copied)
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            tree)

    def body(carry, s):
        x, kv, rec = carry
        p = s["period"]
        for j in range(G):
            pl = layer(params["gdn"], p * G + j)
            h = nn.rmsnorm(pl["ln"], x, cfg.rms_eps)
            y, rec = _gdn(pl, cfg, h, rec, p * G + j, blk)
            x = x + y
            x = x + _ffn(layer(params["moe"], p * (G + 1) + j), cfg, x)
        pa = s["attn"]
        h = nn.rmsnorm(pa["ln"], x, cfg.rms_eps)
        attend = partial(tf._cached_attention, cfg, kv, p,
                         mask=mask, write_slot=None if paged else slot,
                         paged_idx=paged_idx)
        y, kv = _attention(pa, cfg, h, attend, q_pos)
        x = x + y
        x = x + _ffn(layer(params["moe"], p * (G + 1) + G), cfg, x)
        return (x, kv, rec), None

    xs = {"attn": params["attn"],
          "period": jnp.arange(n_per, dtype=jnp.int32)}
    x = params["embed"][tokens]
    (x, kv, rec), _ = jax.lax.scan(body, (x, kv, rec), xs)
    lay.update(kv)
    lay.update(rec)
    if "span_n" in lay:
        lay["span_n"] = (base + n_valid if kept else
                         jnp.where(n_valid > 0, 0, lay["span_n"]))
    state = dataclasses.replace(state, layers=lay)

    if logits_mode == "none":
        return None, state
    if logits_mode == "last":
        idx = jnp.maximum(n_valid - 1, 0)
        x_last = jnp.take_along_axis(
            x, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return _unembed(params, cfg, x_last), state
    return _unembed(params, cfg, x), state


# ---------------------------------------------------------------------------
# Rollback: replay the kept positions from the anchor
# ---------------------------------------------------------------------------
def restore(state, r: jnp.ndarray):
    """Per row, the recurrent state after all but the last ``r[b]``
    positions appended since the anchor: the anchor's conv window and
    delta state, replayed through the kept per-position inputs.  Rows with
    ``r == 0`` keep their state; with no row rolled back nothing runs.
    The next forward moves the anchor (``span_n`` reads 0).  A state made
    without a span cannot be rolled back."""
    lay = state.layers
    if "span_x" not in lay:
        raise ValueError("rollback of a Qwen3-Next state made without a "
                         "rollback span (make_state(rollback_span=...))")
    r = r.astype(jnp.int32)
    n_new = jnp.maximum(lay["span_n"] - r, 0)
    back = r > 0

    def replay(conv, delta, conv0, delta0, sx, sk, sv, sgb):
        kc1 = conv.shape[2]
        rep = delta.shape[2] // sk.shape[3]        # value heads per key head

        def layer(_, xs):
            c, d, c0, d0, x_l, k_l, v_l, gb_l = xs
            full = jnp.concatenate([c0, x_l], axis=1)
            idx = n_new[:, None] + jnp.arange(kc1, dtype=jnp.int32)[None, :]
            win = jnp.take_along_axis(full, idx[..., None], axis=1)
            k_l = jnp.repeat(k_l, rep, axis=2)

            def step(j, S):
                on = (j < n_new)[:, None]
                g = jnp.where(on, gb_l[:, j, 0], 0.0)
                beta = jnp.where(on, gb_l[:, j, 1], 0.0)
                return _delta_update(S, k_l[:, j], v_l[:, j], g, beta)
            S = jax.lax.fori_loop(0, jnp.max(n_new), step, d0)
            return None, (jnp.where(back[:, None, None], win, c),
                          jnp.where(back[:, None, None, None], S, d))
        _, (conv, delta) = jax.lax.scan(
            layer, None, (conv, delta, conv0, delta0, sx, sk, sv, sgb))
        return conv, delta

    with jax.named_scope("gdn_restore"):
        conv, delta = jax.lax.cond(
            jnp.any(back), replay, lambda c, d, *_: (c, d),
            lay["conv"], lay["delta"], lay["conv0"], lay["delta0"],
            lay["span_x"], lay["span_k"], lay["span_v"], lay["span_gb"])
    return dataclasses.replace(state, layers=dict(
        lay, conv=conv, delta=delta, span_n=jnp.zeros_like(lay["span_n"])))
