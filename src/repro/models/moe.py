"""Mixture-of-Experts decoder (kimi-k2-1t, olmoe-1b-7b).

Token-choice top-k routing with per-expert capacity.  Dispatch uses the
"top-C tokens per expert" gather (an O(E·C·D) dense-gather formulation that
shards cleanly: tokens over the data axis, experts over the model axis, so
XLA inserts the all-to-all the paper's MoE baselines rely on).  Tokens beyond
capacity are dropped (standard capacity-factor semantics; the drop rate is
what the aux load-balance loss drives down).
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
from ..sharding import context_mesh


# ---------------------------------------------------------------------------
# MoE FFN
# ---------------------------------------------------------------------------
def init_moe_ffn(key, cfg: ModelConfig):
    m = cfg.moe
    dt = cfg.dtype
    d, E, F = cfg.d_model, m.num_experts, m.d_expert
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    s = 1.0 / math.sqrt(d)
    p = {
        "router": (jax.random.normal(kr, (d, E)) * s).astype(jnp.float32),
        "w_gate": (jax.random.normal(kg, (E, d, F)) * s).astype(dt),
        "w_up": (jax.random.normal(ku, (E, d, F)) * s).astype(dt),
        "w_down": (jax.random.normal(kd, (E, F, d)) / math.sqrt(F)).astype(dt),
    }
    if m.num_shared_experts > 0:
        ds = max(m.d_shared, m.d_expert) * m.num_shared_experts
        p["shared"], _ = nn.init_swiglu(ks, d, ds, dt)
    return p


def moe_ffn_axes(cfg: ModelConfig, prefix=("layers",)):
    ax = {
        "router": prefix + ("embed", "experts"),
        "w_gate": prefix + ("experts", "embed", "expert_mlp"),
        "w_up": prefix + ("experts", "embed", "expert_mlp"),
        "w_down": prefix + ("experts", "expert_mlp", "embed"),
    }
    if cfg.moe.num_shared_experts > 0:
        ax["shared"] = {
            "gate": {"w": prefix + ("embed", "mlp")},
            "up": {"w": prefix + ("embed", "mlp")},
            "down": {"w": prefix + ("mlp", "embed")},
        }
    return ax


def _maybe_constrain(x, spec):
    """with_sharding_constraint when a ('data','model') mesh is in context
    (dry-run / pod execution); no-op on the bare CPU test path."""
    if _ep_mesh() is not None:
        return jax.lax.with_sharding_constraint(x, spec)
    return x


def capacity(cfg: ModelConfig, num_tokens: int) -> int:
    m = cfg.moe
    c = math.ceil(num_tokens * m.top_k / m.num_experts * m.capacity_factor)
    return max(1, min(c, num_tokens))


def moe_ffn(p, cfg: ModelConfig, x: jnp.ndarray):
    """x: (B, T, D) -> (y, aux_loss)."""
    m = cfg.moe
    B, T, D = x.shape
    N = B * T
    E, K = m.num_experts, m.top_k
    C = capacity(cfg, N)
    xf = x.reshape(N, D)

    logits = (xf.astype(jnp.float32) @ p["router"])          # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, K)               # (N, K)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    # dense (N, E) gate matrix — zero outside top-k
    gate = jnp.sum(jax.nn.one_hot(top_idx, E, dtype=jnp.float32)
                   * top_vals[..., None], axis=1)             # (N, E)

    # per-expert top-C token selection among tokens that chose it
    score = jnp.where(gate > 0, probs, -1.0)                  # (N, E)
    sel_score, sel_idx = jax.lax.top_k(score.T, C)            # (E, C)
    sel_valid = sel_score > 0
    # §Perf K1 (EXPERIMENTS.md): dispatch payloads stay in the model dtype
    # (bf16) — the gathered (E,C,D) tensors cross chips; fp32 would double
    # the all-to-all/all-reduce bytes for zero quality gain (expert matmuls
    # accumulate in fp32 on the MXU regardless).
    # §Perf K3: pin the dispatch layout — experts over the model axis,
    # capacity over the data axis — so the token exchange lowers to the
    # minimal (E,C,D) all-to-all instead of dense all-reduces of gathered
    # fp32 intermediates (see EXPERIMENTS.md §Perf pair 2).
    x_e = jnp.take(xf.astype(x.dtype), sel_idx, axis=0)       # (E, C, D)
    gate_e = jnp.take_along_axis(gate.T, sel_idx, axis=1)     # (E, C)
    gate_e = jnp.where(sel_valid, gate_e, 0.0)

    h = jnp.einsum("ecd,edf->ecf", x_e, p["w_gate"],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", x_e, p["w_up"],
                   preferred_element_type=jnp.float32)
    y_e = jnp.einsum("ecf,efd->ecd",
                     (jax.nn.silu(h) * u).astype(x.dtype), p["w_down"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    y_e = y_e * gate_e[..., None].astype(y_e.dtype)

    out = jnp.zeros((N, D), y_e.dtype).at[sel_idx.reshape(-1)].add(
        y_e.reshape(E * C, D), mode="drop")
    if m.num_shared_experts > 0:
        out = out + nn.swiglu(p["shared"], xf)

    # Switch-style load-balance loss
    f = jnp.mean(jnp.sum(jax.nn.one_hot(top_idx, E, dtype=jnp.float32),
                         axis=1), axis=0)                     # (E,)
    pbar = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * pbar) * m.aux_loss_coef
    return out.reshape(B, T, D).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Held-expert FFN: one chip's share of an expert-parallel layer, dropless
# ---------------------------------------------------------------------------
def held_expert_ffn(p, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """x (B, T, D) -> (B, T, D): the part of the layer's output that the
    held experts ``[held_start, held_start + held)`` give, plus the shared
    expert scaled by ``sigmoid(x w_sg)`` (Qwen3-Next's form).

    The router scores all ``num_experts`` (softmax in float32), takes the
    top-k and renormalises over them; a routed pair whose expert is not
    held adds nothing.  Every held expert runs on every token with its
    gate, zero where it was not routed, so no token is ever dropped
    whatever the batch (no capacity).  Reads every held expert's weights
    once per call; a gather of only the routed ones would read less.

    p: ``router`` (D, E); ``w_gate``/``w_up`` (H, D, F); ``w_down``
    (H, F, D); ``shared`` (a SwiGLU) and ``shared_gate`` (D, 1)."""
    m = cfg.moe
    logits = jnp.einsum("btd,de->bte", x, p["router"],
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_v, top_i = jax.lax.top_k(probs, m.top_k)
    top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True)
    held = m.held_start + jnp.arange(m.num_held, dtype=top_i.dtype)
    gate = jnp.sum(jnp.where(top_i[..., None] == held, top_v[..., None],
                             0.0), axis=-2)                  # (B, T, H)
    h = jnp.einsum("btd,edf->btef", x, p["w_gate"],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("btd,edf->btef", x, p["w_up"],
                   preferred_element_type=jnp.float32)
    act = (jax.nn.silu(h) * u * gate[..., None]).astype(x.dtype)
    y = jnp.einsum("btef,efd->btd", act, p["w_down"],
                   preferred_element_type=jnp.float32)
    if m.num_shared_experts > 0:
        y = y + nn.swiglu(p["shared"], x).astype(jnp.float32) \
            * jax.nn.sigmoid(jnp.einsum("btd,do->bto", x, p["shared_gate"],
                                        preferred_element_type=jnp.float32))
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# §Perf K4 (EXPERIMENTS.md pair 2): explicit expert-parallel dispatch via
# shard_map.  XLA's auto-SPMD lowers the take()-based dispatch into dense
# all-reduces / full-activation all-gathers of fp32 intermediates; the
# hand-written exchange moves ONLY the selected top-k payload:
#   local routing -> bucket per expert-shard -> all_to_all("model")
#   -> local expert FFN -> all_to_all back -> local combine.
# Capacity semantics become per-(expert, data-shard) — the standard
# device-local capacity of real EP systems (Switch/GShard); with ample
# capacity factor the output equals moe_ffn exactly (tested).
# ---------------------------------------------------------------------------
def _ep_mesh():
    """The ('data','model') mesh in context, or None (CPU test path)."""
    mesh = context_mesh()
    if mesh is not None and {"data", "model"} <= set(mesh.axis_names):
        return mesh
    return None


def moe_ffn_ep(p, cfg: ModelConfig, x: jnp.ndarray, mesh):
    """Expert-parallel MoE FFN under shard_map. x: (B, T, D)."""
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    B, T, D = x.shape
    E, K = m.num_experts, m.top_k
    n_ep = mesh.shape["model"]               # expert shards
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = 1
    for a in data_axes:
        n_dp *= mesh.shape[a]
    E_loc = E // n_ep
    # tokens sharded over ALL axes for dispatch — replicating them over
    # the model axis would make the all_to_all exchange identical copies
    # (16× redundant expert compute; measured and fixed, see EXPERIMENTS)
    N_loc = (B * T) // (n_dp * n_ep)
    C = max(1, min(N_loc, math.ceil(N_loc * K / E * m.capacity_factor)))

    def local(x_blk, router_w, w_gate, w_up, w_down, shared_p):
        # x_blk: (N_loc, D) — this device's token slice;
        # expert weights: this model shard's E_loc experts
        xf = x_blk.reshape(-1, D)
        logits = xf.astype(jnp.float32) @ router_w          # (N_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_vals, top_idx = jax.lax.top_k(probs, K)
        top_vals = top_vals / jnp.sum(top_vals, -1, keepdims=True)
        gate = jnp.sum(jax.nn.one_hot(top_idx, E, dtype=jnp.float32)
                       * top_vals[..., None], axis=1)       # (N_loc, E)

        # bucket: for each GLOBAL expert, the top-C local tokens (by score)
        score = jnp.where(gate > 0, probs, -1.0)            # (N_loc, E)
        sel_score, sel_idx = jax.lax.top_k(score.T, C)      # (E, C)
        sel_valid = sel_score > 0
        payload = jnp.take(xf, sel_idx, axis=0)             # (E, C, D)
        payload = jnp.where(sel_valid[..., None], payload, 0.0)
        g_e = jnp.take_along_axis(gate.T, sel_idx, axis=1)  # (E, C)
        g_e = jnp.where(sel_valid, g_e, 0.0)

        # exchange over the model axis: send E/n_ep experts to each peer
        snd = payload.reshape(n_ep, E_loc, C, D)
        rcv = jax.lax.all_to_all(snd, "model", split_axis=0,
                                 concat_axis=0, tiled=False)
        # rcv: (n_dp_peers=n_ep groups, E_loc, C, D) — tokens from every
        # model-column peer destined to OUR experts
        xr = rcv.reshape(n_ep, E_loc, C, D)

        h = jnp.einsum("pecd,edf->pecf", xr, w_gate,
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("pecd,edf->pecf", xr, w_up,
                       preferred_element_type=jnp.float32)
        yr = jnp.einsum("pecf,efd->pecd",
                        (jax.nn.silu(h) * u).astype(xr.dtype), w_down,
                        preferred_element_type=jnp.float32
                        ).astype(xr.dtype)
        # send results back to the owning token shards
        back = jax.lax.all_to_all(yr, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
        y_e = back.reshape(E, C, D) * g_e[..., None].astype(back.dtype)
        out = jnp.zeros((N_loc, D), y_e.dtype).at[
            sel_idx.reshape(-1)].add(y_e.reshape(E * C, D), mode="drop")
        if m.num_shared_experts > 0:
            out = out + nn.swiglu(shared_p, xf)

        f = jnp.mean(jnp.sum(jax.nn.one_hot(top_idx, E, dtype=jnp.float32),
                             axis=1), axis=0)
        pbar = jnp.mean(probs, axis=0)
        aux = E * jnp.sum(f * pbar) * m.aux_loss_coef
        # aux is per-token-slice; mean over all slices
        aux = jax.lax.pmean(aux, data_axes + ("model",))
        return out, aux

    shared_p = p.get("shared", {k: {"w": jnp.zeros((1, 1), x.dtype)}
                                for k in ("gate", "up", "down")})
    shared_spec = jax.tree.map(lambda _: P(), shared_p)
    tok_axes = data_axes + ("model",)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(tok_axes, None), P(), P("model", None, None),
                  P("model", None, None), P("model", None, None),
                  shared_spec),
        out_specs=(P(tok_axes, None), P()),
        check_vma=False)
    xf = x.reshape(B * T, D)
    out, aux = fn(xf, p["router"], p["w_gate"], p["w_up"], p["w_down"],
                  shared_p)
    return out.reshape(B, T, D).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Full model: attention blocks + MoE FFN, scanned over layers
# ---------------------------------------------------------------------------
def _init_layer_params(key, cfg: ModelConfig):
    dt = cfg.dtype
    k1, k2 = jax.random.split(key)
    p = {}
    p["ln1"], _ = nn.init_rmsnorm(cfg.d_model, dt)
    p["attn"], _ = nn.init_attention(k1, cfg, dt)
    p["ln2"], _ = nn.init_rmsnorm(cfg.d_model, dt)
    p["moe"] = init_moe_ffn(k2, cfg)
    return p


def _layer_axes(cfg: ModelConfig):
    L = ("layers",)
    return {
        "ln1": {"scale": L + ("embed",)},
        "ln2": {"scale": L + ("embed",)},
        "attn": {
            "q": {"w": L + ("embed", "heads")},
            "k": {"w": L + ("embed", "kv_heads")},
            "v": {"w": L + ("embed", "kv_heads")},
            "o": {"w": L + ("heads", "embed")},
        },
        "moe": moe_ffn_axes(cfg),
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


make_cache = tf.make_cache  # same attention KV cache as dense
make_paged_cache = tf.make_paged_cache


def moe_ffn_dispatch(p, cfg: ModelConfig, x: jnp.ndarray):
    """Route to the shard_map expert-parallel path when a ('data','model')
    mesh is in context and sizes divide; dense-gather path otherwise."""
    mesh = _ep_mesh()
    if mesh is not None:
        n_shards = mesh.shape["model"]
        for a in ("pod", "data"):
            if a in mesh.axis_names:
                n_shards *= mesh.shape[a]
        if (cfg.moe.num_experts % mesh.shape["model"] == 0
                and (x.shape[0] * x.shape[1]) % n_shards == 0):
            return moe_ffn_ep(p, cfg, x, mesh)
    return moe_ffn(p, cfg, x)


def _moe_block(pl, cfg, x, *, k_cached, v_cached, mask, q_pos, theta,
               write_slot=None, paged_idx=None):
    h = nn.rmsnorm(pl["ln1"], x, cfg.rms_eps)
    q, k_new, v_new = nn.attention_qkv(pl["attn"], h, cfg)
    q = tf._rope_traced(q, q_pos, theta, cfg.head_dim)
    k_new = tf._rope_traced(k_new, q_pos, theta, cfg.head_dim)
    if k_cached is not None and paged_idx is not None:
        phys_new, view_idx = paged_idx
        ck, cv = kvc.paged_write_kv(k_cached, v_cached, k_new, v_new,
                                    phys_new)
        attn_out = nn.gqa_attention(q, kvc.paged_gather(ck, view_idx),
                                    kvc.paged_gather(cv, view_idx), mask)
        new_cache = (ck, cv)
    elif k_cached is not None:
        ck, cv = kvc.write_kv(k_cached, v_cached, k_new, v_new, write_slot)
        attn_out = nn.gqa_attention(q, ck, cv, mask)
        new_cache = (ck, cv)
    else:
        attn_out = nn.gqa_attention(q, k_new, v_new, mask)
        new_cache = None
    x = x + nn.attention_out(pl["attn"], attn_out)
    h2 = nn.rmsnorm(pl["ln2"], x, cfg.rms_eps)
    y, aux = moe_ffn_dispatch(pl["moe"], cfg, h2)
    return x + y, aux, new_cache


def forward_cached(params, cfg: ModelConfig, state: kvc.ModelState,
                   tokens, valid=None, logits_mode="all",
                   spec_depth=None, spec_attend=None, **_ignored):
    state, q_pos, slot = kvc.append_tokens(state, tokens, valid,
                                           spec_depth=spec_depth)
    paged = isinstance(state, kvc.PagedModelState)
    mask = nn.build_attention_mask(state.mask, state.pos_buf, q_pos, window=0)
    if spec_attend is not None:   # tree speculation: ancestor-mask override
        T = tokens.shape[1]
        spec_attend = jnp.asarray(spec_attend)
        if paged:
            appended = (valid.any(axis=1) if valid is not None
                        else jnp.ones((tokens.shape[0],), jnp.bool_))
            mask = nn.overlay_block_mask_at(
                mask, state.mask, spec_attend,
                kvc.tree_region_cols(state, spec_attend.shape[1],
                                     appended))
        else:
            mask = nn.overlay_block_mask(mask, state.mask, spec_attend,
                                         slot + T - spec_attend.shape[1])
    paged_idx = ((kvc.physical_slots(state, slot),
                  kvc.physical_view_index(state)) if paged else None)
    x = tf._embed(params, cfg, tokens)
    theta = jnp.float32(cfg.rope_theta)

    def body(x, s):
        x, _aux, (ck, cv) = _moe_block(
            s["pl"], cfg, x, k_cached=s["ck"], v_cached=s["cv"],
            mask=mask, q_pos=q_pos, theta=theta,
            write_slot=None if paged else slot, paged_idx=paged_idx)
        return x, {"k": ck, "v": cv}

    xs = {"pl": params["blocks"], "ck": state.layers["k"],
          "cv": state.layers["v"]}
    x, new_kv = jax.lax.scan(body, x, xs)
    state = dataclasses.replace(
        state, layers={**state.layers, "k": new_kv["k"], "v": new_kv["v"]})
    if logits_mode == "none":
        return None, state
    if logits_mode == "last":
        if valid is None:
            x_last = x[:, -1]
        else:
            idx = jnp.maximum(jnp.sum(valid, axis=1) - 1, 0)
            x_last = jnp.take_along_axis(
                x, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return tf._unembed(params, cfg, x_last), state
    return tf._unembed(params, cfg, x), state


def forward_train(params, cfg: ModelConfig, tokens, remat=True, **_ignored):
    """Returns (logits, aux_loss)."""
    B, S = tokens.shape
    x = tf._embed(params, cfg, tokens)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    ar = jnp.arange(S, dtype=jnp.int32)
    mask = jnp.broadcast_to(ar[None, :, None] >= ar[None, None, :], (B, S, S))
    theta = jnp.float32(cfg.rope_theta)

    def body(carry, s):
        x, aux_sum = carry
        x, aux, _ = _moe_block(s["pl"], cfg, x, k_cached=None, v_cached=None,
                               mask=mask, q_pos=pos, theta=theta)
        return (x, aux_sum + aux), None

    fn = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable) \
        if remat else body
    (x, aux_total), _ = jax.lax.scan(
        fn, (x, jnp.zeros((), jnp.float32)), {"pl": params["blocks"]})
    return tf._unembed(params, cfg, x), aux_total
