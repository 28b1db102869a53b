"""Qwen3-Next (Gated DeltaNet + gated attention + held-expert FFN) against
the benchmark's plain float32 reference, at a tiny size on the CPU: d 64,
one period of 4 layers (3 GDN + 1 attention), 2 key / 4 value heads of 16,
8 experts of which 4 are held, top-2.

The program runs in float32 at ``HIGHEST`` matmul precision here, so every
comparison with the reference differs only in the order of float32 sums
(and in the chunked form's algebra): ``RTOL``/``ATOL`` sit a few hundred
float32 ulps above the largest logit (|logit| < 30).  bf16 is the chip's
business, where ``bench/check.py`` compares."""
import dataclasses
import sys
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.reference import qwen3_next as ref  # noqa: E402
from repro.configs.qwen3_next_80b_a3b import from_hf  # noqa: E402
from repro.core import ModelPool  # noqa: E402
from repro.data.workload import Request  # noqa: E402
from repro.models import kv_cache as kvc  # noqa: E402
from repro.models import layers as nn  # noqa: E402
from repro.models import moe, qwen3_next as q3  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.model import LanguageModel  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402

RTOL, ATOL = 1e-4, 2e-4
HF = {"decoder_sparse_step": 1, "full_attention_interval": 4,
      "head_dim": 16, "hidden_size": 64, "intermediate_size": 128,
      "linear_conv_kernel_dim": 4, "linear_key_head_dim": 16,
      "linear_num_key_heads": 2, "linear_num_value_heads": 4,
      "linear_value_head_dim": 16, "max_position_embeddings": 4096,
      "mlp_only_layers": [], "moe_intermediate_size": 32,
      "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4,
      "router_experts": 8, "num_experts_per_tok": 2,
      "num_hidden_layers": 4, "num_key_value_heads": 2,
      "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-6,
      "rope_theta": 10000.0, "shared_expert_intermediate_size": 32,
      "tie_word_embeddings": False, "vocab_size": 256}
S_REF = 48                      # every reference call: (2, 48) tokens


def _cfg(**moe_kw):
    cfg = from_hf(HF, "tiny-q3n", "test")
    return dataclasses.replace(
        cfg, dtype=jnp.float32,
        moe=dataclasses.replace(cfg.moe, **moe_kw) if moe_kw else cfg.moe)


def _flat(p):
    """Program parameters -> the benchmark's flat layout (the reference's
    input)."""
    g, a, m = p["gdn"], p["attn"], p["moe"]
    return {"embed": p["embed"], "head": p["lm_head"]["w"],
            "final_norm": p["final_norm"]["scale"],
            "g_ln": g["ln"]["scale"], "g_qkvz": g["qkvz"]["w"],
            "g_ba": g["ba"]["w"], "g_conv": g["conv"], "g_A_log": g["A_log"],
            "g_dt_bias": g["dt_bias"], "g_norm": g["norm"]["scale"],
            "g_out": g["out"]["w"], "a_ln": a["ln"]["scale"],
            "a_q": a["q"]["w"], "a_k": a["k"]["w"], "a_v": a["v"]["w"],
            "a_o": a["o"]["w"], "a_qn": a["q_norm"]["scale"],
            "a_kn": a["k_norm"]["scale"], "m_ln": m["ln"]["scale"],
            "m_router": m["router"], "m_wg": m["w_gate"],
            "m_wu": m["w_up"], "m_wd": m["w_down"],
            "m_sg": m["shared"]["gate"]["w"], "m_su": m["shared"]["up"]["w"],
            "m_sd": m["shared"]["down"]["w"], "m_sgate": m["shared_gate"]}


def _jitter_norms(params, key):
    """Norm scales away from 1, so a scale that is dropped shows."""
    def f(path, x):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return x + 0.1 * jax.random.normal(
                jax.random.fold_in(key, zlib.crc32(name.encode())), x.shape,
                x.dtype)
        return x
    return jax.tree_util.tree_map_with_path(f, params)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    lm = LanguageModel(cfg)
    params = jax.jit(lambda k: _jitter_norms(lm.init(k)[0], k))(
        jax.random.PRNGKey(3))
    fwd = jax.jit(lambda p, st, t, v: lm.decode(p, st, t, valid=v,
                                                logits_mode="all"))
    return cfg, lm, params, fwd, jax.jit(lm.rollback)


def _ref_logits(params, seqs):
    """(len(seqs), S_REF, V) reference logits of right-padded rows."""
    toks = np.zeros((len(seqs), S_REF), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        w = _flat(params)
        h = ref.hidden(w, HF, jnp.asarray(toks))
        lg = ref.logits(w["head"], h.reshape(-1, h.shape[-1]), False)
    return np.asarray(lg).reshape(len(seqs), S_REF, -1)


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), want, rtol=RTOL, atol=ATOL)


def _state(lm, span=8):
    return lm.make_state(2, 96, paged=True, rollback_span=span)


def test_a_chunked_prefill_then_paged_decode_equals_the_reference(model):
    """A 40-wide block (chunked form; row 1 left-padded by 8), then three
    single-token decodes through the paged state, against the reference's
    full forward of each row."""
    cfg, lm, params, fwd, _ = model
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 256, 43), rng.integers(0, 256, 35)]
    want = _ref_logits(params, seqs)
    toks = np.zeros((2, 40), np.int32)
    valid = np.zeros((2, 40), bool)
    toks[0], valid[0] = seqs[0][:40], True
    toks[1, 8:], valid[1, 8:] = seqs[1][:32], True
    with jax.default_matmul_precision("highest"):
        state, _ = _state(lm, span=40)
        lg, state = fwd(params, state, jnp.asarray(toks), jnp.asarray(valid))
        _close(np.asarray(lg)[0], want[0, :40])
        _close(np.asarray(lg)[1, 8:], want[1, :32])
        for t in range(3):
            nxt = np.array([[seqs[0][40 + t]], [seqs[1][32 + t]]], np.int32)
            lg, state = fwd(params, state, jnp.asarray(nxt),
                            jnp.ones((2, 1), bool))
            _close(np.asarray(lg)[:, 0],
                   np.stack([want[0, 40 + t], want[1, 32 + t]]))


@pytest.mark.parametrize("T", [23, 64, 130])
def test_b_chunked_delta_rule_equals_the_recurrence(T):
    """The WY form equals the token-by-token rule below, at and above one
    chunk, with a non-zero initial state; left pads (g = beta = 0) leave
    the state and the later outputs as they are."""
    ks = jax.random.split(jax.random.PRNGKey(T), 6)
    B, H, dk, dv, pad = 2, 4, 16, 16, 5

    def unit(k, shape):
        x = jax.random.normal(k, shape)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q, k = unit(ks[0], (B, T, H, dk)) * dk ** -0.5, unit(ks[1], (B, T, H, dk))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    on = (jnp.arange(T) >= pad)[None, :, None]
    g, beta = jnp.where(on, g, 0.0), jnp.where(on, beta, 0.0)
    S0 = jax.random.normal(ks[5], (B, H, dk, dv)) * 0.1
    recurrent = jax.jit(q3.delta_recurrent)
    o_r, s_r = recurrent(q, k, v, g, beta, S0)
    o_c, s_c = jax.jit(q3.delta_chunked)(q, k, v, g, beta, S0)
    np.testing.assert_allclose(o_c, o_r, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s_c, s_r, rtol=1e-4, atol=1e-5)
    o_u, s_u = recurrent(*(a[:, pad:] for a in (q, k, v, g, beta)), S0)
    np.testing.assert_allclose(o_r[:, pad:], o_u, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(s_r, s_u, rtol=1e-5, atol=1e-6)


def test_c_verify_block_then_per_row_rollback_then_decode(model):
    """Prefill, a left-padded verify block of 5 (token by token, inputs
    kept), rollbacks of 1 and 3 positions, then two more decodes: each
    row equals the reference on its own accepted prefix."""
    cfg, lm, params, fwd, rollback = model
    rng = np.random.default_rng(1)
    pre = rng.integers(0, 256, (2, 40)).astype(np.int32)
    blk = rng.integers(0, 256, (2, 5)).astype(np.int32)
    more = rng.integers(0, 256, (2, 2)).astype(np.int32)
    r = np.array([1, 3], np.int32)
    seqs = [np.concatenate([pre[b], blk[b, :5 - r[b]], more[b]])
            for b in range(2)]
    want = _ref_logits(params, seqs)
    want_blk = _ref_logits(params, list(np.concatenate([pre, blk], 1)))
    with jax.default_matmul_precision("highest"):
        state, _ = _state(lm)
        _, state = jax.jit(lm.prefill)(params, state, jnp.asarray(pre))
        toks = np.concatenate([np.zeros((2, 2), np.int32), blk], axis=1)
        valid = np.broadcast_to(np.arange(7)[None, :] >= 2, (2, 7))
        lg, state = fwd(params, state, jnp.asarray(toks), jnp.asarray(valid))
        _close(np.asarray(lg)[:, 2:], want_blk[:, 40:45])
        state = rollback(state, jnp.asarray(r))
        np.testing.assert_array_equal(np.asarray(state.length), 45 - r)
        for t in range(2):
            lg, state = fwd(params, state, jnp.asarray(more[:, t:t + 1]),
                            jnp.ones((2, 1), bool))
            for b in range(2):
                _close(np.asarray(lg)[b, 0], want[b, 45 - r[b] + t])


def _ffn_params(key, d=64, E=8, F=32):
    ks = jax.random.split(key, 8)
    w = lambda k, *shape: jax.random.normal(k, shape) / np.sqrt(shape[-2])
    return {"router": w(ks[0], d, E), "w_gate": w(ks[1], E, d, F),
            "w_up": w(ks[2], E, d, F), "w_down": w(ks[3], E, F, d),
            "shared": {"gate": {"w": w(ks[4], d, F)},
                       "up": {"w": w(ks[5], d, F)},
                       "down": {"w": w(ks[6], F, d)}},
            "shared_gate": w(ks[7], d, 1)}


def test_d_held_shares_sum_to_the_uncut_layer():
    """Each chip's share (experts [4s, 4s + 4) of 8) with the shared
    expert counted once adds up to the layer that holds all 8."""
    full = _cfg(held=8)
    p = _ffn_params(jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 7, 64))
    ffn = jax.jit(moe.held_expert_ffn, static_argnums=1)
    with jax.default_matmul_precision("highest"):
        whole = ffn(p, full, x)
        total = jnp.zeros_like(whole)
        for s in range(2):
            ps = dict(p, **{k: p[k][4 * s:4 * s + 4]
                            for k in ("w_gate", "w_up", "w_down")})
            total = total + ffn(ps, _cfg(held=4, held_start=4 * s), x)
        shared = nn.swiglu(p["shared"], x) * jax.nn.sigmoid(
            x @ p["shared_gate"])
    np.testing.assert_allclose(total - shared, whole, rtol=1e-5, atol=1e-5)


def test_e_dropless_every_token_on_one_expert():
    """A token's output is the same alone and in a batch where every token
    routes to the same expert: no capacity, nothing dropped."""
    cfg = _cfg(held=8)
    p = _ffn_params(jax.random.PRNGKey(7))
    p = dict(p, router=p["router"].at[:, 3].add(5.0))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (1, 16, 64)))
    probs = jax.nn.softmax(x @ p["router"], -1)
    assert bool(jnp.all(jnp.argmax(probs, -1) == 3))
    ffn = jax.jit(moe.held_expert_ffn, static_argnums=1)
    with jax.default_matmul_precision("highest"):
        batch = ffn(p, cfg, x)
        alone = jnp.concatenate([ffn(p, cfg, x[:, i:i + 1])
                                 for i in range(16)], axis=1)
    np.testing.assert_allclose(batch, alone, rtol=1e-5, atol=1e-6)


def test_f_reused_slot_starts_from_zero_state(model):
    """Row 0 serves a request and retires; the next request in that row
    reads what a fresh state reads, and every recurrent leaf of the row
    was zeroed."""
    cfg, lm, params, fwd, _ = model
    rng = np.random.default_rng(2)
    a, b = (rng.integers(0, 256, (2, 40)).astype(np.int32) for _ in range(2))
    ones = jnp.ones((2, 40), bool)
    with jax.default_matmul_precision("highest"):
        state, axes = _state(lm, span=40)
        _, state = fwd(params, state, jnp.asarray(a), ones)
        state = kvc.free_rows(state, np.array([True, False]), axes.layers)
        for name in ("conv", "delta", "conv0", "delta0", "span_x"):
            assert not np.asarray(state.layers[name])[:, 0].any(), name
        only0 = jnp.asarray(np.array([[True], [False]]) & np.ones((1, 40),
                                                                    bool))
        lg, _ = fwd(params, state, jnp.asarray(b), only0)
        fresh, _ = fwd(params, _state(lm, span=40)[0], jnp.asarray(b), ones)
    np.testing.assert_allclose(np.asarray(lg)[0], np.asarray(fresh)[0],
                               rtol=1e-6, atol=1e-6)


def test_g_speculative_pool_serves_target_only_tokens(model):
    """A tiny dense draft before the Qwen3-Next target, fixed chain and
    window 3, serves the same greedy tokens as the target alone, and the
    target's recurrent state was restored on the way."""
    cfg, lm, params, _, _ = model
    dcfg = ModelConfig(name="tiny-draft", arch_type="dense", num_layers=1,
                       d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
                       vocab_size=256, dtype=jnp.float32)
    pool = ModelPool()
    dlm = LanguageModel(dcfg)
    pool.register(dcfg, params=dlm.init(jax.random.PRNGKey(9))[0],
                  param_axes=dlm.param_axes())
    pool.register(cfg, params=params, param_axes=lm.param_axes())
    prompts = list(np.random.default_rng(3).integers(0, 256, (2, 12)))

    def serve(chain, window):
        eng = ServingEngine(pool, cfg.name, batch_size=2,
                            router_kwargs=dict(adaptive=False,
                                               fixed_chain=chain,
                                               fixed_window=window))
        reqs = [Request(f"r{i}", 0.0, p, 8, "test")
                for i, p in enumerate(prompts)]
        with jax.default_matmul_precision("highest"):
            eng.run(reqs)
        return [list(r.output_tokens) for r in reqs], eng._router.profiler

    alone, _ = serve((cfg.name,), 1)
    spec, prof = serve(("tiny-draft", cfg.name), 3)
    assert spec == alone
    assert prof.counters["restore_rows"] > 0


def test_anchor_moves_per_row_and_keeps_the_other_rows_pending(model):
    """Row 0 appends with nothing pending (after an admission of its own)
    while row 1 has 3 positions pending: only row 0's anchor moves, so
    row 1 still rolls back past the block into the one before."""
    cfg, lm, params, fwd, rollback = model
    rng = np.random.default_rng(5)
    pre = rng.integers(0, 256, (2, 20)).astype(np.int32)
    b1 = rng.integers(0, 256, (2, 3)).astype(np.int32)
    adm = rng.integers(0, 256, (2, 4)).astype(np.int32)
    b2 = rng.integers(0, 256, (2, 2)).astype(np.int32)
    more = rng.integers(0, 256, (2, 2)).astype(np.int32)
    seqs = [np.concatenate([pre[0], b1[0], adm[0], b2[0, :1], more[0]]),
            np.concatenate([pre[1], b1[1, :1], more[1]])]
    want = _ref_logits(params, seqs)
    prefill = jax.jit(lm.prefill)
    with jax.default_matmul_precision("highest"):
        state, _ = _state(lm)
        _, state = prefill(params, state, jnp.asarray(pre))
        _, state = fwd(params, state, jnp.asarray(b1), jnp.ones((2, 3), bool))
        _, state = prefill(params, state, jnp.asarray(adm),
                           jnp.asarray(np.array([[True] * 4, [False] * 4])))
        np.testing.assert_array_equal(state.layers["span_n"], [0, 3])
        _, state = fwd(params, state, jnp.asarray(b2), jnp.ones((2, 2), bool))
        np.testing.assert_array_equal(state.layers["span_n"], [2, 5])
        state = rollback(state, jnp.asarray([1, 4]))
        for t in range(2):
            lg, state = fwd(params, state, jnp.asarray(more[:, t:t + 1]),
                            jnp.ones((2, 1), bool))
            _close(np.asarray(lg)[0, 0], want[0, 28 + t])
            _close(np.asarray(lg)[1, 0], want[1, 21 + t])


def test_rollback_state_is_checked_where_it_is_made(model):
    """A block wider than the kept span, other than admission, and a
    rollback of a state made without a span are refused, not run from a
    stale anchor."""
    cfg, lm, params, _, _ = model
    toks = jnp.zeros((2, 9), jnp.int32)
    with pytest.raises(AssertionError, match="9-wide block"):
        jax.eval_shape(lambda p, st: lm.decode(p, st, toks),
                       params, _state(lm)[0])
    bare, _ = lm.make_state(2, 96, paged=True)
    with pytest.raises(ValueError, match="without a rollback span"):
        lm.rollback(bare, jnp.array([1, 0]))
