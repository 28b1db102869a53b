"""Per-slot chain routing with lazy chain membership: O(chain) admission
(pinned prefill/insert counters, zero footprint in non-chain models),
bit-exact grouped sub-cycles for slots on different chains, clean
rejection of over-long prompts, and the vectorized gap-prefix fast
path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ChainRouter, ModelPool, PerformanceProfiler
from repro.core.scheduler import ModelChainScheduler
from repro.core.similarity import SimilarityStore
from repro.core.state_manager import StateManager
from repro.models import ModelConfig
from repro.models.model import LanguageModel


@pytest.fixture(scope="module")
def pool():
    """Three models: s (draft), t (target), u (pool member that no chain
    ever uses — the lazy-membership probe)."""
    p = ModelPool()
    for (n, L, d, s) in [("s", 2, 32, 1), ("t", 3, 48, 2), ("u", 2, 32, 9)]:
        cfg = ModelConfig(name=n, arch_type="dense", num_layers=L,
                          d_model=d, num_heads=4, num_kv_heads=2,
                          d_ff=2 * d, vocab_size=64, dtype=jnp.float32)
        lm = LanguageModel(cfg)
        params, axes = lm.init(jax.random.PRNGKey(s))
        p.register(cfg, params=params, param_axes=axes)
    return p


def _target_only(pool, prompt, budget, rid):
    r = ChainRouter(pool, "t", adaptive=False, fixed_chain=("t",),
                    fixed_window=1)
    return r.generate(prompt[None, :], np.array([len(prompt)]), budget,
                      request_id=rid).generated[0]


# ---------------------------------------------------------------------------
# O(chain) admission: pinned counters + zero non-chain footprint
# ---------------------------------------------------------------------------
def test_admission_touches_only_chain_members(pool):
    """Admission prefill work is O(chain), not O(pool): with the chain
    fixed to (s, t), model u is never prefilled, never inserted into,
    never holds a state — and the s/t counters are pinned to exactly one
    state-creating prefill plus one per-row insert."""
    rng = np.random.default_rng(0)
    router = ChainRouter(pool, "t", adaptive=False, fixed_chain=("s", "t"),
                         fixed_window=3)
    sess = router.start_session(2, 128, session_id="oc")
    sess.admit(0, rng.integers(1, 64, size=6).astype(np.int64), 4)
    sess.admit(1, rng.integers(1, 64, size=7).astype(np.int64), 4)
    c = router.profiler.counters
    # chain members: first admit creates the state (one batched prefill),
    # second admit is a single row insert — pinned exactly
    for m in ("s", "t"):
        assert c.get(f"prefill.{m}.calls", 0) == 1
        assert c.get(f"insert.{m}.calls", 0) == 1
        assert c.get(f"admit.{m}", 0) == 2
    # the non-chain pool member: zero ops, zero state, zero rows/blocks
    assert not any(k for k in c if ".u" in k or k.endswith(".u")
                   or k.startswith(("prefill.u", "insert.u", "admit.u")))
    sid_u = StateManager.key("u", "oc")
    assert not router.states.exists(sid_u)
    for slot in (0, 1):
        assert router.states.row_footprint(sid_u, slot) == 0
    # a slot routed target-only holds no rows in the draft either
    while sess.active.any():
        sess.run_cycle()
    sess.retire(0)
    sess.retire(1)
    # retirement freed the member rows; the emptied states were released
    assert not router.states.exists(StateManager.key("s", "oc"))
    sess.close()


def test_per_slot_chain_leaves_other_models_empty(pool):
    """A slot admitted with an explicit target-only chain must hold zero
    rows in the draft even while another slot routes through it."""
    rng = np.random.default_rng(1)
    router = ChainRouter(pool, "t", adaptive=False)
    sess = router.start_session(2, 128, session_id="pf")
    sess.admit(0, rng.integers(1, 64, size=6).astype(np.int64), 4,
               chain=("s", "t"), window=3)
    sess.admit(1, rng.integers(1, 64, size=6).astype(np.int64), 4,
               chain=("t",))
    sid_s = StateManager.key("s", "pf")
    assert router.states.row_footprint(sid_s, 0) > 0
    assert router.states.row_footprint(sid_s, 1) == 0
    while sess.active.any():
        sess.run_cycle()
    assert router.states.row_footprint(sid_s, 1) == 0
    sess.close()


# ---------------------------------------------------------------------------
# grouped sub-cycles: bit-exactness with slots on DIFFERENT chains
# ---------------------------------------------------------------------------
def test_two_slots_different_chains_bit_exact(pool):
    """Two live slots assigned different chains run as separate masked
    sub-cycles per run_cycle; each stream must equal a fresh target-only
    decode (grouping must not leak state across groups)."""
    rng = np.random.default_rng(2)
    pa = rng.integers(1, 64, size=6).astype(np.int64)
    pb = rng.integers(1, 64, size=8).astype(np.int64)
    router = ChainRouter(pool, "t", adaptive=False)
    sess = router.start_session(2, 128, session_id="2c")
    sess.admit(0, pa, 7, chain=("s", "t"), window=3)
    sess.admit(1, pb, 9, chain=("t",))
    saw_two_groups = False
    while sess.active.any():
        rep = sess.run_cycle()
        if len(rep.groups) == 2:
            saw_two_groups = True
    assert saw_two_groups, "different chains should form distinct groups"
    out_a, out_b = sess.retire(0), sess.retire(1)
    sess.close()
    np.testing.assert_array_equal(out_a, _target_only(pool, pa, 7, "ra"))
    np.testing.assert_array_equal(out_b, _target_only(pool, pb, 9, "rb"))


def test_mid_flight_chain_join_catches_up(pool):
    """A model joining a slot's chain after admission catches up through
    the insert path and the stream stays bit-exact: admit target-only,
    then re-pin the slot to (s, t) mid-generation."""
    rng = np.random.default_rng(4)
    pa = rng.integers(1, 64, size=6).astype(np.int64)
    router = ChainRouter(pool, "t", adaptive=False)
    sess = router.start_session(1, 128, session_id="join")
    sess.admit(0, pa, 8, chain=("t",))
    sess.run_cycle()
    sess.run_cycle()
    assert not router.states.exists(StateManager.key("s", "join"))
    # re-pin mid-flight: the draft materializes lazily at the next cycle
    from repro.core.scheduler import ChainChoice
    sess._slot_choice[0] = ChainChoice(("s", "t"), 3, 0.0)
    sess._forced[0] = True
    while sess.active.any():
        sess.run_cycle()
    assert router.profiler.counters.get("admit.s", 0) >= 1
    out = sess.retire(0)
    sess.close()
    np.testing.assert_array_equal(out, _target_only(pool, pa, 8, "rj"))


# ---------------------------------------------------------------------------
# admission validation (satellite bugfix)
# ---------------------------------------------------------------------------
def test_unknown_chain_model_rejected_before_mutation(pool):
    """An explicit chain naming a model outside the pool must be
    rejected up front — a KeyError mid-admission would leak the slot."""
    rng = np.random.default_rng(7)
    router = ChainRouter(pool, "t", adaptive=False)
    sess = router.start_session(1, 64, session_id="uk")
    with pytest.raises(ValueError):
        sess.admit(0, rng.integers(1, 64, size=6).astype(np.int64), 4,
                   chain=("typo", "t"))
    assert not sess.occupied[0] and not sess.active[0]
    sess.admit(0, rng.integers(1, 64, size=6).astype(np.int64), 4,
               chain=("t",))
    assert sess.occupied[0]
    sess.close()


def test_chain_history_is_bounded(pool):
    router = ChainRouter(pool, "t", adaptive=False, fixed_chain=("t",),
                         fixed_window=1)
    sess = router.start_session(1, 64, session_id="ch")
    assert sess.chain_history.maxlen is not None


def test_overlong_prompt_rejected_before_mutation(pool):
    """A prompt that cannot fit the slot row raises ValueError up front
    and leaves the session consistent: the slot stays free and a valid
    admit afterwards succeeds."""
    rng = np.random.default_rng(5)
    router = ChainRouter(pool, "t", adaptive=False, fixed_chain=("t",),
                         fixed_window=1)
    sess = router.start_session(1, 64, session_id="cap")
    with pytest.raises(ValueError):
        sess.admit(0, rng.integers(1, 64, size=70).astype(np.int64), 4)
    assert not sess.occupied[0] and not sess.active[0]
    assert sess.seq_len[0] == 0
    with pytest.raises(ValueError):   # prompt fits, prompt+budget doesn't
        sess.admit(0, rng.integers(1, 64, size=30).astype(np.int64), 60)
    assert not sess.occupied[0]
    sess.admit(0, rng.integers(1, 64, size=8).astype(np.int64), 4)
    assert sess.occupied[0] and sess.active[0]
    while sess.active.any():
        sess.run_cycle()
    assert len(sess.retire(0)) == 4
    sess.close()


# ---------------------------------------------------------------------------
# vectorized gap prefix == per-row loop reference
# ---------------------------------------------------------------------------
def _gap_prefix_loop_ref(seq, seq_len, cache_len, active, gap, w):
    B = seq.shape[0]
    prefix = np.zeros((B, w), np.int32)
    pvalid = np.zeros((B, w), bool)
    for b in range(B):
        g = int(gap[b])
        if g > 0:
            prefix[b, w - 1 - g:w - 1] = seq[b, cache_len[b]:cache_len[b] + g]
            pvalid[b, w - 1 - g:w - 1] = True
        if active[b]:
            prefix[b, -1] = seq[b, seq_len[b] - 1]
        pvalid[b, -1] = bool(active[b])
    return prefix, pvalid


def test_gap_prefix_vectorization_matches_loop(pool):
    """The numpy fancy-indexed _gap_prefix must reproduce the per-row
    loop exactly on random gaps, inactive rows, and bucket widths."""
    router = ChainRouter(pool, "t", adaptive=False, fixed_chain=("s", "t"),
                         fixed_window=3)
    rng = np.random.default_rng(6)
    B = 5
    sess = router.start_session(B, 64, session_id="gp")
    for s in range(B):
        sess.admit(s, rng.integers(1, 64, size=int(rng.integers(2, 9))
                                   ).astype(np.int64), 4)
    sess.run_cycle()
    sid = StateManager.key("s", "gp")
    for trial in range(20):
        active = rng.random(B) < 0.7
        cache_len = router.states.lengths(sid)
        pfx, pval, gap = router._gap_prefix("s", "gp", sess.seq,
                                            sess.seq_len, active)
        assert pfx is not None
        ref_p, ref_v = _gap_prefix_loop_ref(sess.seq, sess.seq_len,
                                            cache_len, active, gap,
                                            pfx.shape[1])
        # invalid slots may hold different padding; compare only where
        # the mask exposes them, plus the masks themselves
        np.testing.assert_array_equal(pval, ref_v)
        np.testing.assert_array_equal(np.where(pval, pfx, 0),
                                      np.where(ref_v, ref_p, 0))
        sess.run_cycle()
    sess.close()


# ---------------------------------------------------------------------------
# per-slot scheduler view (pure python, fast)
# ---------------------------------------------------------------------------
def test_slot_view_overrides_global_prior():
    """Two slots with opposite acceptance evidence must route onto
    different chains from the same scheduler."""
    prof = PerformanceProfiler()
    prof.record("decode1", "d", 0.005)
    prof.record("decode1", "t", 0.1)
    store = SimilarityStore()
    store.update("d", "t", 0.5)           # middling global prior
    sched = ModelChainScheduler(["d", "t"], "t", prof, store,
                                {"d": 1, "t": 100}, windows=(4,),
                                switch_penalty_steps=1e9)
    for _ in range(6):
        sched.observe_slot("s0", "d", "t", 0.02)   # easy request
        sched.observe_slot("s1", "d", "t", 0.98)   # hard request
    easy = sched.get_optimal_chain(slot="s0")
    hard = sched.get_optimal_chain(slot="s1")
    assert easy.chain == ("d", "t")
    assert hard.chain == ("t",)
    # slot memos are independent: re-query reuses without re-sweeping
    evals = sched.eval_count
    assert sched.get_optimal_chain(slot="s0") is easy
    assert sched.get_optimal_chain(slot="s1") is hard
    assert sched.eval_count == evals
    # released slots fall back to the shared prior
    sched.release_slot("s1")
    fresh = sched.get_optimal_chain(slot="s1")
    glob = sched.get_optimal_chain()
    assert fresh.chain == glob.chain


def test_unobserved_pairs_use_exploration_default():
    """Never-observed pairs must stay schedulable (lazy membership means
    nothing else will ever measure them): with a fast draft the explore
    default admits the chain; observed-bad evidence kills it."""
    prof = PerformanceProfiler()
    prof.record("decode1", "d", 0.001)
    prof.record("decode1", "t", 0.1)
    sched = ModelChainScheduler(["d", "t"], "t", prof, SimilarityStore(),
                                {"d": 1, "t": 100}, windows=(4,),
                                switch_penalty_steps=1e9)
    assert sched.get_optimal_chain().chain == ("d", "t")
    for _ in range(8):
        sched.sims.update("d", "t", 0.99)
    assert sched.get_optimal_chain().chain == ("t",)
