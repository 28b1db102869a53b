"""Admission forwards run over the admitted rows only.

``Executor.prefill`` (a session's first admission) and ``Executor.insert``
(every later one, and a lazy chain join) gather the admitted rows' per-row
leaves into a state of as many rows as their power-of-two bucket, run the
forward there and scatter the rows back.  Against the whole-batch forward
on the same state, for a Qwen2-dense and a reduced-width Qwen3-Next paged
session: the admitted rows read the same distributions and hold the same
per-row leaves, to float tolerance; every other row keeps its leaves bit
for bit; the shared block pool's free stack and the block tables match
exactly.  The programs' batch dimension is the bucket, and the
``admission_rows`` counter counts it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ChainRouter, ModelPool
from repro.core.executor import Executor, InsertRequest, PrefillRequest
from repro.core.profiler import PerformanceProfiler
from repro.core.state_manager import StateManager
from repro.models import ModelConfig
from repro.models import kv_cache as kvc
from repro.models.model import LanguageModel
from test_qwen3_next import _cfg as qwen3_next_cfg

TOL = dict(rtol=2e-5, atol=2e-5)
MAX_LEN = 96
SID = "s"


def _dense_cfg():
    return ModelConfig(name="dense", arch_type="dense", num_layers=2,
                       d_model=48, num_heads=4, num_kv_heads=2, d_ff=96,
                       vocab_size=256, dtype=jnp.float32)


@pytest.fixture(scope="module", params=["dense", "qwen3_next"])
def arch(request):
    cfg = _dense_cfg() if request.param == "dense" else qwen3_next_cfg()
    lm = LanguageModel(cfg)
    params = lm.init(jax.random.PRNGKey(5))[0]
    pool = ModelPool()
    pool.register(cfg, params=params, param_axes=lm.param_axes())
    return cfg, lm, params, pool


def _executor(pool):
    return Executor(pool, StateManager(), PerformanceProfiler())


def _span(cfg):
    return 40 if cfg.recurrent else 0


def _block(B, lens, rng, width):
    """(B, width) left-aligned tokens and mask: row b carries lens[b]."""
    tokens = np.zeros((B, width), np.int32)
    valid = np.zeros((B, width), bool)
    for b, n in enumerate(lens):
        tokens[b, :n] = rng.integers(1, 256, n)
        valid[b, :n] = True
    return tokens, valid


def _copy(state):
    return jax.tree.map(jnp.copy, state)


def _whole_batch(lm, params, state, tokens, valid, kind):
    """The forward over every row (what admission ran before): the
    reference the row-scoped program is held to."""
    fwd = lm.prefill if kind == "prefill" else lm.decode
    logits, state = jax.jit(
        lambda p, s, t, v: fwd(p, s, t, valid=v, logits_mode="last"))(
            params, _copy(state), jnp.asarray(tokens), jnp.asarray(valid))
    return np.asarray(jax.nn.softmax(logits.astype(jnp.float32), -1)), state


def _assert_rows(before, got, want, axes, rows):
    """Rows ``rows`` of ``got`` match ``want`` to float tolerance; every
    other row of ``got`` is ``before``'s bit for bit; the block tables and
    the free stack match ``want`` exactly, the KV pool to tolerance."""
    bax = kvc.batch_axes(got, axes)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(got)[0]]
    others = np.setdiff1d(np.arange(got.batch), rows)
    for name, a, x0, x, y in zip(names, bax, jax.tree.leaves(before),
                                 jax.tree.leaves(got), jax.tree.leaves(want)):
        x0, x, y = np.asarray(x0), np.asarray(x), np.asarray(y)
        if a < 0:
            np.testing.assert_allclose(x, y, err_msg=name, **TOL)
            continue
        np.testing.assert_allclose(np.take(x, rows, axis=a),
                                   np.take(y, rows, axis=a), err_msg=name,
                                   **TOL)
        np.testing.assert_array_equal(np.take(x, others, axis=a),
                                      np.take(x0, others, axis=a),
                                      err_msg=name)
    np.testing.assert_array_equal(got.block_table, want.block_table)
    np.testing.assert_array_equal(got.free_stack, want.free_stack)
    assert int(got.free_top) == int(want.free_top)


def _live_session(ex, cfg, lens, rng):
    """A B-row session whose rows hold ``lens`` tokens (0 = empty)."""
    B = len(lens)
    tokens, valid = _block(B, lens, rng, 32)
    ex.prefill(PrefillRequest(model=cfg.name, request_id=SID, tokens=tokens,
                              valid=valid, max_len=MAX_LEN,
                              rollback_span=_span(cfg)))
    return StateManager.key(cfg.name, SID)


@pytest.mark.parametrize("admit,B", [([1], 4), ([0, 3], 4), ([1, 4, 5], 6)],
                         ids=["one-row", "two-rows", "three-rows-one-pad"])
def test_insert_over_admitted_rows_equals_the_whole_batch(arch, admit, B):
    """Rows ``admit`` join a session whose other rows are live or empty
    (one-row: a retired row is reused)."""
    cfg, lm, params, pool = arch
    rng = np.random.default_rng(len(admit))
    ex = _executor(pool)
    lens = [20, 12, 0, 27, 0, 9][:B]
    sid = _live_session(ex, cfg, lens, rng)
    ex.retire(cfg.name, SID, np.isin(np.arange(B), admit))
    before = _copy(ex.states.get(sid))
    rows = np.isin(np.arange(B), admit)
    tokens, valid = _block(B, [int(r) * (5 + 2 * b) for b, r in
                               enumerate(rows)], rng, 16)
    want_p, want = _whole_batch(lm, params, before, tokens, valid, "insert")
    n0 = ex.profiler.counters["admission_rows"]
    with jax.default_matmul_precision("highest"):
        got_p = ex.insert(InsertRequest(model=cfg.name, request_id=SID,
                                        tokens=tokens, valid=valid,
                                        rows=rows))
    np.testing.assert_allclose(got_p, want_p[admit], **TOL)
    _assert_rows(before, ex.states.get(sid), want, ex.states.axes(sid),
                 np.asarray(admit))
    bucket = 1 << (len(admit) - 1).bit_length()
    assert ex.profiler.counters["admission_rows"] - n0 == bucket


@pytest.mark.parametrize("admit", [[2], [0, 1, 3]], ids=["one-row",
                                                          "three-rows"])
def test_first_admission_prefills_the_admitted_rows_only(arch, admit):
    """A session's first admission: the fresh state's admitted rows hold
    what the whole-batch prefill gives them; the other rows stay empty."""
    cfg, lm, params, pool = arch
    B = 6
    rng = np.random.default_rng(7)
    ex = _executor(pool)
    rows = np.isin(np.arange(B), admit)
    tokens, valid = _block(B, [int(r) * (30 - 4 * b) for b, r in
                               enumerate(rows)], rng, 32)
    fresh, axes = lm.make_state(B, MAX_LEN, rollback_span=_span(cfg),
                                paged=True)
    want_p, want = _whole_batch(lm, params, fresh, tokens, valid, "prefill")
    with jax.default_matmul_precision("highest"):
        got_p, sid = ex.prefill(PrefillRequest(
            model=cfg.name, request_id=SID, tokens=tokens, valid=valid,
            max_len=MAX_LEN, rollback_span=_span(cfg), rows=rows))
    np.testing.assert_allclose(got_p, want_p[admit], **TOL)
    _assert_rows(fresh, ex.states.get(sid), want, axes, np.asarray(admit))
    assert ex.profiler.counters["admission_rows"] == \
        1 << (len(admit) - 1).bit_length()


def test_admission_program_runs_the_row_bucket_not_the_batch(arch):
    """The single-row insert program's activations are one row wide: no
    (B, w, d) tensor appears in it, and the (1, w, d) one does."""
    cfg, lm, params, pool = arch
    B, w = 4, 8
    ex = _executor(pool)
    sid = _live_session(ex, cfg, [20, 0, 11, 5], np.random.default_rng(1))
    state, axes = ex.states.get(sid), ex.states.axes(sid)
    prog = ex._admission(cfg.name, "insert")
    text = prog.lower(params, state, jnp.asarray([1], jnp.int32),
                      jnp.zeros((1, w), jnp.int32), jnp.ones((1, w), bool),
                      {}, bax=kvc.batch_axes(state, axes)).as_text()
    d = cfg.d_model
    assert f"tensor<1x{w}x{d}xf32>" in text
    assert f"tensor<{B}x{w}x{d}xf32>" not in text


def test_a_state_without_row_axes_runs_every_row(arch):
    """A state whose axes do not name every leaf's rows (a snapshot ring
    declares none) is fed as one whole batch, and still only the admitted
    rows' distributions come back."""
    cfg, lm, params, pool = arch
    ex = _executor(pool)
    sid = _live_session(ex, cfg, [20, 0, 11, 5], np.random.default_rng(2))
    axes = ex.states.axes(sid)
    axes.layers = dict(axes.layers, **{k: None for k in
                                       list(axes.layers)[:1]})
    assert kvc.batch_axes(ex.states.get(sid), axes) is None
    rows = np.array([False, True, False, False])
    tokens, valid = _block(4, [0, 6, 0, 0], np.random.default_rng(3), 8)
    n0 = ex.profiler.counters["admission_rows"]
    got = ex.insert(InsertRequest(model=cfg.name, request_id=SID,
                                  tokens=tokens, valid=valid, rows=rows))
    assert got.shape == (1, cfg.vocab_size)
    assert ex.profiler.counters["admission_rows"] - n0 == 4


@pytest.fixture(scope="module")
def two_level_pool():
    p = ModelPool()
    for name, L, d, seed in [("s", 1, 32, 1), ("t", 2, 48, 2)]:
        cfg = ModelConfig(name=name, arch_type="dense", num_layers=L,
                          d_model=d, num_heads=4, num_kv_heads=2,
                          d_ff=2 * d, vocab_size=64, dtype=jnp.float32)
        lm = LanguageModel(cfg)
        p.register(cfg, params=lm.init(jax.random.PRNGKey(seed))[0],
                   param_axes=lm.param_axes())
    return p


def test_each_single_row_admission_computes_one_row(two_level_pool):
    """``admission_rows`` grows by one per admitted row, as ``admit.<m>``
    does, over a session's first admission and the inserts after it."""
    router = ChainRouter(two_level_pool, "t", adaptive=False,
                         fixed_chain=("t",), fixed_window=1)
    sess = router.start_session(5, 128, session_id="a")
    rng = np.random.default_rng(4)
    for slot in (0, 3, 1):
        sess.admit(slot, rng.integers(1, 64, 6 + slot), 4)
    c = router.profiler.counters
    assert c["admit.t"] == 3 and c["admission_rows"] == 3


def test_lazy_join_feeds_the_joining_rows_only(two_level_pool):
    """Rows that join a member already holding others catch up in one
    forward over the joiners, bucketed with one pad row; the members it
    already held keep their leaves, and the joined rows hold their
    committed streams."""
    router = ChainRouter(two_level_pool, "t", adaptive=False,
                         fixed_chain=("t",), fixed_window=1)
    sess = router.start_session(6, 128, session_id="j")
    rng = np.random.default_rng(5)
    for slot in range(5):
        sess.admit(slot, rng.integers(1, 64, 5 + slot), 4, chain=("t",))
    sess._ensure_members(("s", "t"), np.isin(np.arange(6), [0]))
    sid = StateManager.key("s", "j")
    before = _copy(router.states.get(sid))
    n0 = router.profiler.counters["admission_rows"]
    joining = np.isin(np.arange(6), [1, 2, 4])
    sess._ensure_members(("s", "t"), joining)
    after = router.states.get(sid)
    assert router.profiler.counters["admission_rows"] - n0 == 4
    np.testing.assert_array_equal(np.asarray(after.length),
                                  np.where(joining, sess.seq_len - 1,
                                           before.length))
    for x0, x in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        if x0.ndim and x0.shape[0] == 6:
            np.testing.assert_array_equal(np.asarray(x)[[0, 3, 5]],
                                          np.asarray(x0)[[0, 3, 5]])
