"""State management invariants (paper §4.4): logical rollback (Eq. 8),
pointer-rewind physical reclaim (Eq. 9 TPU analogue), defragmentation,
and equivalence of rollback vs from-scratch recompute."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.models import ModelConfig
from repro.models import kv_cache as kvc
from repro.models.model import LanguageModel


def tiny_cfg(**kw):
    d = dict(name="t", arch_type="dense", num_layers=2, d_model=32,
             num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=41,
             dtype=jnp.float32)
    d.update(kw)
    return ModelConfig(**d)


def test_append_rollback_lengths():
    st_ = kvc.make_state(2, 32, {})
    toks = jnp.arange(10).reshape(2, 5).astype(jnp.int32)
    st_, q_pos, slot = kvc.append_tokens(st_, toks)
    assert int(slot) == 0 and int(st_.write_ptr) == 5
    np.testing.assert_array_equal(st_.length, [5, 5])
    st_ = kvc.rollback(st_, jnp.array([2, 0]))
    np.testing.assert_array_equal(st_.length, [3, 5])
    # ptr only reclaims the COMMON suffix (row 1 still valid to slot 4)
    assert int(st_.write_ptr) == 5
    st_ = kvc.rollback(st_, jnp.array([0, 2]))
    assert int(st_.write_ptr) == 3


def test_mask_decouples_validity_from_storage():
    """Paper Fig. 3: invalid entries physically present but ignored."""
    st_ = kvc.make_state(1, 16, {})
    st_, _, _ = kvc.append_tokens(st_, jnp.array([[7, 8, 9]], jnp.int32))
    st_ = kvc.logical_rollback(st_, jnp.array([2]))
    # data still physically there
    np.testing.assert_array_equal(st_.token_buf[0, :3], [7, 8, 9])
    np.testing.assert_array_equal(st_.mask[0, :3], [True, False, False])


def test_valid_mask_partial_append():
    st_ = kvc.make_state(2, 16, {})
    valid = jnp.array([[True, True], [True, False]])
    st_, q_pos, _ = kvc.append_tokens(
        st_, jnp.array([[1, 2], [3, 4]], jnp.int32), valid)
    np.testing.assert_array_equal(st_.length, [2, 1])
    assert int(q_pos[1, 1]) >= 2 ** 29   # invalid -> far-future position


def test_defragment_compacts_holes():
    st_ = kvc.make_state(2, 32, {})
    st_, _, _ = kvc.append_tokens(
        st_, jnp.arange(1, 13).reshape(2, 6).astype(jnp.int32))
    st_ = kvc.logical_rollback(st_, jnp.array([3, 1]))
    st_, _, _ = kvc.append_tokens(
        st_, jnp.array([[91, 92], [93, 94]], jnp.int32))
    frag_before = float(kvc.fragmentation(st_))
    d = kvc.defragment(st_)
    # only raggedness-induced holes remain (rows have different lengths and
    # share one physical pointer); true fragmentation is gone
    lens = np.asarray(d.length)
    residual = float(np.mean(lens.max() - lens) / lens.max())
    assert float(kvc.fragmentation(d)) <= residual + 1e-6
    assert float(kvc.fragmentation(d)) < frag_before
    assert int(d.write_ptr) == int(lens.max())
    # logical stream preserved
    for b, want in enumerate([[1, 2, 3, 91, 92], [7, 8, 9, 10, 11, 93, 94]]):
        order = np.argsort(np.where(d.mask[b], d.pos_buf[b], 1 << 30))
        got = np.asarray(d.token_buf[b])[order][:int(d.length[b])]
        np.testing.assert_array_equal(got, want)


def test_rollback_equals_recompute():
    """Decode 4 tokens, roll back 2, decode 2 more == decode the final
    sequence from scratch (state consistency, greedy logits equality)."""
    cfg = tiny_cfg()
    lm = LanguageModel(cfg)
    params, _ = lm.init(jax.random.PRNGKey(0))
    B = 2
    base = jnp.array([[5, 6, 7], [8, 9, 10]], jnp.int32)
    extra = jnp.array([[11, 12, 13, 14], [15, 16, 17, 18]], jnp.int32)

    st1, _ = lm.make_state(B, 32)
    _, st1 = lm.prefill(params, st1, base)
    _, st1 = lm.decode(params, st1, extra)
    st1 = lm.rollback(st1, jnp.array([2, 2]))
    lg1, st1 = lm.decode(params, st1, jnp.array([[21, 22], [23, 24]],
                                                jnp.int32))

    st2, _ = lm.make_state(B, 32)
    _, st2 = lm.prefill(params, st2, base)
    _, st2 = lm.decode(params, st2, extra[:, :2])
    lg2, st2 = lm.decode(params, st2, jnp.array([[21, 22], [23, 24]],
                                                jnp.int32))
    np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch_kw", [
    dict(arch_type="ssm", num_kv_heads=4, d_ff=0,
         ssm=__import__("repro.models.config", fromlist=["SSMConfig"]
                        ).SSMConfig(slstm_every=2)),
    dict(arch_type="hybrid", sliding_window=8,
         ssm=__import__("repro.models.config", fromlist=["SSMConfig"]
                        ).SSMConfig(state_size=4, expand=2)),
])
def test_ssm_rollback_equals_recompute(arch_kw):
    """DESIGN §5: snapshot-ring rollback for recurrent state."""
    cfg = tiny_cfg(**arch_kw)
    lm = LanguageModel(cfg)
    params, _ = lm.init(jax.random.PRNGKey(1))
    B = 2
    base = jnp.array([[5, 6, 7], [8, 9, 10]], jnp.int32)
    extra = jnp.array([[11, 12, 13, 14], [15, 16, 17, 18]], jnp.int32)
    nxt = jnp.array([[21, 22], [23, 24]], jnp.int32)

    st1, _ = lm.make_state(B, 32, rollback_span=8)
    _, st1 = lm.prefill(params, st1, base)
    _, st1 = lm.decode(params, st1, extra)
    st1 = lm.rollback(st1, jnp.array([2, 2]))
    lg1, _ = lm.decode(params, st1, nxt)

    st2, _ = lm.make_state(B, 32, rollback_span=8)
    _, st2 = lm.prefill(params, st2, base)
    _, st2 = lm.decode(params, st2, extra[:, :2])
    lg2, _ = lm.decode(params, st2, nxt)
    np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2),
                               rtol=1e-4, atol=1e-4)


def test_ssm_rollback_per_row_divergent():
    from repro.models.config import SSMConfig
    cfg = tiny_cfg(arch_type="ssm", num_kv_heads=4, d_ff=0,
                   ssm=SSMConfig(slstm_every=2))
    lm = LanguageModel(cfg)
    params, _ = lm.init(jax.random.PRNGKey(2))
    B = 2
    base = jnp.array([[5, 6], [8, 9]], jnp.int32)
    extra = jnp.array([[11, 12, 13], [15, 16, 17]], jnp.int32)
    nxt = jnp.array([[21], [23]], jnp.int32)

    st1, _ = lm.make_state(B, 32, rollback_span=8)
    _, st1 = lm.prefill(params, st1, base)
    _, st1 = lm.decode(params, st1, extra)
    st1 = lm.rollback(st1, jnp.array([1, 3]))     # divergent rollback
    lg1, _ = lm.decode(params, st1, nxt)

    # row 0 reference: kept 2 of the extras
    st2, _ = lm.make_state(B, 32, rollback_span=8)
    _, st2 = lm.prefill(params, st2, base)
    _, st2 = lm.decode(params, st2, extra[:, :2])
    lg2, _ = lm.decode(params, st2, nxt)
    np.testing.assert_allclose(np.asarray(lg1[0]), np.asarray(lg2[0]),
                               rtol=1e-4, atol=1e-4)
    # row 1 reference: kept none
    st3, _ = lm.make_state(B, 32, rollback_span=8)
    _, st3 = lm.prefill(params, st3, base)
    lg3, _ = lm.decode(params, st3, nxt)
    np.testing.assert_allclose(np.asarray(lg1[1]), np.asarray(lg3[1]),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(ops=st.lists(
    st.tuples(st.integers(1, 4),            # append length
              st.integers(0, 3), st.integers(0, 3)),  # rollbacks per row
    min_size=1, max_size=6))
def test_state_property_stream_consistency(ops):
    """Property: after any append/rollback interleaving, the logical stream
    equals the reference stream maintained in plain Python."""
    st_ = kvc.make_state(2, 128, {})
    ref = [[], []]
    tok = 1
    for (n, r0, r1) in ops:
        toks = np.arange(tok, tok + 2 * n).reshape(2, n).astype(np.int32)
        tok += 2 * n
        st_, _, _ = kvc.append_tokens(st_, jnp.asarray(toks))
        for b in range(2):
            ref[b].extend(toks[b].tolist())
        r = [min(r0, len(ref[0])), min(r1, len(ref[1]))]
        st_ = kvc.rollback(st_, jnp.asarray(r))
        for b in range(2):
            if r[b]:
                del ref[b][-r[b]:]
    for b in range(2):
        order = np.argsort(np.where(st_.mask[b], st_.pos_buf[b], 1 << 30))
        got = np.asarray(st_.token_buf[b])[order][:int(st_.length[b])]
        np.testing.assert_array_equal(got, ref[b])
