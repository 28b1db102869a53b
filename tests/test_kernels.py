"""Per-kernel validation: shape/dtype sweeps asserting allclose against the
pure-jnp oracles (interpret mode executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# DTV kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,V,dtype", [
    (1, 100, jnp.float32), (5, 2048, jnp.float32), (8, 5000, jnp.bfloat16),
    (3, 2049, jnp.float32), (16, 300, jnp.bfloat16),
])
def test_dtv_matches_ref(B, V, dtype):
    ka, kb = jax.random.split(KEY)
    a = (jax.random.normal(ka, (B, V)) * 3).astype(dtype)
    b = (jax.random.normal(kb, (B, V)) * 3).astype(dtype)
    got = ops.dtv(a, b)
    want = ref.dtv_ref(a, b)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert np.all(got >= -1e-6) and np.all(got <= 1 + 1e-6)


def test_softmax_stats_matches_ref():
    """The standalone stats kernel (dtv's first pass) against its oracle:
    padded tile boundaries and an uneven tail."""
    from repro.kernels import dtv as _dtv
    for R, V in [(_dtv.BLK_R, _dtv.BLK_V), (2 * _dtv.BLK_R, 2 * _dtv.BLK_V)]:
        x = (jax.random.normal(KEY, (R, V)) * 3).astype(jnp.float32)
        m, s = _dtv.softmax_stats(x, interpret=True)
        m_ref, s_ref = ref.softmax_stats_ref(x)
        np.testing.assert_allclose(m[:, 0], m_ref, rtol=1e-6)
        np.testing.assert_allclose(s[:, 0], s_ref, rtol=2e-5)


def test_dtv_identical_is_zero():
    a = jax.random.normal(KEY, (4, 1000))
    np.testing.assert_allclose(ops.dtv(a, a), 0.0, atol=1e-6)


def test_dtv_disjoint_is_one():
    a = jnp.full((2, 256), -100.0).at[:, 0].set(100.0)
    b = jnp.full((2, 256), -100.0).at[:, 1].set(100.0)
    np.testing.assert_allclose(ops.dtv(a, b), 1.0, atol=1e-5)


# ---------------------------------------------------------------------------
# Verify-stats kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("R,V,dtype", [
    (4, 511, jnp.float32), (8, 2048, jnp.float32), (12, 3000, jnp.bfloat16),
    (1, 130000, jnp.float32),
])
def test_verify_stats_matches_ref(R, V, dtype):
    kx, kc = jax.random.split(KEY)
    x = (jax.random.normal(kx, (R, V)) * 2).astype(dtype)
    cand = jax.random.randint(kc, (R,), 0, V)
    am, m, s, cl = ops.verify_row_stats(x, cand)
    am_r, m_r, s_r, cl_r = ref.verify_stats_ref(x, cand)
    np.testing.assert_array_equal(am, am_r)
    np.testing.assert_allclose(m, m_r, rtol=1e-6)
    np.testing.assert_allclose(s, s_r, rtol=2e-5)
    np.testing.assert_allclose(cl, cl_r, rtol=1e-6)


def test_greedy_accept_epilogue():
    x = jax.random.normal(KEY, (6, 777))
    cand = jnp.argmax(x, -1).astype(jnp.int32).at[3].add(1)  # row 3 mismatch
    am, m, s, cl = ops.verify_row_stats(x, cand)
    match, p = ops.greedy_accept_from_stats(cand, am, m, s, cl)
    want = np.ones(6, bool)
    want[3] = False
    np.testing.assert_array_equal(np.asarray(match), want)
    probs = jax.nn.softmax(x, -1)
    want_p = np.take_along_axis(np.asarray(probs),
                                np.asarray(cand)[:, None], 1)[:, 0]
    np.testing.assert_allclose(p, want_p, rtol=2e-5)


# ---------------------------------------------------------------------------
# Masked decode attention kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,Hkv,D,dtype", [
    (2, 128, 4, 2, 64, jnp.float32),
    (1, 700, 8, 8, 128, jnp.float32),      # unaligned S
    (3, 512, 25, 5, 64, jnp.bfloat16),     # hymba-style heads
    (2, 300, 48, 1, 128, jnp.float32),     # granite MQA
    (1, 1024, 32, 16, 168, jnp.bfloat16),  # gemma3 head_dim 168 (pad to 256)
])
def test_attention_matches_ref(B, S, H, Hkv, D, dtype):
    kq, kk, kv, km = jax.random.split(KEY, 4)
    q = jax.random.normal(kq, (B, H, D)).astype(dtype)
    k = jax.random.normal(kk, (B, S, Hkv, D)).astype(dtype)
    v = jax.random.normal(kv, (B, S, Hkv, D)).astype(dtype)
    mask = jax.random.bernoulli(km, 0.7, (B, S))
    got = ops.masked_decode_attention(q, k, v, mask)
    want = ref.masked_decode_attention_ref(q, k, v, mask)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_attention_fully_masked_row_is_zero():
    q = jax.random.normal(KEY, (2, 4, 64))
    k = jax.random.normal(KEY, (2, 256, 2, 64))
    v = jax.random.normal(KEY, (2, 256, 2, 64))
    mask = jnp.zeros((2, 256), bool).at[1].set(True)
    out = ops.masked_decode_attention(q, k, v, mask)
    np.testing.assert_allclose(out[0], 0.0, atol=1e-6)
    assert float(jnp.max(jnp.abs(out[1]))) > 0


# ---------------------------------------------------------------------------
# Property-based sweeps (hypothesis)
# ---------------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(B=st.integers(1, 6), V=st.integers(2, 3000), seed=st.integers(0, 99))
def test_dtv_property(B, V, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(k1, (B, V)) * 4
    b = jax.random.normal(k2, (B, V)) * 4
    got = np.asarray(ops.dtv(a, b))
    want = np.asarray(ref.dtv_ref(a, b))
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)
    # metric properties: symmetry + bounds
    got_sym = np.asarray(ops.dtv(b, a))
    np.testing.assert_allclose(got, got_sym, rtol=1e-6)


@settings(max_examples=10, deadline=None)
@given(S=st.integers(1, 600), Hkv=st.sampled_from([1, 2, 4]),
       g=st.sampled_from([1, 2, 5]), seed=st.integers(0, 99))
def test_attention_property(S, Hkv, g, seed):
    kk = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(kk, 4)
    B, D = 2, 64
    H = Hkv * g
    q = jax.random.normal(k1, (B, H, D))
    k = jax.random.normal(k2, (B, S, Hkv, D))
    v = jax.random.normal(k3, (B, S, Hkv, D))
    mask = jax.random.bernoulli(k4, 0.5, (B, S))
    got = np.asarray(ops.masked_decode_attention(q, k, v, mask))
    want = np.asarray(ref.masked_decode_attention_ref(q, k, v, mask))
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)
