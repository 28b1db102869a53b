"""Per-architecture smoke tests (assignment requirement f): a REDUCED
variant of each assigned family runs one forward/train step on CPU with
shape + finiteness assertions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_smoke_config
from repro.models.model import LanguageModel

pytestmark = pytest.mark.slow   # one forward per assigned arch, ~90 s on CPU


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_forward_and_decode(arch, key):
    cfg = get_smoke_config(arch)
    lm = LanguageModel(cfg)
    params, axes = lm.init(key)
    B, Tp = 2, 6
    state, _ = lm.make_state(B, 48, rollback_span=8)
    toks = jax.random.randint(key, (B, Tp), 0, cfg.vocab_size)
    extras = lm.extras_for(B, key)
    logits, state = lm.prefill(params, state, toks, logits_mode="last",
                               **extras)
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # one decode step (serve_step shape)
    t2 = jax.random.randint(key, (B, 1), 0, cfg.vocab_size)
    lg, state = lm.decode(params, state, t2, **extras)
    assert lg.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(lg)))
    # multi-token verify-style block + rollback
    t3 = jax.random.randint(key, (B, 4), 0, cfg.vocab_size)
    lg3, state = lm.decode(params, state, t3, **extras)
    assert bool(jnp.all(jnp.isfinite(lg3)))
    st2 = lm.rollback(state, jnp.array([2, 3]))
    np.testing.assert_array_equal(np.asarray(st2.length),
                                  np.asarray(state.length) - [2, 3])


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_train_step(arch, key):
    cfg = get_smoke_config(arch)
    lm = LanguageModel(cfg)
    params, _ = lm.init(key)
    B, S = 2, 8
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    extras = lm.extras_for(B, key)

    def loss_fn(p):
        out = lm.train_logits(p, toks, remat=False, **extras)
        logits, aux = out if lm.has_aux_loss() else (out, 0.0)
        tgt = jnp.roll(toks, -1, axis=1)
        ll = jnp.take_along_axis(
            jax.nn.log_softmax(logits.astype(jnp.float32), -1),
            tgt[..., None], axis=-1)[..., 0]
        return -jnp.mean(ll[:, :-1]) + aux

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss))
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0
