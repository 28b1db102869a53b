"""§Perf optimizations stay semantics-preserving (EXPERIMENTS.md §Perf):
  H2  — chunkwise-parallel mLSTM ≡ recurrent form
  K4b — shard_map expert-parallel MoE ≡ dense-gather reference
  G2b — int8-KV attention ≈ full-precision (bounded error, argmax-stable)
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import ModelConfig, SSMConfig
from repro.models.model import LanguageModel


def test_chunkwise_mlstm_equals_recurrent():
    from repro.models import ssm
    for slstm_every in (0, 2):
        cfg = ModelConfig(name="t", arch_type="ssm", num_layers=4,
                          d_model=32, num_heads=2, num_kv_heads=2, d_ff=0,
                          vocab_size=61,
                          ssm=SSMConfig(slstm_every=slstm_every),
                          dtype=jnp.float32)
        lm = LanguageModel(cfg)
        params, _ = lm.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 61)
        a = ssm.forward_train(params, cfg, toks, chunkwise=True)
        b = ssm.forward_train(params, cfg, toks, chunkwise=False)
        rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert rel < 1e-3, (slstm_every, rel)


def test_ep_moe_matches_dense(tmp_path):
    import subprocess, sys, textwrap
    # needs >1 device: run in a subprocess with forced host device count
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.models import ModelConfig, MoEConfig
        from repro.models import moe
        cfg = ModelConfig(name="t", arch_type="moe", num_layers=1,
                          d_model=32, num_heads=2, num_kv_heads=2, d_ff=0,
                          vocab_size=61,
                          moe=MoEConfig(num_experts=8, top_k=2, d_expert=16,
                                        capacity_factor=16.0,
                                        num_shared_experts=1, d_shared=16),
                          dtype=jnp.float32)
        p = moe.init_moe_ffn(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
        y_ref, _ = moe.moe_ffn(p, cfg, x)
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        with jax.set_mesh(mesh):
            y_ep, _ = jax.jit(lambda p, x: moe.moe_ffn_ep(p, cfg, x,
                                                          mesh))(p, x)
        rel = float(jnp.max(jnp.abs(y_ep - y_ref))
                    / jnp.max(jnp.abs(y_ref)))
        assert rel < 1e-5, rel
        print("EP_OK", rel)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src"})
    assert "EP_OK" in r.stdout, r.stderr[-2000:]


def test_int8_kv_attention_bounded_error():
    cfg = ModelConfig(name="t", arch_type="dense", num_layers=3,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=101, dtype=jnp.float32)
    cfgq = dataclasses.replace(cfg, kv_quant=True)
    lm, lmq = LanguageModel(cfg), LanguageModel(cfgq)
    params, _ = lm.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, 101)
    s1, _ = lm.make_state(2, 32)
    s2, _ = lmq.make_state(2, 32)
    _, s1 = lm.prefill(params, s1, toks)
    _, s2 = lmq.prefill(params, s2, toks)
    t2 = jax.random.randint(jax.random.PRNGKey(2), (2, 1), 0, 101)
    d1, _ = lm.decode(params, s1, t2)
    d2, _ = lmq.decode(params, s2, t2)
    rel = float(jnp.max(jnp.abs(d1 - d2)) / jnp.max(jnp.abs(d1)))
    assert rel < 0.05, rel
    assert bool(jnp.all(jnp.argmax(d1, -1) == jnp.argmax(d2, -1)))


def test_int8_kv_rollback_consistent():
    """The paper's rollback machinery must hold for the quantized cache."""
    cfg = ModelConfig(name="t", arch_type="dense", num_layers=2,
                      d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                      vocab_size=41, dtype=jnp.float32, kv_quant=True)
    lm = LanguageModel(cfg)
    params, _ = lm.init(jax.random.PRNGKey(0))
    base = jnp.array([[5, 6, 7], [8, 9, 10]], jnp.int32)
    extra = jnp.array([[11, 12, 13, 14], [15, 16, 17, 18]], jnp.int32)
    nxt = jnp.array([[21, 22], [23, 24]], jnp.int32)
    s1, _ = lm.make_state(2, 32)
    _, s1 = lm.prefill(params, s1, base)
    _, s1 = lm.decode(params, s1, extra)
    s1 = lm.rollback(s1, jnp.array([2, 2]))
    lg1, _ = lm.decode(params, s1, nxt)
    s2, _ = lm.make_state(2, 32)
    _, s2 = lm.prefill(params, s2, base)
    _, s2 = lm.decode(params, s2, extra[:, :2])
    lg2, _ = lm.decode(params, s2, nxt)
    np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2),
                               rtol=1e-4, atol=1e-4)
