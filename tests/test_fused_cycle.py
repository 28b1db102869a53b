"""Device-resident fused cycles: the whole draft→multi-level-verify→commit
loop runs as ONE jitted program per (chain, window | tree) group, session
buffers live on device, and one small summary crosses to host per cycle.

Pinned here:
  * greedy bit-equality: fused == per-op == target-only, linear (2- and
    3-deep) and tree groups;
  * session lifecycle on the fused path — mid-cycle EOS termination,
    retire-then-readmit into a fused group;
  * the profiling-cycle interleave: scheduler T_i EMAs keep updating
    while fused output stays bit-exact;
  * strictly fewer host syncs per cycle than the per-op path;
  * the sampling-without-rng footgun raises instead of silently reusing
    PRNGKey(0) every cycle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ChainRouter, ModelPool, Placement
from repro.core.executor import DraftRequest
from repro.models import ModelConfig
from repro.models.model import LanguageModel


def build_pool(mesh=None):
    p = ModelPool(placement=Placement.from_spec(mesh)
                  if mesh is not None else None)
    for (n, L, d, s) in [("m68", 2, 32, 1), ("m1b", 3, 48, 2),
                         ("m7b", 4, 64, 3)]:
        cfg = ModelConfig(name=n, arch_type="dense", num_layers=L,
                          d_model=d, num_heads=4, num_kv_heads=2,
                          d_ff=2 * d, vocab_size=61, dtype=jnp.float32)
        lm = LanguageModel(cfg)
        params, axes = lm.init(jax.random.PRNGKey(s))
        p.register(cfg, params=params, param_axes=axes)
    if not p.placement.is_trivial:
        p.placement.auto_assign(p.capability(), "m7b")
    return p


@pytest.fixture(scope="module")
def pool():
    return build_pool()


@pytest.fixture(scope="module")
def reference(pool):
    prompt = np.array(jax.random.randint(jax.random.PRNGKey(0),
                                         (3, 7), 0, 61))
    plens = np.array([7, 5, 6])
    r = ChainRouter(pool, "m7b", greedy=True, adaptive=False,
                    fixed_chain=("m7b",), fixed_window=1, fused=False)
    ref = r.generate(prompt, plens, 14, request_id="ref")
    return prompt, plens, ref


@pytest.mark.slow   # 4 router pairs x full jit compile, ~2 min on CPU
@pytest.mark.parametrize("chain,window,tree", [
    (("m68", "m7b"), 4, None),
    (("m68", "m1b", "m7b"), 3, None),
    (("m7b",), 1, None),
    (("m68", "m7b"), 3, "2x2x1"),
])
def test_fused_bit_exact(pool, reference, chain, window, tree):
    """Fused greedy output == per-op output == target-only, and the fused
    run takes the same number of cycles (it is the same cycle, relocated
    on device)."""
    prompt, plens, ref = reference
    kw = dict(greedy=True, adaptive=False, fixed_chain=chain)
    if tree is not None:
        kw["fixed_tree"] = tree
    else:
        kw["fixed_window"] = window
    unf = ChainRouter(pool, "m7b", fused=False, **kw)
    ru = unf.generate(prompt, plens, 14, request_id="u")
    fus = ChainRouter(pool, "m7b", fused=True, profile_every=4, **kw)
    rf = fus.generate(prompt, plens, 14, request_id="f")
    assert rf.steps == ru.steps
    for b in range(3):
        np.testing.assert_array_equal(rf.generated[b], ru.generated[b])
        np.testing.assert_array_equal(rf.generated[b], ref.generated[b])


def test_fused_fewer_host_syncs(pool, reference):
    """The fused path's host-sync count per cycle must be strictly below
    the per-op path on the same workload (the one-transfer-per-cycle
    contract)."""
    prompt, plens, _ = reference
    counts = {}
    for fused in (False, True):
        r = ChainRouter(pool, "m7b", greedy=True, adaptive=False,
                        fixed_chain=("m68", "m1b", "m7b"), fixed_window=3,
                        fused=fused, profile_every=8)
        r.generate(prompt, plens, 14, request_id="w")
        s0 = r.profiler.counters["host_sync"]
        out = r.generate(prompt, plens, 14, request_id="x")
        counts[fused] = (r.profiler.counters["host_sync"] - s0) / out.steps
    assert counts[True] < counts[False]


def test_fused_eos_termination(pool):
    """Mid-cycle EOS with device-resident buffers: rows must truncate
    exactly where target-only does, deactivate in the device mirror, and
    survive the budget clamp ordering."""
    prompt = np.array(jax.random.randint(jax.random.PRNGKey(9),
                                         (2, 6), 0, 61))
    plens = np.array([6, 4])
    kw = dict(greedy=True, adaptive=False, eos_token=2)
    ref = ChainRouter(pool, "m7b", fixed_chain=("m7b",), fixed_window=1,
                      fused=False, **kw).generate(prompt, plens, 20,
                                                  request_id="r")
    # profile_every high => every post-0 cycle (incl. the terminating one)
    # runs fused
    out = ChainRouter(pool, "m7b", fixed_chain=("m68", "m7b"),
                      fixed_window=4, fused=True, profile_every=1000,
                      **kw).generate(prompt, plens, 20, request_id="s")
    for b in range(2):
        np.testing.assert_array_equal(out.generated[b], ref.generated[b])


def test_fused_retire_then_readmit(pool, reference):
    """Session lifecycle on a fused group: retire a finished slot, admit a
    new request into it, keep cycling fused — every request bit-exact."""
    prompt, plens, _ = reference
    r = ChainRouter(pool, "m7b", greedy=True, adaptive=False,
                    fixed_chain=("m68", "m7b"), fixed_window=4, fused=True,
                    profile_every=6)
    sess = r.start_session(2, 96, session_id="s")
    sess.admit(0, prompt[0, :plens[0]], 10)
    sess.admit(1, prompt[1, :plens[1]], 10)
    while sess.active.any():
        sess.run_cycle()
    out0, out1 = sess.retire(0), sess.retire(1)
    sess.admit(0, prompt[2, :plens[2]], 10)      # readmit into slot 0
    while sess.active.any():
        sess.run_cycle()
    out2 = sess.retire(0)
    sess.close()
    ref = ChainRouter(pool, "m7b", greedy=True, adaptive=False,
                      fixed_chain=("m7b",), fixed_window=1, fused=False
                      ).generate(prompt, plens, 10, request_id="rr")
    np.testing.assert_array_equal(out0, ref.generated[0])
    np.testing.assert_array_equal(out1, ref.generated[1])
    np.testing.assert_array_equal(out2, ref.generated[2])


def test_profiling_cycle_interleave_updates_t_i(pool, reference):
    """Fusing hides per-op timings, so every profile_every-th cycle runs
    the per-op path: the scheduler's decode1/verify EMAs must keep
    accumulating across a fused run while output stays bit-exact."""
    prompt, plens, ref = reference
    r = ChainRouter(pool, "m7b", greedy=True, adaptive=False,
                    fixed_chain=("m68", "m7b"), fixed_window=4, fused=True,
                    profile_every=3)
    out = r.generate(prompt, plens, 14, request_id="p")
    for b in range(3):
        np.testing.assert_array_equal(out.generated[b], ref.generated[b])
    # cycles 0, 3, 6, ... ran the per-op path: T_i evidence accumulates
    dec = r.profiler.emas[("decode1", "m68")]
    ver = [e for k, e in r.profiler.emas.items()
           if k[0] == "verify" and k[1] == "m7b" and e.count]
    assert dec.count >= out.steps // 3
    assert ver and sum(e.count for e in ver) >= out.steps // 3
    # and the scheduler reads a real measurement, not the cold default
    assert r.profiler.decode_time("m68", default=-1.0) > 0.0
    # fused cycles ran between the profiling cycles (not all per-op)
    assert r.profiler.emas[("fused_cycle", "m68+m7b")].count > 0


@pytest.mark.slow   # extra compile pair on the placed pool
@pytest.mark.parametrize("mesh", ["1x1"])
def test_fused_mesh_bit_exact(pool, reference, mesh):
    """The fused cycle built over a 1x1-PLACED pool (NamedSharding state
    buffers, level-boundary reshard closures compiled in) commits the
    exact same tokens as the unmeshed fused path, in the same number of
    cycles, with the same single host transfer per cycle."""
    prompt, plens, _ = reference
    meshed = build_pool(mesh)
    kw = dict(greedy=True, adaptive=False, fixed_chain=("m68", "m7b"),
              fixed_window=4, fused=True, profile_every=1000)
    ref = ChainRouter(pool, "m7b", **kw).generate(
        prompt, plens, 14, request_id="u")
    r = ChainRouter(meshed, "m7b", **kw)
    out = r.generate(prompt, plens, 14, request_id="m")
    assert out.steps == ref.steps
    for b in range(3):
        np.testing.assert_array_equal(out.generated[b], ref.generated[b])


@pytest.mark.mesh
@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 spawned devices (XLA_FLAGS="
                           "--xla_force_host_platform_device_count=8)")
def test_fused_mesh_2x4_one_transfer_per_cycle(pool, reference):
    """On the 2x4 mesh the fused cycle still makes exactly ONE host
    transfer per cycle — the commit slab moves between chain levels via
    device-side collectives, never through the host — and commits the
    same greedy tokens as the unmeshed fused path."""
    prompt, plens, _ = reference
    meshed = build_pool("2x4")
    kw = dict(greedy=True, adaptive=False, fixed_chain=("m68", "m7b"),
              fixed_window=4, fused=True, profile_every=1000)
    ref = ChainRouter(pool, "m7b", **kw).generate(
        prompt, plens, 14, request_id="u")
    r = ChainRouter(meshed, "m7b", **kw)
    out = r.generate(prompt, plens, 14, request_id="m")
    for b in range(3):
        np.testing.assert_array_equal(out.generated[b], ref.generated[b])
    # steady-state transfer count: cycle 0 of a session is the per-op
    # profiling cycle (intentional syncs); every fused cycle after it
    # must make exactly one host transfer
    sess = r.start_session(2, 96, session_id="m24")
    sess.admit(0, prompt[0, :plens[0]], 10)
    sess.admit(1, prompt[1, :plens[1]], 10)
    sess.run_cycle()
    steps, s0 = 0, r.profiler.counters["host_sync"]
    while sess.active.any() and steps < 6:
        sess.run_cycle()
        steps += 1
    assert steps > 0
    assert r.profiler.counters["host_sync"] - s0 == steps
    sess.close()


def test_sampling_without_rng_raises(pool):
    """The PRNGKey(0)-every-cycle fallback is gone: a sampling request
    without an rng must raise instead of silently repeating draws."""
    r = ChainRouter(pool, "m7b", greedy=False, adaptive=False,
                    fixed_chain=("m68", "m7b"), fixed_window=2)
    prompt = np.array([[1, 2, 3, 4]])
    sess = r.start_session(1, 64, session_id="q")
    sess.admit(0, prompt[0], 4)
    with pytest.raises(ValueError, match="sampling requested without"):
        r.executor.draft(DraftRequest(
            model="m68", request_id="q",
            prefix_tokens=np.array([[4]], np.int32),
            prefix_valid=np.array([[True]]),
            window=2, active=np.array([True]), greedy=False, rng=None))
    sess.close()


@pytest.mark.slow   # second full compile pair in sampling mode
def test_fused_sampling_matches_per_op(pool):
    """Bonus guarantee: the fused program consumes the session RNG stream
    exactly as the per-op path (one key per chain position), so even
    SAMPLING output is bit-equal between the paths."""
    prompt = np.array(jax.random.randint(jax.random.PRNGKey(4),
                                         (2, 6), 0, 61))
    plens = np.array([6, 5])
    kw = dict(greedy=False, temperature=1.0, adaptive=False,
              fixed_chain=("m68", "m7b"), fixed_window=4, seed=11)
    a = ChainRouter(pool, "m7b", fused=False, **kw).generate(
        prompt, plens, 10, request_id="a")
    b = ChainRouter(pool, "m7b", fused=True, profile_every=3, **kw
                    ).generate(prompt, plens, 10, request_id="b")
    for i in range(2):
        np.testing.assert_array_equal(a.generated[i], b.generated[i])
