"""Slot-level continuous batching: admission into freed slots mid-flight,
queueing-delay billing, retirement semantics, equality with the router's
bulk ``generate``, and no head-of-line blocking behind a long request
(tiny random pool — fast)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ChainRouter, ModelPool
from repro.data.workload import Request
from repro.models import ModelConfig
from repro.models.model import LanguageModel
from repro.serving import ServingEngine


@pytest.fixture(scope="module")
def pool():
    p = ModelPool()
    for (n, L, d, s) in [("s", 2, 32, 1), ("t", 3, 48, 2)]:
        cfg = ModelConfig(name=n, arch_type="dense", num_layers=L,
                          d_model=d, num_heads=4, num_kv_heads=2,
                          d_ff=2 * d, vocab_size=64, dtype=jnp.float32)
        lm = LanguageModel(cfg)
        params, axes = lm.init(jax.random.PRNGKey(s))
        p.register(cfg, params=params, param_axes=axes)
    return p


def _req(i, arrival, plen, budget, rng):
    return Request(request_id=f"r{i}", arrival_s=arrival,
                   prompt=rng.integers(1, 64, size=plen).astype(np.int64),
                   max_new_tokens=budget, dataset="synthetic")


def _hol_workload():
    """One long request, then a burst of short ones right behind it."""
    rng = np.random.default_rng(0)
    reqs = [_req(0, 0.0, 8, 32, rng)]
    reqs += [_req(i, 0.01 * i, 8, 4, rng) for i in range(1, 6)]
    return reqs


# ---------------------------------------------------------------------------
# session-level semantics
# ---------------------------------------------------------------------------
def test_mid_flight_admission_fills_freed_slot(pool):
    """A request admitted after another retires reuses its slot row and
    decodes the same stream as a fresh target-only reference."""
    rng = np.random.default_rng(3)
    router = ChainRouter(pool, "t", adaptive=False, fixed_chain=("s", "t"),
                        fixed_window=3)
    sess = router.start_session(2, 128, session_id="sess")
    pa = rng.integers(1, 64, size=6).astype(np.int64)
    pb = rng.integers(1, 64, size=8).astype(np.int64)
    pc = rng.integers(1, 64, size=7).astype(np.int64)
    sess.admit(0, pa, 4)
    sess.admit(1, pb, 12)
    while sess.active[0]:
        sess.run_cycle()
    out_a = sess.retire(0)
    assert len(out_a) == 4 and not sess.occupied[0]
    assert sess.occupied[1]              # slot 1 kept running

    # mid-flight admission into the freed slot, while slot 1 is live
    sess.admit(0, pc, 6)
    assert sess.occupied[0] and sess.active[0]
    while sess.active.any():
        sess.run_cycle()
    out_c = sess.retire(0)
    out_b = sess.retire(1)
    sess.close()
    assert len(out_c) == 6 and len(out_b) == 12

    # greedy equivalence: the admitted-into-dirty-slot stream must be
    # bit-identical to a fresh single-row target-only decode
    ref_router = ChainRouter(pool, "t", adaptive=False, fixed_chain=("t",),
                             fixed_window=1)
    ref = ref_router.generate(pc[None, :], np.array([7]), 6,
                              request_id="ref")
    np.testing.assert_array_equal(out_c, ref.generated[0])


def test_retired_slot_stops_billing(pool):
    """Cycles run after a slot retires leave its request's finish time and
    token count untouched."""
    rng = np.random.default_rng(4)
    reqs = [_req(0, 0.0, 6, 3, rng), _req(1, 0.0, 6, 20, rng)]
    eng = ServingEngine(pool, "t", batch_size=2,
                        router_kwargs=dict(adaptive=False,
                                           fixed_chain=("t",),
                                           fixed_window=1))
    eng.run(reqs)
    short, long = reqs
    # the short request finished well before the long one, even though the
    # engine kept cycling the shared slot pool afterwards
    assert short.finish_s < long.finish_s
    assert short.generated == 3
    assert long.generated == 20
    assert short.latency < long.latency


def test_ttft_includes_queueing_delay(pool):
    """A request that arrives while all slots are busy must bill its wait
    for a free slot into TTFT: first_token - arrival >= start - arrival > 0
    and start_s (admission) is after the blocking work."""
    rng = np.random.default_rng(5)
    # 1 slot: r1 arrives immediately but must wait for r0 to finish
    reqs = [_req(0, 0.0, 8, 16, rng), _req(1, 0.01, 6, 4, rng)]
    eng = ServingEngine(pool, "t", batch_size=1,
                        router_kwargs=dict(adaptive=False,
                                           fixed_chain=("t",),
                                           fixed_window=1))
    eng.run(reqs)
    r0, r1 = reqs
    assert r1.start_s >= r0.finish_s - 1e-9       # waited for the slot
    assert r1.queue_delay > 0
    assert r1.ttft >= r1.queue_delay              # queueing billed to TTFT
    assert r1.first_token_s > r1.start_s


def test_continuous_matches_generate_on_single_batch(pool):
    """When every request fits the slot pool at once, the engine serves
    the same token streams as the router's bulk ``generate`` over the same
    prompts and budgets, with sane metrics."""
    rng = np.random.default_rng(6)
    reqs = [_req(i, 0.001 * i, 6 + i, 5 + i, rng) for i in range(3)]
    kw = dict(adaptive=False, fixed_chain=("s", "t"), fixed_window=3)
    m = ServingEngine(pool, "t", batch_size=3, router_kwargs=kw).run(reqs)

    lens = np.array([len(r.prompt) for r in reqs])
    prompt = np.zeros((len(reqs), lens.max()), np.int64)
    for b, r in enumerate(reqs):
        prompt[b, :lens[b]] = r.prompt
    budgets = np.array([r.max_new_tokens for r in reqs])
    ref = ChainRouter(pool, "t", **kw).generate(
        prompt, lens, max_new_tokens=budgets, request_id="ref")

    assert m.num_requests == 3
    assert m.total_tokens == ref.committed_tokens
    for b, r in enumerate(reqs):
        np.testing.assert_array_equal(r.output_tokens, ref.generated[b])
        assert r.generated == len(ref.generated[b]) == r.max_new_tokens
        assert r.finish_s >= r.first_token_s >= r.arrival_s
    assert np.isfinite(m.avg_ttft_s) and m.avg_ttft_s >= 0
    assert m.goodput_tps > 0


# ---------------------------------------------------------------------------
# head-of-line blocking
# ---------------------------------------------------------------------------
def test_short_requests_are_not_held_behind_a_long_one(pool):
    """One long request ahead of several short ones: every short request
    gets its first token while the long one is still generating, because
    slots freed by short requests are refilled between cycles."""
    kw = dict(adaptive=False, fixed_chain=("t",), fixed_window=1)
    eng = ServingEngine(pool, "t", batch_size=3, router_kwargs=kw)
    reqs = _hol_workload()
    m = eng.run(reqs)
    long, shorts = reqs[0], reqs[1:]
    assert m.num_requests == len(reqs)
    assert long.generated == long.max_new_tokens
    for r in shorts:
        assert r.generated == r.max_new_tokens
        assert r.first_token_s < long.finish_s
