"""The profiler's spans and counters inside the serving cycle: span
counts, seconds and self seconds nest; a wait on the device is a
``cycle.wait`` span that counts one ``host_sync``; the cycle's phase
spans, its report's wait time and per-op groups; the fused path's
fallbacks by reason; the engine's spans; and fused programs named by
their key."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ChainRouter, ModelPool, PerformanceProfiler,
                        TokenTree)
from repro.models import ModelConfig
from repro.models.model import LanguageModel


@pytest.fixture(scope="module")
def pool():
    p = ModelPool()
    for (n, L, d, s) in [("d", 2, 32, 1), ("t", 3, 48, 2)]:
        cfg = ModelConfig(name=n, arch_type="dense", num_layers=L,
                          d_model=d, num_heads=4, num_kv_heads=2,
                          d_ff=2 * d, vocab_size=61, dtype=jnp.float32)
        lm = LanguageModel(cfg)
        params, axes = lm.init(jax.random.PRNGKey(s))
        p.register(cfg, params=params, param_axes=axes)
    return p


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, 61, n).astype(np.int64)


def test_spans_accumulate_and_nest():
    prof = PerformanceProfiler()
    for _ in range(3):
        with prof.span("outer", model="m"):
            time.sleep(0.002)
            with prof.span("inner"):
                time.sleep(0.004)
            with prof.span("inner"):
                time.sleep(0.004)
    n, secs, self_s = prof.spans["outer"]
    ni, secs_i, self_i = prof.spans["inner"]
    assert (n, ni) == (3, 6)
    assert secs_i == pytest.approx(self_i)          # a leaf is all self
    assert secs >= secs_i + 0.006
    assert self_s == pytest.approx(secs - secs_i)
    assert not prof._open                           # every span closed
    # the EMAs and counters still see every record
    for i in range(100):
        prof.record("decode1", "m", 0.001 * i)
    assert prof.counters["decode1.m.calls"] == 100


def test_span_closes_on_error_and_timed_is_an_op_span():
    prof = PerformanceProfiler()
    with pytest.raises(RuntimeError):
        with prof.span("outer"):
            with prof.wait():
                raise RuntimeError("device lost")
    assert not prof._open
    assert prof.spans["cycle.wait"][0] == 1
    assert prof.counters["host_sync"] == 1
    with prof.timed("prefill", "m", tokens=5):
        with prof.wait():
            pass
    assert prof.spans["op.prefill"][0] == 1
    assert prof.emas[("prefill", "m")].count == 1
    assert prof.counters["prefill.m.tokens"] == 5
    assert prof.counters["host_sync"] == 2


def test_cycle_phases_wait_time_and_syncs(pool):
    """Each fused cycle is a ``cycle`` span with one child per phase; its
    report's ``wait_s`` lies within ``wall_s`` and its ``host_syncs``
    equals the ``cycle.wait`` spans opened in it."""
    r = ChainRouter(pool, "t", adaptive=False, fixed_chain=("d", "t"),
                    fixed_window=3)
    prof = r.profiler
    sess = r.start_session(2, 96, session_id="ph")
    sess.admit(0, _prompt(0, 7), 12)
    sess.admit(1, _prompt(1, 5), 12)
    reports = []
    while sess.active.any():
        waits0 = prof.spans.get("cycle.wait", [0])[0]
        rep = sess.run_cycle()
        reports.append(rep)
        assert 0.0 < rep.wait_s <= rep.wall_s
        assert rep.host_syncs == prof.spans["cycle.wait"][0] - waits0
    assert reports[0].per_op_groups == 1 and not reports[0].fused
    assert all(rep.fused and rep.per_op_groups == 0
               for rep in reports[1:])
    n = len(reports)
    assert prof.spans["cycle"][0] == n
    for phase in ("cycle.schedule", "cycle.prepare", "cycle.finish"):
        assert prof.spans[phase][0] == n
    for phase in ("cycle.dispatch", "cycle.mirror"):
        assert prof.spans[phase][0] == n - 1
    assert prof.spans["cycle.per_op"][0] == 1
    assert prof.counters["groups"] == n
    # the phases hold all but the loop's own bookkeeping
    assert prof.spans["cycle"][2] < prof.spans["cycle"][1]
    sess.close()


def test_fallback_counters_by_reason(pool):
    """The first cycle of a session and every ``profile_every``-th one run
    per-op (``fallback.profiling``); a chain with no timing yet falls back
    in its first fused cycle (``fallback.untimed``)."""
    r = ChainRouter(pool, "t", adaptive=False, fixed_chain=("t",),
                    fixed_window=1, profile_every=16)
    c = r.profiler.counters
    sess = r.start_session(2, 128, session_id="fb")
    sess.admit(0, _prompt(2, 6), 40)
    for _ in range(17):
        sess.run_cycle()
    assert c["fallback.profiling"] == 2          # cycles 0 and 16
    assert c["groups"] == 17
    assert c.get("fallback.untimed", 0) == 0
    # a slot pinned to a chain whose draft has never run: its first group
    # cannot be priced, so it runs per-op while the target-only slot fuses
    sess.admit(1, _prompt(3, 6), 8, chain=("d", "t"), window=2)
    rep = sess.run_cycle()
    assert c["fallback.untimed"] == 1
    assert rep.per_op_groups == 1 and len(rep.groups) == 2
    rep = sess.run_cycle()
    assert c["fallback.untimed"] == 1 and rep.per_op_groups == 0
    sess.close()


def test_engine_spans_cover_queue_collect_and_retire(pool):
    from repro.data.workload import Request
    from repro.serving import ServingEngine
    eng = ServingEngine(pool, "t", batch_size=2, router_kwargs=dict(
        adaptive=False, fixed_chain=("d", "t"), fixed_window=2))
    reqs = [Request(request_id=f"r{i}", arrival_s=0.0,
                    prompt=_prompt(10 + i, 6), max_new_tokens=6,
                    dataset="test")
            for i in range(3)]
    eng.run(reqs)
    spans = eng._router.profiler.spans
    cycles = spans["cycle"][0]
    assert spans["serve.queue"][0] == spans["serve.collect"][0] == cycles
    assert spans["serve.retire"][0] == spans["serve.admit"][0] == 3
    # retirement nests inside the collect pass
    assert spans["serve.collect"][1] >= spans["serve.retire"][1]


def test_fused_programs_are_named_by_their_key(pool):
    r = ChainRouter(pool, "t", adaptive=False, fixed_chain=("d", "t"),
                    fixed_window=3)
    out = r.generate(_prompt(4, 6)[None, :], np.array([6]), 10,
                     request_id="nm")
    assert out.committed_tokens > 0
    names = {prog.__name__ for k, prog in r.executor._jit_cache.items()
             if k[0] == "fusedcycle"}
    assert names and all(n.startswith("fused_2L_w3_p") for n in names)
    tree = r.executor._fused_program(("d", "t"), 2, TokenTree((2, 1)),
                                     True, 1.0, 4, -1)
    assert tree.__name__ == "fused_2L_t2x1_p4"
