"""Serving engine integration: Poisson workload through SpecRouter with
metric sanity (uses a tiny random pool — fast)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ModelPool
from repro.data import CorpusConfig, SyntheticCorpus, make_workload
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


def test_engine_end_to_end(pool):
    corpus = SyntheticCorpus(CorpusConfig(vocab_size=64))
    reqs = make_workload(corpus, "gsm8k", rate_rps=2.0, duration_s=3.0,
                         seed=2, scale=0.08, max_prompt=16, max_out=8)
    assert len(reqs) >= 2
    eng = ServingEngine(pool, "t", batch_size=3, slo_latency_s=120.0,
                        router_kwargs=dict(adaptive=True))
    m = eng.run(reqs)
    assert m.num_requests == len(reqs)
    assert m.total_tokens > 0
    assert m.goodput_tps > 0
    assert np.isfinite(m.avg_ttft_s) and m.avg_ttft_s >= 0
    assert 0.0 <= m.slo_attainment <= 1.0
    for r in reqs:
        assert r.finish_s >= r.first_token_s >= r.arrival_s
        assert 0 < r.generated <= r.max_new_tokens


def test_metrics_nan_safe_on_degenerate_runs(pool):
    """Empty done-set and zero makespan must yield NaN-safe metrics, not
    ZeroDivisionError / ValueError on max() of an empty sequence."""
    from repro.data.workload import Request
    eng = ServingEngine(pool, "t")
    m0 = eng._metrics([], [])
    assert m0.num_requests == 0 and m0.total_tokens == 0
    assert np.isnan(m0.goodput_tps) and np.isnan(m0.avg_ttft_s)

    # single instant request: finish == arrival -> makespan == 0
    r = Request("r0", 1.0, np.array([1, 2]), 4, "synthetic",
                start_s=1.0, first_token_s=1.0, finish_s=1.0, generated=4)
    m1 = eng._metrics([r], [1.0])
    assert m1.makespan_s == 0.0
    assert np.isnan(m1.goodput_tps) and np.isnan(m1.request_throughput_rps)
    assert m1.num_requests == 1 and m1.total_tokens == 4
    assert np.isfinite(m1.avg_ttft_s)


def test_termination_scans_only_new_commits(pool):
    """The EOS scan is bounded to this cycle's commits: a token equal to
    EOS sitting in the already-scanned region is never re-examined (and
    the full-scan fallback without scan_from still finds it)."""
    from repro.core import ChainRouter
    router = ChainRouter(pool, "t", eos_token=9)
    seq = np.zeros((1, 32), np.int32)
    seq[0, :8] = [1, 2, 3, 9, 5, 6, 7, 8]     # "EOS" at committed pos 3
    seq_len = np.array([8], np.int64)
    prompt = np.array([2], np.int64)
    budget = np.array([20], np.int64)
    active = np.array([True])
    # scan_from = 7: only the last commit (token 8) is examined
    router._apply_termination(seq, seq_len, prompt, budget, active,
                              scan_from=np.array([7]))
    assert active[0] and seq_len[0] == 8
    # fallback full scan (no scan_from) finds the stale EOS
    router._apply_termination(seq, seq_len, prompt, budget, active)
    assert not active[0] and seq_len[0] == 2 + 2


def test_engine_batches_respect_arrival_order(pool):
    corpus = SyntheticCorpus(CorpusConfig(vocab_size=64))
    reqs = make_workload(corpus, "mgsm", rate_rps=3.0, duration_s=2.0,
                         seed=5, scale=0.08, max_prompt=12, max_out=6)
    eng = ServingEngine(pool, "t", batch_size=2,
                        router_kwargs=dict(adaptive=False,
                                           fixed_chain=("t",),
                                           fixed_window=1))
    m = eng.run(reqs)
    starts = [r.start_s for r in sorted(reqs, key=lambda r: r.arrival_s)]
    assert all(b >= a - 1e-9 for a, b in zip(starts, starts[1:]))
