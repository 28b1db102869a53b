"""The trace reduction: busy time, idle share and the breakdown from a
small recorded trace."""
import jax
import jax.numpy as jnp
import pytest

import benchfix  # noqa: F401  (repository root on the import path)
from bench import tracereduce as tr

MS = 1_000_000


def test_reduce_gives_busy_idle_and_breakdown():
    # device ops: a 0-10 ms, b 5-20 ms (overlapping a), a 30-40 ms;
    # window 0-50 ms; the host ran a cycle over 20-30 and an admission
    # over 40-50
    dev = [[("a", 0, 10 * MS), ("b", 5 * MS, 15 * MS),
            ("a", 30 * MS, 10 * MS), ("late", 60 * MS, 5 * MS)]]
    spans = [("window", 0, 50 * MS), ("run_cycle", 19 * MS, 12 * MS),
             ("admit", 40 * MS, 10 * MS)]
    out = tr.reduce(dev, spans)
    assert out["window_s"] == pytest.approx(0.050)
    assert out["busy_s"] == pytest.approx(0.030)     # union, clipped
    # exclusive time: the first a gives up the 5 ms that b shares with it
    assert dict((k, v) for k, v in out["device_ops"]) == pytest.approx(
        {"a": 0.015, "b": 0.015})
    assert out["idle_gaps"] == [["run_cycle", pytest.approx(0.010)],
                                ["admit", pytest.approx(0.010)]]


def test_reduce_counts_a_loop_and_its_ops_once():
    """``device_ops`` gives each op its exclusive time, as
    ``phases.scoped_busy`` does: a loop less the fusions it runs."""
    dev = [[("while.13", 0, 10 * MS), ("fusion.1", 1 * MS, 3 * MS),
            ("fusion.2", 5 * MS, 2 * MS), ("copy.1", 12 * MS, 4 * MS)]]
    out = tr.reduce(dev, [("window", 0, 15 * MS)])
    ops = dict((k, v) for k, v in out["device_ops"])
    assert ops == pytest.approx({"while.13": 0.005, "fusion.1": 0.003,
                                 "fusion.2": 0.002, "copy.1": 0.003})
    assert sum(ops.values()) == pytest.approx(out["busy_s"])
    assert [k for k, _ in out["device_ops"]][0] == "while.13"


def test_reduce_averages_busy_over_chips():
    spans = [("window", 0, 10 * MS)]
    out = tr.reduce([[("x", 0, 10 * MS)], [("x", 0, 5 * MS)]], spans)
    assert out["busy_s"] == pytest.approx(0.0075)


def test_reduce_names_uncovered_gaps_engine():
    out = tr.reduce([[("x", 0, 1 * MS)]], [("window", 0, 4 * MS)])
    assert out["idle_gaps"] == [["engine", pytest.approx(0.003)]]


def test_recorded_trace_is_read(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("run_cycle"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    devices, spans, layout = tr.read_xplane(str(tmp_path), ("run_cycle",))
    names = {s[0] for s in spans}
    assert {"window", "run_cycle"} <= names
    assert all(name.startswith("/device:") for name in layout)
    out = tr.reduce(devices, spans)
    assert out["window_s"] > 0
    if not devices:                 # the CPU has no device op line
        assert out["busy_s"] is None
