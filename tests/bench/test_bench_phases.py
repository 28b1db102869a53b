"""Where a traced run's time goes: idle time by the innermost program span,
device time by name scope (exclusive of nested ops), the two readers of
the fused cycle's spans and counters, and ``bench/phases.py`` end to end
on a tiny cell."""
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchfix import ROOT
from bench import phases as ph
from bench import run as br
from bench import tracereduce as tr

MS = 1_000_000


def _run(cycles):
    return br.Run(requests=[], wall_s=1.0, setup_s=0.0, committed_tokens=0,
                  cycles=cycles, admit_s=[], trace=None,
                  target_flops_per_token=0.0, peak_flops=float("nan"))


def test_idle_by_span_splits_each_gap_by_the_innermost_span():
    # device busy 0-10 and 30-40 ms of a 0-50 ms window; the host ran a
    # cycle over 5-45 with a wait inside it over 8-32 and a mirror over
    # 32-36; 45-50 is covered by the window alone
    dev = [[("a", 0, 10 * MS), ("a", 30 * MS, 10 * MS)]]
    spans = [("window", 0, 50 * MS), ("cycle", 5 * MS, 40 * MS),
             ("cycle.wait", 8 * MS, 24 * MS),
             ("cycle.mirror", 32 * MS, 4 * MS)]
    out = ph.idle_by_span(dev, spans)
    assert out == {"cycle.wait": pytest.approx(0.020),
                   "cycle": pytest.approx(0.005),
                   "engine": pytest.approx(0.005)}
    assert list(out)[0] == "cycle.wait"              # largest first


def test_program_spans_of_a_tiny_pool_are_traced_and_name_idle(tmp_path):
    """A session's ``run_cycle`` leaves the program's own spans in a CPU
    trace, inside the window span; a gap laid under them is named by
    them."""
    from repro.core import ChainRouter, ModelPool
    from repro.models import ModelConfig
    from repro.models.model import LanguageModel
    pool = ModelPool()
    cfg = ModelConfig(name="t", arch_type="dense", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=61,
                      dtype=jnp.float32)
    lm = LanguageModel(cfg)
    params, axes = lm.init(jax.random.PRNGKey(0))
    pool.register(cfg, params=params, param_axes=axes)
    r = ChainRouter(pool, "t", adaptive=False, fixed_chain=("t",),
                    fixed_window=1)
    sess = r.start_session(1, 64, session_id="tr")
    sess.admit(0, np.arange(1, 7, dtype=np.int64), 8)
    sess.run_cycle()                   # the per-op profiling cycle
    sess.run_cycle()                   # compiles the fused program
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        sess.run_cycle()
    jax.profiler.stop_trace()
    sess.close()
    _, spans, _ = tr.read_xplane(str(tmp_path), ph.PROGRAM_SPANS)
    win = next(s for s in spans if s[0] == "window")
    names = {s[0] for s in spans
             if win[1] <= s[1] and s[1] + s[2] <= win[1] + win[2]}
    assert {"cycle", "cycle.schedule", "cycle.prepare", "cycle.dispatch",
            "cycle.wait", "cycle.mirror", "cycle.finish"} <= names
    # a synthetic device that ran only before and after the wait
    wait = next(s for s in spans if s[0] == "cycle.wait")
    dev = [[("x", win[1], wait[1] - win[1]),
            ("x", wait[1] + wait[2], win[1] + win[2] - wait[1] - wait[2])]]
    out = ph.idle_by_span(dev, spans)
    assert list(out) == ["cycle.wait"]
    assert out["cycle.wait"] == pytest.approx(wait[2] / 1e9)


def test_scoped_busy_counts_exclusive_time():
    # a 10-ms loop under "decode" runs two fusions (3 ms under kv_gather,
    # 2 ms unscoped inside it); a copy with no scope runs after it
    path = "jit(fused_1L_w1_p2)/decode/while/body"
    dev = [[("while.13", 0, 10 * MS, f"{path[:-11]}/while"),
            ("fusion.1", 1 * MS, 3 * MS, f"{path}/kv_gather/gather"),
            ("fusion.2", 5 * MS, 2 * MS, "jit(fused_1L_w1_p2)/add"),
            ("copy.193", 12 * MS, 4 * MS, "")]]
    spans = [("window", 0, 20 * MS)]
    assert ph.scoped_busy(dev, spans) == {
        "decode": pytest.approx(0.008), "unscoped": pytest.approx(0.006)}
    assert ph.scoped_busy(dev, spans, key=ph.kv_scope) == {
        "other": pytest.approx(0.011), "kv_gather": pytest.approx(0.003)}
    # clipped to the window: only the last 2 ms of the copy fall inside
    assert ph.scoped_busy(dev, [("window", 14 * MS, 6 * MS)]) == {
        "unscoped": pytest.approx(0.002)}


def test_scope_names_from_op_paths():
    assert ph.top_scope("jit(fused_3L_w4_p2)/verify.2/while/body/dot:") \
        == "verify.2"
    assert ph.top_scope("jit(body)/jit(main)/commit/select_n") == "commit"
    assert ph.top_scope("jit(fused_1L_w1_p2)/copy") == "unscoped"
    assert ph.top_scope("") == "unscoped"
    # a per-op program has loops and einsums but no phase scope
    assert ph.top_scope("jit(f)/while/body/closed_call/add:") == "unscoped"
    assert ph.top_scope("jit(f)/...d,df->...f/dot_general") == "unscoped"
    assert ph.kv_scope("jit(f)/decode/while/body/kv_write/scatter") \
        == "kv_write"
    assert ph.kv_scope("jit(f)/decode/while/body/dot_general") == "other"


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "jit(f)/commit/select_n" } }
  event_metadata { key: 7 value { id: 7
    name: "%fusion.1 = bf16[2048,20,128]{2,1,0} fusion(a)"
    stats { metadata_id: 1
            str_value: "jit(f)/decode/while/body/kv_gather/gather" } } }
  event_metadata { key: 8 value { id: 8 name: "%fusion.2 = f32[2] fusion(b)"
    stats { metadata_id: 1 ref_value: 2 } } }
  event_metadata { key: 9 value { id: 9
    name: "%copy.193 = bf16[40,2048,20,128]{3,2,1,0} copy(c)" } }
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 8 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 9 offset_ps: 6000000 duration_ps: 2000000 } }
}
planes { id: 2 name: "/host:CPU" lines { id: 1 name: "python" } }
"""


def test_read_scoped_takes_op_paths_from_event_metadata(tmp_path):
    """A device op's path is a stat of its event metadata, held as a
    string or as a reference to a stat name; an op without one reads
    ``""``, which ``top_scope`` counts as unscoped."""
    from jax.profiler import ProfileData
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "h.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    devices, stat = ph.read_scoped(str(tmp_path))
    assert stat == "tf_op"
    assert devices == [[
        ("fusion.1 bf16[2048,20,128]", 1000, 3000,
         "jit(f)/decode/while/body/kv_gather/gather"),
        ("fusion.2 f32[2]", 5000, 1000, "jit(f)/commit/select_n"),
        ("copy.193 bf16[40,2048,20,128]", 7000, 2000, "")]]
    spans = [("window", 0, 10_000)]
    assert ph.scoped_busy(devices, spans) == {
        "decode": pytest.approx(3e-6), "unscoped": pytest.approx(2e-6),
        "commit": pytest.approx(1e-6)}


def test_span_delta_keeps_what_the_window_added():
    before = {"cycle": [2, 0.5, 0.01]}
    after = {"cycle": [5, 1.25, 0.04], "cycle.wait": [3, 0.6, 0.6]}
    out = ph.span_delta(before, after)
    assert out["cycle"] == [3, pytest.approx(0.75), pytest.approx(0.03)]
    assert out["cycle.wait"] == [3, 0.6, 0.6]
    assert ph.span_delta(after, after) == {}


def test_cycle_host_ms_reads_wall_less_wait():
    cycles = [SimpleNamespace(groups=[1], wall_s=0.025, wait_s=0.020,
                              per_op_groups=0),
              SimpleNamespace(groups=[1], wall_s=0.030, wait_s=0.021,
                              per_op_groups=1),
              SimpleNamespace(groups=[], wall_s=9.0, wait_s=0.0,
                              per_op_groups=0)]        # an idle cycle
    assert br.reader("cycle_host_ms", ROOT)(_run(cycles)) == \
        pytest.approx(7.0)


def test_fused_fallback_share_reads_per_op_groups_over_groups():
    cycles = [SimpleNamespace(groups=[1], wall_s=0.1, wait_s=0.0,
                              per_op_groups=1)] + \
        [SimpleNamespace(groups=[1, 2], wall_s=0.1, wait_s=0.0,
                         per_op_groups=0)] * 7 + \
        [SimpleNamespace(groups=[], wall_s=0.0, wait_s=0.0,
                         per_op_groups=0)]
    assert br.reader("fused_fallback_share", ROOT)(_run(cycles)) == \
        pytest.approx(100.0 / 15)


@pytest.mark.parametrize("name", ["cycle_host_ms", "fused_fallback_share"])
def test_new_readers_are_silent_on_reports_without_the_fields(name):
    """Cycle reports of a program that has neither field read nothing."""
    cycles = [SimpleNamespace(groups=[1], wall_s=0.025, host_syncs=1)] * 3
    assert br.reader(name, ROOT)(_run(cycles)) is None


def test_phases_runs_a_tiny_cell_end_to_end(tiny_tree, capsys):
    """The traced run with the program's span table and trace reading; on
    the CPU the trace holds no device ops, so nothing is read from it."""
    rc = ph.main(["--workload", "tiny.closed", "--seed", str(2**31 + 5),
                  "--seconds", "0.5"], require_tpu=False, root=tiny_tree,
                 cache_dir=None)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    spans = json.loads(next(x for x in lines if x.startswith("spans "))[6:])
    assert spans["cycles"] > 0
    assert spans["spans"]["cycle.wait"][0] >= spans["cycles"]
    got = json.loads(next(x for x in lines if x.startswith("phases "))[7:])
    assert got["scope_stat"] is None and got["kv_device_share"] is None
    result = json.loads(lines[-1])
    assert result["correct"]
    assert {"cycle_host_ms", "fused_fallback_share"} <= set(
        result["metrics"])
    assert br.SPANS == ("admit", "run_cycle", "retire")   # restored
