"""The benchmark's metric arithmetic: a rate over the whole window, drains
included, per-cycle means over the window's cycles, a roofline share, the
program's spans, counters and scoped device time as a run hands them to
its readers, and readers that stay silent where they find nothing to
read."""
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchfix import ROOT
from bench import run as br

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
MS = 1_000_000


def _run(reqs, **kw):
    base = dict(requests=reqs, wall_s=1.0, setup_s=0.0, committed_tokens=0,
                cycles=[], admit_s=[], trace=None,
                target_flops_per_token=0.0, peak_flops=float("nan"))
    base.update(kw)
    return br.Run(**base)


def test_tokens_per_s_counts_the_drain():
    """A window of closed batches ends with the last batch's drain: the
    batch started before the deadline runs to its end and is counted."""
    class Engine:
        def run(self, reqs):
            time.sleep(0.05)
            for r in reqs:
                r.output_tokens = np.zeros(r.max_new_tokens, np.int64)
                r.generated = r.max_new_tokens

    mix = {"mode": "closed_batches", "classes": {"easy": 1},
           "prompt": {"median": 8, "sigma": 0.5, "min": 4, "max": 16,
                      "strata": 2},
           "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16,
                      "strata": 2},
           "pairing": [1, 0]}
    serving = SimpleNamespace(cfg={"planting": {"classes": {
        "easy": [10, 10]}}}, engine=Engine())
    reqs, wall = br.serve_window(serving, mix, 5, 0.12)
    assert len(reqs) >= 2 * 2 and len(reqs) % 2 == 0   # whole batches
    assert wall > 0.12             # the last batch ran past the deadline
    tokens = sum(len(r.output_tokens) for r in reqs)
    rate = br.reader("tokens_per_s", ROOT)(
        _run(reqs, wall_s=wall, committed_tokens=tokens))
    assert rate == pytest.approx(tokens / wall)


def test_cycle_readers_average_over_the_cycles():
    reqs = []
    cycles = [SimpleNamespace(groups=[1], wall_s=0.2, acc_mean=3.0,
                              host_syncs=1),
              SimpleNamespace(groups=[1, 2], wall_s=0.4, acc_mean=1.0,
                              host_syncs=2),
              SimpleNamespace(groups=[], wall_s=9.0, acc_mean=0.0,
                              host_syncs=0)]        # an idle cycle
    run = _run(reqs, cycles=cycles)
    assert br.reader("cycle_ms", ROOT)(run) == pytest.approx(300.0)
    assert br.reader("tokens_per_slot_cycle", ROOT)(run) == 2.0
    assert br.reader("groups_per_cycle", ROOT)(run) == 1.5
    assert br.reader("host_syncs_per_cycle", ROOT)(run) == 1.5


def test_device_readers_are_silent_without_a_device():
    run = _run([], trace={"busy_s": None, "window_s": 1.0})
    assert br.reader("device_idle_share", ROOT)(run) is None
    assert br.reader("mfu", ROOT)(run) is None
    run = _run([], trace={"busy_s": 0.75, "window_s": 1.0},
               committed_tokens=100, target_flops_per_token=1e9,
               peak_flops=1e12)
    assert br.reader("device_idle_share", ROOT)(run) == pytest.approx(25.0)
    assert br.reader("mfu", ROOT)(run) == pytest.approx(10.0)


@pytest.mark.parametrize("name", [m["name"] for m in MANIFEST["per_layer"]])
def test_per_layer_reader_is_silent_on_an_empty_run(name):
    """A reader that finds nothing to read returns nothing (never 0)."""
    run = _run([], wall_s=1.0, trace=None)
    assert br.reader(name, ROOT)(run) is None


def test_roofline_share_is_the_larger_bound_over_the_time():
    from bench.stats import roofline_share
    peaks = {"bf16_flops": 200e12, "hbm_bytes_per_s": 800e9}
    # compute-bound: 0.5 s of operations against 0.125 s of bytes
    assert roofline_share(100e12, 100e9, 1.0, peaks) == pytest.approx(50.0)
    # bandwidth-bound: 0.5 s of bytes against 0.005 s of operations
    assert roofline_share(1e12, 400e9, 2.0, peaks) == pytest.approx(25.0)
    assert roofline_share(1e12, 0.0, 0.0, peaks) is None
    assert roofline_share(0.0, 0.0, 1.0, peaks) is None
    assert roofline_share(1e12, 0.0, 1.0, {}) is None


def test_kv_device_share_is_what_phases_reads_from_the_same_events():
    """The reader of ``Run.scoped_busy`` gives the share that
    ``bench/phases.py`` computes from the same device ops: exclusive time
    under ``kv_gather``/``kv_write`` at any depth over all busy time."""
    from bench import phases as ph
    path = "jit(fused_1L_w1_p2)/decode/while/body"
    dev = [[("while.13", 0, 10 * MS, "jit(fused_1L_w1_p2)/decode/while"),
            ("fusion.1", 1 * MS, 3 * MS, f"{path}/kv_gather/gather"),
            ("fusion.3", 8 * MS, 1 * MS, f"{path}/kv_write/scatter"),
            ("fusion.2", 5 * MS, 2 * MS, "jit(fused_1L_w1_p2)/add"),
            ("copy.193", 12 * MS, 4 * MS, "")]]
    spans = [("window", 0, 20 * MS)]
    run = _run([], scoped_busy=ph.scoped_busy(dev, spans,
                                              key=lambda p: p))
    busy = ph.scoped_busy(dev, spans, key=lambda p: "busy")["busy"]
    kv = sum(v for k, v in ph.scoped_busy(dev, spans, key=ph.kv_scope)
             .items() if k in ph.KV_SCOPES)
    share = br.reader("kv_device_share", ROOT)(run)
    assert share == pytest.approx(100.0 * kv / busy)
    assert share == pytest.approx(100.0 * 4 / 14)
    # ops with no scope path, or none under the KV scopes: silent
    assert br.reader("kv_device_share", ROOT)(
        _run([], scoped_busy={"": 0.5})) is None
    assert br.reader("kv_device_share", ROOT)(
        _run([], scoped_busy={"jit(f)/decode/add": 0.5})) is None


def test_run_carries_the_windows_spans_and_counters(tiny_tree, capsys):
    """A traced tiny run hands its readers what the window added to the
    program's span table and counters (one ``cycle`` span per cycle the
    benchmark recorded); the CPU's trace holds no device op, so
    ``scoped_busy`` is empty and ``kv_device_share`` stays silent."""
    (tiny_tree / "bench/metrics/cycle_spans_per_report.py").write_text(
        "def read(run):\n"
        "    return run.spans['cycle'][0] / len(run.cycles)\n")
    (tiny_tree / "bench/metrics/host_syncs_counted.py").write_text(
        "def read(run):\n"
        "    return run.counters.get('host_sync') or None\n")
    (tiny_tree / "bench/metrics/scope_paths.py").write_text(
        "def read(run):\n"
        "    return len(run.scoped_busy) or None\n")
    m = json.loads((tiny_tree / "BENCHMARK.json").read_text())
    for name in ("cycle_spans_per_report", "host_syncs_counted",
                 "scope_paths"):
        m["per_layer"].append({"name": name, "unit": "x",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "engine", "moves": "setup_s"})
    (tiny_tree / "BENCHMARK.json").write_text(json.dumps(m))
    rc = br.run_cell("tiny.closed", 2**33 + 3, 1.0, True, require_tpu=False,
                     root=tiny_tree, cache_dir=None)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["correct"]
    got = out["metrics"]
    assert got["cycle_spans_per_report"]["value"] == 1.0
    assert got["host_syncs_counted"]["value"] >= 1
    assert "scope_paths" not in got and "kv_device_share" not in got
