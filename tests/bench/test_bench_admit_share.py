"""``admit_share``: the share of the window's host wall time spent in
``RouterSession.admit``, its arithmetic, its silence where a run kept no
admission times, and a traced tiny run in which every admission forward
computes the one row it admits (``admission_rows`` over ``admit.<m>``)."""
import json

import pytest

from benchfix import ROOT
from bench import run as br

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


def _run(**kw):
    base = dict(requests=[], wall_s=2.0, setup_s=0.0, committed_tokens=0,
                cycles=[], admit_s=[], trace=None,
                target_flops_per_token=0.0, peak_flops=float("nan"))
    base.update(kw)
    return br.Run(**base)


def test_admit_share_is_admission_time_over_the_window():
    read = br.reader("admit_share", ROOT)
    assert read(_run(admit_s=[0.1, 0.2, 0.1])) == pytest.approx(20.0)
    assert read(_run(admit_s=[0.5], wall_s=0.5)) == pytest.approx(100.0)


@pytest.mark.parametrize("kw", [dict(admit_s=[]), dict(wall_s=0.0,
                                                        admit_s=[0.1])])
def test_admit_share_is_silent_without_admission_times(kw):
    assert br.reader("admit_share", ROOT)(_run(**kw)) is None


def test_admit_share_is_declared_for_both_cells():
    entry = next(m for m in MANIFEST["per_layer"]
                 if m["name"] == "admit_share")
    assert entry["layer"] == "admission" and entry["better"] == "lower"
    assert entry["source"] == "host_clock"
    assert entry["moves"] == "tokens_per_s"
    assert sorted(entry["workloads"]) == sorted(
        w["name"] for w in MANIFEST["workloads"])


def test_traced_run_reports_admit_share_and_one_row_per_admission(
        tiny_tree, capsys, monkeypatch):
    """A traced tiny run with its router pinned target-only, as the
    Qwen3-Next cell's is: admission takes a share of the window, and the
    window's admission forwards computed one row for each row admitted.
    The device trace stops inside the one-second window, as it does inside
    a cell's longer one, so the window's wall time leaves out only what
    it held."""
    monkeypatch.setattr(br.Recorder, "TRACE_SECONDS", 0.0)
    cfg_path = tiny_tree / "bench/configs/tiny.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["router"] = {"adaptive": False, "fixed_chain": ["tiny-target"],
                     "fixed_window": 1}
    cfg_path.write_text(json.dumps(cfg))
    (tiny_tree / "bench/metrics/rows_per_admitted_row.py").write_text(
        "def read(run):\n"
        "    admitted = sum(v for k, v in run.counters.items()\n"
        "                   if k.startswith('admit.'))\n"
        "    rows = run.counters.get('admission_rows', 0.0)\n"
        "    return rows / admitted if admitted else None\n")
    m = json.loads((tiny_tree / "BENCHMARK.json").read_text())
    m["per_layer"].append({"name": "rows_per_admitted_row", "unit": "x",
                           "better": "lower", "source": "program_counter",
                           "layer": "admission", "moves": "setup_s"})
    (tiny_tree / "BENCHMARK.json").write_text(json.dumps(m))
    rc = br.run_cell("tiny.closed", 2**33 + 5, 1.0, True, require_tpu=False,
                     root=tiny_tree, cache_dir=None)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["correct"]
    got = out["metrics"]
    assert 0.0 < got["admit_share"]["value"] < 100.0
    assert got["rows_per_admitted_row"]["value"] == 1.0
