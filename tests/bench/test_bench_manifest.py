"""``BENCHMARK.json`` keeps to its contract, and a configuration, a
traffic mix and a per-layer metric are each added as a new file plus a new
manifest entry, with no existing file edited."""
import json
import re
import shutil

from benchfix import ROOT
from bench import run as br

M = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_manifest_keys_and_names():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["command"] == ["python3", "bench/run.py"]
    for p in M["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= M["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in M[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in M["paths"]))
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (ROOT / "bench/traffic" / f"{w['traffic']}.json").is_file()
    assert len(M["workloads"]) == len({(w["config"], w["traffic"])
                                       for w in M["workloads"]})


def test_metrics_have_bounds_readers_and_cells():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in M["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in M["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        for w in m["workloads"]:     # the cell reports what it moves
            assert "workloads" not in e2e[m["moves"]] or \
                w in e2e[m["moves"]]["workloads"]
    for m in M["end_to_end"] + M["per_layer"]:
        br.reader(m["name"])         # every metric has a reader
    for w in M["workloads"]:
        _, _, _, ends, layers = br.load_cell(w["name"])
        got = {m["name"] for m in ends}
        assert "setup_s" in got and len(got) >= 2 and layers


def test_full_check_fits_its_time_budget():
    runs = 2 + 14 * 24
    total = runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_new_config_mix_and_metric_need_no_edit(tiny_tree, capsys):
    """A new cell from three new files and new manifest entries only."""
    before = {p: p.read_bytes() for p in (tiny_tree / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((tiny_tree / "bench/configs/tiny.json").read_text())
    cfg["name"] = "tiny2"
    cfg["slots"] = 2
    (tiny_tree / "bench/configs/tiny2.json").write_text(json.dumps(cfg))
    shutil.copy(tiny_tree / "bench/traffic/tiny-closed.json",
                tiny_tree / "bench/traffic/tiny-closed2.json")
    (tiny_tree / "bench/metrics/cycles_seen.py").write_text(
        "def read(run):\n    return len(run.cycles) or None\n")
    m = json.loads((tiny_tree / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny2", "source": "test",
                         "file": "bench/configs/tiny2.json", "reduced": [],
                         "why": "dummy"})
    m["workloads"].append({"name": "tiny2.closed2", "config": "tiny2",
                           "traffic": "tiny-closed2", "chips": 1,
                           "why": "dummy"})
    m["per_layer"].append({"name": "cycles_seen", "unit": "cycles",
                           "better": "higher", "source": "program_counter",
                           "layer": "engine", "moves": "setup_s",
                           "workloads": ["tiny2.closed2"]})
    (tiny_tree / "BENCHMARK.json").write_text(json.dumps(m))
    assert all(p.read_bytes() == b for p, b in before.items())
    rc = br.run_cell("tiny2.closed2", 7, 1.0, True, require_tpu=False,
                     root=tiny_tree, cache_dir=None)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["correct"]
    assert out["metrics"]["cycles_seen"]["value"] > 0
    assert list(out)[-1] == "checks"


def test_run_without_a_chip_exits_nonzero_with_no_result(tmp_path):
    """In a directory holding only the manifest and the benchmark's own
    files, and with no TPU, a run fails and prints no result line."""
    import os
    import subprocess
    import sys
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in M["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    cell = M["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, *M["command"][1:], "--workload", cell, "--seed",
         str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
