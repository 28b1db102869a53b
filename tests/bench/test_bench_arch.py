"""Architectures are modules that a configuration names: the Qwen2 module
makes the same weights and counts the same FLOPs as the harness did before
it was split out, only its module reads Qwen2's keys, and a new
architecture with its configuration, traffic mix and cell runs from new
files alone."""
import copy
import hashlib
import importlib.util
import json
import shutil
import sys

import numpy as np
import pytest

from benchfix import ROOT, tiny_config
from bench import run as br
from bench import weights as wt

ARCHS = sorted(p.stem for p in (ROOT / "bench/arch").glob("*.py")
               if p.stem != "__init__")
INTERFACE = ("program_config", "make_weights", "to_program",
             "flops_per_token", "published_params")
# sha256 of every leaf (sorted by name: name, shape, bytes) of the tiny
# pool's weights for seed 2**40 + 7, and forward FLOPs per token at
# contexts 0, 37.5 and 1000.25, as the harness gave them before the
# architecture was a module of its own
WEIGHTS_SHA256 = {
    "tiny-draft":
        "5eb9615307cb6aa014429bfc3923c9880995d7289318b4f79eadbc5f005a4b2f",
    "tiny-target":
        "d7ee26ca041672bb7619b65b8ab321cc69e7b4c99dd80402377c02e7e40f3ec8"}
CONTEXTS = (0.0, 37.5, 1000.25)
FLOPS = {"tiny-draft": [688128.0, 707328.0, 1200256.0],
         "tiny-target": [2031616.0, 2089216.0, 3568000.0],
         "qwen1.5-0.5b": [927727616.0, 931414016.0, 1026056192.0],
         "qwen1.5-1.8b": [3050831872.0, 3058204672.0, 3247489024.0],
         "qwen1.5-4b": [7121797120.0, 7137157120.0, 7531499520.0]}
QWEN2_KEYS = ("hidden_size", "num_hidden_layers", "intermediate_size",
              "qkv_bias", 'arch_type="dense"')


def _sha256(w):
    h = hashlib.sha256()
    for k in sorted(w):
        a = np.asarray(w[k])
        h.update(k.encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", ARCHS)
def test_arch_module_has_the_interface(name):
    mod = br.arch_module(name)
    for fn in INTERFACE:
        assert callable(getattr(mod, fn)), fn
    assert mod.REFERENCE.__name__.startswith("bench.reference.")
    assert callable(mod.REFERENCE.hidden) and callable(mod.REFERENCE.logits)


def test_qwen2_dense_weights_are_bit_identical_to_the_pinned_ones():
    cfg = tiny_config()
    arch = br.arch_module("qwen2_dense")
    for i, m in enumerate(cfg["members"]):
        w = arch.make_weights(m["config"], cfg["planting"], m["planted"],
                              wt.member_key(2**40 + 7, i))
        assert _sha256(w) == WEIGHTS_SHA256[m["name"]], m["name"]


def test_qwen2_dense_flops_are_the_pinned_ones():
    arch = br.arch_module("qwen2_dense")
    real = json.loads((ROOT / "bench/configs/qwen1.5-0.5b-1.8b-4b.json")
                      .read_text())
    for m in tiny_config()["members"] + real["members"]:
        assert [arch.flops_per_token(m["config"], c) for c in CONTEXTS] \
            == FLOPS[m["name"]], m["name"]


def test_only_the_qwen2_modules_read_qwen2_keys():
    allowed = {ROOT / "bench/arch/qwen2_dense.py",
               ROOT / "bench/reference/qwen2_dense.py"}
    for p in sorted((ROOT / "bench").rglob("*.py")):
        if p in allowed:
            continue
        text = p.read_text()
        found = [k for k in QWEN2_KEYS if k in text]
        assert not found, (str(p.relative_to(ROOT)), found)


RECORDED = '''"""qwen2_dense, recording each call of its interface."""
import types
from pathlib import Path

from bench.arch import qwen2_dense as base

LOG = Path(__file__).with_suffix(".calls")


def _record(name):
    with LOG.open("a") as f:
        f.write(name + "\\n")


def program_config(member):
    _record("program_config")
    return base.program_config(member)


def make_weights(hf, planting, planted, key):
    _record("make_weights")
    return base.make_weights(hf, planting, planted, key)


def to_program(w):
    _record("to_program")
    return base.to_program(w)


def flops_per_token(hf, context):
    _record("flops_per_token")
    return base.flops_per_token(hf, context)


def published_params(hf):
    _record("published_params")
    return base.published_params(hf)


def _hidden(w, hf, tokens, quant=None):
    _record("REFERENCE.hidden")
    return base.REFERENCE.hidden(w, hf, tokens, quant)


REFERENCE = types.ModuleType("bench.reference.qwen2_recorded")
REFERENCE.hidden = _hidden
REFERENCE.logits = base.REFERENCE.logits
'''


def _metric(manifest, name):
    return next(m for m in manifest["end_to_end"] if m["name"] == name)


def test_a_new_architecture_and_its_cell_need_new_files_only(
        tmp_path, capsys, monkeypatch):
    """In a copy of the benchmark, a second architecture module, a
    configuration naming it, a traffic mix and a cell run through
    ``load_cell``, ``Serving`` and ``judge`` on the CPU; the new module is
    the one used, and every file that was there keeps its bytes."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "tests/bench").mkdir(parents=True)
    shutil.copy(ROOT / "tests/bench/benchfix.py", root / "tests/bench")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    old = json.loads((root / "BENCHMARK.json").read_text())

    monkeypatch.setattr(sys, "path", list(sys.path))   # the copy's fixture
    spec = importlib.util.spec_from_file_location(     # module edits it
        "copied_benchfix", root / "tests/bench/benchfix.py")
    fix = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fix)
    cfg = fix.tiny_config()
    cfg["name"] = "tiny-recorded"
    for m in cfg["members"]:
        m["arch"] = "qwen2_recorded"
    (root / "bench/arch/qwen2_recorded.py").write_text(RECORDED)
    (root / "bench/configs/tiny-recorded.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny-recorded.json").write_text(
        json.dumps(fix.TINY_CLOSED))
    cell = "tiny-recorded.closed"
    added = {"configs": [{"name": "tiny-recorded", "source": "test",
                          "file": "bench/configs/tiny-recorded.json",
                          "reduced": [], "why": "proof"}],
             "workloads": [{"name": cell, "config": "tiny-recorded",
                            "traffic": "tiny-recorded", "chips": 1,
                            "why": "proof"}]}
    new = copy.deepcopy(old)
    for key, entries in added.items():
        new[key].extend(entries)
    _metric(new, "tokens_per_s")["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    rc = br.run_cell(cell, 2**35 + 9, 1.0, False, require_tpu=False,
                     root=root, cache_dir=None)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["correct"] and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    calls = set((root / "bench/arch/qwen2_recorded.calls").read_text()
                .split())
    assert calls >= {"program_config", "make_weights", "to_program",
                     "flops_per_token", "REFERENCE.hidden"}

    for p, b in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == b, p
    # the manifest only gained entries, and the cell in the list of a
    # metric that it reports
    back = json.loads((root / "BENCHMARK.json").read_text())
    for key, entries in added.items():
        assert back[key][-len(entries):] == entries
        del back[key][-len(entries):]
    _metric(back, "tokens_per_s")["workloads"].remove(cell)
    assert back == old
