"""Helpers of the benchmark harness tests: the repository root on the
import path, and a checkout-like tree whose manifest holds a tiny cell that
runs on the CPU."""
from __future__ import annotations

import copy
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

_COMMON = {"hidden_act": "silu", "max_position_embeddings": 4096,
           "rms_norm_eps": 1e-06, "torch_dtype": "bfloat16",
           "use_sliding_window": False, "vocab_size": 4096}


def _member(name, d, ff, heads, layers, tied, code_scale, copies):
    return {"name": name, "source": "test", "arch": "qwen2_dense",
            "config": dict(_COMMON, hidden_size=d, intermediate_size=ff,
                           num_attention_heads=heads,
                           num_hidden_layers=layers,
                           num_key_value_heads=heads, rope_theta=10000.0,
                           tie_word_embeddings=tied),
            "planted": {"code_scale": code_scale, "copies": copies}}


def tiny_config() -> dict:
    """A two-level pool with the real configurations' planting, at a size
    the CPU runs in seconds."""
    planting = json.loads((ROOT / "bench/configs/qwen1.5-0.5b-1.8b-4b.json")
                          .read_text())["planting"]
    planting = dict(planting, classes={"easy": [1000, 256],
                                       "medium": [2000, 256],
                                       "hard": [3000, 256]})
    return {"name": "tiny", "source": "test", "dtype": "bfloat16",
            "members": [
                _member("tiny-draft", 64, 128, 4, 2, True,
                        {"easy": 2.0, "medium": 0.05, "hard": 1.0}, []),
                _member("tiny-target", 128, 256, 4, 3, False,
                        {"easy": 1.0, "medium": 1.0, "hard": 1.0},
                        ["easy", "medium"])],
            "reduced": [], "assumed": [], "router": {}, "slots": 3,
            "planting": planting, "limits": {"max_gap": 0.1}}


TINY_CLOSED = {"mode": "closed_batches",
               "classes": {"easy": 1, "medium": 1, "hard": 1},
               "prompt": {"median": 12, "sigma": 0.6, "min": 4, "max": 40,
                          "strata": 2},
               "output": {"median": 8, "sigma": 0.6, "min": 4, "max": 16,
                          "strata": 2},
               "pairing": [1, 0]}


def make_tree(base: Path) -> Path:
    """Copy of ``bench/`` plus a manifest whose cells are tiny and run on
    the CPU; every metric of the real manifest applies to the tiny cell."""
    root = base / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench/configs/tiny.json").write_text(json.dumps(tiny_config()))
    (root / "bench/traffic/tiny-closed.json").write_text(
        json.dumps(TINY_CLOSED))
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m = copy.deepcopy(m)
    m["configs"] = [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "tiny"}]
    m["workloads"] = [{"name": "tiny.closed", "config": "tiny",
                       "traffic": "tiny-closed", "chips": 1, "why": "tiny"}]
    for e in m["end_to_end"] + m["per_layer"]:
        e.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root
