"""The offered work of every benchmark cell is fixed: the seed moves token
ids and order among equals, never the count, the lengths, the classes or
the engine's row capacity."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

from benchfix import ROOT
from bench import run as br
from bench import traffic as tg

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in MANIFEST["workloads"]]
SEEDS = (1, 2, 2**31 + 5, 987654321987)


def _offered(cell, seed):
    _, cfg, mix, _, _ = br.load_cell(cell)
    classes = cfg["planting"]["classes"]
    specs = (tg.closed_batch(mix, classes, seed, 0)
             + tg.closed_batch(mix, classes, seed, 1))
    return specs, classes


def _row_cap(cell, need):
    """The engine's row capacity, from a router over the cell's pool (no
    weights are needed to build one)."""
    from repro.core import ChainRouter, ModelPool
    _, cfg, _, _, _ = br.load_cell(cell)
    pool = ModelPool()
    for m in cfg["members"]:
        pool.register(br.arch_module(m["arch"]).program_config(m))
    router = ChainRouter(pool, cfg["members"][-1]["name"], **cfg["router"])
    return br.Serving.row_cap(SimpleNamespace(router=router), need)


@pytest.mark.parametrize("cell", CELLS)
def test_offered_multiset_and_cap_do_not_depend_on_seed(cell):
    base = None
    for seed in SEEDS:
        specs, classes = _offered(cell, seed)
        shape = sorted((len(s.prompt), s.max_new_tokens, s.cls)
                       for s in specs)
        cap = _row_cap(cell, tg.row_need(specs))
        if base is None:
            base = (shape, cap)
        assert (shape, cap) == base, f"seed {seed} changes the offered work"
        assert cap == 1024
        for s in specs:      # every prompt token lies in its class's range
            lo, n = classes[s.cls]
            assert lo <= s.prompt.min() and s.prompt.max() < lo + n


@pytest.mark.parametrize("cell", CELLS)
def test_seed_changes_tokens(cell):
    a, _ = _offered(cell, 3)
    b, _ = _offered(cell, 4)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_closed_batch_serves_longest_output_first():
    mix = json.loads((ROOT / "bench/traffic/mixed-saturated.json")
                     .read_text())
    classes = {"easy": [0, 9], "medium": [10, 9], "hard": [20, 9]}
    for seed in SEEDS:
        outs = [s.max_new_tokens for s in
                tg.closed_batch(mix, classes, seed, 0)]
        assert outs == sorted(outs, reverse=True)


def test_strata_are_midpoint_quantiles_inside_the_clip():
    spec = {"median": 160, "sigma": 0.6, "min": 16, "max": 448,
            "strata": 4}
    lens = tg.strata_lengths(spec)
    assert lens == sorted(lens) and lens[0] >= 16 and lens[-1] <= 448
    assert abs(np.sqrt(lens[1] * lens[2]) - 160) < 3   # symmetric in log
