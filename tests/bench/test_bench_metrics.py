"""The benchmark's metric arithmetic: a rate over the whole window, drains
included, per-cycle means over the window's cycles, and readers that stay
silent where they find nothing to read."""
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchfix import ROOT
from bench import run as br

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


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
