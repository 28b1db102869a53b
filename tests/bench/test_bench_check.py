"""The comparison that decides ``correct``: a sound run passes it, and a
run whose timed path alters a token where it is produced does not."""
import json

import numpy as np

import benchfix  # noqa: F401  (repository root on the import path)
from bench import run as br


def _result(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(tiny_tree, capsys):
    rc = br.run_cell("tiny.closed", 2**31 + 11, 1.0, False,
                     require_tpu=False, root=tiny_tree, cache_dir=None)
    out = _result(capsys)
    assert rc == 0 and out["correct"] and out["failed"] == 0
    assert out["checks"]["max_gap"]["value"] <= \
        out["checks"]["max_gap"]["limit"]
    assert set(out["metrics"]) >= {"setup_s", "tokens_per_s"}


def test_altered_token_is_not_correct(tiny_tree, capsys, monkeypatch):
    """The committed stream of every retired slot loses its last token to
    its neighbour id: the run must read ``correct`` false."""
    from repro.core.chain_router import RouterSession
    produced = RouterSession.generated

    def altered(self, slot):
        out = produced(self, slot)
        if out.size:
            out = out.copy()
            out[-1] = out[-1] + 1
        return out

    monkeypatch.setattr(RouterSession, "generated", altered)
    rc = br.run_cell("tiny.closed", 13, 1.0, False, require_tpu=False,
                     root=tiny_tree, cache_dir=None)
    out = _result(capsys)
    assert rc == 0 and not out["correct"]
    assert out["checks"]["max_gap"]["value"] > \
        out["checks"]["max_gap"]["limit"]


def test_missing_tokens_are_not_correct(tiny_tree, capsys, monkeypatch):
    from repro.core.chain_router import RouterSession
    produced = RouterSession.generated
    monkeypatch.setattr(RouterSession, "generated",
                        lambda self, slot: np.asarray(
                            produced(self, slot))[:-1])
    br.run_cell("tiny.closed", 17, 1.0, False, require_tpu=False,
                root=tiny_tree, cache_dir=None)
    out = _result(capsys)
    assert not out["correct"] and out["failed"] > 0


def test_control_reads_above_the_limit_and_the_program_below(tiny_tree):
    """The control of ``bench/control.py`` at a size the CPU holds: on
    every seed ``judge`` passes the served tokens, and judges the tokens
    that fp8 puts first at the same positions not correct."""
    from bench.check import judge
    _, cfg, mix, _, _ = br.load_cell("tiny.closed", tiny_tree)
    ref = br.reference_module(cfg, tiny_tree)
    limit = cfg["limits"]["max_gap"]
    target = cfg["members"][-1]
    serving = br.Serving(cfg, 21, tiny_tree)
    for seed in (21, 22, 23):
        serving.make_weights(seed)
        reqs, _ = br.serve_window(serving, mix, seed, 1.0)
        w = serving.weights[target["name"]]
        program = judge(ref, w, target["config"], reqs, cfg["limits"])
        control = judge(ref, w, target["config"], reqs, cfg["limits"],
                        quant="fp8")
        assert program["correct"]
        assert program["compared"]["max_gap"]["value"] <= limit
        assert not control["correct"]
        assert control["compared"]["max_gap"]["value"] > limit


def test_window_compiles_nothing(tiny_tree, capsys):
    """Warm-up compiles every program the window drives: the result's
    ``window_compiles`` reads 0 and its checks still come last."""
    rc = br.run_cell("tiny.closed", 2**40 + 3, 1.5, False,
                     require_tpu=False, root=tiny_tree, cache_dir=None)
    captured = capsys.readouterr()
    out = json.loads(captured.out.strip().splitlines()[-1])
    window = json.loads(next(line for line in captured.out.splitlines()
                             if line.startswith("window "))[7:])
    assert rc == 0 and out["window_compiles"] == 0, window
    assert window["window_programs"] == []
    assert list(out)[-1] == "checks"
    assert captured.err.strip().splitlines()[-1].startswith(
        "check short_requests")
