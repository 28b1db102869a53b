"""``chip_smoke.py`` rehearsed on the CPU: its serving phase and reference
check on the tiny demo pool, its refusal of any backend but the TPU, the
published configs it serves, and a package that initialises no JAX
backend at import (a process that imports ``repro`` must not take the
chip)."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs.llama_pool import demo_pool, full_pool
from repro.core import Placement

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def pool(smoke):
    return smoke.build_pool(demo_pool(vocab_size=97), seed=0,
                            placement=Placement.single())


PHASES = {
    "chain3-w4": dict(adaptive=False, fixed_window=4,
                      fixed_chain=("demo-68m", "demo-1b", "demo-7b")),
    "tree-2x2x1": dict(adaptive=False, fixed_chain=("demo-68m", "demo-7b"),
                       fixed_tree="2x2x1"),
    "adaptive": dict(adaptive=True),
}


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_serve_phase_matches_reference(smoke, pool, phase):
    def reqs():
        return smoke.make_requests(97, seed=3, prompt_lens=(5, 9, 12, 16),
                                   new_tokens=8)

    # float32 demo models: the served stream must be the reference argmax
    # up to float rounding, far inside the bf16 tolerance of the chip run
    rep = smoke.serve_phase(pool, "demo-7b", phase, PHASES[phase], reqs,
                            tol=1e-3)
    assert rep["tokens"] == 4 * 8
    assert rep["ref_worst_deficit"] <= 1e-3
    if phase != "adaptive":
        # fixed chains: every steady cycle is one fused program, one sync
        assert rep["host_syncs_per_fused_cycle"] == 1.0


def test_reference_check_rejects_a_wrong_token(smoke, pool):
    reqs = smoke.make_requests(97, seed=3, prompt_lens=(6,), new_tokens=4)
    lm, params = pool.model("demo-7b"), pool.params("demo-7b")
    logits = np.asarray(lm.train_logits(params, reqs[0].prompt[None]
                                        .astype(np.int32), remat=False))
    worst = int(np.argmin(logits[0, -1]))    # the least likely next token
    reqs[0].output_tokens = np.array([worst, 0, 0, 0])
    with pytest.raises(AssertionError, match="below the reference maximum"):
        smoke.reference_check(pool, "demo-7b", reqs, tol=1e-3)


def test_main_refuses_a_non_tpu_backend(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("name,published", [
    ("llama-68m", 68e6), ("tinyllama-1.1b", 1.10e9), ("llama-2-7b", 6.74e9)])
def test_full_pool_param_counts_match_published(name, published):
    cfg = {c.name: c for c in full_pool()}[name]
    assert not cfg.tie_embeddings
    assert abs(cfg.param_count() - published) / published < 0.01


def test_import_initialises_no_backend():
    """Importing every module of the package under a platform name JAX
    cannot initialise fails on the first module that touches a backend."""
    mods = sorted(
        ".".join(p.relative_to(ROOT / "src").with_suffix("").parts)
        for p in (ROOT / "src" / "repro").rglob("*.py")
        if p.name != "__init__.py")
    code = "import importlib\nfor m in %r:\n    importlib.import_module(m)\n" \
        % (mods,)
    env = {**os.environ, "JAX_PLATFORMS": "no-such-backend",
           "PYTHONPATH": str(ROOT / "src")}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
