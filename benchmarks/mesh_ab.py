"""Single-device vs mesh-sharded serving A/B (placement-aware fused cycles).

Same dispatch-bound pool, same prompts, same seed, fused linear cycles:
the TRIVIAL placement (unmeshed — the legacy single-device path) against
the pool placed on a ``("data","model")`` mesh of 8 virtual CPU devices
(target tensor-parallel, drafts replicated — the serving default from
``auto_assign``).  Measures per arm

  * steady-state host syncs per fused cycle — the PR 5 one-transfer
    contract must SURVIVE the mesh: the commit slab moves between chain
    levels through device-side collectives, never through the host, so
    the count stays exactly 1 on both arms;
  * per-cycle wall time (median) and committed tok/s — on spawned
    virtual CPU devices the mesh arm pays emulated collectives, so this
    is an overhead *report*, not a speedup claim (the win needs real
    accelerators); and
  * greedy bit-equality of the committed stream across arms.

With ``--assert`` both arms must hold syncs/cycle == 1 in steady state
and commit bit-identical tokens — the CI smoke for mesh-sharded serving.
Emits a ``BENCH_9.json`` snapshot.

Run directly (under ``JAX_PLATFORMS=cpu`` the module spawns the virtual
devices itself; on accelerators the mesh takes the host's devices):

    PYTHONPATH=src python -m benchmarks.mesh_ab [--assert] [--mesh 2x4]

Output CSV: mesh_ab,<arm>,<steps>,<syncs_steady>,<cycle_ms_median>,
<tok_per_s>,<bit_identical>.
"""
from __future__ import annotations

import argparse
import json
from typing import Dict

import numpy as np

from repro.core import ChainRouter, ModelPool, Placement
from repro.launch.mesh import request_cpu_devices

CHAIN = ("bench-68m", "bench-1b", "bench-7b")


def build_bench_pool(mesh=None, vocab: int = 127) -> ModelPool:
    """cycle_overhead's 3-deep dispatch-bound pool, optionally placed:
    small dense models so per-cycle wall time is orchestration (dispatch
    gaps, transfers, collectives), not FLOPs."""
    import jax
    import jax.numpy as jnp
    from repro.models import ModelConfig
    from repro.models.model import LanguageModel
    pool = ModelPool(placement=Placement.from_spec(mesh)
                     if mesh is not None else None)
    for (n, L, d, s) in [("bench-68m", 2, 32, 1), ("bench-1b", 3, 48, 2),
                         ("bench-7b", 4, 64, 3)]:
        cfg = ModelConfig(name=n, arch_type="dense", num_layers=L,
                          d_model=d, num_heads=4, num_kv_heads=2,
                          d_ff=2 * d, vocab_size=vocab, dtype=jnp.float32)
        params, axes = LanguageModel(cfg).init(jax.random.PRNGKey(s))
        pool.register(cfg, params=params, param_axes=axes)
    if not pool.placement.is_trivial:
        pool.placement.auto_assign(pool.capability(), CHAIN[-1])
    return pool


def run_arm(pool, prompts, lens, max_new: int, window: int) -> Dict:
    router = ChainRouter(pool, CHAIN[-1], greedy=True, seed=0,
                         adaptive=False, fixed_chain=CHAIN,
                         fixed_window=window, fused=True,
                         profile_every=1000)
    # warmup at the SAME max_new populates every compiled shape
    router.generate(prompts, lens, max_new, request_id="warm")
    out = router.generate(prompts, lens, max_new, request_id="run")
    wall = sum(out.cycle_wall_s)

    # steady-state transfer count via a session: cycle 0 is the per-op
    # profiling cycle (intentional extra syncs), so burn it first — every
    # fused cycle after it must make exactly ONE host transfer
    sess = router.start_session(2, 96, session_id="probe")
    sess.admit(0, prompts[0, :lens[0]], 10)
    sess.admit(1, prompts[1, :lens[1]], 10)
    sess.run_cycle()
    probed, s0 = 0, router.profiler.counters["host_sync"]
    while sess.active.any() and probed < 8:
        sess.run_cycle()
        probed += 1
    syncs = (router.profiler.counters["host_sync"] - s0) / max(probed, 1)
    sess.close()

    return dict(
        generated=out.generated,
        steps=out.steps,
        syncs_steady=syncs,
        cycle_ms_median=1e3 * float(np.median(out.cycle_wall_s)),
        tok_s=out.committed_tokens / max(wall, 1e-9),
    )


def main(max_new: int = 32, batch: int = 4, window: int = 4,
         mesh: str = "2x4", do_assert: bool = False,
         out_json: str = "BENCH_9.json", print_csv: bool = True) -> Dict:
    import jax
    need = int(np.prod([int(x) for x in mesh.split("x")]))
    if jax.device_count() < need:
        raise RuntimeError(
            f"mesh_ab needs {need} devices, have {jax.device_count()} "
            "(on the CPU: JAX_PLATFORMS=cpu XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need})")

    prompts = np.array(jax.random.randint(jax.random.PRNGKey(7),
                                          (batch, 12), 0, 127))
    lens = np.array([12, 9, 11, 7][:batch] + [10] * max(batch - 4, 0))

    report: Dict[str, Dict] = {}
    for arm, spec in (("single", None), ("mesh", mesh)):
        pool = build_bench_pool(spec)
        report[arm] = run_arm(pool, prompts, lens, max_new, window)
    ident = all(np.array_equal(a, b)
                for a, b in zip(report["single"]["generated"],
                                report["mesh"]["generated"]))
    for arm in ("single", "mesh"):
        r = report[arm]
        if print_csv:
            print(f"mesh_ab,{arm},{r['steps']},{r['syncs_steady']:.2f},"
                  f"{r['cycle_ms_median']:.2f},{r['tok_s']:.1f},"
                  f"{int(ident)}")
        r.pop("generated")
    report["bit_identical"] = ident

    if out_json:
        with open(out_json, "w") as f:
            json.dump({"bench": "mesh_ab", "mesh": mesh,
                       "max_new": max_new, "batch": batch,
                       "window": window, "arms": report}, f, indent=2)

    if do_assert:
        assert ident, "mesh arm committed different greedy tokens than " \
                      "the single-device arm"
        for arm in ("single", "mesh"):
            s = report[arm]["syncs_steady"]
            assert s == 1.0, \
                (f"{arm}: fused steady-state cycles must make exactly one "
                 f"host transfer (got {s:.2f}/cycle)")
        print("mesh_ab,assert,ok")
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--assert", dest="do_assert", action="store_true",
                    help="fail unless both arms hold exactly one host "
                         "transfer per steady-state fused cycle with "
                         "bit-equal greedy output")
    ap.add_argument("--mesh", default="2x4",
                    help="mesh spec for the placed arm (default 2x4)")
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--window", type=int, default=4)
    ap.add_argument("--out-json", default="BENCH_9.json")
    a = ap.parse_args()
    request_cpu_devices(int(np.prod([int(x) for x in a.mesh.split("x")])))
    main(max_new=a.max_new, batch=a.batch, window=a.window, mesh=a.mesh,
         do_assert=a.do_assert, out_json=a.out_json)
