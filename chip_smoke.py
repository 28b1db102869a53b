"""Chip smoke: serve the paper's three-level Llama pool end to end on a TPU.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --mesh 1x4   # four chips: the mesh-sharded path only

One process drives the main serving path — ``ModelPool`` ->
``ServingEngine`` -> ``ChainRouter`` with per-slot routing, paged KV and
fused cycles — over the pool of ``configs/llama_pool.full_pool()`` at its
published widths, with random weights drawn from ``--seed``:

  * one chip: llama-68m and tinyllama-1.1b whole, llama-2-7b cut to its
    first 16 of 32 layers (the full target is 13.5 GB of bf16 weights and
    does not fit one 16 GB chip beside the drafts).  Three router
    settings, each served once to warm up and once measured: the fixed
    chain 68m -> 1.1b -> 7b with a linear window of 4, the fixed chain
    68m -> 7b with the 2x2x1 token tree (the compiled ``draft_topk``
    kernel), and the adaptive router;
  * ``--mesh 1x4``: the same three-level chain with the FULL 32-layer
    llama-2-7b tensor-parallel over the ``model`` axis and the drafts
    replicated, plus a check that each device holds about a quarter of
    the target's bytes.

Every served request is checked against the target's non-cached forward
(``LanguageModel.train_logits``) over prompt + output.  Earlier lines
report each phase; the last line is the JSON result.  Without a TPU, or
when any phase fails, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.llama_pool import full_pool  # noqa: E402
from repro.core import ModelPool, PerformanceProfiler, Placement  # noqa: E402
from repro.data.workload import Request  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.model import LanguageModel  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402

DRAFT, MID, TARGET = "llama-68m", "tinyllama-1.1b", "llama-2-7b"
ONE_CHIP_TARGET_LAYERS = 16
PROMPT_LENS = (64, 128, 192, 256)
NEW_TOKENS = 32
SLOTS = 4
# A committed token must be the reference argmax or score within TOL of
# the reference maximum logit.  The served path (paged KV, cached decode,
# fused verify) and the reference (one non-cached forward) round bf16
# activations at different points; over 16-32 layers at d_model 4096 that
# moves a logit by a few hundredths, while the random-weight logits are
# ~N(0, 1), so a wrong token sits ~4 below the maximum.
TOL = 0.25

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0, 0]          # seconds, count — fed by the JAX listener


def _on_event(event: str, seconds: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        _compile_s[0] += seconds
        _compile_s[1] += 1


def make_requests(vocab: int, seed: int, prompt_lens=PROMPT_LENS,
                  new_tokens: int = NEW_TOKENS):
    """Seeded closed batch: every request arrives at t=0."""
    rng = np.random.default_rng(seed)
    return [Request(request_id=f"r{i}", arrival_s=0.0,
                    prompt=rng.integers(0, vocab, n).astype(np.int64),
                    max_new_tokens=new_tokens, dataset="smoke")
            for i, n in enumerate(prompt_lens)]


def build_pool(cfgs, seed: int, placement: Placement) -> ModelPool:
    """Register every config with a seeded random ``init_fn`` that draws
    the weights directly under the member's placement sharding (a 7B
    target never materializes whole on one device of a mesh)."""
    pool = ModelPool(placement=placement)
    if not placement.is_trivial:
        placement.auto_assign({c.name: c.param_count() for c in cfgs},
                              cfgs[-1].name)
    for i, cfg in enumerate(cfgs):
        lm = LanguageModel(cfg)

        def init_fn(lm=lm, cfg=cfg, member_seed=seed + i):
            axes = lm.param_axes()
            sharding = placement.param_sharding(
                cfg.name, axes, lm.abstract_params(), cfg=cfg)
            params = jax.jit(lambda k: lm.init(k)[0], out_shardings=sharding)(
                jax.random.PRNGKey(member_seed))
            return params, axes

        pool.register(cfg, init_fn=init_fn)
    return pool


@partial(jax.jit, static_argnums=0)
def _reference_logits(lm: LanguageModel, params, tokens):
    return lm.train_logits(params, tokens, remat=False)


def reference_check(pool: ModelPool, target: str, reqs, tol: float):
    """Teacher-forced reference: one non-cached target forward over each
    request's prompt + output (padded to a common length — causal, so the
    tail padding never reaches a checked position).  Returns (worst
    deficit, fraction of tokens equal to the reference argmax)."""
    lm, params = pool.model(target), pool.params(target)
    seqs = [np.concatenate([r.prompt, r.output_tokens]) for r in reqs]
    L = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), L), np.int32)
    for b, s in enumerate(seqs):
        toks[b, :len(s)] = s
    with pool.placement.mesh_context():
        logits = _reference_logits(lm, params, toks)
    logits = np.asarray(logits, np.float32)
    worst, exact, n = 0.0, 0, 0
    for b, r in enumerate(reqs):
        out = np.asarray(r.output_tokens)
        if len(out) != r.max_new_tokens:
            raise AssertionError(f"{r.request_id}: {len(out)} tokens "
                                 f"committed, budget {r.max_new_tokens}")
        Lp = len(r.prompt)
        lg = logits[b, Lp - 1:Lp - 1 + len(out)]      # predicts output[i]
        picked = lg[np.arange(len(out)), out]
        deficit = lg.max(axis=-1) - picked
        worst = max(worst, float(deficit.max()))
        exact += int(np.sum(lg.argmax(axis=-1) == out))
        n += len(out)
        if deficit.max() > tol:
            i = int(deficit.argmax())
            raise AssertionError(
                f"{r.request_id}: output token {i} ({out[i]}) scores "
                f"{deficit[i]:.4f} below the reference maximum "
                f"(tol {tol})")
    return worst, exact / max(n, 1)


def serve_phase(pool: ModelPool, target: str, name: str, router_kwargs,
                make_reqs, tol: float = TOL, slots: int = SLOTS) -> dict:
    """Warm-up pass + measured pass of one router setting through
    ``ServingEngine``, then the reference check of the measured outputs.
    Returns the phase's report."""
    prof = PerformanceProfiler()
    eng = ServingEngine(pool, target, batch_size=slots,
                        router_kwargs=dict(router_kwargs, profiler=prof),
                        mesh=None if pool.placement.is_trivial
                        else pool.placement)
    c0 = list(_compile_s)
    t0 = time.perf_counter()
    eng.run(make_reqs())
    warm_s = time.perf_counter() - t0
    c1 = list(_compile_s)
    counters0 = dict(prof.counters)
    reqs = make_reqs()
    t0 = time.perf_counter()
    m = eng.run(reqs)
    wall = time.perf_counter() - t0
    c2 = list(_compile_s)
    delta = {k: v - counters0.get(k, 0.0) for k, v in prof.counters.items()}
    cycles = max(delta.get("cycles", 0.0), 1.0)
    accepted = {k[len("accept."):]: v / cycles for k, v in delta.items()
                if k.startswith("accept.")}
    worst, exact = reference_check(pool, target, reqs, tol)
    return dict(
        phase=name,
        warmup_s=round(warm_s, 3),
        compile_s=round(c1[0] - c0[0], 3), compiles=c1[1] - c0[1],
        measured_compiles=c2[1] - c1[1],
        tokens=m.total_tokens, wall_s=round(wall, 4),
        tokens_per_s=round(m.total_tokens / wall, 2),
        ttft_p50_s=round(float(np.median([r.ttft for r in reqs])), 4),
        accepted_per_cycle=accepted,
        commit_per_slot_cycle=round(m.avg_acceptance_len, 4),
        host_syncs_per_fused_cycle=m.fused_cycle_host_syncs,
        ref_worst_deficit=round(worst, 5), ref_exact_frac=round(exact, 4),
    )


def device_bytes(params) -> dict:
    """Bytes each device holds of one member's parameters."""
    out: dict = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            out[shard.device] = out.get(shard.device, 0) + shard.data.nbytes
    return out


def peak_bytes(devices) -> list:
    return [d.memory_stats().get("peak_bytes_in_use", -1) for d in devices]


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default=None, metavar="DXM",
                    help="serve the three-level chain on a ('data','model') "
                         "mesh with the full-depth target tensor-parallel, "
                         "e.g. 1x4; runs only that phase")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_event)

    cfgs = {c.name: c for c in full_pool()}
    pool_cfgs = [cfgs[DRAFT], cfgs[MID], cfgs[TARGET]]
    if args.mesh is None:
        pool_cfgs[-1] = dataclasses.replace(
            pool_cfgs[-1], num_layers=ONE_CHIP_TARGET_LAYERS)
        print(f"cut: {TARGET} keeps {ONE_CHIP_TARGET_LAYERS} of "
              f"{cfgs[TARGET].num_layers} layers (published widths)")
        placement = Placement.single()
    else:
        placement = Placement.from_spec(args.mesh)
    pool = build_pool(pool_cfgs, args.seed, placement)
    mesh_devices = (list(placement.mesh.devices.flat)
                    if placement.mesh is not None else [dev])
    t0 = time.perf_counter()
    for c in sorted(pool_cfgs, key=lambda c: -c.param_count()):
        pool.ensure_loaded(c.name)   # largest first: init transients fit
    print(f"init: {time.perf_counter() - t0:.1f}s, params "
          + ", ".join(f"{c.name}={c.param_count() / 1e9:.3f}B"
                      for c in pool_cfgs))

    vocab = cfgs[TARGET].vocab_size

    def reqs():
        return make_requests(vocab, args.seed)

    chain3 = dict(adaptive=False, fixed_chain=(DRAFT, MID, TARGET),
                  fixed_window=4)
    if args.mesh is None:
        phases = [("chain3-w4", chain3),
                  ("tree-2x2x1", dict(adaptive=False,
                                      fixed_chain=(DRAFT, TARGET),
                                      fixed_tree="2x2x1")),
                  ("adaptive", dict(adaptive=True))]
    else:
        per_dev = device_bytes(pool.params(TARGET))
        total = sum(per_dev.values())
        shares = [per_dev.get(d, 0) / total for d in mesh_devices]
        print(f"target bytes per device: "
              + ", ".join(f"{d.id}={per_dev.get(d, 0)}"
                          for d in mesh_devices)
              + f" of {total} (shares "
              + ", ".join(f"{s:.3f}" for s in shares) + ")")
        quarter = 1.0 / len(mesh_devices)
        if not all(abs(s - quarter) < 0.1 * quarter for s in shares):
            raise AssertionError("target is not evenly sharded: "
                                 f"shares {shares}")
        phases = [(f"chain3-w4-mesh{args.mesh}", chain3)]

    for name, kw in phases:
        rep = serve_phase(pool, TARGET, name, kw, reqs)
        rep["peak_bytes_in_use"] = peak_bytes(mesh_devices)
        print("phase " + json.dumps(rep), flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
