"""Multi-pod dry-run (deliverable e): prove every (architecture × input
shape × mesh) combination lowers AND compiles on the production meshes,
and harvest the roofline terms from the compiled artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-27b \
      --shape decode_32k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]

Results are cached as JSON under benchmarks/dryrun_results/ (resumable).
"""
# The VERY FIRST lines — before ANY other import — because jax locks the
# device count on first init (system-prompt requirement).
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from functools import partial  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ..configs import (ASSIGNED_ARCHS, INPUT_SHAPES, effective_shape,  # noqa: E402
                       get_config, shape_applicable)
from ..models.model import LanguageModel  # noqa: E402
from ..optim import adamw_init  # noqa: E402
from ..sharding import (RULES, build_sharding, spec_for,  # noqa: E402
                        with_decode_rules, with_long_context_rules)
from ..train import TrainState, make_train_step  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../benchmarks/dryrun_results")

_COLL_RE = re.compile(
    r"=\s*(.+?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_WHILE_RE = re.compile(
    r"while\(.*?\), condition=%?([\w.\-]+), body=%?([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str):
    """Sum output-shape bytes of every collective op in the optimized HLO,
    multiplying collectives inside while-loop bodies (layer scans) by the
    loop trip count (max integer constant in the loop condition — the XLA
    idiom for counted scans)."""
    # split into computations
    comps = {}
    cur, buf = "__top__", []
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line.strip())
        if m and line.rstrip().endswith("{"):
            comps[cur] = "\n".join(buf)
            cur, buf = m.group(1), []
        else:
            buf.append(line)
    comps[cur] = "\n".join(buf)

    # per-computation raw collective bytes
    per_comp = {}
    for name, text in comps.items():
        agg = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
               "all-to-all": 0, "collective-permute": 0}
        for m in _COLL_RE.finditer(text):
            if "-done(" in m.group(0):
                continue
            agg[m.group(2)] += _shape_bytes(m.group(1))
        per_comp[name] = agg

    # loop multipliers: body computation -> trip count
    mult = {name: 1 for name in comps}
    for m in _WHILE_RE.finditer(hlo_text):
        cond, body = m.group(1), m.group(2)
        consts = [int(c) for c in _CONST_RE.findall(comps.get(cond, ""))]
        if consts:
            mult[body] = max(mult.get(body, 1), max(consts))

    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    flat = dict(out)
    for name, agg in per_comp.items():
        for op, v in agg.items():
            out[op] += v * mult.get(name, 1)
            flat[op] += v
    out["total"] = sum(out[k] for k in
                       ("all-reduce", "all-gather", "reduce-scatter",
                        "all-to-all", "collective-permute"))
    flat["total"] = sum(flat[k] for k in
                        ("all-reduce", "all-gather", "reduce-scatter",
                         "all-to-all", "collective-permute"))
    out["unrolled_total"] = out["total"]
    out["flat_total"] = flat["total"]
    return out


# ---------------------------------------------------------------------------
def _batch_spec(mesh, batch, rules):
    return NamedSharding(mesh, spec_for(("batch", "seq"), (batch, 1 << 30),
                                        mesh, rules))


def build_case(arch: str, shape_name: str, mesh, multi_pod: bool,
               kv_quant: bool = False):
    """Returns (fn, arg_specs, in_shardings) ready to lower."""
    cfg = get_config(arch)
    if kv_quant:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, kv_quant=True)
    shape = INPUT_SHAPES[shape_name]
    seq_len, batch, clipped = effective_shape(cfg, shape)
    lm = LanguageModel(cfg)
    if shape_name == "long_500k":
        rules = with_long_context_rules(RULES)
    elif shape.kind == "decode":
        rules = with_decode_rules(RULES)
    else:
        rules = RULES

    params = lm.abstract_params()
    paxes = lm.param_axes()
    p_shard = build_sharding(paxes, params, mesh, rules)
    tok_sharding = NamedSharding(
        mesh, spec_for(("batch", "seq"), (batch, seq_len), mesh, rules))

    extras_specs = lm.extras_specs(batch)
    extras_shard = {k: NamedSharding(mesh, P())
                    for k in extras_specs}

    if shape.kind == "train":
        step = make_train_step(lm, remat=True)
        opt = jax.eval_shape(adamw_init, params)
        ts = TrainState(params=params, opt=opt)
        ts_shard = TrainState(
            params=p_shard,
            opt=jax.eval_shape(adamw_init, params).__class__(
                step=NamedSharding(mesh, P()),
                m=build_sharding(paxes, params, mesh, rules),
                v=build_sharding(paxes, params, mesh, rules)))
        tokens = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)

        if cfg.arch_type == "vlm":
            npatch = cfg.vlm.num_patch_tokens
            patch = jax.ShapeDtypeStruct((batch, npatch, cfg.d_model),
                                         cfg.dtype)

            def fn(ts, tokens, patch):
                def ext_step(ts, tokens):
                    # splice stub patch embeddings over the first Np slots
                    from ..models import transformer as tf
                    emb = tf._embed(ts.params, cfg, tokens)
                    emb = jnp.concatenate([patch, emb[:, npatch:]], axis=1)
                    return step(ts, tokens,
                                extras={"input_embeds": emb})
                return ext_step(ts, tokens)
            args = (ts, tokens, patch)
            shards = (ts_shard, tok_sharding, NamedSharding(mesh, P()))
        elif extras_specs:
            def fn(ts, tokens, enc):
                return step(ts, tokens, extras={"enc_states": enc})
            args = (ts, tokens) + tuple(extras_specs.values())
            shards = (ts_shard, tok_sharding) + tuple(extras_shard.values())
        else:
            fn = step
            args = (ts, tokens)
            shards = (ts_shard, tok_sharding)
        return fn, args, shards, cfg, dict(seq=seq_len, batch=batch,
                                           clipped=clipped)

    # inference shapes
    if shape.kind == "prefill":
        cap = seq_len
        state, st_axes = lm.abstract_state(batch, cap)
        st_shard = build_sharding(st_axes, state, mesh, rules)
        tokens = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)

        def fn(params, state, tokens, *extra):
            ex = dict(zip(extras_specs.keys(), extra))
            return lm.prefill(params, state, tokens, logits_mode="last",
                              **ex)
        args = (params, state, tokens) + tuple(extras_specs.values())
        shards = (p_shard, st_shard, tok_sharding) \
            + tuple(extras_shard.values())
        return fn, args, shards, cfg, dict(seq=seq_len, batch=batch,
                                           clipped=clipped)

    # decode: ONE new token against a seq_len KV cache (serve_step);
    # capacity rounded up to a 512 multiple so the seq axis stays shardable
    cap = ((seq_len + 4 + 511) // 512) * 512
    state, st_axes = lm.abstract_state(batch, cap)
    st_shard = build_sharding(st_axes, state, mesh, rules)
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    tok1_shard = NamedSharding(
        mesh, spec_for(("batch", None), (batch, 1), mesh, rules))

    def fn(params, state, tokens, *extra):
        ex = dict(zip(extras_specs.keys(), extra))
        return lm.decode(params, state, tokens, logits_mode="all", **ex)
    args = (params, state, tokens) + tuple(extras_specs.values())
    shards = (p_shard, st_shard, tok1_shard) + tuple(extras_shard.values())
    return fn, args, shards, cfg, dict(seq=seq_len, batch=batch,
                                       clipped=clipped)


def run_case(arch: str, shape_name: str, mesh_kind: str,
             outdir: str, force: bool = False, verbose: bool = True,
             kv_quant: bool = False):
    os.makedirs(outdir, exist_ok=True)
    out_path = os.path.join(outdir, f"{arch}__{shape_name}__{mesh_kind}.json")
    if os.path.exists(out_path) and not force:
        if verbose:
            print(f"[skip cached] {out_path}")
        return json.load(open(out_path))
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "kind": shape.kind, "ok": False}
    if not shape_applicable(cfg, shape):
        rec.update(skipped=True,
                   reason="long_500k needs sub-quadratic attention "
                          "(DESIGN §5)")
        json.dump(rec, open(out_path, "w"), indent=1)
        print(f"[skip n/a] {arch} x {shape_name}")
        return rec

    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    t0 = time.perf_counter()
    try:
        fn, args, shards, cfg, meta = build_case(arch, shape_name, mesh,
                                                 multi, kv_quant=kv_quant)
        rec.update(meta)
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=shards)
            lowered = jitted.lower(*args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        # loop-aware roofline accounting (cost_analysis counts while
        # bodies once — see hlo_analysis docstring)
        from . import hlo_analysis
        la = hlo_analysis.analyze(hlo)
        import gzip
        with gzip.open(out_path.replace(".json", ".hlo.txt.gz"), "wt") as f:
            f.write(hlo)
        rec.update(
            flops_loop_aware=la["flops"],
            hbm_bytes_loop_aware=la["hbm_bytes"],
            collective_bytes_loop_aware=la["collective_bytes"],
            collectives_by_op=la["collectives"],
        )
        rec.update(
            ok=True,
            devices=mesh.devices.size,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            flops=float(cost.get("flops", -1)),
            bytes_accessed=float(cost.get("bytes accessed", -1)),
            peak_bytes_per_device=int(
                getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                - getattr(mem, "alias_size_in_bytes", 0)),
            temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0)),
            arg_bytes=int(getattr(mem, "argument_size_in_bytes", 0)),
            out_bytes=int(getattr(mem, "output_size_in_bytes", 0)),
            collectives=coll,
            param_count=cfg.param_count(),
            active_param_count=cfg.active_param_count(),
        )
        print(f"[ok] {arch} x {shape_name} x {mesh_kind}: "
              f"flops={rec['flops']:.3e} bytes={rec['bytes_accessed']:.3e} "
              f"coll={coll['total']:.3e} "
              f"peak/dev={rec['peak_bytes_per_device']/2**30:.2f}GiB "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec.update(error=str(e)[:2000], tb=traceback.format_exc()[-4000:])
        print(f"[FAIL] {arch} x {shape_name} x {mesh_kind}: {e}")
    json.dump(rec, open(out_path, "w"), indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--outdir", default=RESULTS_DIR)
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache variant (§Perf G2)")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cases = [(a, s) for a in ASSIGNED_ARCHS for s in INPUT_SHAPES]
    else:
        assert args.arch and args.shape
        cases = [(args.arch, args.shape)]
    n_ok = n_fail = 0
    for a, s in cases:
        for mk in meshes:
            rec = run_case(a, s, mk, args.outdir, force=args.force,
                           kv_quant=args.kv_quant)
            if rec.get("ok") or rec.get("skipped"):
                n_ok += 1
            else:
                n_fail += 1
    print(f"done: {n_ok} ok/skipped, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
