"""End-to-end serving driver (the paper's kind of system): Poisson request
arrivals from a dataset profile, slot-level continuously-batched
multi-level speculative serving, full §5 metric report, with TMO / SSD
baselines for the EAF speedup.

    PYTHONPATH=src python examples/serve_specrouter.py \
        [--dataset gsm8k] [--rate 0.5] [--duration 20] [--batch 4] \
        [--tree 2x2x1]      # token-tree speculation (SSD-Tree baseline +
                            # the shape joins SpecRouter's search space)
        [--no-continuous]   # legacy stop-the-world batch formation
        [--no-paged]        # legacy contiguous shared-pointer KV (A/B)
        [--no-slot-routing] # legacy global-chain routing: one chain per
                            # cycle, whole pool prefilled at admission
        [--no-fused]        # legacy host-orchestrated per-op cycles (A/B)
        [--profile-every N] # unfused profiling-cycle cadence (default 16)
        [--workload burst]  # MMPP bursty arrivals instead of Poisson
        [--workload trace --trace-file t.jsonl]  # JSONL trace replay
        [--ttft-slo 2.0] [--tpot-slo 0.5]  # per-request SLOs: activates
                            # the goodput-aware chain search + EDF
                            # admission (per-dataset defaults via the
                            # workload's with_slo are in data/workload.py)
        [--shed]            # drop queued requests whose TTFT deadline is
                            # already unmeetable (goodput over latency)
        [--mesh dxm]        # mesh-sharded serving: place the pool on a
                            # ("data","model") device mesh (target
                            # tensor-parallel, drafts replicated); under
                            # JAX_PLATFORMS=cpu virtual devices are
                            # spawned to fill the mesh

The compile cache lives in $JAX_COMPILATION_CACHE_DIR when it is set, else
in ``.jax_cache/`` at the repository root.
"""
import argparse
import math

from repro.data import load_trace, make_bursty_workload, make_workload
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import request_cpu_devices
from repro.serving import ServingEngine
from repro.train.pool import build_trained_pool


def build_requests(corpus, args):
    slo = dict(ttft_slo=args.ttft_slo, tpot_slo=args.tpot_slo)
    if args.workload == "trace":
        return load_trace(args.trace_file, **slo)
    if args.workload == "burst":
        # ON bursts at 4x the nominal rate, 25% duty cycle -> same
        # offered load as the Poisson arm but arriving in clumps
        return make_bursty_workload(
            corpus, args.dataset, rate_on_rps=4.0 * args.rate,
            duration_s=args.duration, mean_on_s=2.0, mean_off_s=6.0,
            seed=7, **slo)
    return make_workload(corpus, args.dataset, args.rate, args.duration,
                         seed=7, **slo)


def run(pool, corpus, args, label, router_kwargs):
    router_kwargs = dict(router_kwargs, paged=not args.no_paged,
                         slot_routing=not args.no_slot_routing,
                         fused=not args.no_fused,
                         profile_every=args.profile_every)
    reqs = build_requests(corpus, args)
    eng = ServingEngine(pool, "demo-7b", batch_size=args.batch,
                        slo_latency_s=args.slo,
                        shed_policy="ttft" if args.shed else "none",
                        router_kwargs=router_kwargs,
                        continuous=not args.no_continuous,
                        mesh=args.mesh)
    m = eng.run(reqs)
    line = (f"[{label:<22}] goodput {m.goodput_tps:7.1f} tok/s | "
            f"TTFT {m.avg_ttft_s:6.2f}s (p95 {m.p95_ttft_s:5.2f}s, "
            f"queue {m.avg_queue_s:5.2f}s) | TPOT {m.avg_tpot_s*1e3:7.1f}ms | "
            f"p95 lat {m.p95_latency_s:6.2f}s | SLO {m.slo_attainment:5.1%} | "
            f"acc-len {m.avg_acceptance_len:4.2f}")
    if not math.isnan(m.request_slo_attainment):
        line += (f" | SLO-req {m.request_slo_attainment:5.1%} "
                 f"(shed {m.num_shed})")
    print(line)
    return m


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="gsm8k",
                    choices=["gsm8k", "humaneval", "mtbench", "mgsm"])
    ap.add_argument("--rate", type=float, default=0.4)
    ap.add_argument("--duration", type=float, default=25.0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--slo", type=float, default=60.0)
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--tree", default=None, metavar="SHAPE",
                    help="token-tree speculation shape, e.g. 2x2x1: adds "
                         "an SSD-Tree static baseline and lets the "
                         "adaptive scheduler pick the tree draft")
    ap.add_argument("--no-continuous", action="store_true",
                    help="legacy stop-the-world batch formation (A/B)")
    ap.add_argument("--no-paged", action="store_true",
                    help="legacy contiguous shared-pointer KV state "
                         "instead of the paged per-slot block tables (A/B)")
    ap.add_argument("--no-slot-routing", action="store_true",
                    help="legacy global-chain routing — one chain for "
                         "every slot per cycle and O(pool) admission "
                         "prefill — instead of per-slot lazy chains (A/B)")
    ap.add_argument("--no-fused", action="store_true",
                    help="legacy host-orchestrated per-op speculation "
                         "cycles instead of the device-resident fused "
                         "cycle program (A/B)")
    ap.add_argument("--profile-every", type=int, default=16,
                    help="run an unfused profiling cycle every N cycles "
                         "to refresh the scheduler's per-op timings "
                         "(0 = never)")
    ap.add_argument("--workload", default="poisson",
                    choices=["poisson", "burst", "trace"],
                    help="arrival process: Poisson open loop (default), "
                         "MMPP bursty (ON/OFF clumps at the same offered "
                         "load), or JSONL trace replay")
    ap.add_argument("--trace-file", default=None, metavar="PATH",
                    help="JSONL trace for --workload trace (see "
                         "data/workload.py save_trace/load_trace)")
    ap.add_argument("--ttft-slo", type=float, default=None, metavar="S",
                    help="per-request time-to-first-token SLO in seconds; "
                         "setting any SLO turns on the goodput-aware "
                         "chain search and EDF admission")
    ap.add_argument("--tpot-slo", type=float, default=None, metavar="S",
                    help="per-request time-per-output-token SLO in "
                         "seconds")
    ap.add_argument("--shed", action="store_true",
                    help="shed queued requests whose TTFT deadline "
                         "cannot be met anymore (needs --ttft-slo)")
    ap.add_argument("--mesh", default=None, metavar="DXM",
                    help="place the pool on a ('data','model') device "
                         "mesh, e.g. 2x4: the target is tensor-parallel "
                         "over the model axis, drafts are replicated; "
                         "under JAX_PLATFORMS=cpu virtual devices are "
                         "spawned to fill the mesh")
    args = ap.parse_args()
    # both must precede the first JAX computation
    enable_compile_cache()
    if args.mesh:
        request_cpu_devices(math.prod(int(p) for p in args.mesh.split("x")))
    if args.workload == "trace" and not args.trace_file:
        ap.error("--workload trace requires --trace-file")
    if args.shed and args.ttft_slo is None:
        ap.error("--shed needs --ttft-slo (deadline to shed against)")

    pool, corpus = build_trained_pool(steps=args.steps)

    tmo = run(pool, corpus, args, "TMO (target only)",
              dict(adaptive=False, fixed_chain=("demo-7b",),
                   fixed_window=1))
    ssd = run(pool, corpus, args, "SSD-Smallest (static)",
              dict(adaptive=False, fixed_chain=("demo-68m", "demo-7b"),
                   fixed_window=4))
    tree_kw = {}
    if args.tree:
        sst = run(pool, corpus, args, f"SSD-Tree {args.tree} (static)",
                  dict(adaptive=False,
                       fixed_chain=("demo-68m", "demo-7b"),
                       fixed_tree=args.tree))
        tree_kw = dict(tree_shapes=(args.tree,))
    ours = run(pool, corpus, args, "SpecRouter (ours)",
               dict(adaptive=True, **tree_kw))
    eaf = f"\nEAF (vs TMO): SSD {tmo.avg_tpot_s/ssd.avg_tpot_s:.2f}x | "
    if args.tree:
        eaf += f"SSD-Tree {tmo.avg_tpot_s/sst.avg_tpot_s:.2f}x | "
    eaf += f"SpecRouter {tmo.avg_tpot_s/ours.avg_tpot_s:.2f}x"
    print(eaf)


if __name__ == "__main__":
    main()
