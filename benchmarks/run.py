"""Benchmark harness entry point: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,...,derived`` CSV per benchmark (see each module docstring).
Everything runs in this one process, and a benchmark that fails ends the
run with a non-zero exit.
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller batch set / shorter workloads")
    ap.add_argument("--skip-serving", action="store_true")
    ap.add_argument("--no-continuous", action="store_true",
                    help="serve with the legacy stop-the-world batch-"
                         "formation engine instead of slot-level "
                         "continuous batching (A/B baseline)")
    ap.add_argument("--skip-tree", action="store_true",
                    help="skip the linear-vs-tree speculation A/B")
    ap.add_argument("--skip-routing", action="store_true",
                    help="skip the per-slot vs global-chain routing A/B")
    ap.add_argument("--skip-cycle", action="store_true",
                    help="skip the fused vs per-op cycle-overhead A/B")
    ap.add_argument("--skip-goodput", action="store_true",
                    help="skip the SLO-aware vs latency-only goodput A/B")
    ap.add_argument("--skip-mesh", action="store_true",
                    help="skip the single-device vs mesh-sharded A/B")
    ap.add_argument("--tree-shapes", default=None,
                    help="comma-separated tree shapes for the A/B, e.g. "
                         "'1x1x1,2x1x1,2x2x1' (equal depth; default: a "
                         "depth-4 sweep)")
    args = ap.parse_args()

    from repro.launch.mesh import request_cpu_devices
    if not args.skip_mesh:
        # mesh_ab runs in this process: its 8 devices must exist before
        # the first JAX computation below (virtual ones on the CPU)
        request_cpu_devices(8)

    from . import (analytic_model, chain_selection, cycle_overhead,
                   goodput_ab, mesh_ab, roofline, routing_ab,
                   serving_metrics, table2_speedup, tree_ab)

    t0 = time.time()
    print("# analytic_model (paper Eq. 2/3/4)")
    analytic_model.main()

    print("# roofline (deliverable g - from dry-run artifacts)")
    for mesh in ("single", "multi"):
        roofline.main(mesh=mesh)

    print("# chain_selection (paper Fig. 2)")
    chain_selection.main()

    print("# table2_speedup (paper Table 2)")
    batches = (1, 4, 8) if args.quick else (1, 4, 8, 16, 32, 64)
    table2_speedup.main(batches=batches,
                        max_new=12 if args.quick else 24)

    if not args.skip_tree:
        print("# tree_ab (linear vs token-tree speculation)")
        if args.tree_shapes:
            shapes = tuple(args.tree_shapes.split(","))
        else:
            shapes = (("1x1x1", "2x2x1") if args.quick else tree_ab.SHAPES)
        tree_ab.main(shapes=shapes, max_new=12 if args.quick else 24)

    if not args.skip_routing:
        print("# routing_ab (per-slot lazy routing vs global-chain)")
        routing_ab.main(n_reqs=6 if args.quick else 10)

    if not args.skip_cycle:
        print("# cycle_overhead (fused vs per-op speculative cycles)")
        # out_json="": the committed BENCH_5.json baseline is the canonical
        # max_new=32 run (regenerated by the CI smoke) — a sweep at other
        # sizes must not clobber it
        cycle_overhead.main(max_new=12 if args.quick else 24, out_json="")

    if not args.skip_goodput:
        print("# goodput_ab (SLO-aware vs latency-only under burst)")
        goodput_ab.main()

    if not args.skip_mesh:
        print("# mesh_ab (single-device vs mesh-sharded placement)")
        # out_json="": the committed BENCH_9.json baseline is the
        # canonical max_new=32 run (regenerated by the mesh-serving CI job)
        mesh_ab.main(max_new=12 if args.quick else 24, out_json="")

    if not args.skip_serving:
        print("# serving_metrics (paper SS5 metrics)")
        serving_metrics.main(
            datasets=("gsm8k",) if args.quick
            else ("gsm8k", "humaneval", "mtbench", "mgsm"),
            duration=6.0 if args.quick else 12.0,
            continuous=not args.no_continuous)

    print(f"# total bench time: {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
