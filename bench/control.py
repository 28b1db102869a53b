"""Readings that set a cell's limit: the program's widest gap and the
control's, seed by seed, in one process.  Not part of a benchmark run.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 --seconds 51

For each seed the pool's weights are made anew from the seed, a window of
the cell's own traffic is served for ``--seconds`` (whole closed batches),
and every request is judged by ``bench/check.py``'s ``judge``: once as the
program served it, and once for each lower precision, where the tokens
judged at the same positions are those that the reference computed in
that precision puts first.  Each line gives the widest gaps and each
verdict; the limit lies between the largest program reading and the
smallest control reading, and the control's verdict has to read
``correct`` false.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as br  # noqa: E402
from bench.check import judge  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--quant", nargs="+", default=["int8", "fp8"])
    a = ap.parse_args(argv)
    cell, cfg, mix, _, _ = br.load_cell(a.workload)
    dev = br.jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"control: needs a TPU, JAX found {dev.platform!r}")
    br.use_compile_cache(br.ROOT / ".jax_cache")
    ref = br.reference_module(cfg)
    target = cfg["members"][-1]
    serving = br.Serving(cfg, a.seeds[0])
    cap = br.offered_cap(serving, mix, a.seeds[0])
    br.warm_up(serving, mix, a.seeds[0], cap)
    for i, seed in enumerate(a.seeds):
        if i:
            serving.make_weights(seed)
        reqs, wall = br.serve_window(serving, mix, seed, a.seconds)
        gc.collect()
        w = serving.weights[target["name"]]
        row = dict(seed=seed, requests=len(reqs), window_s=wall)
        t0 = time.perf_counter()
        for q in [None] + a.quant:
            v = judge(ref, w, target["config"], reqs, cfg["limits"],
                      quant=q)
            name = q or "program"
            row["tokens"] = v["tokens"]
            row[f"{name}_max_gap"] = v["compared"]["max_gap"]["value"]
            row[f"{name}_correct"] = v["correct"]
            row[f"{name}_by_class"] = v["by_class"]
        row["limit"] = cfg["limits"]["max_gap"]
        row["replay_s"] = time.perf_counter() - t0
        print("control " + json.dumps(row), flush=True)
        del w                 # the next seed's weights need the room
    return 0


if __name__ == "__main__":
    sys.exit(main())
