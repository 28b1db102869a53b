"""Roofline analysis (deliverable g): derive the three roofline terms per
(arch × shape × mesh) from the dry-run's compiled artifacts.

  compute term    = HLO_FLOPs(loop-aware, per device) / peak_FLOP/s
  memory term     = HLO_bytes(loop-aware, per device) / HBM_bw
  collective term = collective_bytes(per device)      / link_bw

plus MODEL_FLOPS = 6·N·D (train) or 2·N_active·D (inference fwd) and the
useful-compute ratio.  Emits benchmarks/roofline_summary.{md,json}.

Output CSV: roofline,<arch>,<shape>,<mesh>,<t_comp>,<t_mem>,<t_coll>,<dom>.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List

from repro.configs import INPUT_SHAPES, get_config, effective_shape
from repro.launch.mesh import chip_peaks

# the dry-run's production mesh models a TPU v5e pod
DRYRUN_DEVICE_KIND = "TPU v5 lite"

HERE = os.path.dirname(__file__)
RESULTS = os.path.join(HERE, "dryrun_results")


def model_flops_per_device(rec: Dict) -> float:
    cfg = get_config(rec["arch"])
    shape = INPUT_SHAPES[rec["shape"]]
    seq, batch, _ = effective_shape(cfg, shape)
    n_active = cfg.active_param_count()
    if rec["kind"] == "train":
        tokens = seq * batch
        total = 6.0 * n_active * tokens
    elif rec["kind"] == "prefill":
        total = 2.0 * n_active * seq * batch
    else:  # decode: one token per row
        total = 2.0 * n_active * batch
    return total / rec.get("devices", 256)


def analyze_record(rec: Dict) -> Dict:
    flops = rec.get("flops_loop_aware", rec.get("flops", 0.0))
    hbm = rec.get("hbm_bytes_loop_aware", rec.get("bytes_accessed", 0.0))
    coll = rec.get("collective_bytes_loop_aware",
                   rec.get("collectives", {}).get("total", 0.0))
    peaks = chip_peaks(DRYRUN_DEVICE_KIND)
    t_comp = flops / peaks.flops_bf16
    t_mem = hbm / peaks.hbm_bw
    t_coll = coll / peaks.ici_bw_per_link
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_device(rec)
    ratio = mf / flops if flops else 0.0
    bound_time = max(terms.values())
    suggestions = {
        "compute": "increase per-chip arithmetic intensity (larger "
                   "microbatch / fuse elementwise into matmuls); compute-"
                   "bound is the healthy end state",
        "memory": "cut HBM traffic: remat policy, bf16 accumulators, "
                  "ring-buffer SWA cache, fused attention kernel "
                  "(avoid materialized scores), chunked loss",
        "collective": "reshard to cut cross-chip traffic: FSDP->TP swap, "
                      "overlap collectives with compute, reduce-scatter "
                      "instead of all-reduce+slice, expert-parallel "
                      "all-to-all fusion",
    }
    return dict(
        arch=rec["arch"], shape=rec["shape"], mesh=rec["mesh"],
        kind=rec["kind"], t_compute_s=t_comp, t_memory_s=t_mem,
        t_collective_s=t_coll, dominant=dominant,
        model_flops_per_dev=mf, hlo_flops_per_dev=flops,
        useful_compute_ratio=ratio,
        bound_time_s=bound_time,
        peak_bytes_per_device=rec.get("peak_bytes_per_device", 0),
        suggestion=suggestions[dominant],
    )


def main(print_csv: bool = True, mesh: str = "single") -> List[Dict]:
    rows = []
    for f in sorted(glob.glob(os.path.join(RESULTS, f"*__{mesh}.json"))):
        rec = json.load(open(f))
        if rec.get("skipped") or not rec.get("ok"):
            continue
        r = analyze_record(rec)
        rows.append(r)
        if print_csv:
            print(f"roofline,{r['arch']},{r['shape']},{r['mesh']},"
                  f"{r['t_compute_s']:.3e},{r['t_memory_s']:.3e},"
                  f"{r['t_collective_s']:.3e},{r['dominant']}")
    out = os.path.join(HERE, f"roofline_summary_{mesh}.json")
    json.dump(rows, open(out, "w"), indent=1)

    md = [f"| arch | shape | t_comp (s) | t_mem (s) | t_coll (s) | "
          f"dominant | useful-FLOP ratio | peak GiB/dev |",
          "|---|---|---|---|---|---|---|---|"]
    for r in rows:
        md.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.2e} | "
            f"{r['t_memory_s']:.2e} | {r['t_collective_s']:.2e} | "
            f"**{r['dominant']}** | {r['useful_compute_ratio']:.2f} | "
            f"{r['peak_bytes_per_device']/2**30:.1f} |")
    with open(os.path.join(HERE, f"roofline_summary_{mesh}.md"), "w") as fh:
        fh.write("\n".join(md) + "\n")
    return rows


if __name__ == "__main__":
    main()
