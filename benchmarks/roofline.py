"""Roofline analysis (EXPERIMENTS §Roofline): three terms per (arch ×
shape) on the single-pod mesh, derived from the dry-run artifacts.

    compute    = FLOPs_dev / peak FLOP/s       (bf16 MXU peak per chip)
    memory     = HLO_bytes_dev / HBM B/s       (HBM bandwidth per chip)
    collective = coll_bytes_dev / ICI B/s      (ICI per link)

with the peaks of the target chip (TPU v5e) read from the one
device-kind table, ``repro.obs.cost.PEAKS``.

All inputs are PER-DEVICE (verified: XLA cost_analysis reports post-SPMD
per-device numbers) with while-loop undercount corrected by the unrolled
probe extrapolation (dryrun.py). MODEL_FLOPS uses 6·N·D (dense) /
6·N_active·D (MoE) for train, 2·N·D for decode/prefill token counts.

  PYTHONPATH=src python -m benchmarks.roofline results/dryrun_single_pod.json
"""
from __future__ import annotations

import json
import sys

from repro.obs.cost import PEAKS

#: the dry-run's target chip
TARGET = PEAKS["TPU v5 lite"]


def model_flops(cfg, shape_kind, seq_len, global_batch):
    pc = cfg.param_count()
    n_active = pc["active"]
    if shape_kind == "train":
        tokens = seq_len * global_batch
        return 6 * n_active * tokens
    if shape_kind == "prefill":
        tokens = seq_len * global_batch
        return 2 * n_active * tokens
    # decode: one new token per row
    return 2 * n_active * global_batch


def analyze(cells, *, with_probes=True):
    from repro.configs import get_config
    from repro.launch.steps import SHAPES
    rows = []
    for c in cells:
        if "error" in c:
            rows.append({"arch": c["arch"], "shape": c["shape"],
                         "error": c["error"]})
            continue
        probe = c.get("probe", {}).get("extrapolated", {})
        flops_dev = probe.get("flops", c["flops"])
        bytes_dev = probe.get("hlo_bytes", c["hlo_bytes"])
        coll_dev = probe.get("collective_bytes_total",
                             c["collective_bytes"].get("total", 0))
        t_comp = flops_dev / TARGET.flops
        t_mem = bytes_dev / TARGET.mem_bw
        t_coll = coll_dev / TARGET.collective_bw
        dominant = max((("compute", t_comp), ("memory", t_mem),
                        ("collective", t_coll)), key=lambda kv: kv[1])[0]
        cfg = get_config(c["arch"])
        shape = SHAPES[c["shape"]]
        mf = model_flops(cfg, shape.kind, shape.seq_len, shape.global_batch)
        mf_dev = mf / c["devices"]
        useful = mf_dev / max(flops_dev, 1)
        # roofline fraction: useful work over the time the dominant term
        # implies (= achievable MFU bound for this artifact)
        t_star = max(t_comp, t_mem, t_coll)
        frac = (mf_dev / TARGET.flops) / max(t_star, 1e-30)
        mem = c["memory"]
        hbm = ((mem["argument_size"] or 0) + (mem["temp_size"] or 0)
               + (mem["output_size"] or 0)) / 2 ** 30
        rows.append({
            "arch": c["arch"], "shape": c["shape"],
            "compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll,
            "dominant": dominant, "useful_ratio": useful,
            "roofline_frac": frac, "hbm_gib": hbm,
        })
    return rows


def main(path="results/dryrun_single_pod.json"):
    cells = json.load(open(path))
    rows = analyze(cells)
    hdr = (f"{'arch':24s} {'shape':12s} {'compute':>9s} {'memory':>9s} "
           f"{'coll':>9s} {'dom':>10s} {'useful':>7s} {'frac':>6s} "
           f"{'HBM GiB':>8s}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        if "error" in r:
            print(f"{r['arch']:24s} {r['shape']:12s} ERROR {r['error'][:60]}")
            continue
        print(f"{r['arch']:24s} {r['shape']:12s} "
              f"{r['compute_s']:9.4f} {r['memory_s']:9.4f} "
              f"{r['collective_s']:9.4f} {r['dominant']:>10s} "
              f"{r['useful_ratio']:7.3f} {r['roofline_frac']:6.3f} "
              f"{r['hbm_gib']:8.2f}")
    return rows


if __name__ == "__main__":
    main(*sys.argv[1:])
