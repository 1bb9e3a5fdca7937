"""Roofline analysis from the dry-run's compiled artifacts (§Roofline).

    PYTHONPATH=src python benchmarks/roofline.py [--out f]

TPU v5e constants: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
``compiled.cost_analysis()`` on an SPMD-partitioned executable reports
PER-DEVICE FLOPs/bytes (verified empirically: an 8-way sharded matmul
reports 1/8 of global FLOPs), so each term is computed per chip directly:

    compute    = flops_per_device / 197e12            [s]
    memory     = bytes_per_device / 819e9             [s]
    collective = collective_bytes_per_device / 50e9   [s]

MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE), divided by the device
count for the per-device useful-compute ratio.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, "src")

from repro.bench import BenchReport, Benchmark, bench_main      # noqa: E402
from repro.core.engine import enable_compile_cache              # noqa: E402

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def analyse(cell: dict) -> dict:
    corr = cell.get("corrected") or {}
    if "error" in corr:
        corr = {}
    devices = max(cell.get("devices", 1), 1)
    # compute term: analytic FLOPs (exact; scan bodies are undercounted in
    # HLO cost analysis — see EXPERIMENTS.md §Roofline)
    flops = cell.get("analytic_flops_global", 0.0) / devices \
        if cell.get("analytic_flops_global") else corr.get(
            "flops", cell.get("flops", 0.0))
    flops_hlo = corr.get("flops", cell.get("flops", 0.0))
    flops = max(flops, flops_hlo)
    bts = corr.get("bytes", cell.get("bytes", 0.0)) \
        + cell.get("attn_hbm_topup_global", 0.0) / devices
    coll = sum(corr.get("collective_bytes",
                        cell.get("collective_bytes", {})).values())
    t_c = flops / PEAK_FLOPS
    t_m = bts / HBM_BW
    t_n = coll / ICI_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_n}
    bottleneck = max(terms, key=terms.get)
    out = dict(cell)
    out.update(t_compute=t_c, t_memory=t_m, t_collective=t_n,
               bottleneck=bottleneck,
               bound_time=max(t_c, t_m, t_n))
    if cell.get("n_params"):
        mult = 6.0 if cell.get("kind") == "train" else 2.0
        model_flops = mult * cell["n_active_params"] * cell["tokens"]
        per_dev = model_flops / devices
        out["model_flops_per_dev"] = per_dev
        out["useful_ratio"] = per_dev / flops if flops else 0.0
        out["mfu_bound"] = (per_dev / PEAK_FLOPS) / out["bound_time"] \
            if out["bound_time"] else 0.0
    return out


def load_cells(dryrun_dir: str = "results/dryrun"):
    cells = []
    for p in sorted(Path(dryrun_dir).glob("*.json")):
        c = json.loads(p.read_text())
        if "error" in c or c.get("skipped"):
            cells.append(c)
            continue
        cells.append(analyse(c))
    return cells


def render_table(cells, mesh: str = "single") -> str:
    rows = []
    hdr = (f"{'arch':22s} {'shape':12s} {'comp(ms)':>9s} {'mem(ms)':>9s} "
           f"{'coll(ms)':>9s} {'bound':>10s} {'useful':>7s} {'roofMFU':>8s}")
    rows.append(hdr)
    rows.append("-" * len(hdr))
    for c in cells:
        if c.get("mesh") != mesh:
            continue
        if c.get("skipped"):
            rows.append(f"{c['arch']:22s} {c['shape']:12s} "
                        f"{'—  skipped: sub-quadratic required':>40s}")
            continue
        if "error" in c:
            rows.append(f"{c['arch']:22s} {c['shape']:12s}  ERROR")
            continue
        rows.append(
            f"{c['arch']:22s} {c['shape']:12s} "
            f"{c['t_compute']*1e3:9.2f} {c['t_memory']*1e3:9.2f} "
            f"{c['t_collective']*1e3:9.2f} {c['bottleneck']:>10s} "
            f"{c.get('useful_ratio', 0)*100:6.1f}% "
            f"{c.get('mfu_bound', 0)*100:7.1f}%")
    return "\n".join(rows)


def add_args(ap) -> None:
    ap.add_argument("--dryrun-dir", default="results/dryrun",
                    help="directory of dry-run artifact JSONs "
                         "(python -m repro.launch.dryrun --all)")


def run_bench(args) -> BenchReport:
    """Contract entry: analyse the dry-run artifacts when present (the
    report is empty — not an error — when none exist yet)."""
    rep = BenchReport("roofline", meta={"params": {
        "dryrun_dir": args.dryrun_dir}})
    if not Path(args.dryrun_dir).exists() or \
            not any(Path(args.dryrun_dir).glob("*.json")):
        print(f"(no dry-run artifacts under {args.dryrun_dir}; run "
              f"`python -m repro.launch.dryrun --all` first)")
        rep.meta["skipped"] = "no dry-run artifacts"
        return rep
    cells = load_cells(args.dryrun_dir)
    print("\n=== roofline (single-pod) ===")
    print(render_table(cells, "single"))
    print("\n=== multi-pod (2x16x16): compile-proof cells ===")
    print("(probe-corrected costs are reported single-pod per the "
          "assignment; multi-pod cells prove the 'pod' axis shards — "
          "raw HLO numbers below are scan-undercounted, see EXPERIMENTS)")
    print(render_table(cells, "multi"))
    rep.raw = {"cells": cells}
    for c in cells:
        if c.get("skipped") or "error" in c or not c.get("arch"):
            continue
        key = f"roofline.{c['arch']}.{c.get('shape', '')}.{c.get('mesh', '')}"
        rep.add(f"{key}.mfu_bound", round(c.get("mfu_bound", 0.0), 4),
                unit="ratio", direction="higher", gate=False,
                tags={"bottleneck": c.get("bottleneck", "?")})
    return rep


BENCH = Benchmark(
    area="roofline",
    title="Roofline analysis over the dry-run compile artifacts",
    add_args=add_args,
    run=run_bench,
    gated=False,
)


def main(argv=None) -> BenchReport:
    enable_compile_cache()
    return bench_main(BENCH, argv)


if __name__ == "__main__":
    main()
