"""Benchmark runner — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick|--full-scale] [--only X]

Prints a ``name,us_per_call,derived`` CSV summary at the end; per-figure
detail lands in results/*.json (consumed by EXPERIMENTS.md).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import time

MODULES = [
    "bench_aggregation",     # Figs 5-8
    "bench_odirect",         # Figs 9-10
    "bench_engines",         # Figs 11-12, 15-16
    "bench_restore_alloc",   # Figs 13-14
    "bench_llm_realistic",   # Figs 17-18
    "bench_tiered",          # §8 tiered flush/prefetch vs shutil baseline
    "bench_train_overhead",  # Fig 3
    "io_hillclimb",          # §Perf I/O hypothesis loop
    "roofline",              # §Roofline from the dry-run
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small sizes (CI-friendly)")
    ap.add_argument("--full-scale", action="store_true",
                    help="paper-scale sizes (needs ~80GB disk + hours)")
    ap.add_argument("--only", default="",
                    help="comma-separated module suffixes")
    ap.add_argument("--refresh", action="store_true",
                    help="re-measure even when results/<module>.json exists")
    args = ap.parse_args()

    from benchmarks.common import RESULTS_DIR
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    only = {m.strip() for m in args.only.split(",") if m.strip()}
    csv_rows = [("name", "us_per_call", "derived")]
    for name in MODULES:
        if only and not any(name.endswith(o) or o in name for o in only):
            continue
        print(f"\n===== {name} =====", flush=True)
        cached = os.path.join(RESULTS_DIR, f"{name}.json")
        t0 = time.perf_counter()
        if name != "roofline" and not args.refresh and os.path.exists(cached):
            print(f"  (summarizing existing {cached}; --refresh re-measures)")
            for r in json.load(open(cached)):
                print("  " + " ".join(f"{k}={v}" for k, v in r.items()))
            out_path = cached
        else:
            mod = importlib.import_module(f"benchmarks.{name}")
            out_path = mod.run(full_scale=args.full_scale, quick=args.quick)
        elapsed = time.perf_counter() - t0
        derived = ""
        if out_path and os.path.exists(out_path):
            rows = json.load(open(out_path))
            if rows and "write_gbps" in rows[0]:
                best = max(r.get("write_gbps", 0) for r in rows)
                derived = f"best_write={best:.2f}GB/s"
            elif rows and "read_gbps" in rows[0]:
                best = max(r.get("read_gbps", 0) for r in rows)
                derived = f"best_read={best:.2f}GB/s"
            elif rows and "roofline_mfu" in rows[0]:
                avg = sum(r["roofline_mfu"] for r in rows) / len(rows)
                derived = f"mean_roofline_mfu={avg:.3f}"
            elif rows and "wall_s" in rows[0]:
                derived = f"rows={len(rows)}"
        csv_rows.append((name, f"{elapsed * 1e6:.0f}", derived))

    print("\n=== summary CSV ===")
    for r in csv_rows:
        print(",".join(str(x) for x in r))


if __name__ == "__main__":
    main()
