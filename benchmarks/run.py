"""Benchmark harness: one function per paper table plus the TPU-adaptation
reports.  Prints ``name,us_per_call,derived`` CSV rows (run.py contract).

    PYTHONPATH=src python -m benchmarks.run [--fast] [--skip-host]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _csv(row: dict) -> str:
    name = row.pop("name")
    us = row.pop("us_per_call", "")
    derived = ";".join(f"{k}={_fmt(v)}" for k, v in row.items())
    return f"{name},{_fmt(us)},{derived}"


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller ibench sweeps")
    ap.add_argument("--skip-host", action="store_true",
                    help="skip wall-clock host benchmarks (CI)")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    t0 = time.time()

    # ---- paper tables (static predictions; exact) -------------------
    from benchmarks import paper_tables
    for table, fn in paper_tables.ALL_TABLES.items():
        for row in fn():
            print(_csv(dict(row)))

    # ---- sweep-engine throughput (perf trajectory) ------------------
    # writes BENCH_sweep.json and emits one CSV row per batch size; see
    # benchmarks/sweep_bench.py and docs/performance.md.  Wall-clock
    # timing like the host benches, so --skip-host skips it too.
    if args.skip_host:
        print("sweep_bench/skipped,,run benchmarks.sweep_bench directly")
    else:
        from benchmarks.sweep_bench import run_bench
        sweep_report = run_bench(fast=args.fast)
        with open("BENCH_sweep.json", "w", encoding="utf-8") as f:
            json.dump(sweep_report, f, indent=2)
        for brow in sweep_report["batches"]:
            row = {"name": f"sweep_bench/batch{brow['batch']}"}
            for backend, r in brow["backends"].items():
                row[f"{backend}_pts_per_s"] = r["points_per_s"]
            if "speedup_jit_vs_numpy" in brow:
                row["speedup_jit_vs_numpy"] = \
                    brow["speedup_jit_vs_numpy"]
                row["speedup_jit_vs_pointwise"] = \
                    brow["speedup_jit_vs_pointwise"]
            print(_csv(row))
        sw = sweep_report["sweep"]
        print(_csv({"name": "sweep_bench/service_grid",
                    "backend": sw["backend"],
                    "cold_cells_per_s": sw["cold_cells_per_s"],
                    "warm_cells_per_s": sw["warm_cells_per_s"],
                    "group_dispatches": sw["group_dispatches"],
                    "sim_runs": sw["sim_runs"]}))
        # the full ServiceStats.hit_rate() breakdown: a cache
        # regression (cold programs, re-resolved machines, ...) shows
        # up here in every bench run, not only in the --check gate
        print(_csv({"name": "sweep_bench/cache_hit_rates",
                    **{f"{k}_hit_rate": sw["hit_rates"][k]
                       for k in ("result", "lookup", "lp", "edge",
                                 "program", "classify", "machine")}}))
        # journal health: replay hits on resume plus the compacted
        # on-disk footprint of the 10k-cell kill/resume probe
        # (docs/robustness.md#journal-segments)
        rs, cpn = sweep_report["resume"], sweep_report["compaction"]
        print(_csv({"name": "sweep_bench/journal",
                    "resume_journal_hits": rs["journal_hits"],
                    "resume_bit_identical": rs["resume_bit_identical"],
                    "compaction_cells": cpn["cells"],
                    "compaction_journal_hits": cpn["journal_hits"],
                    "journal_records": cpn["journal_final"]["records"],
                    "journal_segments": cpn["journal_final"]["segments"],
                    "journal_loose_files":
                        cpn["journal_final"]["loose_files"],
                    "journal_bytes": cpn["journal_final"]["bytes"]}))

    # ---- prediction-service load replay (docs/serving-service.md) ---
    if args.skip_host:
        print("service_bench/skipped,,run benchmarks.service_bench "
              "directly")
    else:
        from benchmarks.service_bench import run_bench as run_service
        service_report = run_service(fast=args.fast)
        with open("BENCH_service.json", "w", encoding="utf-8") as f:
            json.dump(service_report, f, indent=2)
        m, p = service_report["measured"], service_report["predicted"]
        print(_csv({"name": "service_bench/latency",
                    "requests": service_report["traffic"]["requests"],
                    "measured_p50_s": m["p50_s"],
                    "measured_p99_s": m["p99_s"],
                    "predicted_p50_s": p["p50_s"],
                    "predicted_p99_s": p["p99_s"]}))
        print(_csv({"name": "service_bench/dispatch",
                    "service_dispatches":
                        service_report["dispatches"]["service"],
                    "serial_dispatches":
                        service_report["dispatches"]["serial"],
                    "bit_identical":
                        service_report["bit_identical"],
                    "dropped": service_report["dropped"]}))

    # ---- roofline reports over the dry-run sweeps ---------------------
    # v0 = paper-faithful framework baseline; v1 = beyond-baseline
    # optimized defaults (EXPERIMENTS.md §Perf) — both recorded.
    from benchmarks.roofline import compare, report
    for tag, path in (("v0", "results/dryrun_baseline.json"),
                      ("v1", "results/dryrun_v1.json")):
        if not os.path.exists(path):
            print(f"roofline-{tag}/missing,,run repro.launch.dryrun "
                  f"--all --out {path}")
            continue
        for mesh in ("16x16", "2x16x16"):
            for row in report(path, mesh):
                row = dict(row)
                row["name"] = f"roofline-{tag}/{mesh}/" + row.pop("name")
                if "skipped" in row:
                    print(_csv({"name": row["name"],
                                "skipped": row["skipped"]}))
                else:
                    row.pop("model_flops", None)
                    row.pop("hlo_flops", None)
                    print(_csv(row))
    if os.path.exists("results/dryrun_v1.json") and \
            os.path.exists("results/dryrun_baseline.json"):
        lines = compare().splitlines()
        if lines and lines[-1].startswith("geomean"):
            print(f"roofline/geomean_speedup_v0_v1,,{lines[-1]}")

    # ---- host measurements (paper Sec. II/III methodology) ----------
    if not args.skip_host:
        from benchmarks.host_validation import all_host_benchmarks
        for row in all_host_benchmarks():
            print(_csv(dict(row)))
        from benchmarks.ibench_suite import (conflict_probe, host_model,
                                             ibench_sweep)
        for row in ibench_sweep(fast=True):
            print(_csv(dict(row)))
        for row in conflict_probe():
            print(_csv(dict(row)))
        for row in host_model():
            print(_csv(dict(row)))

    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
