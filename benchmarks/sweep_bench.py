"""Sweep-throughput benchmark: the perf trajectory of the batch engine.

Measures points/sec of the three ways this repo can run a
``mode="simulate"`` sweep point and writes ``BENCH_sweep.json``:

* ``pointwise`` — the legacy hot path: one reference tick-loop
  simulation (``sim.pipeline.simulate``) per sweep point, which is what
  ``AnalysisService.sweep`` dispatched before the grouped planner.
* ``numpy`` — the vectorized struct-of-arrays driver
  (``simulate_many(backend="numpy")``).
* ``jit`` — the compiled driver (``backend="jit"``): sharded
  ``jax.jit`` recurrence, float64, bit-compatible with numpy to 1e-9.

It also runs a service-level grid through the grouped
``AnalysisService.sweep`` planner and records the cache hit rates
(result/edge/program/classify/machine) plus the number of compiled
group dispatches — the counters that tell you whether a production
sweep is amortizing its preprocessing.

Usage::

    PYTHONPATH=src python benchmarks/sweep_bench.py \
        [--fast] [--out BENCH_sweep.json] [--check]

``--check`` exits non-zero if the jit backend is slower than numpy at
any batch >= 64, if an ECM re-sweep leaves the planner fast path, or
if the recompute pass (sweep again after ``drop_results()`` expiry)
fails to reuse any compiled ``SimProgram`` (the program cache must not
be cold across successive sweeps).  See docs/performance.md for how to
read the output.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time


def _build_programs():
    """Compile the paper kernels on both CPU models (prep is excluded
    from the timed region — the planner memoizes it in production)."""
    from repro.core import extract_kernel
    from repro.core import paper_kernels as pk
    from repro.core.arch.skylake import build_skylake_db
    from repro.core.arch.zen import build_zen_db
    from repro.core.sim import compile_program

    skl, zen = build_skylake_db(), build_zen_db()
    cases = [("skl", pk.TRIAD_SKL_O3), ("zen", pk.TRIAD_ZEN_O3),
             ("skl", pk.PI_O1), ("zen", pk.PI_O1),
             ("skl", pk.PI_O2), ("zen", pk.PI_O2),
             ("skl", pk.PI_SKL_O3), ("zen", pk.PI_ZEN_O3)]
    return [compile_program(extract_kernel(src),
                            skl if arch == "skl" else zen)
            for arch, src in cases]


def _time(fn, repeats: int = 1) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_batches(batches: list[int], repeats: int = 2) -> list[dict]:
    """Driver throughput at each batch size; same programs, bit-equal
    results across backends (asserted)."""
    from repro.core.sim import simulate, simulate_many

    base = _build_programs()
    rows = []
    for B in batches:
        progs = (base * (-(-B // len(base))))[:B]
        row: dict = {"batch": B, "backends": {}}

        # legacy pointwise reference: constant per-point cost, so the
        # rate is measured on a bounded prefix
        n_pt = min(B, 16)
        t_pt = _time(lambda: [simulate(p) for p in progs[:n_pt]])
        row["backends"]["pointwise"] = {
            "points_per_s": round(n_pt / t_pt, 2),
            "measured_points": n_pt,
        }

        t_np = _time(lambda: simulate_many(progs, backend="numpy"),
                     repeats)
        row["backends"]["numpy"] = {
            "seconds": round(t_np, 4),
            "points_per_s": round(B / t_np, 2),
        }

        res_np = simulate_many(progs, backend="numpy")
        t_cold = _time(lambda: simulate_many(progs, backend="jit"))
        t_jit = _time(lambda: simulate_many(progs, backend="jit"),
                      repeats)
        res_jit = simulate_many(progs, backend="jit")
        drift = max(abs(a.cycles_per_iteration -
                        b.cycles_per_iteration)
                    for a, b in zip(res_np, res_jit))
        assert drift < 1e-9, f"backend drift {drift}"
        row["backends"]["jit"] = {
            "cold_seconds": round(t_cold, 4),
            "seconds": round(t_jit, 4),
            "points_per_s": round(B / t_jit, 2),
            "max_drift_vs_numpy": drift,
        }
        row["speedup_jit_vs_numpy"] = round(t_np / t_jit, 2)
        row["speedup_jit_vs_pointwise"] = round(
            (B / t_jit) / row["backends"]["pointwise"]
            ["points_per_s"], 2)
        rows.append(row)
    return rows


def bench_sweep(cells_target: int = 1024) -> dict:
    """A service-level grid through the grouped planner: cache hit
    rates and dispatch counts for a ~``cells_target``-cell sweep."""
    from repro.core import AnalysisService
    from repro.core import paper_kernels as pk

    kernels = {"triad_skl": pk.TRIAD_SKL_O3, "triad_zen": pk.TRIAD_ZEN_O3,
               "pi_o1": pk.PI_O1, "pi_o2": pk.PI_O2,
               "pi_skl_o3": pk.PI_SKL_O3, "pi_zen_o3": pk.PI_ZEN_O3}
    # force the compiled driver: "auto" would pick numpy here (each
    # machine group holds only len(kernels) unique programs, below
    # AUTO_JIT_MIN_BATCH)
    backend = "jit"
    svc = AnalysisService(sim_backend=backend)
    reps = max(1, cells_target // (len(kernels) * 2 * 2))
    # cold: the first grid pays parsing, analytic passes, program
    # compilation and the grouped dispatches; warm: every further grid
    # is the dedupe/cache path a steady-state sweeping service runs on.
    # The two rates answer different questions — keep them separate.
    t0 = time.perf_counter()
    grid = svc.sweep(kernels, archs=("skl", "zen"),
                     schedulers=("uniform", "balanced"),
                     mode="simulate")
    cold_dt = time.perf_counter() - t0
    cells = len(grid)
    t1 = time.perf_counter()
    warm_cells = 0
    for _ in range(reps - 1):
        warm_cells += len(svc.sweep(
            kernels, archs=("skl", "zen"),
            schedulers=("uniform", "balanced"), mode="simulate"))
    warm_dt = time.perf_counter() - t1
    # recompute pass: expire the volatile caches (results, sims) the
    # way a persistent service does when result TTLs lapse, then sweep
    # again — compiled SimPrograms and dependency edges must be
    # *reused* (program-cache hits), not recompiled.  Before this pass
    # existed the program cache recorded hit rate 0.0 on every bench
    # run: nothing ever exercised reuse across sweeps.
    t2 = time.perf_counter()
    svc.drop_results()
    recompute_cells = len(svc.sweep(
        kernels, archs=("skl", "zen"),
        schedulers=("uniform", "balanced"), mode="simulate"))
    recompute_dt = time.perf_counter() - t2
    program_hit_rate = svc.stats.hit_rate("program")
    # ECM pass over the already-swept grid (docs/ecm.md): must reuse
    # every cached analytic pass and simulation — the working set only
    # keys the traffic memo, never the sim cache
    sim_runs_before = svc.stats.sim_runs
    dispatches_before = svc.stats.sim_group_dispatches
    t3 = time.perf_counter()
    ecm_grid = svc.sweep(kernels, archs=("skl", "zen"),
                         schedulers=("uniform", "balanced"),
                         mode="simulate", working_set=64.0 * 2**20)
    ecm_dt = time.perf_counter() - t3
    ecm_extra_sims = svc.stats.sim_runs - sim_runs_before
    ecm_extra_dispatches = (svc.stats.sim_group_dispatches
                            - dispatches_before)
    s = svc.stats
    return {
        "backend": backend,
        "cells": cells + warm_cells,
        "cold_cells": cells,
        "cold_seconds": round(cold_dt, 4),
        "cold_cells_per_s": round(cells / cold_dt, 2),
        "warm_cells": warm_cells,
        "warm_seconds": round(warm_dt, 4),
        "warm_cells_per_s": round(warm_cells / warm_dt, 2)
        if warm_dt else 0.0,
        "sim_runs": s.sim_runs,
        "group_dispatches": s.sim_group_dispatches,
        "recompute_cells": recompute_cells,
        "recompute_seconds": round(recompute_dt, 4),
        "program_hits": s.program_hits,
        "program_hit_rate": round(program_hit_rate, 4),
        "ecm_cells": len(ecm_grid),
        "ecm_seconds": round(ecm_dt, 4),
        "ecm_cells_per_s": round(len(ecm_grid) / ecm_dt, 2)
        if ecm_dt else 0.0,
        "ecm_extra_sim_runs": ecm_extra_sims,
        "ecm_extra_group_dispatches": ecm_extra_dispatches,
        "hit_rates": {k: round(s.hit_rate(k), 4)
                      for k in ("result", "lookup", "lp", "edge",
                                "program", "classify", "machine")},
        "stats": s.as_dict(),
    }


def bench_resume() -> dict:
    """Crash-safe resume probe (docs/robustness.md): journal a sweep,
    kill it mid-flight with an injected abort, resume from the journal
    and demand a bit-identical grid with zero re-dispatch of the
    journaled machine group."""
    import tempfile

    from repro.core import AnalysisService, FaultPlan, FaultSpec
    from repro.core import paper_kernels as pk
    from repro.core.faults import FaultAbort

    kernels = {"triad_skl": pk.TRIAD_SKL_O3, "pi_o2": pk.PI_O2}
    sweep_kw = dict(archs=("skl", "zen"), schedulers=("uniform",),
                    mode="simulate")

    t0 = time.perf_counter()
    reference = AnalysisService(sim_backend="numpy").sweep(
        kernels, **sweep_kw)
    ref_dt = time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as td:
        # the second engine.dispatch fire (the zen machine group) dies
        # the way a SIGKILL would: no containment, no ladder, the sweep
        # call never returns.  The skl group's record is already on
        # disk by then — RecordJournal.append is atomic per record.
        plan = FaultPlan(specs=(
            FaultSpec(point="engine.dispatch", mode="abort", skip=1),))
        killed = AnalysisService(sim_backend="numpy", faults=plan)
        aborted = False
        try:
            killed.sweep(kernels, journal=td, **sweep_kw)
        except FaultAbort:
            aborted = True

        resumed_svc = AnalysisService(sim_backend="numpy")
        t1 = time.perf_counter()
        resumed = resumed_svc.sweep(kernels, journal=td,
                                    resume_from=td, **sweep_kw)
        resume_dt = time.perf_counter() - t1

    identical = (set(resumed) == set(reference) and all(
        (resumed[k].predicted_cycles, resumed[k].bound_sim,
         resumed[k].binding)
        == (reference[k].predicted_cycles, reference[k].bound_sim,
            reference[k].binding)
        for k in reference))
    s = resumed_svc.stats
    return {
        "cells": len(reference),
        "aborted_mid_sweep": aborted,
        "journal_hits": s.journal_hits,
        "group_dispatches_on_resume": s.sim_group_dispatches,
        "resume_bit_identical": identical,
        "reference_seconds": round(ref_dt, 4),
        "resume_seconds": round(resume_dt, 4),
    }


def bench_compaction(cells_target: int = 10000,
                     segment_size: int = 8) -> dict:
    """Journal-compaction probe (docs/robustness.md#journal-segments):
    a >= ``cells_target``-cell journaled sweep over many derived
    machine models is killed mid-run, resumed through at least one
    compaction cycle, and must come back bit-identical with zero
    re-dispatch of journaled groups while the journal keeps
    O(segments) live files instead of O(records).

    The grid is wide, not deep: kernel-name aliases share two unique
    kernel texts and the derived machines share the base model's
    tables, so the engine's dedupe keeps the compute bounded — the
    probe measures journal mechanics at 10k-cell scale, not the
    simulator."""
    import tempfile

    from repro.core import (AnalysisService, FaultPlan, FaultSpec,
                            get_model)
    from repro.core import paper_kernels as pk
    from repro.core.faults import FaultAbort
    from repro.core.journal import SweepJournal

    n_machines = 25
    kill_after = n_machines // 2
    base = get_model("skl")
    texts = [pk.TRIAD_SKL_O3, pk.PI_O2]
    n_names = -(-cells_target // n_machines)
    kernels = {f"k{i:04d}": texts[i % len(texts)]
               for i in range(n_names)}

    def service(**kw):
        svc = AnalysisService(sim_backend="numpy", **kw)
        archs = tuple(svc.register(base.derive(f"skl_v{i:03d}"))
                      for i in range(n_machines))
        return svc, archs

    sweep_kw = dict(schedulers=("uniform",), mode="simulate",
                    backend="numpy")

    svc_ref, archs = service()
    t0 = time.perf_counter()
    reference = svc_ref.sweep(kernels, archs=archs, **sweep_kw)
    ref_dt = time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as td:
        # simulated SIGKILL after kill_after machine groups journaled
        plan = FaultPlan(specs=(
            FaultSpec(point="engine.dispatch", mode="abort",
                      skip=kill_after),))
        svc_kill, archs_k = service(faults=plan)
        aborted = False
        try:
            svc_kill.sweep(kernels, archs=archs_k, journal=td,
                           journal_segment_size=segment_size, **sweep_kw)
        except FaultAbort:
            aborted = True
        mid = SweepJournal(td).stats()
        svc_res, archs_r = service()
        t1 = time.perf_counter()
        resumed = svc_res.sweep(kernels, archs=archs_r, journal=td,
                                resume_from=td,
                                journal_segment_size=segment_size,
                                **sweep_kw)
        resume_dt = time.perf_counter() - t1
        final = SweepJournal(td).stats()

    identical = (set(resumed) == set(reference) and all(
        (resumed[k].predicted_cycles, resumed[k].bound_sim,
         resumed[k].binding)
        == (reference[k].predicted_cycles, reference[k].bound_sim,
            reference[k].binding)
        for k in reference))
    s = svc_res.stats
    return {
        "cells": len(reference),
        "machine_groups": n_machines,
        "segment_size": segment_size,
        "aborted_mid_sweep": aborted,
        "journal_hits": s.journal_hits,
        "group_dispatches_on_resume": s.sim_group_dispatches,
        "resume_bit_identical": identical,
        "journal_at_kill": mid,
        "journal_final": final,
        "engine_journal_stats": {
            "records": s.journal_records,
            "segments": s.journal_segments,
            "bytes": s.journal_bytes},
        "reference_seconds": round(ref_dt, 4),
        "resume_seconds": round(resume_dt, 4),
    }


def run_bench(fast: bool = False) -> dict:
    from repro.core.sim import AUTO_JIT_MIN_BATCH, JIT_SHARD

    batches = [1, 64, 256] if fast else [1, 64, 1024]
    report = {
        "benchmark": "sweep_bench",
        "host": {"cpu_count": os.cpu_count(),
                 "platform": platform.platform(),
                 "python": platform.python_version()},
        "config": {"fast": fast, "jit_shard": JIT_SHARD,
                   "auto_jit_min_batch": AUTO_JIT_MIN_BATCH},
        "batches": bench_batches(batches, repeats=1 if fast else 2),
        "sweep": bench_sweep(256 if fast else 1024),
        "resume": bench_resume(),
        "compaction": bench_compaction(),
    }
    gate_rows = [r for r in report["batches"]
                 if r["batch"] >= 64]
    # the jit-vs-numpy speedup scales with how many cores the shard
    # pool gets; 10x was measured on a 16-core host.  Scale the target
    # to this container so the gate carries signal instead of being a
    # hard false on the 2-core CI reference (docs/performance.md)
    cores = os.cpu_count() or 1
    scale_target = max(1.0, 10.0 * cores / 16)
    # both 10x readings are recorded so the trajectory is honest about
    # what is and is not met on this host: vs the legacy per-point hot
    # path the planner replaced, and vs the vectorized numpy driver
    # (the latter needs more cores than the 2-core reference container
    # gives the shard pool — see docs/performance.md)
    report["gate"] = {
        "jit_not_slower_than_numpy_at_64plus": all(
            r["speedup_jit_vs_numpy"] >= 1.0 for r in gate_rows),
        "jit_10x_pointwise_at_max_batch": bool(
            gate_rows and gate_rows[-1]
            ["speedup_jit_vs_pointwise"] >= 10.0),
        "jit_10x_numpy_at_max_batch": bool(
            gate_rows and gate_rows[-1]
            ["speedup_jit_vs_numpy"] >= 10.0),
        # scale-aware variant of the 10x-vs-numpy reading: target
        # proportional to the container's core count (recorded in
        # host.cpu_count), floored at parity
        "jit_numpy_scale_aware_target": round(scale_target, 2),
        "jit_numpy_scale_aware": bool(
            gate_rows and gate_rows[-1]
            ["speedup_jit_vs_numpy"] >= scale_target),
        # a killed, journaled sweep must resume bit-identical with
        # zero re-dispatch of journaled machine groups
        "resume_bit_identical": (
            report["resume"]["resume_bit_identical"]
            and report["resume"]["aborted_mid_sweep"]),
        "resume_zero_redispatch": (
            report["resume"]["journal_hits"] >= 1
            and report["resume"]["group_dispatches_on_resume"]
            + report["resume"]["journal_hits"] == 2),
        # a killed 10k-cell journaled sweep must resume bit-identical
        # through at least one compaction cycle, with zero re-dispatch
        # of journaled groups and a live file count bounded by the
        # segment size (docs/robustness.md#journal-segments)
        "compaction_bit_identical": (
            report["compaction"]["resume_bit_identical"]
            and report["compaction"]["aborted_mid_sweep"]
            and report["compaction"]["cells"] >= 10000),
        "compaction_zero_redispatch": (
            report["compaction"]["journal_hits"] >= 1
            and report["compaction"]["journal_hits"]
            + report["compaction"]["group_dispatches_on_resume"]
            == report["compaction"]["machine_groups"]),
        "compaction_files_bounded": (
            report["compaction"]["journal_final"]["segments"] >= 1
            and report["compaction"]["journal_final"]["loose_files"]
            <= report["compaction"]["segment_size"]
            and report["compaction"]["journal_final"]["records"]
            == report["compaction"]["machine_groups"]),
        # an ECM sweep over a warm grid must stay on the planner fast
        # path: zero additional simulations or compiled dispatches
        "ecm_zero_extra_dispatches": (
            report["sweep"]["ecm_extra_sim_runs"] == 0
            and report["sweep"]["ecm_extra_group_dispatches"] == 0),
        # compiled SimPrograms must be *reused* when a later sweep
        # re-simulates after result expiry (the recompute pass) — a
        # 0.0 program hit rate means every sweep recompiles from
        # scratch
        "program_cache_reused": report["sweep"]["program_hits"] > 0,
    }
    return report


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="smaller batches (CI perf-smoke)")
    ap.add_argument("--out", default="BENCH_sweep.json")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless jit >= numpy at batch >= 64")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    report = run_bench(fast=args.fast)
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=2)
    for row in report["batches"]:
        line = f"batch={row['batch']:5d}"
        for name, r in row["backends"].items():
            line += f"  {name}={r['points_per_s']:.0f} pts/s"
        if "speedup_jit_vs_numpy" in row:
            line += (f"  (jit {row['speedup_jit_vs_numpy']}x numpy, "
                     f"{row['speedup_jit_vs_pointwise']}x pointwise)")
        print(line)
    sw = report["sweep"]
    print(f"sweep[{sw['backend']}]: cold {sw['cold_cells']} cells at "
          f"{sw['cold_cells_per_s']} cells/s "
          f"({sw['group_dispatches']} dispatches, {sw['sim_runs']} "
          f"simulations), warm {sw['warm_cells']} cells at "
          f"{sw['warm_cells_per_s']} cells/s, recompute "
          f"{sw['recompute_cells']} cells with program hit rate "
          f"{sw['program_hit_rate']}, ecm {sw['ecm_cells']} "
          f"cells at {sw['ecm_cells_per_s']} cells/s "
          f"(+{sw['ecm_extra_sim_runs']} sims)")
    rs = report["resume"]
    print(f"resume: {rs['cells']} cells, aborted={rs['aborted_mid_sweep']}, "
          f"journal_hits={rs['journal_hits']}, "
          f"dispatches={rs['group_dispatches_on_resume']}, "
          f"bit_identical={rs['resume_bit_identical']}")
    cp = report["compaction"]
    print(f"compaction: {cp['cells']} cells over "
          f"{cp['machine_groups']} machine groups, "
          f"journal_hits={cp['journal_hits']}, "
          f"dispatches={cp['group_dispatches_on_resume']}, "
          f"segments={cp['journal_final']['segments']}, "
          f"loose={cp['journal_final']['loose_files']} "
          f"(bound {cp['segment_size']}), "
          f"bit_identical={cp['resume_bit_identical']}")
    print(f"wrote {args.out}")
    failures = []
    if args.check:
        if not report["gate"]["jit_not_slower_than_numpy_at_64plus"]:
            failures.append("jit backend slower than numpy at "
                            "batch >= 64")
        if not report["gate"]["jit_numpy_scale_aware"]:
            failures.append(
                f"jit speedup over numpy below the scale-aware target "
                f"{report['gate']['jit_numpy_scale_aware_target']}x "
                f"for this host (see docs/performance.md)")
        if not report["gate"]["ecm_zero_extra_dispatches"]:
            failures.append("ECM sweep left the planner fast path "
                            "(extra sim runs/dispatches)")
        if not report["gate"]["program_cache_reused"]:
            failures.append("program cache cold: recompute sweep "
                            "after drop_results() reused no compiled "
                            "SimPrograms (hit rate 0.0)")
        if not report["gate"]["resume_bit_identical"]:
            failures.append("resumed sweep is not bit-identical to an "
                            "uninterrupted reference sweep")
        if not report["gate"]["resume_zero_redispatch"]:
            failures.append("resume re-dispatched a journaled machine "
                            "group (journal replay must cost zero "
                            "dispatches)")
        if not report["gate"]["compaction_bit_identical"]:
            failures.append("compacted 10k-cell sweep did not resume "
                            "bit-identical to an uninterrupted "
                            "reference")
        if not report["gate"]["compaction_zero_redispatch"]:
            failures.append("compacted resume re-dispatched a "
                            "journaled machine group")
        if not report["gate"]["compaction_files_bounded"]:
            failures.append("journal live file count not bounded by "
                            "the segment size after compaction")
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
