"""The third prediction backend: cycle-level pipeline simulation.

Runs every paper kernel through all three backends — the analytic port
bound, the loop-carried-dependency bound, and the out-of-order pipeline
simulator — on both CPU models, then shows the vectorized batch driver
producing the same sweep in one struct-of-arrays pass.

Run:  PYTHONPATH=src python examples/simulate_pipeline.py
"""
from repro.core import (AnalysisRequest, compile_program, default_service,
                        extract_kernel, simulate_many)
from repro.core import paper_kernels as pk

CASES = {
    "triad_skl_O3": ("skl", pk.TRIAD_SKL_O3, 4),
    "triad_zen_O3": ("zen", pk.TRIAD_ZEN_O3, 2),
    "pi_skl_O1": ("skl", pk.PI_O1, 1),
    "pi_skl_O2": ("skl", pk.PI_O2, 1),
    "pi_skl_O3": ("skl", pk.PI_SKL_O3, 8),
    "pi_zen_O1": ("zen", pk.PI_O1, 1),
    "pi_zen_O3": ("zen", pk.PI_ZEN_O3, 2),
}


def main():
    svc = default_service()

    print("=" * 76)
    print("Three backends per kernel [cy/asm-iteration]")
    print("=" * 76)
    print(f"{'kernel':16s} {'port':>6s} {'LCD':>6s} {'sim':>6s}"
          f"  {'binding':<11s} {'sim bottleneck':<14s}")
    for name, (arch, src, unroll) in CASES.items():
        res = svc.predict(AnalysisRequest(
            kernel=src, arch=arch, unroll_factor=unroll, mode="simulate"))
        print(f"{name:16s} {res.port_bound_cycles:6.2f} "
              f"{res.lcd_cycles:6.2f} {res.bound_sim:6.2f}"
              f"  {res.binding:<11s} {res.sim_result.bottleneck:<14s}")

    print()
    print("Detailed simulator report for pi -O1 on Skylake (the paper's")
    print("Table V outlier, measured 9.02 cy/it):")
    res = svc.predict(AnalysisRequest(kernel=pk.PI_O1, arch="skl",
                                      mode="simulate"))
    print(res.sim_result.render())

    print()
    print("=" * 76)
    print("Vectorized batch driver: the same sweep in one SoA pass")
    print("=" * 76)
    # compile_program accepts an arch id directly: it resolves through
    # the architecture registry (cached MachineModel -> InstructionDB)
    programs = [compile_program(extract_kernel(src), arch)
                for arch, src, _ in CASES.values()]
    for name, sim in zip(CASES, simulate_many(programs)):
        print(f"{name:16s} {sim.cycles_per_iteration:6.2f} cy/it  "
              f"(converged={sim.converged}, {sim.bottleneck})")

    print()
    print("Compiled backend (jax.jit, float64): same numbers to 1e-9")
    for name, sim in zip(CASES,
                         simulate_many(programs, backend="jit")):
        print(f"{name:16s} {sim.cycles_per_iteration:6.2f} cy/it")
    # a bulk sweep dispatches one compiled call per machine model:
    grid = svc.sweep({n: src for n, (_, src, _) in CASES.items()},
                     archs=("skl", "zen"), mode="simulate",
                     backend="jit")
    print(f"sweep: {len(grid)} cells, "
          f"{svc.stats.sim_group_dispatches} compiled dispatches "
          f"(see docs/performance.md and BENCH_sweep.json)")


if __name__ == "__main__":
    main()
