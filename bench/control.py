#!/usr/bin/env python3
"""Control of the check that decides ``correct``: the plain reference,
put in the program's place and computed in a lower precision, has to
come out as not correct.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 \
        [--dtypes float32 bfloat16]

For each seed it draws the bodies the cell's window uses, samples them
as a run's check does, answers them with the batched reference
recurrence in each ``--dtype`` (where a run has the device's answers)
and counts, with the run's own comparison, the answers that differ from
the float64 reference.  It needs no chip and touches no program code;
the last line of standard output is one JSON object of the counts.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402
from bench.reference import batch as reference  # noqa: E402
from bench.reference.x86 import Machine, compile_source  # noqa: E402


def dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes
        return ml_dtypes.bfloat16
    return np.dtype(name).type


def control(cell: harness.Cell, seed: int, seconds: float,
            dtypes: list[str]) -> dict[str, int]:
    """Mismatches of the reference in each of ``dtypes`` against the
    float64 reference, on the sample a run of ``seed`` checks."""
    machine = Machine(cell.config["machine"])
    ctx = harness.Context(config=cell.config, traffic=cell.traffic,
                          seed=seed, seconds=seconds, machine=machine,
                          model=None, clock=time.perf_counter,
                          span=lambda name: contextlib.nullcontext())
    items = [harness.Item(src, (), "jit", False)
             for src in cell.driver.sources(ctx)]
    sample = harness.sample_items(items, cell.traffic["check_sample"], seed)
    progs = [compile_source(it.source, machine) for it in sample]
    out = {}
    for name in dtypes:
        answers = reference.simulate(progs, machine.ports, machine.pipeline,
                                     dtype(name))
        low = [harness.Item(it.source, (a.cycles_per_iteration,
                                        a.converged, a.bottleneck),
                            "jit", False)
               for it, a in zip(sample, answers)]
        out[name] = harness.check(low, machine)["mismatches"]["value"]
        print(f"seed {seed} {name}: {out[name]} of {len(sample)} answers "
              "differ from float64", file=sys.stderr, flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float,
                    help="window length (default: run_seconds)")
    ap.add_argument("--dtypes", nargs="+", default=["float32", "bfloat16"])
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    seconds = args.seconds or harness.load_json(
        harness.ROOT / "BENCHMARK.json")["run_seconds"]
    result = {seed: control(cell, seed, seconds, args.dtypes)
              for seed in args.seeds}
    print(json.dumps({"workload": cell.name, "mismatches": result}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
