#!/usr/bin/env python3
"""One traced window of a bulk cell, broken down by the program's own
spans and counters.

    python3 bench/breakdown.py --workload <cell> --seed <n> \
        [--seconds <s>]

Sets the cell up as ``bench/run.py`` does (the same inputs from
``--seed``, the same warm-up), runs its window under the JAX profiler
(at most ``TRACE_SECONDS``), and prints one JSON line: each call's
seconds, the device reduction of ``bench/trace.py``, the reduction of
the program's ``repro.*`` spans (``bench/program_spans.py``), the
change of the engine's ``sim_*`` counters over the window, and the
per-layer values these give.  It checks no answer: ``bench/run.py``
does.  Run from the root of the checkout, on the chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, program_spans, trace  # noqa: E402
from bench.reference.x86 import Machine  # noqa: E402
from bench.run import TRACE_DIR, TRACE_SECONDS  # noqa: E402  (sets the cache)


def layer_values(prog: dict, counters: dict, predictions: int,
                 batch_size: int) -> dict:
    """The per-layer values of one window: self times per 1,000
    predictions of the traced calls, shares of the traced window, and
    counter ratios over every call of the window."""
    out = {}
    if prog.get("calls"):
        kpred = prog["calls"] * batch_size / 1000.0
        self_s = prog["self_s"]

        def ms(*names):
            return sum(self_s.get(n, 0.0) for n in names) * 1000.0 / kpred

        out.update({
            "analytic_ms_per_kpred": ms("repro.analytic"),
            "program_compile_ms_per_kpred": ms("repro.compile_programs"),
            "pack_ms_per_kpred": ms("repro.sim.pack"),
            "postprocess_ms_per_kpred": ms("repro.sim.steady_state",
                                           "repro.sim.finish",
                                           "repro.combine"),
            "escalation_busy_share": prog["escalation_busy_share"],
            "idle_unattributed_share": prog["idle_unattributed_share"]})
    if counters.get("sim_slot_capacity"):
        out["padded_slot_share"] = 1.0 - (counters["sim_slot_steps"]
                                          / counters["sim_slot_capacity"])
    if predictions:
        out["device_calls_per_kpred"] = (counters["sim_device_calls"]
                                         * 1000.0 / predictions)
    return out


def main(argv=None, device_info=harness.device_info, adjust=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=TRACE_SECONDS)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    if adjust is not None:
        adjust(cell)
    try:
        device = device_info(cell.chips)
    except harness.NoAccelerator as exc:
        print(f"no result: {exc}", file=sys.stderr)
        return 2

    import jax
    from repro.compile_cache import enable_compile_cache
    from repro.core.machine import MachineModel

    enable_compile_cache()
    ctx = harness.Context(
        config=cell.config, traffic=cell.traffic, seed=args.seed,
        seconds=min(args.seconds, TRACE_SECONDS),
        machine=Machine(cell.config["machine"]),
        model=MachineModel.from_dict(cell.config["machine"]),
        clock=time.perf_counter, span=jax.profiler.TraceAnnotation)
    state = cell.driver.prepare(ctx)
    gc.collect()
    gc.freeze()
    before = state["engine"].stats.as_dict()
    run = harness.Run(cell=cell.name)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    with ctx.span(trace.WINDOW_SPAN):
        cell.driver.window(ctx, state, run)
    jax.profiler.stop_trace()
    gc.unfreeze()
    host, mods, ops = trace.read_profile(str(TRACE_DIR))
    spans = program_spans.read(str(TRACE_DIR))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    after = state["engine"].stats.as_dict()
    counters = {k: after[k] - before[k] for k in after
                if k.startswith("sim_")}

    device_trace = trace.reduce(host, mods, ops)
    prog = program_spans.reduce(host, mods, spans)
    print(json.dumps({
        "cell": cell.name, "seed": args.seed, "device": device,
        "calls_s": run.values["calls_s"],
        "trace": {k: device_trace[k] for k in
                  ("window_s", "busy_s", "idle_share", "calls",
                   "truncated", "idle_gaps")} if device_trace else {},
        "program": prog, "counters": counters,
        "values": layer_values(prog, counters, run.values["predictions"],
                               run.values["batch_size"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
