#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Loads the cell named in ``BENCHMARK.json`` with its configuration,
traffic mix and driver, fails at once where JAX finds no TPU (or fewer
chips than the cell asks for), generates the cell's inputs from
``--seed``, warms every compiled shape they use, measures for
``--seconds``, then checks a sample of the answers against the plain
reference.  With ``--trace 1`` the window (at most ``TRACE_SECONDS``
of it) runs under the JAX profiler and the run reports the cell's
per-layer metrics; otherwise its end-to-end ones.  The last line of standard output is one JSON object;
the last lines of standard error are the numbers compared, each with
its limit.  Run from the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
#: JAX's persistent compilation cache: one fixed directory inside the
#: checkout, set before JAX is imported so that the program takes it
#: and no cache is shared with another checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

from bench import harness  # noqa: E402
from bench.trace import WINDOW_SPAN  # noqa: E402
from bench.reference.x86 import Machine  # noqa: E402

#: where a traced run writes its profile (inside the checkout; removed
#: once reduced)
TRACE_DIR = ROOT / ".bench_trace"
#: a traced run measures at most this long: the device records several
#: million operations a second, more than the profiler keeps or a run
#: can read back in its time
TRACE_SECONDS = 2.0


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class _collection_timer:
    """Times the full (generation 2) garbage collections."""

    def __init__(self, clock):
        self.clock = clock
        self.spans: list[tuple[float, float]] = []
        self._t0 = 0.0
        gc.callbacks.append(self.on_gc)

    def on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] == 2:
            if phase == "start":
                self._t0 = self.clock()
            else:
                self.spans.append((self._t0, self.clock()))

    def between(self, t0: float, t1: float) -> str:
        inside = [b - a for a, b in self.spans if t0 <= a <= t1]
        return f"{len(inside)} taking {sum(inside):.3f} s"


def main(argv=None, device_info=harness.device_info, adjust=None) -> int:
    """One run; ``device_info`` and ``adjust`` (a function applied to
    the loaded cell) let tests drive it without a chip at small sizes."""
    args = _args(argv)
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

    clock = time.perf_counter
    cache_dir = enable_compile_cache()
    counter = harness.CompileCounter(clock)
    ctx = harness.Context(
        config=cell.config, traffic=cell.traffic, seed=args.seed,
        seconds=min(args.seconds, TRACE_SECONDS) if args.trace
        else args.seconds, machine=Machine(cell.config["machine"]),
        model=MachineModel.from_dict(cell.config["machine"]), clock=clock,
        span=jax.profiler.TraceAnnotation)
    ctx.log(f"cell {cell.name} seed {args.seed} on {device['platform']} "
            f"{device['kind']!r} x{device['count']}, jax "
            f"{jax.__version__}, compile cache {cache_dir}")

    state = cell.driver.prepare(ctx)
    ctx.log(f"set-up: {len(counter.events)} executables built or loaded "
            f"({counter.cache_hits} from the compile cache) in "
            f"{counter.seconds():.2f} s")
    # the set-up's garbage (the warm-up's engine) is collected here, and
    # what set-up keeps is left out of the window's collections
    gc.collect()
    gc.freeze()
    collections = _collection_timer(clock)

    run = harness.Run(cell=cell.name)
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # the Python tracer would record every Python call of the host
        # path and slow it; device operations and the benchmark's own
        # spans are what the reduction reads
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    with ctx.span(WINDOW_SPAN):
        cell.driver.window(ctx, state, run)
    t_end = clock()
    if args.trace:
        jax.profiler.stop_trace()
        from bench import trace
        run.trace = trace.reduce(*trace.read_profile(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    run.setup_s = ctx.t_window - T_START
    run.window_s = t_end - ctx.t_window
    run.compiles_in_window = counter.between(ctx.t_window, t_end)
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    if run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    if run.trace:
        ctx.log(f"trace: {run.trace['n_modules']} program executions, "
                f"{run.trace['n_ops_sampled']} operations sampled, traced "
                f"window {run.trace['window_s']:.3f} s"
                + (" (cut back: the device record stops early)"
                   if run.trace["truncated"] else ""))
    ctx.log(f"window: {run.window_s:.3f} s, {run.attempted} attempted, "
            f"{run.failed} failed, {run.compiles_in_window} executables "
            f"built or loaded inside it; full garbage collections "
            f"{collections.between(ctx.t_window, t_end)}")
    gc.callbacks.remove(collections.on_gc)
    gc.unfreeze()

    cell.driver.release(state)
    gc.collect()
    t0 = clock()
    items = cell.driver.items(state)
    sample = harness.sample_items(items, cell.traffic["check_sample"],
                                  args.seed)
    compared = harness.check(sample, ctx.machine)
    ctx.log(f"reference check: {len(sample)} of {len(items)} answers "
            f"in {clock() - t0:.2f} s")
    correct = all(c["value"] <= c["limit"] for c in compared.values())

    metrics = {}
    for m in cell.per_layer if args.trace else cell.end_to_end:
        value = harness.read_metric(m, run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace:
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
        ctx.log("idle by host span (s): " + json.dumps(
            run.trace["idle_by_span"]))
    result["check"] = compared
    print(json.dumps(result), flush=True)
    for name, c in compared.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
