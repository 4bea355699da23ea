"""Tests of the served cell (``zen-autotune.served``), run on the CPU::

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests

``run.main`` is driven without a chip, the cell cut to a 2 s window and
a small interactive pool.
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, run  # noqa: E402
from bench.corpus import length_table  # noqa: E402
from bench.reference.batch import composed_edges  # noqa: E402
from bench.reference.x86 import Machine, compile_source  # noqa: E402

CELL = "zen-autotune.served"


def _cpu(chips):
    return {"platform": "cpu", "kind": "cpu", "count": 1}


def _small(cell, check_sample=64):
    cell.config["clients"]["interactive"]["pool"] = 256
    cell.traffic.update(check_sample=check_sample)


def _run(capsys, seed=2**31 + 99, trace="0", check_sample=64):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "2", "--trace", trace], device_info=_cpu,
                  adjust=lambda cell: _small(cell, check_sample))
    captured = capsys.readouterr()
    return rc, json.loads(captured.out.strip().splitlines()[-1]), \
        captured.err


def _ctx(seconds=2.0, seed=2**31 + 5):
    cell = harness.find_cell(CELL)
    _small(cell)
    return harness.Context(config=cell.config, traffic=cell.traffic,
                           seed=seed, seconds=seconds,
                           machine=Machine(cell.config["machine"]),
                           model=None, clock=time.perf_counter,
                           span=lambda name: contextlib.nullcontext())


def test_population_follows_the_seed_and_never_repeats():
    cell = harness.find_cell(CELL)
    ctx = _ctx()
    pop = cell.driver.population(ctx)
    assert pop == cell.driver.population(ctx)
    assert pop != cell.driver.population(_ctx(seed=7))
    at = ctx.config["clients"]["autotune"]
    rounds = [rnd for mine in pop["rounds"] for rnd in mine]
    # a client starts at most one round per pause in the window
    assert len(pop["rounds"]) == at["clients"]
    assert len(rounds) == at["clients"] * 8
    every = pop["pool"] + [s for rnd in rounds for s in rnd]
    assert len(set(every)) == len(every)
    # each round holds the same multiset of lengths
    want = sorted(length_table(ctx.config["corpus"]["length"], at["round"]))
    for rnd in rounds:
        assert sorted(s.count("\n") - 4 for s in rnd) == want


def test_warm_bound_counts_what_the_program_packs():
    """Set-up warms shapes up to the largest body by the reference's
    counts, which equal the program's own."""
    from repro.core import compile_program, extract_kernel
    from repro.core.machine import MachineModel
    from repro.core.sim.batch import _composed_edges

    ctx = _ctx()
    model = MachineModel.from_dict(ctx.config["machine"])
    pool = harness.find_cell(CELL).driver.population(ctx)["pool"][:64]
    for src in pool:
        ref = compile_source(src, ctx.machine)
        prog = compile_program(extract_kernel(src), model)
        assert (len(ref.uops), len(composed_edges(ref))) == \
            (len(prog.uops), len(_composed_edges(prog)))


def test_served_run_is_correct_and_builds_nothing_in_the_window(capsys):
    rc, result, err = _run(capsys)
    assert rc == 0 and result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"predictions_per_s", "setup_s"}
    assert result["metrics"]["predictions_per_s"]["value"] > 0
    assert "0 executables built or loaded inside it" in err


def test_traced_run_reports_the_served_metrics(capsys):
    rc, result, _ = _run(capsys, trace="1")
    assert rc == 0 and result["correct"] is True
    cell = harness.find_cell(CELL)
    # the device trace's metrics need a chip: off it the trace holds no
    # device operations and their readers report nothing
    assert set(result["metrics"]) == {
        m["name"] for m in cell.per_layer if m["source"] != "device_trace"}
    assert "device_idle_share.served" in {m["name"] for m in cell.per_layer}
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["compiles_in_window.served"] == 0
    assert m["queue_wait_ms_p90.served"] >= 0
    assert m["requests_per_dispatch.served"] >= 1
    assert 0 <= m["cache_hit_share.served"] < 1
    assert m["device_calls_per_kpred.served"] > 0


def test_planted_wrong_answer_is_not_correct(capsys, monkeypatch):
    """One lane of every shard retires a cycle late from iteration 40
    on: its slope, so its answer, moves.  Every answer is checked."""
    from repro.core.sim import batch

    real = batch._run_shard

    def late(*a, **k):
        out = real(*a, **k).copy()
        out[0, 40:] += np.arange(1, out.shape[1] - 39)
        return out

    monkeypatch.setattr(batch, "_run_shard", late)
    rc, result, _ = _run(capsys, check_sample=10**6)
    assert rc == 0 and result["correct"] is False
    assert result["check"]["mismatches"]["value"] > 0


def test_planted_tick_rung_answer_is_not_correct(capsys, monkeypatch):
    """The first request of every cohort answered by the tick loop, as
    ``predict`` answers it: off the device recurrence whatever it says.
    Every answer is checked."""
    from repro.core import AnalysisService

    batched = AnalysisService.predict_batch

    def first_by_tick(self, requests, *a, **k):
        return [self.predict(requests[0])] + batched(self, requests[1:],
                                                     *a, **k)

    monkeypatch.setattr(AnalysisService, "predict_batch", first_by_tick)
    rc, result, _ = _run(capsys, check_sample=10**6)
    assert rc == 0 and result["correct"] is False
    assert result["check"]["off_rung_or_degraded"]["value"] > 0
