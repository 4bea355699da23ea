"""Tests of the reduction of the program's ``repro.*`` spans
(``bench/program_spans.py``), on a recorded sample and on a CPU
profile of one ``predict_batch`` call::

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, program_spans, trace  # noqa: E402

DATA = Path(__file__).parent / "data"

#: every span the batched path records, by where it is opened
SPANS = {"repro.predict_batch", "repro.plan", "repro.analytic",
         "repro.compile_programs", "repro.dispatch", "repro.sim.pack",
         "repro.sim.shard", "repro.sim.steady_state", "repro.sim.escalate",
         "repro.sim.finish", "repro.sim.numpy", "repro.combine"}


def _sample(name):
    data = harness.load_json(DATA / name)
    host = [trace.Event(*s) for s in data["host_spans"]]
    mods = [[trace.Event(*m) for m in dev] for dev in data["device_modules"]]
    spans = [program_spans.Span(*s) for s in data.get("program_spans", [])]
    return host, mods, spans


def test_idle_is_split_and_labelled_by_the_innermost_program_span():
    host, mods, spans = _sample("program_trace_sample.json")
    r = program_spans.reduce(host, mods, spans)
    assert r["idle_by_span"] == {
        "repro.plan": pytest.approx(0.1),
        "repro.analytic": pytest.approx(3.4),       # 1.4 + 2.0: two calls
        "repro.compile_programs": pytest.approx(0.5),
        "repro.sim.pack": pytest.approx(0.4),
        # worker-thread shards outrank the caller's older dispatch span
        "repro.sim.shard": pytest.approx(0.7),
        "repro.sim.steady_state": pytest.approx(0.1),
        "repro.sim.escalate": pytest.approx(0.2),
        "repro.sim.finish": pytest.approx(0.3),
        "repro.combine": pytest.approx(0.3),
        "repro.predict_batch": pytest.approx(0.1),
        "repro.dispatch": pytest.approx(2.0),
        "bench.predict_batch": pytest.approx(0.6)}   # no program span
    top = r["idle_gaps"]
    assert [g[0] for g in top[:3]] == ["repro.analytic", "repro.analytic",
                                       "repro.dispatch"]
    assert [g[1] for g in top[:3]] == pytest.approx([2.0, 1.4, 1.2])
    assert r["idle_unattributed_share"] == pytest.approx(0.6 / 8.7)


def test_self_times_exclude_children_and_other_calls():
    host, mods, spans = _sample("program_trace_sample.json")
    r = program_spans.reduce(host, mods, spans)
    # call 0 ran before the window: its spans count nowhere
    assert r["calls"] == 2
    assert r["self_s"] == {
        "repro.predict_batch": pytest.approx(0.1),
        "repro.plan": pytest.approx(0.1),
        "repro.analytic": pytest.approx(3.4),
        "repro.compile_programs": pytest.approx(0.5),
        # 2.5 less pack, steady state, escalate and finish (same
        # thread); the worker's shards are not its children
        "repro.dispatch": pytest.approx(1.2 + 2.5),
        "repro.sim.pack": pytest.approx(0.4),
        "repro.sim.steady_state": pytest.approx(0.1),
        "repro.sim.escalate": pytest.approx(0.2),    # less its shard
        "repro.sim.finish": pytest.approx(0.3),
        "repro.combine": pytest.approx(0.3),
        "repro.sim.shard": pytest.approx(0.6 + 0.6 + 0.3 + 0.4)}
    assert r["escalation_busy_share"] == pytest.approx(0.2 / 1.3)


def test_spans_belong_to_their_call_by_its_stat():
    """A worker-thread span is counted with the call its ``call`` stat
    names, wherever it lies in time."""
    host, mods, spans = _sample("program_trace_sample.json")
    moved = [program_spans.Span(s.name, s.start, s.end, s.thread, 0)
             if s.name == "repro.sim.shard" and s.call == 2 else s
             for s in spans]
    r = program_spans.reduce(host, mods, moved)
    assert r["self_s"]["repro.sim.shard"] == pytest.approx(1.5)


def test_program_spans_leave_window_and_calls_as_they_were():
    host, mods, spans = _sample("program_trace_sample.json")
    settled = trace.reduce(host, mods)
    late = spans + [program_spans.Span("repro.predict_batch", 19.9, 25.0,
                                       0, 3)]
    r = program_spans.reduce(host, mods, late)
    assert r["window_s"] == settled["window_s"] == pytest.approx(10.0)
    assert r["calls"] == 2 and settled["calls"] == 2

    # a device record cut short: the window is cut back to the last
    # benchmark span inside it, whatever program spans run on
    host = [trace.Event("bench.window", 0.0, 20.0)] + [
        trace.Event("bench.predict_batch", 2.5 * i, 2.5 * i + 2.5)
        for i in range(8)]
    mods = [[trace.Event("jit_run", 2.5 * i + 2.0, 2.5 * i + 2.3)
             for i in range(3)]]
    spans = [program_spans.Span("repro.predict_batch", 2.5 * i,
                                2.5 * i + 2.4, 0, i) for i in range(8)]
    r = program_spans.reduce(host, mods, spans)
    assert r["window_s"] == trace.reduce(host, mods)["window_s"] == 5.0
    assert r["calls"] == 2


def test_without_program_spans_gaps_are_those_of_the_trace_reduction():
    host, mods, _ = _sample("trace_sample.json")
    settled = trace.reduce(host, mods)
    r = program_spans.reduce(host, mods, [])
    assert r["idle_gaps"] == settled["idle_gaps"]
    assert r["idle_by_span"] == settled["idle_by_span"]
    assert r["self_s"] == {} and r["idle_unattributed_share"] == 1.0


def test_predict_batch_records_every_span_once_per_phase(tmp_path):
    """One machine group on the CPU: two first-pass shards on worker
    threads, one exotic lane on the numpy driver, one lane escalated."""
    import jax

    from repro.core import AnalysisRequest, AnalysisService
    from repro.core import paper_kernels as pk
    from bench.corpus import Corpus
    from bench.reference.x86 import Machine

    cfg = harness.load_json(ROOT / "bench" / "configs" / "zen-bhive.json")
    corpus = Corpus(cfg, Machine(cfg["machine"]), 5, "spans")
    bodies = [corpus.body(i, (4, 4))[0] for i in range(70)]
    # more ported uops than Zen's scheduler holds: exotic
    exotic = "\n".join(f"vaddpd %ymm{i % 8}, %ymm{8 + i % 8}, "
                       f"%ymm{8 + (i + 1) % 8}" for i in range(90))
    # still in its transient at 96 iterations on Zen: escalated
    reqs = [AnalysisRequest(kernel=k, arch="zen", mode="simulate")
            for k in bodies + [pk.PI_O2, exotic]]
    svc = AnalysisService(sim_backend="jit")
    jax.profiler.start_trace(str(tmp_path))
    svc.predict_batch(reqs)
    jax.profiler.stop_trace()
    assert svc.stats.sim_host_lanes == 1
    assert svc.stats.sim_escalated_lanes >= 1

    spans = program_spans.read(str(tmp_path))
    assert {s.name for s in spans} == SPANS
    assert len({s.call for s in spans}) == 1
    shards = [s for s in spans if s.name == "repro.sim.shard"]
    assert len(shards) == svc.stats.sim_device_calls
    assert len(spans) <= 16 + len(shards)
    # the first pass's shards ran on worker threads
    (top,) = [s for s in spans if s.name == "repro.predict_batch"]
    assert any(s.thread != top.thread for s in shards)
    # spans of one thread nest; the caller's lie inside the call
    for a in spans:
        for b in spans:
            if a.thread == b.thread and a is not b:
                assert a.end <= b.start or b.end <= a.start or \
                    (a.start <= b.start and b.end <= a.end) or \
                    (b.start <= a.start and a.end <= b.end)
        assert top.start <= a.start and a.end <= top.end
    (esc,) = [s for s in spans if s.name == "repro.sim.escalate"]
    inside = {s.name for s in spans
              if esc.start <= s.start and s.end <= esc.end}
    assert {"repro.sim.pack", "repro.sim.shard",
            "repro.sim.steady_state"} <= inside


def test_breakdown_reports_the_window_counters(capsys):
    """Off the chip the profile has no device plane, so only the
    counters and call times come back."""
    import json

    from bench import breakdown

    def small(cell):
        cell.traffic.update(batch_size=96, batches_per_s=1.0)

    rc = breakdown.main(["--workload", "skylake-bhive.bulk", "--seed", "3",
                         "--seconds", "1"],
                        device_info=lambda chips: {"platform": "cpu"},
                        adjust=small)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["program"] == {} and out["trace"] == {}
    c = out["counters"]
    assert c["sim_group_dispatches"] == len(out["calls_s"])
    # 96 lanes a call: two 64-lane shards, the second half padding
    assert c["sim_lanes"] == 96 * len(out["calls_s"])
    assert c["sim_device_calls"] >= 2 * len(out["calls_s"])
    assert 0.5 < out["values"]["padded_slot_share"] < 1.0
    assert out["values"]["device_calls_per_kpred"] == pytest.approx(
        c["sim_device_calls"] * 1000 / (96 * len(out["calls_s"])))
