"""Tests of the benchmark harness, run on the CPU::

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests

They drive the harness without a chip (``run.main`` takes the device
look-up as an argument) at small sizes.  The repository's ``testpaths``
is ``tests``, so the tier-1 suite does not collect them.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import control, harness, run, trace  # noqa: E402
from bench.corpus import Corpus, length_table  # noqa: E402
from bench.reference import batch as ref_batch  # noqa: E402
from bench.reference.x86 import Machine  # noqa: E402

BENCH = harness.load_json(ROOT / "BENCHMARK.json")
CONFIGS = [c["name"] for c in BENCH["configs"]]
CELLS = [w["name"] for w in BENCH["workloads"]]


def _cpu(chips):
    return {"platform": "cpu", "kind": "cpu", "count": 1}


def _small(cell):
    cell.traffic.update(batch_size=96, batches_per_s=1.0, check_sample=40)


def _run(capsys, workload, seed=3, seconds="2"):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", seconds, "--trace", "0"],
                  device_info=_cpu, adjust=_small)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


# --------------------------------------------------------------------------
# trace reduction
# --------------------------------------------------------------------------

def test_trace_reduction_on_recorded_trace():
    data = harness.load_json(Path(__file__).parent / "data"
                             / "trace_sample.json")
    spans = [trace.Event(*s) for s in data["host_spans"]]
    ops = [[trace.Event(*o) for o in dev] for dev in data["device_ops"]]
    modules = [[trace.Event(*o) for o in dev]
               for dev in data["device_modules"]]
    r = trace.reduce(spans, modules, ops)
    assert r["window_s"] == pytest.approx(10.0) and not r["truncated"]
    assert r["busy_s"] == pytest.approx(2.2)          # 0.5 + 1.0 + 0.5 + 0.2
    assert r["idle_share"] == pytest.approx(0.78)
    kinds = dict(r["device_ops"])
    assert kinds["while"] == pytest.approx(0.9)       # self time only
    assert kinds["fusion"] == pytest.approx(0.8)
    assert kinds["copy-start"] == pytest.approx(0.5)  # clipped to window
    assert "copy" not in kinds                        # after the window
    gaps = r["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench.predict_batch"] * 4
    assert [g[1] for g in gaps] == pytest.approx([4.0, 2.5, 0.8, 0.5])
    assert r["idle_by_span"] == {"bench.predict_batch": pytest.approx(7.8)}
    assert r["calls"] == 2


def test_trace_reduction_cuts_a_truncated_device_record():
    spans = [trace.Event("bench.window", 0.0, 20.0)] + [
        trace.Event("bench.predict_batch", 2.5 * i, 2.5 * i + 2.5)
        for i in range(8)]
    mods = [[trace.Event("jit_run", 2.5 * i + 2.0, 2.5 * i + 2.3)
             for i in range(3)]]             # the record stops in call 3
    r = trace.reduce(spans, mods)
    assert r["truncated"] and r["window_s"] == pytest.approx(5.0)
    assert r["calls"] == 2 and r["busy_s"] == pytest.approx(0.6)


def test_trace_reduction_without_window_or_device_reads_nothing():
    assert trace.reduce([], [[trace.Event("x", 0, 1)]]) == {}
    assert trace.reduce([trace.Event("bench.window", 0, 1)], []) == {}


# --------------------------------------------------------------------------
# generator and reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("config", CONFIGS)
def test_generator_is_deterministic_and_jit_compatible(config):
    from repro.core import compile_program, extract_kernel
    from repro.core.machine import MachineModel
    from repro.core.sim.batch import _jit_compatible

    cfg = harness.load_json(ROOT / "bench" / "configs" / f"{config}.json")
    machine = Machine(cfg["machine"])
    model = MachineModel.from_dict(cfg["machine"])
    lengths = length_table(cfg["corpus"]["length"], 64)
    a = [Corpus(cfg, machine, 2**33 + 5, "t").body(i, (n, n))[0]
         for i, n in enumerate(lengths)]
    b = [Corpus(cfg, machine, 2**33 + 5, "t").body(i, (n, n))[0]
         for i, n in enumerate(lengths)]
    c = [Corpus(cfg, machine, 6, "t").body(i, (n, n))[0]
         for i, n in enumerate(lengths)]
    assert a == b and a != c
    progs = [compile_program(extract_kernel(src), model) for src in a]
    assert all(_jit_compatible([p], model.pipeline) for p in progs)


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_agrees_with_program_numpy_driver(config):
    """The yardstick computes what the program's numpy driver computes,
    on generated bodies."""
    from repro.core import compile_program, extract_kernel
    from repro.core.machine import MachineModel
    from repro.core.sim import simulate_many

    cfg = harness.load_json(ROOT / "bench" / "configs" / f"{config}.json")
    machine = Machine(cfg["machine"])
    model = MachineModel.from_dict(cfg["machine"])
    corpus = Corpus(cfg, machine, 9, "t")
    bodies = [corpus.body(i, (n, n)) for i, n in
              enumerate(length_table(cfg["corpus"]["length"], 48))]
    progs = [compile_program(extract_kernel(src), model) for src, _ in bodies]
    key = lambda r: (r.cycles_per_iteration, r.converged,  # noqa: E731
                     r.bottleneck)
    want = [key(r) for r in simulate_many(progs, backend="numpy")]
    got = [key(r) for r in ref_batch.simulate([p for _, p in bodies],
                                              machine.ports,
                                              machine.pipeline)]
    assert got == want


def test_bulk_batches_share_their_lengths_and_never_repeat():
    cell = harness.find_cell("skylake-bhive.bulk")
    cell.traffic.update(batch_size=64)
    machine = Machine(cell.config["machine"])
    ctx = harness.Context(config=cell.config, traffic=cell.traffic,
                          seed=2**31 + 7, seconds=4.0, machine=machine,
                          model=None, clock=None, span=None)
    batches = cell.driver.batches(ctx)
    assert len(batches) == 2                      # ceil(4 s x 0.5 / s)
    assert batches == cell.driver.batches(ctx)
    srcs = [s for b in batches for s in b]
    assert len(set(srcs)) == len(srcs)
    forms = [sorted(s.count("\n") for s in b) for b in batches]
    assert forms[0] == forms[1]
    assert [s.count("\n") for s in batches[0]] != \
        [s.count("\n") for s in batches[1]]


def test_answers_off_the_device_recurrence_are_not_compared():
    """An answer from another rung (the host tick loop) or a degraded
    one counts as off rung, whatever it says."""
    cfg = harness.load_json(ROOT / "bench" / "configs" / "zen-bhive.json")
    machine = Machine(cfg["machine"])
    src, prog = Corpus(cfg, machine, 5, "t").body(0, (6, 6))
    ref = ref_batch.simulate([prog], machine.ports, machine.pipeline)[0]
    answer = (ref.cycles_per_iteration, ref.converged, ref.bottleneck)
    items = [harness.Item(src, answer, "jit", False),
             harness.Item(src, answer, "tick", False),
             harness.Item(src, answer, "jit", True)]
    c = harness.check(items, machine)
    assert c["mismatches"]["value"] == 0
    assert c["off_rung_or_degraded"]["value"] == 2


def test_sample_holds_the_longest_body_and_follows_the_seed():
    items = [harness.Item("x\n" * (k % 17 + 1) + str(k), (), "jit", False)
             for k in range(300)]
    a = harness.sample_items(items, 20, 4)
    assert len(a) == 20 and a == harness.sample_items(items, 20, 4)
    assert a[0].source.count("\n") == 17
    assert a != harness.sample_items(items, 20, 5)


# --------------------------------------------------------------------------
# discovery by name
# --------------------------------------------------------------------------

def test_every_name_in_benchmark_json_has_its_files():
    for cfg in BENCH["configs"]:
        data = harness.load_json(ROOT / cfg["file"])
        assert data["name"] == cfg["name"]
        assert all(k in data for k in cfg["reduced"])
        Machine(data["machine"])
    for metric in BENCH["end_to_end"] + BENCH["per_layer"]:
        mod = harness.load_module(ROOT / "bench" / "metrics"
                                  / f"{metric['name']}.py")
        assert mod.read(harness.Run(cell="none")) is None or \
            metric["name"] in ("setup_s", "compiles_in_window.bulk")
    for name in CELLS:
        cell = harness.find_cell(name)
        for attr in ("prepare", "window", "release", "items", "sources"):
            assert callable(getattr(cell.driver, attr))
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        harness.find_cell("no-such.cell")


# --------------------------------------------------------------------------
# the run itself, without a chip
# --------------------------------------------------------------------------

def test_command_fails_off_the_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("workload", CELLS)
def test_last_line_shape(capsys, workload):
    rc, result = _run(capsys, workload)
    assert rc == 0
    assert list(result)[:5] == ["correct", "attempted", "failed",
                                "metrics", "device"]
    assert list(result)[-1] == "check"
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    cell = harness.find_cell(workload)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert all(c["limit"] == 0 for c in result["check"].values())


# --------------------------------------------------------------------------
# the comparison has to fail: the control and planted faults
# --------------------------------------------------------------------------

def test_control_in_lower_precision_is_not_correct():
    cell = harness.find_cell("skylake-bhive.bulk")
    cell.traffic.update(batch_size=64, check_sample=32)
    counts = control.control(cell, 11, 1.0,
                             ["float64", "float32", "bfloat16"])
    assert counts["float64"] == 0
    # every value of the recurrence is a small dyadic number, exact in
    # float32; bfloat16 cannot hold cycle counts past 256
    assert counts["float32"] == 0
    assert counts["bfloat16"] > 0


def _unchanged_state(out):
    return np.zeros_like(out)


def _half_the_lanes(out):
    out = out.copy()
    half = len(out) // 2
    out[half:] = out[:len(out) - half]
    return out


@pytest.mark.parametrize("fault", [_unchanged_state, _half_the_lanes])
def test_broken_device_recurrence_is_not_correct(capsys, monkeypatch,
                                                 fault):
    from repro.core.sim import batch

    real = batch._run_jax
    monkeypatch.setattr(batch, "_run_jax",
                        lambda *a, **k: fault(real(*a, **k)))
    rc, result = _run(capsys, "skylake-bhive.bulk")
    assert rc == 0 and result["correct"] is False


@pytest.mark.parametrize("workload", CELLS)
def test_altered_answer_is_not_correct(capsys, monkeypatch, workload):
    """One answer changed where it is produced: the batched driver's
    steady state for its first lane."""
    from repro.core.sim import batch

    steady = batch._steady_state

    def altered(*a, **k):
        cpi, conv = steady(*a, **k)
        cpi = cpi.copy()
        cpi[0] += 0.5
        return cpi, conv

    monkeypatch.setattr(batch, "_steady_state", altered)
    rc, result = _run(capsys, workload)
    assert rc == 0 and result["correct"] is False
    assert result["check"]["mismatches"]["value"] > 0
