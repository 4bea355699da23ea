"""Chaos suite: deterministic fault injection, the degradation ladder,
circuit breakers, and crash-safe resumable sweeps (docs/robustness.md).

The invariants pinned here:

* an engine with **no armed plan** is bit-identical to one with an
  empty plan (the fault layer is zero-cost when disarmed — the golden
  suites stay pinned);
* under a persistent injected backend failure, every request still
  resolves — demoted down the ladder or to the analytic floor — and
  the response carries ``degraded`` / ``backend_used`` /
  ``fault_trace_id`` provenance;
* breakers honor their cooldowns (closed -> open -> half_open, with an
  injectable clock, no sleeping);
* a killed, journaled sweep resumes **bit-for-bit** with zero
  re-dispatch of journaled machine groups;
* re-registering a machine model never serves a stale prediction
  (engine epoch check + service cache invalidation);
* the hypothesis schedule property: any random fault schedule replayed
  through the service resolves every admitted request exactly once —
  ``ok`` or a typed error, never a hang or a drop.

On a property failure the injector's event trace is written to
``FAULT_TRACE_PATH`` (when set) so CI can upload it as an artifact.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import math
import os
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:          # optional dev dependency
    from repro.testing import given, settings, st

from repro.core import AnalysisService, paper_kernels as pk
from repro.core.degrade import (BreakerBoard, BreakerConfig,
                                CircuitBreaker, validate_sims)
from repro.core.engine import AnalysisRequest
from repro.core.faults import (FAULT_POINTS, FaultAbort, FaultInjector,
                               FaultPlan, FaultSpec, InjectedFault)

KERNELS = {"triad_skl": pk.TRIAD_SKL_O3, "pi_o2": pk.PI_O2}


def _dump_trace(injector: FaultInjector | None) -> None:
    """CI artifact hook: persist the fault-event trace on failure."""
    path = os.environ.get("FAULT_TRACE_PATH")
    if path and injector is not None:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(injector.export(), f, indent=2)


# ----------------------------------------------------------------------
# plan / spec serialization
# ----------------------------------------------------------------------
def test_plan_json_round_trip_and_digest():
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="fail_n", count=2,
                  skip=1, match={"backend": "jit"}),
        FaultSpec(point="cache.get", mode="latency", delay_s=0.01),
        FaultSpec(point="engine.compile", mode="corrupt",
                  corrupt="negative", probability=0.5),
    ), seed=7)
    clone = FaultPlan.from_json(plan.to_json())
    assert clone == plan
    assert clone.digest == plan.digest
    assert FaultPlan(specs=plan.specs, seed=8).digest != plan.digest


@pytest.mark.parametrize("kwargs", [
    {"point": "engine.nope"},
    {"point": "engine.dispatch", "mode": "explode"},
    {"point": "engine.dispatch", "mode": "corrupt", "corrupt": "zero"},
    {"point": "engine.dispatch", "skip": -1},
    {"point": "engine.dispatch", "count": 0},
    {"point": "engine.dispatch", "probability": 1.5},
    {"point": "engine.dispatch", "delay_s": -0.1},
])
def test_spec_validation_fails_loudly(kwargs):
    with pytest.raises(ValueError):
        FaultSpec(**kwargs)


# ----------------------------------------------------------------------
# injector decision core
# ----------------------------------------------------------------------
def _fires(inj: FaultInjector, point: str, n: int, **ctx) -> int:
    fired = 0
    for _ in range(n):
        try:
            inj.fire(point, **ctx)
        except InjectedFault:
            fired += 1
    return fired


def test_fail_once_fires_exactly_once():
    inj = FaultInjector(FaultPlan(specs=(
        FaultSpec(point="engine.compile", mode="fail_once"),)))
    assert _fires(inj, "engine.compile", 10) == 1


def test_fail_n_with_skip():
    inj = FaultInjector(FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="fail_n", count=3,
                  skip=2),)))
    outcomes = []
    for _ in range(8):
        try:
            inj.fire("engine.dispatch")
            outcomes.append("ok")
        except InjectedFault:
            outcomes.append("fault")
    assert outcomes == ["ok", "ok", "fault", "fault", "fault",
                        "ok", "ok", "ok"]


def test_latency_uses_injected_sleep():
    slept: list[float] = []
    inj = FaultInjector(
        FaultPlan(specs=(FaultSpec(point="cache.get", mode="latency",
                                   delay_s=0.25, count=2),)),
        sleep=slept.append)
    for _ in range(5):
        inj.fire("cache.get")        # latency never raises
    assert slept == [0.25, 0.25]
    assert [e.action for e in inj.events()] == ["delayed", "delayed"]


def test_corrupt_nan_and_negative():
    inj = FaultInjector(FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="corrupt",
                  corrupt="nan", count=1),
        FaultSpec(point="engine.dispatch", mode="corrupt",
                  corrupt="negative"),)))
    v1, e1 = inj.corrupt("engine.dispatch", 4.0)
    assert math.isnan(v1) and e1 > 0
    v2, e2 = inj.corrupt("engine.dispatch", 4.0)
    assert v2 < 0 and e2 > e1
    # an unarmed point passes values through untouched
    v3, e3 = inj.corrupt("engine.traffic", 4.0)
    assert v3 == 4.0 and e3 == 0


def test_match_restricts_firing_to_context():
    inj = FaultInjector(FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="fail",
                  match={"backend": "jit"}),)))
    inj.fire("engine.dispatch", backend="numpy")      # no match, no fire
    with pytest.raises(InjectedFault):
        inj.fire("engine.dispatch", backend="jit")


def test_probability_is_deterministic_across_injectors():
    plan = FaultPlan(specs=(
        FaultSpec(point="cache.put", mode="fail", probability=0.5),),
        seed=42)
    a = [bool(_fires(FaultInjector(plan), "cache.put", 1))
         for _ in range(1)]
    # same plan, same call order => identical decision streams
    one, two = FaultInjector(plan), FaultInjector(plan)
    seq1 = [bool(_fires(one, "cache.put", 1)) for _ in range(40)]
    seq2 = [bool(_fires(two, "cache.put", 1)) for _ in range(40)]
    assert seq1 == seq2
    assert any(seq1) and not all(seq1)    # the coin actually flips
    del a


def test_unknown_point_rejected_at_fire_time():
    inj = FaultInjector(FaultPlan())
    with pytest.raises(ValueError):
        inj.fire("engine.nope")
    with pytest.raises(ValueError):
        inj.corrupt("engine.nope", 1.0)


def test_trace_is_bounded_with_monotone_ids():
    inj = FaultInjector(
        FaultPlan(specs=(FaultSpec(point="cache.get", mode="fail"),)),
        trace_capacity=4)
    _fires(inj, "cache.get", 10)
    events = inj.events()
    assert len(events) == 4                       # bounded
    assert [e.id for e in events] == [7, 8, 9, 10]  # monotone, newest kept
    exp = inj.export()
    assert exp["plan_digest"] and exp["fired"] == [10]
    assert inj.summary()["fired_by_point"] == {"cache.get": 10}
    inj.reset()
    assert inj.events() == [] and inj.summary()["fired_by_point"] == {}


# ----------------------------------------------------------------------
# circuit breakers
# ----------------------------------------------------------------------
def test_breaker_honors_cooldown_with_fake_clock():
    clock = SimpleNamespace(t=0.0)
    br = CircuitBreaker(BreakerConfig(failure_threshold=2,
                                      cooldown_s=10.0),
                        clock=lambda: clock.t)
    assert br.allow() and br.state == "closed"
    br.record_failure()
    assert br.state == "closed" and br.allow()    # below threshold
    br.record_failure()
    assert br.state == "open" and not br.allow()
    clock.t = 9.9
    assert not br.allow()                          # cooldown not elapsed
    clock.t = 10.0
    assert br.allow() and br.state == "half_open"
    assert not br.allow()                          # one probe only
    br.record_failure()                            # probe failed
    assert br.state == "open" and not br.allow()
    clock.t = 25.0
    assert br.allow() and br.state == "half_open"
    br.record_success()
    assert br.state == "closed" and br.failures == 0 and br.allow()


def test_breaker_board_logs_transitions():
    clock = SimpleNamespace(t=0.0)
    board = BreakerBoard(BreakerConfig(failure_threshold=1,
                                       cooldown_s=5.0),
                         clock=lambda: clock.t)
    br = board.breaker("a" * 64, "jit")
    br.record_failure()
    clock.t = 6.0
    br.allow()
    br.record_success()
    transitions = [(e["from"], e["to"]) for e in board.events()]
    assert transitions == [("closed", "open"), ("open", "half_open"),
                           ("half_open", "closed")]
    snap = board.snapshot()
    assert snap["breakers"][f"{'a' * 12}/jit"]["state"] == "closed"


def test_validate_sims_flags_corrupt_output():
    prog = SimpleNamespace(digest="k", port_bound_cycles=2.0)
    sim = lambda cpi: SimpleNamespace(cycles_per_iteration=cpi)  # noqa: E731
    assert validate_sims([sim(2.5)], [prog]) == []
    assert "non-finite" in validate_sims([sim(float("nan"))], [prog])[0]
    assert "negative" in validate_sims([sim(-1.0)], [prog])[0]
    assert "diverges above" in validate_sims([sim(2.0 * 51)], [prog])[0]
    assert "diverges below" in validate_sims([sim(2.0 / 51)], [prog])[0]
    # a zero analytic bound disables the divergence guard only
    free = SimpleNamespace(digest="k", port_bound_cycles=0.0)
    assert validate_sims([sim(1000.0)], [free]) == []


# ----------------------------------------------------------------------
# engine: ladder, floor, provenance
# ----------------------------------------------------------------------
def _sim_reqs(scheduler: str = "uniform") -> list[AnalysisRequest]:
    return [AnalysisRequest(kernel=src, arch=arch, mode="simulate",
                            scheduler=scheduler)
            for arch, src in (("skl", pk.TRIAD_SKL_O3),
                              ("zen", pk.TRIAD_ZEN_O3),
                              ("skl", pk.PI_O2))]


def test_persistent_dispatch_fault_degrades_to_analytic_floor():
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="fail"),))
    svc = AnalysisService(sim_backend="numpy", faults=plan,
                          breaker_config=BreakerConfig(
                              failure_threshold=1, cooldown_s=3600.0))
    results = svc.predict_batch(_sim_reqs())
    clean = AnalysisService()
    for req, res in zip(_sim_reqs(), results):
        assert res.degraded and res.backend_used == "analytic"
        assert res.fault_trace_id > 0
        assert res.bound_sim == 0.0 and res.sim_result is None
        assert math.isfinite(res.predicted_cycles)
        # the floor is the analytic bound, bit-identical to a clean
        # analytic-mode prediction of the same cell
        ana = clean.predict(dataclasses.replace(req, mode="analytic"))
        assert res.predicted_cycles == ana.predicted_cycles
        assert res.binding == ana.binding
    assert svc.stats.degraded_results >= len(results)
    assert svc.faults.summary()["fired_by_point"]["engine.dispatch"] >= 1
    # the numpy breaker opened for both machine models
    states = {k: v["state"]
              for k, v in svc.breakers.snapshot()["breakers"].items()}
    assert states and all(s == "open" for s in states.values())


def test_jit_failure_demotes_to_numpy_bit_identically():
    reqs = [AnalysisRequest(kernel=src, arch=arch, mode="simulate")
            for arch, src in (("skl", pk.TRIAD_SKL_O3),
                              ("zen", pk.TRIAD_ZEN_O3)) for _ in range(1)]
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="fail",
                  match={"backend": "jit"}),))
    faulty = AnalysisService(sim_backend="jit", faults=plan)
    degraded = faulty.predict_batch(reqs)
    clean = AnalysisService(sim_backend="numpy").predict_batch(reqs)
    for d, c in zip(degraded, clean):
        assert d.degraded and d.backend_used == "numpy"
        assert d.fault_trace_id > 0
        assert d.bound_sim == c.bound_sim        # numpy rung answered
        assert d.predicted_cycles == c.predicted_cycles


def test_corrupt_backend_output_is_caught_by_validator():
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="corrupt",
                  corrupt="nan"),))
    svc = AnalysisService(sim_backend="numpy", faults=plan,
                          breaker_config=BreakerConfig(
                              failure_threshold=1, cooldown_s=3600.0))
    results = svc.predict_batch(_sim_reqs())
    assert all(r.degraded for r in results)
    assert all(math.isfinite(r.predicted_cycles) for r in results)
    assert all(r.bound_sim >= 0.0 for r in results)


def test_single_predict_tick_fault_falls_to_floor():
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="fail",
                  match={"backend": "tick"}),))
    svc = AnalysisService(faults=plan)
    res = svc.predict(AnalysisRequest(kernel=pk.PI_O2, arch="skl",
                                      mode="simulate"))
    assert res.degraded and res.backend_used == "analytic"
    assert res.bound_sim == 0.0 and math.isfinite(res.predicted_cycles)
    assert svc.stats.degraded_results == 1


def _raiser(exc: BaseException):
    def broken(*args, **kwargs):
        raise exc
    return broken


@pytest.mark.parametrize("bug", [ImportError, AttributeError, TypeError,
                                 NotImplementedError])
def test_programming_error_in_a_rung_propagates(monkeypatch, bug):
    """Only real faults demote: a bug inside the jit rung surfaces
    instead of being answered quietly by numpy."""
    import repro.core.sim.batch as batch

    monkeypatch.setattr(batch, "_run_jax", _raiser(bug("rung is broken")))
    svc = AnalysisService(sim_backend="jit")
    with pytest.raises(bug, match="rung is broken"):
        svc.predict_batch(_sim_reqs())
    assert svc.stats.degraded_results == 0


def test_programming_error_in_tick_path_propagates(monkeypatch):
    import repro.core.sim as sim

    monkeypatch.setattr(sim, "simulate",
                        _raiser(AttributeError("tick is broken")))
    svc = AnalysisService()
    with pytest.raises(AttributeError, match="tick is broken"):
        svc.predict(AnalysisRequest(kernel=pk.PI_O2, arch="skl",
                                    mode="simulate"))
    assert svc.stats.degraded_results == 0


def test_device_runtime_error_demotes_to_numpy(monkeypatch):
    from jax.errors import JaxRuntimeError

    import repro.core.sim.batch as batch

    monkeypatch.setattr(batch, "_run_jax", _raiser(
        JaxRuntimeError("RESOURCE_EXHAUSTED: out of device memory")))
    results = AnalysisService(sim_backend="jit").predict_batch(_sim_reqs())
    clean = AnalysisService(sim_backend="numpy").predict_batch(_sim_reqs())
    for d, c in zip(results, clean):
        assert d.degraded and d.backend_used == "numpy"
        assert d.bound_sim == c.bound_sim


def test_pallas_refused_on_tpu_and_not_demoted(monkeypatch):
    """Mosaic takes no float64: on a TPU the pallas rung raises up
    front, and the ladder does not turn that into a jit answer."""
    import jax

    from repro.core import compile_program, extract_kernel
    from repro.core.sim import simulate_many

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prog = compile_program(extract_kernel(pk.PI_O2), "skl")
    with pytest.raises(NotImplementedError, match="float64"):
        simulate_many([prog], backend="pallas")
    svc = AnalysisService(sim_backend="pallas")
    with pytest.raises(NotImplementedError, match="float64"):
        svc.predict_batch(_sim_reqs())


def test_compile_fault_degrades_only_affected_cells():
    # the first compile dies once; the ladder floor answers that cell,
    # every other cell is full fidelity
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.compile", mode="fail_once"),))
    svc = AnalysisService(sim_backend="numpy", faults=plan)
    results = svc.predict_batch(_sim_reqs())
    flags = [r.degraded for r in results]
    assert flags.count(True) == 1
    assert all(math.isfinite(r.predicted_cycles) for r in results)


def test_disarmed_plan_is_bit_identical_to_no_plan():
    baseline = AnalysisService(sim_backend="numpy")
    armed_empty = AnalysisService(sim_backend="numpy",
                                  faults=FaultPlan())
    # cache-layer faults must never touch engine results either
    reqs = _sim_reqs() + [AnalysisRequest(kernel=pk.PI_O1, arch="skl")]
    a = baseline.predict_batch(reqs)
    b = armed_empty.predict_batch(reqs)
    for x, y in zip(a, b):
        assert x.predicted_cycles == y.predicted_cycles
        assert x.bound_sim == y.bound_sim
        assert x.binding == y.binding
        assert not y.degraded and y.fault_trace_id == 0
    assert armed_empty.faults.events() == []


# ----------------------------------------------------------------------
# crash-safe resume
# ----------------------------------------------------------------------
def test_killed_sweep_resumes_bit_identical(tmp_path):
    sweep_kw = dict(archs=("skl", "zen"), schedulers=("uniform",),
                    mode="simulate")
    reference = AnalysisService(sim_backend="numpy").sweep(
        KERNELS, **sweep_kw)

    # the second machine-group dispatch dies like a SIGKILL
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="abort", skip=1),))
    killed = AnalysisService(sim_backend="numpy", faults=plan)
    with pytest.raises(FaultAbort):
        killed.sweep(KERNELS, journal=str(tmp_path), **sweep_kw)

    resumed_svc = AnalysisService(sim_backend="numpy")
    resumed = resumed_svc.sweep(KERNELS, journal=str(tmp_path),
                                resume_from=str(tmp_path), **sweep_kw)
    assert set(resumed) == set(reference)
    for k in reference:
        assert resumed[k].predicted_cycles == reference[k].predicted_cycles
        assert resumed[k].bound_sim == reference[k].bound_sim
        assert resumed[k].binding == reference[k].binding
        assert resumed[k].sim_result.cycles_per_iteration == \
            reference[k].sim_result.cycles_per_iteration
    # exactly one group replayed from the journal, one dispatched live
    assert resumed_svc.stats.journal_hits == 1
    assert resumed_svc.stats.sim_group_dispatches == 1


def test_resume_ignores_foreign_plan_and_torn_records(tmp_path):
    from repro.checkpoint.store import RecordJournal

    sweep_kw = dict(archs=("skl",), schedulers=("uniform",),
                    mode="simulate")
    first = AnalysisService(sim_backend="numpy")
    ref = first.sweep(KERNELS, journal=str(tmp_path), **sweep_kw)

    # crash debris: a stray tmp file and a torn (truncated) record
    (tmp_path / "rec_0000000099.json.tmp").write_text("{", encoding="utf-8")
    (tmp_path / "rec_0000000042.json").write_text('{"plan": "x",',
                                                  encoding="utf-8")
    # a record for a *different* plan must be inert
    RecordJournal(str(tmp_path)).append(
        {"plan": "deadbeef", "machine": "m", "programs": ["p"],
         "backend_used": "numpy", "degraded": False, "sims": None})

    resumed_svc = AnalysisService(sim_backend="numpy")
    resumed = resumed_svc.sweep(KERNELS, resume_from=str(tmp_path),
                                **sweep_kw)
    assert resumed_svc.stats.journal_hits == 1     # only the real record
    assert resumed_svc.stats.sim_group_dispatches == 0
    for k in ref:
        assert resumed[k].predicted_cycles == ref[k].predicted_cycles
        assert resumed[k].bound_sim == ref[k].bound_sim


def test_record_journal_append_is_atomic_and_ordered(tmp_path):
    from repro.checkpoint.store import RecordJournal

    j = RecordJournal(str(tmp_path))
    j.append({"n": 1})
    j.append({"n": 2})
    assert [r["n"] for r in j.records()] == [1, 2]
    assert not list(Path(tmp_path).glob("*.tmp"))  # no debris on success
    j.clear()
    assert j.records() == []


# ----------------------------------------------------------------------
# cache invalidation on model re-registration
# ----------------------------------------------------------------------
def _slowed(model):
    """The same machine with every uop port pressure doubled — the
    port bound doubles, so any stale cache entry is immediately visible
    as an unchanged prediction."""
    forms = tuple(dataclasses.replace(
        f, uops=tuple(dataclasses.replace(u, cycles=u.cycles * 2)
                      for u in f.uops))
        for f in model.forms)
    return model.derive(model.arch_id, forms=forms)


def test_reregistration_never_serves_stale_predictions():
    svc = AnalysisService()
    req = AnalysisRequest(kernel=pk.TRIAD_SKL_O3, arch="skl")
    before = svc.predict(req)
    # mutate the registry *directly* (not through svc.register, which
    # invalidates eagerly): only the epoch check protects this path
    model = svc.registry.model("skl")
    svc.registry.register(_slowed(model), replace=True)
    after = svc.predict(req)
    assert after.predicted_cycles != before.predicted_cycles
    assert after.predicted_cycles > before.predicted_cycles


def test_service_cache_invalidated_on_reregistration():
    from repro.service import (PredictionService, ServiceRequest,
                               replay)

    svc = PredictionService()
    req = ServiceRequest(analysis=AnalysisRequest(
        kernel=pk.TRIAD_SKL_O3, arch="skl"))
    [first] = replay(svc, [(0.0, req)])
    assert first.ok
    [warm] = replay(svc, [(0.0, req)])
    assert warm.cache_hit            # the TTL cache is working...
    model = svc.engine.registry.model("skl")
    svc.engine.registry.register(_slowed(model), replace=True)
    [fresh] = replay(svc, [(0.0, req)])
    assert fresh.ok and not fresh.cache_hit   # ...and was dropped
    assert fresh.result.predicted_cycles > first.result.predicted_cycles


# ----------------------------------------------------------------------
# service under faults: deadlines, cancellation
# ----------------------------------------------------------------------
def test_deadline_expired_member_dropped_under_dispatch_latency():
    from repro.service import (DeadlineExceeded, PredictionService,
                               ServiceConfig, ServiceRequest, replay)

    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="latency",
                  delay_s=0.5, count=1),))
    engine = AnalysisService(faults=plan)
    svc = PredictionService(engine, ServiceConfig(
        batch_window_s=0.01, dispatch_timeout_s=30.0))
    # request 2 lands while the dispatcher is stuck in request 1's
    # delayed dispatch; its 0.05s deadline expires in the queue
    traffic = [
        (0.0, ServiceRequest(analysis=AnalysisRequest(
            kernel=pk.PI_O1, arch="skl", mode="simulate"))),
        (0.1, ServiceRequest(analysis=AnalysisRequest(
            kernel=pk.PI_O2, arch="zen", mode="simulate"),
            timeout_s=0.05)),
    ]
    r1, r2 = replay(svc, traffic)
    assert r1.ok and not r1.degraded
    assert isinstance(r2.error, DeadlineExceeded)
    assert svc.telemetry.tenant("default").deadline_exceeded == 1
    assert engine.faults.summary()["fired_by_point"] == \
        {"engine.dispatch": 1}


def test_predict_async_cancellation_under_latency():
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="latency",
                  delay_s=0.4, count=1),))
    svc = AnalysisService(faults=plan)
    req = AnalysisRequest(kernel=pk.PI_O1, arch="skl", mode="simulate")

    async def go():
        task = asyncio.ensure_future(svc.predict_async(req))
        await asyncio.sleep(0.05)
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        # the abandoned executor call completes in the background and
        # fills the caches; a re-await is served without re-faulting
        return await svc.predict_async(req)

    res = asyncio.run(go())
    assert res.bound_sim > 0 and not res.degraded
    assert svc.faults.summary()["fired_by_point"] == \
        {"engine.dispatch": 1}


def test_predict_async_timeout_then_retry_succeeds():
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="latency",
                  delay_s=0.4, count=1),))
    svc = AnalysisService(faults=plan)
    req = AnalysisRequest(kernel=pk.PI_O1, arch="skl", mode="simulate")

    async def go():
        return await svc.predict_async(req, timeout=0.1, retries=2,
                                       backoff_s=0.01)

    res = asyncio.run(go())
    assert res.bound_sim > 0 and not res.degraded


# ----------------------------------------------------------------------
# model artifact lint (tools/check_models.py hardening)
# ----------------------------------------------------------------------
def _load_check_models():
    import importlib.util
    path = Path(__file__).resolve().parent.parent / "tools" / \
        "check_models.py"
    spec = importlib.util.spec_from_file_location("check_models_tool",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_models_rejects_nan_and_negative_constants():
    tool = _load_check_models()
    from repro.core.arch.registry import default_registry

    model = default_registry().model("skl")
    errs: list[str] = []
    tool.check_numbers(model, "skl", errs)
    assert errs == []                      # shipped artifact is clean

    f0 = dataclasses.replace(model.forms[0], latency=float("nan"))
    u0 = dataclasses.replace(model.forms[1].uops[0], cycles=-2.0)
    f1 = dataclasses.replace(model.forms[1],
                             uops=(u0,) + model.forms[1].uops[1:])
    lv = dataclasses.replace(model.hierarchy.levels[0],
                             load_bw=float("nan"))
    hz = dataclasses.replace(model.hierarchy,
                             levels=(lv,) + model.hierarchy.levels[1:])
    bad = model.derive(model.arch_id,
                       forms=(f0, f1) + model.forms[2:], hierarchy=hz)
    errs = []
    tool.check_numbers(bad, "bad", errs)
    text = "\n".join(errs)
    assert "latency" in text
    assert "port pressure" in text
    assert "hierarchy level 0" in text
    assert len(errs) == 3


# ----------------------------------------------------------------------
# the schedule property: no request ever hangs or vanishes
# ----------------------------------------------------------------------
_POINTS = [p for p in FAULT_POINTS]
_MODES = ["fail", "fail_once", "fail_n", "latency", "corrupt"]

_spec_st = st.builds(
    FaultSpec,
    point=st.sampled_from(_POINTS),
    mode=st.sampled_from(_MODES),
    count=st.one_of(st.none(), st.integers(min_value=1, max_value=3)),
    skip=st.integers(min_value=0, max_value=2),
    delay_s=st.just(0.01),
    corrupt=st.sampled_from(["nan", "negative"]),
    probability=st.sampled_from([0.5, 1.0]),
)


@settings(max_examples=10, deadline=None, derandomize=True)
@given(st.lists(_spec_st, min_size=0, max_size=4),
       st.integers(min_value=0, max_value=2**16))
def test_any_schedule_resolves_every_request_exactly_once(specs, seed):
    """Replay a fixed traffic mix under an arbitrary (non-abort) fault
    schedule: every request comes back exactly once, ``ok`` or a typed
    error — never dropped, never duplicated — and every ok result is
    finite."""
    from repro.service import (PredictionService, ServiceConfig,
                               ServiceRequest, replay)

    plan = FaultPlan(specs=tuple(specs), seed=seed)
    engine = AnalysisService(
        faults=plan,
        breaker_config=BreakerConfig(failure_threshold=1,
                                     cooldown_s=0.01))
    svc = PredictionService(engine, ServiceConfig(batch_window_s=0.005))
    traffic = [
        (0.0, ServiceRequest(analysis=AnalysisRequest(
            kernel=pk.PI_O1, arch="skl", mode="simulate"))),
        (0.0, ServiceRequest(analysis=AnalysisRequest(
            kernel=pk.PI_O1, arch="zen", mode="simulate"))),
        (0.01, ServiceRequest(analysis=AnalysisRequest(
            kernel=pk.PI_O2, arch="skl"))),
        (0.01, ServiceRequest(analysis=AnalysisRequest(
            kernel=pk.TRIAD_SKL_O3, arch="skl", mode="simulate",
            working_set=64.0 * 2**20))),
        (0.02, ServiceRequest(analysis=AnalysisRequest(
            kernel=pk.PI_O2, arch="skl"))),      # duplicate of #3
    ]
    try:
        resps = replay(svc, traffic)
        assert len(resps) == len(traffic)
        for r in resps:
            assert r is not None
            assert r.ok or r.error is not None
            if r.ok:
                assert math.isfinite(r.result.predicted_cycles)
                if r.degraded:
                    assert r.backend_used
    except Exception:
        _dump_trace(engine.faults)
        raise
