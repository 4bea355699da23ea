"""Health-aware dispatch: breaker-aware routing, retry governance, and
journal compaction (docs/robustness.md).

The invariants pinned here:

* the :class:`HealthRouter` starts every dispatch at the healthiest
  rung — an open rung is skipped *before* a dispatch is paid, a rung
  whose cooldown has elapsed gets at most one scheduled probe per
  window, and when every rung is unhealthy the group takes the
  analytic floor with zero dispatch attempts;
* with the router disabled (the default) the engine is bit-identical
  to the pre-routing behavior — provenance fields stay empty;
* routed results carry ``routed_from`` / ``probe`` provenance end to
  end (engine ``AnalysisResult`` and service ``ServiceResponse``);
* service retries are governed: capped full-jitter backoff, recorded
  sleeps, per-tenant retry budgets that fail fast with an explicit
  reason, and hedged dispatch that races the next rung against a
  straggling primary;
* journal compaction folds loose records into sealed, digest-verified
  segments — readback is ordered, torn segments are skipped, resumed
  sweeps stay bit-identical with zero re-dispatch, and the live file
  count stays bounded by the segment size.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time

import pytest

from repro.core import AnalysisService, paper_kernels as pk
from repro.core.degrade import (BreakerBoard, BreakerConfig,
                                HealthRouter, RoutePlan, RouterConfig)
from repro.core.engine import AnalysisRequest
from repro.core.faults import FaultAbort, FaultPlan, FaultSpec
from repro.core.journal import SweepJournal
from repro.checkpoint.store import RecordJournal
from repro.service import (DispatchError, PredictionService,
                           ServiceConfig, ServiceRequest, TenantPolicy,
                           replay)
from repro.service.request import HloRequest

KERNELS = {"triad_skl": pk.TRIAD_SKL_O3, "pi_o2": pk.PI_O2}


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _sim_reqs() -> list[AnalysisRequest]:
    return [AnalysisRequest(kernel=src, arch=arch, mode="simulate")
            for arch, src in (("skl", pk.TRIAD_SKL_O3),
                              ("zen", pk.TRIAD_ZEN_O3),
                              ("skl", pk.PI_O2))]


# ----------------------------------------------------------------------
# HealthRouter unit semantics (fake clock, no engine)
# ----------------------------------------------------------------------
def test_route_plan_healthy_start():
    clock = FakeClock()
    board = BreakerBoard(BreakerConfig(), clock=clock)
    router = HealthRouter(clock=clock)
    plan = router.plan(board, "d" * 64, ("jit", "numpy"))
    assert plan == RoutePlan(("jit", "numpy"), "", False)
    assert router.stats["plans"] == 1 and router.stats["routed"] == 0


def test_route_skips_open_rung_without_dispatch():
    clock = FakeClock()
    board = BreakerBoard(BreakerConfig(failure_threshold=1,
                                       cooldown_s=10.0), clock=clock)
    board.breaker("d" * 64, "jit").record_failure()     # open
    router = HealthRouter(clock=clock)
    plan = router.plan(board, "d" * 64, ("jit", "numpy"))
    assert plan.rungs == ("numpy",)
    assert plan.routed_from == "jit" and not plan.probe
    assert router.stats["routed"] == 1
    # the skipped breaker never transitioned: no dispatch was paid
    assert board.breaker("d" * 64, "jit").state == "open"


def test_probe_slot_consumed_once_per_window():
    clock = FakeClock()
    board = BreakerBoard(BreakerConfig(failure_threshold=1,
                                       cooldown_s=10.0), clock=clock)
    board.breaker("d" * 64, "jit").record_failure()
    clock.t = 11.0                                      # cooldown over
    router = HealthRouter(clock=clock)
    # preview never consumes the slot
    seen = router.preview(board, "d" * 64, ("jit", "numpy"))
    assert seen.rungs[0] == "jit" and seen.probe
    assert router.stats["probes"] == 0
    first = router.plan(board, "d" * 64, ("jit", "numpy"))
    assert first.rungs[0] == "jit" and first.probe
    # same window: all other traffic routes below the probing rung
    second = router.plan(board, "d" * 64, ("jit", "numpy"))
    assert second.rungs == ("numpy",)
    assert second.routed_from == "jit" and not second.probe
    # next window: a new probe is scheduled
    clock.t = 21.5
    third = router.plan(board, "d" * 64, ("jit", "numpy"))
    assert third.probe and third.rungs[0] == "jit"
    assert router.stats["probes"] == 2


def test_route_floor_when_every_rung_open():
    clock = FakeClock()
    board = BreakerBoard(BreakerConfig(failure_threshold=1,
                                       cooldown_s=10.0), clock=clock)
    for rung in ("jit", "numpy"):
        board.breaker("d" * 64, rung).record_failure()
    router = HealthRouter(clock=clock)
    plan = router.plan(board, "d" * 64, ("jit", "numpy"))
    assert plan.rungs == () and plan.routed_from == "jit"
    assert router.stats["floor_routes"] == 1


def test_router_json_round_trip_and_reset():
    router = HealthRouter(RouterConfig(probe_interval_s=7.5))
    clone = HealthRouter.from_json(router.to_json())
    assert clone.config == router.config
    assert json.loads(router.to_json()) == router.to_dict()
    router.stats["plans"] = 3
    router.reset()
    assert router.stats == {"plans": 0, "routed": 0, "probes": 0,
                            "floor_routes": 0}
    with pytest.raises(ValueError):
        RouterConfig(probe_interval_s=-1.0)


# ----------------------------------------------------------------------
# engine integration
# ----------------------------------------------------------------------
def test_router_disabled_and_healthy_router_are_bit_identical():
    reqs = _sim_reqs()
    plain = AnalysisService(sim_backend="numpy").predict_batch(reqs)
    routed = AnalysisService(sim_backend="numpy",
                             router=HealthRouter()).predict_batch(reqs)
    for a, b in zip(plain, routed):
        assert a.predicted_cycles == b.predicted_cycles
        assert a.bound_sim == b.bound_sim
        assert (b.routed_from, b.probe) == ("", False)
        assert (a.routed_from, a.probe) == ("", False)


def test_batch_routes_around_open_rung_with_zero_attempts():
    # pallas and jit both die on their first (and only) attempts; from
    # then on the router starts every cohort at numpy without paying a
    # dispatch against the open rungs
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="fail",
                  match={"backend": "pallas"}),
        FaultSpec(point="engine.dispatch", mode="fail",
                  match={"backend": "jit"}),))
    svc = AnalysisService(sim_backend="pallas", faults=plan,
                          router=HealthRouter(),
                          breaker_config=BreakerConfig(
                              failure_threshold=1, cooldown_s=3600.0))
    first = svc.predict_batch(_sim_reqs())
    assert all(r.degraded and r.backend_used == "numpy" for r in first)
    attempts_after_trip = dict(svc.stats.rung_attempts)
    svc.drop_results()
    second = svc.predict_batch(_sim_reqs())
    for res in second:
        assert res.routed_from == "pallas" and not res.probe
        assert res.degraded and res.backend_used == "numpy"
        assert math.isfinite(res.predicted_cycles)
    # zero new attempts against the open rungs, numpy attempts grew
    assert svc.stats.rung_attempts.get("pallas", 0) == \
        attempts_after_trip.get("pallas", 0)
    assert svc.stats.rung_attempts.get("jit", 0) == \
        attempts_after_trip.get("jit", 0)
    assert svc.stats.rung_attempts["numpy"] > \
        attempts_after_trip["numpy"]
    assert svc.stats.routed_groups >= 2
    clean = AnalysisService(sim_backend="numpy").predict_batch(
        _sim_reqs())
    for d, c in zip(second, clean):
        assert d.predicted_cycles == c.predicted_cycles


def test_tick_floor_and_scheduled_probe():
    # tick's only fallback is the analytic floor; the fault dies once,
    # so after the cooldown the router schedules exactly one probe and
    # the probe's answer is full fidelity, flagged probe=True
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="fail", count=1,
                  match={"backend": "tick"}),))
    svc = AnalysisService(faults=plan, router=HealthRouter(),
                          breaker_config=BreakerConfig(
                              failure_threshold=1, cooldown_s=0.05))
    req = AnalysisRequest(kernel=pk.PI_O2, arch="skl", mode="simulate")
    res = svc.predict(req)
    assert res.degraded and res.backend_used == "analytic"
    # while the breaker is open (cooldown pending) the router floors
    # the request without a dispatch attempt
    attempts = svc.stats.rung_attempts.get("tick", 0)
    svc.drop_results()
    res2 = svc.predict(req)
    assert res2.degraded and res2.backend_used == "analytic"
    assert svc.stats.rung_attempts.get("tick", 0) == attempts
    time.sleep(0.08)
    svc.drop_results()
    res3 = svc.predict(req)
    assert res3.probe and not res3.degraded
    # the probe answered on the requested rung: a clean, full-fidelity
    # result that names the rung like any undegraded answer
    assert res3.sim_result is not None and res3.backend_used == "tick"
    assert svc.stats.probe_dispatches == 1


# ----------------------------------------------------------------------
# service integration: routing provenance, budgets, hedging
# ----------------------------------------------------------------------
def _service_burst(tag: str) -> list[tuple[float, ServiceRequest]]:
    # a full grid burst: large enough that each machine cohort takes
    # the grouped dispatch path (where routing and hedging live), not
    # the small-batch tick path
    cells = [("skl", pk.TRIAD_SKL_O3), ("zen", pk.TRIAD_ZEN_O3),
             ("skl", pk.PI_O1), ("zen", pk.PI_O1),
             ("skl", pk.PI_O2), ("zen", pk.PI_O2),
             ("skl", pk.PI_SKL_O3), ("zen", pk.PI_ZEN_O3)]
    return [(0.0, ServiceRequest(
        analysis=AnalysisRequest(kernel=src, arch=arch,
                                 mode="simulate"),
        tenant="t", tag=tag)) for arch, src in cells]


def test_service_responses_carry_routing_provenance():
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="fail",
                  match={"backend": "pallas"}),
        FaultSpec(point="engine.dispatch", mode="fail",
                  match={"backend": "jit"}),))
    engine = AnalysisService(faults=plan, router=HealthRouter(),
                             breaker_config=BreakerConfig(
                                 failure_threshold=1, cooldown_s=300.0))
    svc = PredictionService(engine, ServiceConfig(
        batch_window_s=0.01, backend="pallas", cache_ttl_s=0.0))
    replay(svc, _service_burst("r0"))     # trips pallas + jit breakers
    engine.drop_results()
    resps = replay(svc, _service_burst("r1"))
    for r in resps:
        assert r.ok and r.routed_from == "pallas" and not r.probe
        assert r.degraded and r.backend_used == "numpy"
        assert r.provenance_of(r.result)["routed_from"] == "pallas"
    stats = svc.export_stats()
    assert stats["router"] is not None
    assert stats["router"]["stats"]["routed"] >= 2
    assert sum(c["routed"] for c in
               stats["cohort_classes"].values()) >= 1
    assert engine.stats.rung_attempts.get("pallas", 0) <= 2


def _hlo_burst(tenant: str) -> list[tuple[float, ServiceRequest]]:
    text = """
HloModule dot64, entry_computation_layout={()->f32[64,64]{1,0}}

ENTRY %main.1 () -> f32[64,64] {
  %a = f32[64,64]{1,0} constant({...})
  ROOT %d = f32[64,64]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    return [(0.0, ServiceRequest(hlo=HloRequest(text=text),
                                 tenant=tenant))]


def test_governed_retries_recover_with_recorded_sleeps():
    # two transient parse failures, then clean: the retry loop must
    # recover under capped full-jitter backoff and record every sleep
    engine = AnalysisService(faults=FaultPlan(specs=(
        FaultSpec(point="engine.hlo_parse", mode="fail", count=2),)))
    svc = PredictionService(engine, ServiceConfig(
        batch_window_s=0.005, max_retries=3, retry_backoff_s=0.005,
        retry_backoff_cap_s=0.02))
    resp = replay(svc, _hlo_burst("patient"))[0]
    assert resp.ok
    tele = svc.telemetry
    assert sum(c.retries for c in tele.cohort_classes.values()) == 2
    assert tele.retry_sleep.count == 2
    # capped full jitter can never sleep past the cap
    assert tele.retry_sleep.max <= 0.02 + 1e-9


def test_retry_backoff_is_deterministic_per_seed():
    cfg = ServiceConfig(retry_backoff_s=0.05, retry_backoff_cap_s=0.2,
                        retry_seed=42)
    a = PredictionService(config=cfg)
    b = PredictionService(config=cfg)
    seq_a = [a._backoff_s(i) for i in range(1, 6)]
    seq_b = [b._backoff_s(i) for i in range(1, 6)]
    assert seq_a == seq_b
    assert all(0.0 <= s <= 0.2 for s in seq_a)
    c = PredictionService(config=dataclasses.replace(cfg, retry_seed=7))
    assert [c._backoff_s(i) for i in range(1, 6)] != seq_a


def test_exhausted_retry_budget_fails_fast():
    engine = AnalysisService(faults=FaultPlan(specs=(
        FaultSpec(point="engine.hlo_parse", mode="fail", count=2),)))
    svc = PredictionService(engine, ServiceConfig(
        batch_window_s=0.005, max_retries=3, retry_backoff_s=0.005,
        default_policy=TenantPolicy(retry_rate_per_s=0.0,
                                    retry_burst=0.0)))
    resp = replay(svc, _hlo_burst("broke"))[0]
    assert not resp.ok and isinstance(resp.error, DispatchError)
    assert "retry budget" in str(resp.error)
    assert svc.telemetry.tenant("broke").retry_budget_exhausted == 1
    # no sleep was paid for the denied retry
    assert svc.telemetry.retry_sleep.count == 0


def test_retry_budget_refills_over_time():
    from repro.service import AdmissionController
    ctl = AdmissionController(default_policy=TenantPolicy(
        retry_rate_per_s=1.0, retry_burst=1.0))
    assert ctl.try_retry("t", now=0.0)
    assert not ctl.try_retry("t", now=0.1)
    assert ctl.try_retry("t", now=1.2)      # bucket refilled


def test_hedged_dispatch_races_next_rung():
    # the primary jit dispatch straggles behind an injected latency
    # fault; after the hedge delay the numpy rung races it and wins.
    # The delay is generous so the hedge still wins on a loaded host.
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="latency", delay_s=2.0,
                  match={"backend": "jit"}),))
    engine = AnalysisService(faults=plan)
    svc = PredictionService(engine, ServiceConfig(
        batch_window_s=0.01, backend="jit", hedge=True,
        hedge_delay_s=0.05))
    resps = replay(svc, _service_burst("h0"))
    assert all(r.ok for r in resps)
    cls = svc.telemetry.cohort_classes
    assert sum(c.hedges for c in cls.values()) >= 1
    assert sum(c.hedge_wins for c in cls.values()) >= 1


def test_hedge_disabled_by_default_and_stats_shape():
    svc = PredictionService(config=ServiceConfig(batch_window_s=0.005))
    resps = replay(svc, _service_burst("plain"))
    assert all(r.ok for r in resps)
    assert all((r.routed_from, r.probe) == ("", False) for r in resps)
    stats = svc.export_stats()
    assert stats["router"] is None
    assert all(c["hedges"] == 0 for c in
               stats["cohort_classes"].values())
    assert stats["stages"]["retry_sleep"]["count"] == 0


# ----------------------------------------------------------------------
# journal compaction
# ----------------------------------------------------------------------
def test_segment_seal_readback_and_append_continues(tmp_path):
    j = RecordJournal(str(tmp_path), segment_size=5)
    for i in range(17):
        j.append({"i": i})
    st = j.stats()
    assert st["records"] == 17 and st["segments"] == 3
    assert st["loose_files"] == 2 and st["bytes"] > 0
    assert [r["i"] for r in j.records()] == list(range(17))
    # a fresh instance reads the same state and appends after the
    # sealed tail
    k = RecordJournal(str(tmp_path), segment_size=5)
    assert [r["i"] for r in k.records()] == list(range(17))
    k.append({"i": 17})
    assert [r["i"] for r in k.records()] == list(range(18))
    # manual compaction folds the remaining loose records
    sealed = k.compact()
    assert sealed == 3 and k.stats()["loose_files"] == 0
    assert [r["i"] for r in k.records()] == list(range(18))


def test_torn_segment_is_skipped_not_fatal(tmp_path):
    j = RecordJournal(str(tmp_path), segment_size=4)
    for i in range(8):
        j.append({"i": i})
    segs = sorted(p for p in os.listdir(tmp_path)
                  if p.startswith("seg_"))
    assert len(segs) == 2
    # corrupt the first segment's checksum footer (torn write)
    victim = tmp_path / segs[0]
    victim.write_text(victim.read_text()[:-10] + "deadbeef!\n")
    k = RecordJournal(str(tmp_path), segment_size=4)
    assert [r["i"] for r in k.records()] == [4, 5, 6, 7]


def test_segment_size_none_keeps_loose_layout(tmp_path):
    j = RecordJournal(str(tmp_path))
    for i in range(6):
        j.append({"i": i})
    names = os.listdir(tmp_path)
    assert all(n.startswith("rec_") for n in names) and len(names) == 6
    st = j.stats()
    assert st["segments"] == 0 and st["loose_files"] == 6


def test_sweep_journal_compaction_resume_bit_identical(tmp_path):
    sweep_kw = dict(archs=("skl", "zen"), schedulers=("uniform",),
                    mode="simulate")
    reference = AnalysisService(sim_backend="numpy").sweep(
        KERNELS, **sweep_kw)
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="abort", skip=1),))
    killed = AnalysisService(sim_backend="numpy", faults=plan)
    with pytest.raises(FaultAbort):
        killed.sweep(KERNELS, journal=str(tmp_path),
                     journal_segment_size=1, **sweep_kw)
    # the surviving group was sealed into a segment before the kill
    assert SweepJournal(str(tmp_path)).stats()["segments"] >= 1
    resumed_svc = AnalysisService(sim_backend="numpy")
    resumed = resumed_svc.sweep(KERNELS, journal=str(tmp_path),
                                resume_from=str(tmp_path),
                                journal_segment_size=1, **sweep_kw)
    assert set(resumed) == set(reference)
    for k in reference:
        assert resumed[k].predicted_cycles == \
            reference[k].predicted_cycles
        assert resumed[k].bound_sim == reference[k].bound_sim
    s = resumed_svc.stats
    assert s.journal_hits == 1 and s.sim_group_dispatches == 1
    # ServiceStats surfaces the on-disk journal footprint
    assert s.journal_records == 2 and s.journal_segments >= 1
    assert s.journal_bytes > 0
