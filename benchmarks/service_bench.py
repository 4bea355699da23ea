"""Prediction-service load bench: replay heavy mixed traffic, compare
measured latency percentiles against the analytic SLO self-model, and
prove the batching front saves compiled dispatches.

Three tenants replay the traffic mix the ROADMAP names:

* ``sweeper``   — bursts of ``mode="simulate"`` sweep cells (the
  paper-kernel grid on both CPU models, both schedulers), repeated
  rounds so later rounds exercise the cross-request cache;
* ``interactive`` — steady single-point analytic requests;
* ``hlo-dryrun`` — HLO module dry-runs (the TPU serving path).

The replay records per-request latency envelopes
(:class:`repro.service.ServiceResponse`), then:

1. **SLO validation** — the service's busy-period self-model
   (``repro.service.slo``, calibrated only from arrival rates, the
   batch window and measured dispatch costs) predicts p50/p99; the
   bench records measured vs. predicted into ``BENCH_service.json``.
   Cache hits bypass the queue entirely, so the SLO comparison is over
   the *queued* (non-cache-hit) requests; the all-traffic percentiles
   are recorded alongside.
2. **Dispatch accounting** — the same requests are issued serially
   through a fresh ``AnalysisService.predict`` / ``predict_hlo``; the
   service must have issued *strictly fewer* compiled dispatches
   (cohort batching turns one round of sweep cells into one
   ``simulate_many`` dispatch per machine model) with bit-identical
   results.
3. **Admission probe** — a deliberately tiny service (queue depth and
   token bucket both small) replays a burst and must reject explicitly
   (``AdmissionError``), not queue unboundedly.

Usage::

    PYTHONPATH=src python benchmarks/service_bench.py \
        [--fast] [--out BENCH_service.json] [--check]

``--check`` (the CI ``service-smoke`` gate) exits non-zero unless:
zero dropped requests at nominal load; the SLO p99 prediction is
within 50% of measurement; the service issued strictly fewer compiled
dispatches than the serial baseline; results are bit-identical.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import random
import sys
import time


_HLO_MODULES = {
    "dot64": """
HloModule dot64, entry_computation_layout={()->f32[64,64]{1,0}}

ENTRY %main.1 () -> f32[64,64] {
  %a = f32[64,64]{1,0} constant({...})
  ROOT %d = f32[64,64]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
""",
    "chain512": """
HloModule chain512, entry_computation_layout={()->f32[512,512]{1,0}}

ENTRY %main.1 () -> f32[512,512] {
  %a = f32[512,512]{1,0} constant({...})
  %d = f32[512,512]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %s = f32[512,512]{1,0} add(%d, %d)
}
""",
    "wide128": """
HloModule wide128, entry_computation_layout={()->f32[128,128]{1,0}}

ENTRY %main.1 () -> f32[128,128] {
  %a = f32[128,128]{1,0} constant({...})
  %b = f32[128,128]{1,0} constant({...})
  %x = f32[128,128]{1,0} add(%a, %a)
  %y = f32[128,128]{1,0} multiply(%b, %b)
  ROOT %d = f32[128,128]{1,0} dot(%x, %y), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
""",
}


def _sweep_cells():
    """The matched kernel x arch grid (each triad on its own model —
    the pairs on which the tick-loop and batch drivers are locked
    bit-identical by tests/test_sweep_engine.py)."""
    from repro.core import paper_kernels as pk
    return [("skl", pk.TRIAD_SKL_O3), ("zen", pk.TRIAD_ZEN_O3),
            ("skl", pk.PI_O1), ("zen", pk.PI_O1),
            ("skl", pk.PI_O2), ("zen", pk.PI_O2),
            ("skl", pk.PI_SKL_O3), ("zen", pk.PI_ZEN_O3)]


def build_traffic(fast: bool = False, seed: int = 0):
    """``[(offset_s, ServiceRequest), ...]`` for the nominal replay."""
    from repro.core.engine import AnalysisRequest
    from repro.service import HloRequest, ServiceRequest

    rng = random.Random(seed)
    cells = _sweep_cells()
    rounds = 2 if fast else 4
    n_interactive = 16 if fast else 48
    n_hlo = 6 if fast else 12
    span = 1.2 if fast else 2.5      # arrival horizon (seconds)
    traffic: list[tuple[float, ServiceRequest]] = []

    # sweeper: one burst of the full grid per round (both schedulers)
    for r in range(rounds):
        t0 = r * span / rounds
        for arch, src in cells:
            for sched in ("uniform", "balanced"):
                traffic.append((t0 + rng.uniform(0, 0.01),
                                ServiceRequest(
                    analysis=AnalysisRequest(kernel=src, arch=arch,
                                             scheduler=sched,
                                             mode="simulate"),
                    tenant="sweeper", tag=f"round{r}")))

    # interactive: steady single analytic points, heavy duplicates
    for i in range(n_interactive):
        arch, src = cells[rng.randrange(len(cells))]
        traffic.append((rng.uniform(0, span), ServiceRequest(
            analysis=AnalysisRequest(kernel=src, arch=arch),
            tenant="interactive", tag=f"pt{i}")))

    # hlo dry-runs: the serving path, a few distinct modules
    names = list(_HLO_MODULES)
    for i in range(n_hlo):
        text = _HLO_MODULES[names[i % len(names)]]
        traffic.append((rng.uniform(0, span), ServiceRequest(
            hlo=HloRequest(text=text), tenant="hlo-dryrun",
            tag=f"hlo{i}")))

    traffic.sort(key=lambda t: t[0])
    return traffic


def _percentiles(xs: list[float]) -> dict[str, float]:
    if not xs:
        return {"count": 0, "p50_s": 0.0, "p99_s": 0.0, "max_s": 0.0}
    ys = sorted(xs)

    def q(p: float) -> float:
        i = p * (len(ys) - 1)
        lo = int(i)
        hi = min(lo + 1, len(ys) - 1)
        return ys[lo] + (ys[hi] - ys[lo]) * (i - lo)

    return {"count": len(ys), "p50_s": round(q(0.50), 6),
            "p90_s": round(q(0.90), 6), "p99_s": round(q(0.99), 6),
            "max_s": round(ys[-1], 6)}


def _result_signature(sreq, result) -> tuple:
    """The exact-comparison fields for bit-identity between the
    service (batched) and serial (per-request) paths."""
    if sreq.analysis is not None:
        return (result.predicted_cycles, result.port_bound_cycles,
                result.lcd_cycles, result.bound_sim, result.binding)
    t = result.terms
    return (t.bound_combined, t.bound_overlap, t.critical_path_s)


def serial_baseline(traffic) -> tuple[list[tuple], int]:
    """The same requests, in arrival order, through per-request
    ``AnalysisService.predict`` / ``predict_hlo`` on a fresh engine.
    Returns (result signatures, compiled dispatch count)."""
    from repro.core.engine import AnalysisService
    engine = AnalysisService()
    sigs = []
    for _, sreq in traffic:
        if sreq.analysis is not None:
            res = engine.predict(sreq.analysis)
        else:
            h = sreq.hlo
            res = engine.predict_hlo(
                h.text, ici_links=h.ici_links, flop_dtype=h.flop_dtype,
                mode=h.mode, machine=h.machine,
                working_set=h.working_set)
        sigs.append(_result_signature(sreq, res))
    # each cold simulate cell is one tick-loop dispatch; each unique
    # HLO module is one analysis dispatch
    return sigs, engine.stats.sim_runs + engine.stats.hlo_misses


def admission_probe() -> dict:
    """A deliberately tiny service must reject a burst explicitly."""
    from repro.core import paper_kernels as pk
    from repro.core.engine import AnalysisRequest
    from repro.service import (PredictionService, ServiceConfig,
                               ServiceRequest, TenantPolicy, replay)

    svc = PredictionService(config=ServiceConfig(
        batch_window_s=0.005, max_queue_depth=8,
        default_policy=TenantPolicy(max_in_flight=4, rate_per_s=50.0,
                                    burst=4.0)))
    burst = [(0.0, ServiceRequest(
        analysis=AnalysisRequest(kernel=pk.PI_O1, arch="skl",
                                 unroll_factor=1 + (i % 8)),
        tenant="flooder")) for i in range(32)]
    resps = replay(svc, burst)
    from repro.service import AdmissionError
    rejected = sum(1 for r in resps
                   if isinstance(r.error, AdmissionError))
    served = sum(1 for r in resps if r.ok)
    return {"requests": len(burst), "rejected": rejected,
            "served": served,
            "rejected_reasons": sorted(
                {r.error.reason for r in resps
                 if isinstance(r.error, AdmissionError)})}


def chaos_probe() -> dict:
    """Persistent single-backend failure must degrade, never drop.

    A fault plan kills every dispatch on the primary simulation
    backend; the degradation ladder (docs/robustness.md) must demote
    each affected cohort down the rungs, every admitted request must
    still resolve, affected responses must carry ``degraded=True``
    with the fallback backend recorded, and the circuit breaker must
    visibly open and half-open across replay rounds."""
    from repro.core import (AnalysisService, BreakerConfig, FaultPlan,
                            FaultSpec)
    from repro.core.engine import AnalysisRequest
    from repro.service import (PredictionService, ServiceConfig,
                               ServiceRequest, replay)

    primary = "jit"
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="fail",
                  match={"backend": primary}),))
    engine = AnalysisService(
        faults=plan,
        breaker_config=BreakerConfig(failure_threshold=1,
                                     cooldown_s=0.05))
    svc = PredictionService(engine, ServiceConfig(
        batch_window_s=0.01, backend=primary,
        cache_ttl_s=0.0))       # no cross-request hits: every round
    #                             must re-enter the engine

    cells = _sweep_cells()
    rounds = 3
    resolved = 0
    degraded = []
    for r in range(rounds):
        burst = [(0.0, ServiceRequest(
            analysis=AnalysisRequest(kernel=src, arch=arch,
                                     mode="simulate"),
            tenant="chaos", tag=f"round{r}")) for arch, src in cells]
        resps = replay(svc, burst)
        resolved += sum(1 for x in resps if x.ok or x.error is not None)
        degraded += [x for x in resps if x.ok and x.degraded]
        # past the breaker cooldown, so the next round probes the dead
        # primary rung through half_open instead of skipping it while
        # open; drop the memoized results so the cohort re-dispatches
        time.sleep(0.08)
        svc.engine.drop_results()

    snap = engine.breakers.snapshot()
    transitions = {e["to"] for e in snap["events"]}
    fallbacks = sorted({x.backend_used for x in degraded})
    return {
        "primary_backend": primary,
        "requests": rounds * len(cells),
        "resolved": resolved,
        "dropped": rounds * len(cells) - resolved,
        "degraded_responses": len(degraded),
        "fallback_backends": fallbacks,
        "fallback_recorded": bool(degraded) and all(
            x.backend_used and x.backend_used != primary
            for x in degraded),
        "breaker_transitions": sorted(transitions),
        "breaker_opened": "open" in transitions,
        "breaker_half_opened": "half_open" in transitions,
        "fault_events": engine.faults.summary(),
    }


def routing_probe() -> dict:
    """Breaker-aware routing (docs/robustness.md#health-aware-routing):
    once the pallas rung's breaker is open, the :class:`HealthRouter`
    must start every later cohort below it — zero dispatch attempts
    against the open rung, zero drops, and the skip recorded as
    provenance (``routed_from``) on every affected response.

    The fault plan kills the pallas dispatch before the driver runs,
    so the probe is jax-independent: round one trips the breaker, and
    from then on any further pallas attempt is a routing bug, not a
    scheduled probe (the cooldown is far past the bench horizon)."""
    from repro.core import (AnalysisService, BreakerConfig, FaultPlan,
                            FaultSpec, HealthRouter)
    from repro.core.engine import AnalysisRequest
    from repro.service import (PredictionService, ServiceConfig,
                               ServiceRequest, replay)

    primary = "pallas"
    plan = FaultPlan(specs=(
        FaultSpec(point="engine.dispatch", mode="fail",
                  match={"backend": primary}),))
    engine = AnalysisService(
        faults=plan, router=HealthRouter(),
        breaker_config=BreakerConfig(failure_threshold=1,
                                     cooldown_s=300.0))
    svc = PredictionService(engine, ServiceConfig(
        batch_window_s=0.01, backend=primary, cache_ttl_s=0.0))

    cells = _sweep_cells()
    rounds = 3
    resolved = 0
    routed = []
    attempts_round1 = None
    for r in range(rounds):
        burst = [(0.0, ServiceRequest(
            analysis=AnalysisRequest(kernel=src, arch=arch,
                                     mode="simulate"),
            tenant="router", tag=f"round{r}")) for arch, src in cells]
        resps = replay(svc, burst)
        resolved += sum(1 for x in resps if x.ok)
        routed += [x for x in resps if x.ok and x.routed_from]
        if attempts_round1 is None:
            attempts_round1 = engine.stats.rung_attempts.get(primary, 0)
        svc.engine.drop_results()

    attempts_final = engine.stats.rung_attempts.get(primary, 0)
    return {
        "primary_backend": primary,
        "requests": rounds * len(cells),
        "resolved": resolved,
        "dropped": rounds * len(cells) - resolved,
        "primary_attempts_round1": attempts_round1,
        "primary_attempts_after_trip": attempts_final - attempts_round1,
        "routed_responses": len(routed),
        "routed_from_recorded": bool(routed) and all(
            x.routed_from == primary and x.backend_used != primary
            for x in routed),
        "routed_groups": engine.stats.routed_groups,
        "router_stats": engine.router.snapshot()["stats"],
    }


def retry_probe() -> dict:
    """Retry governance (docs/robustness.md#retry-budgets): transient
    dispatch faults must be retried under capped full-jitter backoff
    and resolve, while a tenant with an exhausted retry budget must
    fail fast with an explicit reason instead of looping."""
    from repro.core import AnalysisService, FaultPlan, FaultSpec
    from repro.service import (PredictionService, ServiceConfig,
                               ServiceRequest, TenantPolicy, replay)
    from repro.service.request import HloRequest

    def burst(tenant):
        # hlo_parse faults propagate as DispatchError (the ladder does
        # not contain the parse stage), so they drive the retry loop
        return [(0.0, ServiceRequest(
            hlo=HloRequest(text=_HLO_MODULES["dot64"]),
            tenant=tenant))]

    # transient: two parse failures, then clean — governed retries win
    engine = AnalysisService(faults=FaultPlan(specs=(
        FaultSpec(point="engine.hlo_parse", mode="fail", count=2),)))
    svc = PredictionService(engine, ServiceConfig(
        batch_window_s=0.005, max_retries=3, retry_backoff_s=0.005,
        retry_backoff_cap_s=0.02))
    ok_resps = replay(svc, burst("patient"))
    tele = svc.telemetry
    recovered = all(r.ok for r in ok_resps)
    retries = sum(c.retries for c in tele.cohort_classes.values())
    sleeps = tele.retry_sleep.count

    # exhausted budget: same transient fault, but the tenant has no
    # retry tokens — the response must fail fast with the reason
    engine2 = AnalysisService(faults=FaultPlan(specs=(
        FaultSpec(point="engine.hlo_parse", mode="fail", count=2),)))
    svc2 = PredictionService(engine2, ServiceConfig(
        batch_window_s=0.005, max_retries=3, retry_backoff_s=0.005,
        default_policy=TenantPolicy(retry_rate_per_s=0.0,
                                    retry_burst=0.0)))
    broke_resps = replay(svc2, burst("broke"))
    failed_fast = all((not r.ok) and r.error is not None
                      and "retry budget" in str(r.error)
                      for r in broke_resps)
    exhausted = svc2.telemetry.tenant("broke").retry_budget_exhausted
    return {
        "recovered": recovered,
        "retries": retries,
        "retry_sleeps_recorded": sleeps,
        "budget_failed_fast": failed_fast,
        "budget_exhausted_count": exhausted,
    }


def run_bench(fast: bool = False) -> dict:
    from repro.service import PredictionService, ServiceConfig, replay

    window = 0.02
    traffic = build_traffic(fast=fast)
    svc = PredictionService(config=ServiceConfig(
        batch_window_s=window, max_queue_depth=1024,
        backend="numpy"))       # grouped vectorized dispatch, always
    t0 = time.perf_counter()
    resps = replay(svc, traffic)
    wall = time.perf_counter() - t0

    dropped = sum(1 for r in resps if not r.ok)
    queued = [r for r in resps if r.ok and not r.cache_hit]
    measured_queued = _percentiles([r.total_s for r in queued])
    measured_all = _percentiles([r.total_s for r in resps if r.ok])
    prediction = svc.predict_slo()

    # warm tail: replay a slice of the same traffic against the (still
    # warm) cross-request cache — these must be submit-time cache hits
    rng = random.Random(1)
    tail = [(rng.uniform(0, 0.1), sreq)
            for _, sreq in traffic[:: max(1, len(traffic) // 10)]]
    tail_resps = replay(svc, tail)
    tail_hits = sum(1 for r in tail_resps if r.ok and r.cache_hit)
    stats = svc.export_stats()

    sigs_service = [_result_signature(r.request, r.result)
                    for r in resps if r.ok]
    sigs_service += [_result_signature(r.request, r.result)
                     for r in tail_resps if r.ok]
    sigs_serial, serial_dispatches = serial_baseline(
        [t for t, r in zip(traffic, resps) if r.ok]
        + [t for t, r in zip(tail, tail_resps) if r.ok])
    bit_identical = sigs_service == sigs_serial
    service_dispatches = svc.telemetry.engine_dispatches

    p99_meas = measured_queued["p99_s"]
    p99_pred = prediction.p99_s
    p99_ratio = (p99_pred / p99_meas) if p99_meas else float("inf")

    report = {
        "benchmark": "service_bench",
        "host": {"cpu_count": os.cpu_count(),
                 "platform": platform.platform(),
                 "python": platform.python_version()},
        "config": {"fast": fast, "batch_window_s": window,
                   "backend": "numpy"},
        "traffic": {
            "requests": len(traffic),
            "tenants": sorted({r.tenant for _, r in traffic}),
            "kinds": {
                "x86_simulate": sum(
                    1 for _, r in traffic
                    if r.analysis is not None
                    and r.analysis.mode == "simulate"),
                "x86_analytic": sum(
                    1 for _, r in traffic
                    if r.analysis is not None
                    and r.analysis.mode == "analytic"),
                "hlo": sum(1 for _, r in traffic if r.hlo is not None),
            },
            "wall_s": round(wall, 4),
        },
        "dropped": dropped,
        "measured": measured_queued,
        "measured_all": measured_all,
        "predicted": {
            "p50_s": round(prediction.p50_s, 6),
            "p99_s": round(prediction.p99_s, 6),
            "utilization": round(prediction.utilization, 4),
            "per_class": prediction.per_class,
        },
        "slo": {
            "p99_measured_s": p99_meas,
            "p99_predicted_s": round(p99_pred, 6),
            "p99_ratio": round(p99_ratio, 4),
            "within_50pct": bool(0.5 <= p99_ratio <= 1.5),
        },
        "dispatches": {"service": service_dispatches,
                       "serial": serial_dispatches},
        "bit_identical": bit_identical,
        "warm_tail": {"requests": len(tail), "cache_hits": tail_hits},
        "cache": stats["cache"],
        "stages": stats["stages"],
        "batch_size": stats["batch_size"],
        "tenants": stats["tenants"],
        "engine_hit_rates": stats["engine_hit_rates"],
        "admission_probe": admission_probe(),
        "chaos_probe": chaos_probe(),
        "routing_probe": routing_probe(),
        "retry_probe": retry_probe(),
    }
    return report


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="smaller replay (CI service-smoke)")
    ap.add_argument("--out", default="BENCH_service.json")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 on dropped requests, SLO p99 "
                         "off by >50%%, no dispatch savings, or "
                         "result drift")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    report = run_bench(fast=args.fast)
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=2)

    m, p = report["measured"], report["predicted"]
    print(f"replayed {report['traffic']['requests']} requests "
          f"({', '.join(report['traffic']['tenants'])}) in "
          f"{report['traffic']['wall_s']}s, dropped {report['dropped']}")
    print(f"measured  p50 {m['p50_s'] * 1e3:8.2f} ms   "
          f"p99 {m['p99_s'] * 1e3:8.2f} ms  "
          f"({m['count']} queued requests)")
    print(f"predicted p50 {p['p50_s'] * 1e3:8.2f} ms   "
          f"p99 {p['p99_s'] * 1e3:8.2f} ms  "
          f"(utilization {p['utilization']})")
    d = report["dispatches"]
    wt = report["warm_tail"]
    print(f"dispatches: service {d['service']} vs serial "
          f"{d['serial']}  bit_identical={report['bit_identical']}  "
          f"warm tail {wt['cache_hits']}/{wt['requests']} cache hits "
          f"(overall hit rate {report['cache']['hit_rate']:.3f})")
    ap_ = report["admission_probe"]
    print(f"admission probe: {ap_['rejected']}/{ap_['requests']} "
          f"rejected ({', '.join(ap_['rejected_reasons'])})")
    cp = report["chaos_probe"]
    print(f"chaos probe [{cp['primary_backend']} down]: "
          f"{cp['resolved']}/{cp['requests']} resolved, "
          f"{cp['degraded_responses']} degraded via "
          f"{', '.join(cp['fallback_backends']) or '-'}; breaker "
          f"transitions: {', '.join(cp['breaker_transitions']) or '-'}")
    rt = report["routing_probe"]
    print(f"routing probe [{rt['primary_backend']} tripped]: "
          f"{rt['resolved']}/{rt['requests']} resolved, "
          f"{rt['routed_responses']} routed past the open rung "
          f"({rt['primary_attempts_after_trip']} attempts after trip)")
    rp = report["retry_probe"]
    print(f"retry probe: recovered={rp['recovered']} after "
          f"{rp['retries']} governed retries; budget fail-fast="
          f"{rp['budget_failed_fast']} "
          f"({rp['budget_exhausted_count']} exhausted)")
    print(f"wrote {args.out}")

    if args.check:
        failures = []
        if report["dropped"]:
            failures.append(f"{report['dropped']} requests dropped at "
                            "nominal load")
        if not report["slo"]["within_50pct"]:
            failures.append(
                f"SLO self-model p99 off by more than 50% "
                f"(predicted {report['slo']['p99_predicted_s']}s vs "
                f"measured {report['slo']['p99_measured_s']}s, ratio "
                f"{report['slo']['p99_ratio']})")
        if d["service"] >= d["serial"]:
            failures.append(
                f"no dispatch savings: service {d['service']} vs "
                f"serial {d['serial']}")
        if not report["bit_identical"]:
            failures.append("service results drifted from serial "
                            "predict")
        if not wt["cache_hits"]:
            failures.append("warm tail produced no cross-request "
                            "cache hits")
        if not ap_["rejected"]:
            failures.append("admission probe rejected nothing")
        if cp["dropped"]:
            failures.append(f"chaos probe dropped {cp['dropped']} "
                            "requests under single-backend failure")
        if not (cp["degraded_responses"] and cp["fallback_recorded"]):
            failures.append("chaos probe responses not flagged "
                            "degraded with a fallback backend "
                            "recorded")
        if not (cp["breaker_opened"] and cp["breaker_half_opened"]):
            failures.append(
                f"breaker open/half-open not visible in telemetry "
                f"(saw: {cp['breaker_transitions']})")
        if rt["dropped"]:
            failures.append(f"routing probe dropped {rt['dropped']} "
                            "requests with the primary rung open")
        if rt["primary_attempts_after_trip"]:
            failures.append(
                f"router allowed {rt['primary_attempts_after_trip']} "
                f"dispatch attempts against the open "
                f"{rt['primary_backend']} rung")
        if not (rt["routed_responses"] and rt["routed_from_recorded"]):
            failures.append("routed responses missing routed_from/"
                            "backend_used provenance")
        if not (rp["recovered"] and rp["retries"]
                and rp["retry_sleeps_recorded"]):
            failures.append("transient faults did not recover through "
                            "governed retries")
        if not (rp["budget_failed_fast"]
                and rp["budget_exhausted_count"]):
            failures.append("exhausted retry budget did not fail fast "
                            "with an explicit reason")
        if failures:
            for f_ in failures:
                print(f"FAIL: {f_}", file=sys.stderr)
            raise SystemExit(1)


if __name__ == "__main__":
    main()
