"""Property tests for the service's cohort/batch former.

Satellite guarantees of the prediction service (``repro.service``):

* ``form_cohorts`` is a *partition* of the in-flight request list —
  every request lands in exactly one cohort, no index is dropped or
  duplicated, regardless of the traffic mix (hypothesis);
* a cohort never mixes incompatible requests: all members share one
  ``cohort_key`` — same kind (x86 vs HLO), same resolved machine
  digest, same mode, same backend (and same pricing knobs for HLO);
* ``max_cohort`` splits oversized cohorts without breaking either
  property;
* batching is *semantically invisible*: results produced through the
  batched dispatch path are bit-identical to per-request
  ``AnalysisService.predict`` on a fresh engine — for the analytic
  path under hypothesis-generated mixes, and for the full
  queue → cohort → ``simulate_many`` service path on the matched
  kernel x arch grid (the pairs pinned identical across simulator
  drivers by tests/test_sweep_engine.py);
* every cohort size, one included, is answered by the compiled
  recurrence (rung ``jit``), bit-identical to the numpy recurrence,
  cross-request cache hits included: ``predict_batch([r])`` takes the
  planner, and a small ``"auto"`` group runs numpy, never the tick
  loop that only ``predict`` runs.
"""
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:      # optional [dev] dependency
    from repro.testing import given, settings, st

from repro.core import AnalysisRequest, AnalysisService, default_service
from repro.core import paper_kernels as pk
from repro.service import (HloRequest, PredictionService, ServiceConfig,
                           ServiceRequest, TenantPolicy, cohort_key,
                           form_cohorts, is_partition, replay)

SERVICE = default_service()

HLO_A = """
HloModule a, entry_computation_layout={()->f32[64,64]{1,0}}

ENTRY %main.1 () -> f32[64,64] {
  %a = f32[64,64]{1,0} constant({...})
  ROOT %d = f32[64,64]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
HLO_B = """
HloModule b, entry_computation_layout={()->f32[128,128]{1,0}}

ENTRY %main.1 () -> f32[128,128] {
  %a = f32[128,128]{1,0} constant({...})
  %x = f32[128,128]{1,0} add(%a, %a)
  ROOT %d = f32[128,128]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""

# matched kernel x arch pairs: every driver (tick loop, numpy batch,
# jit batch) is pinned bit-identical on these by the sweep-engine suite
MATCHED = [("skl", pk.TRIAD_SKL_O3), ("zen", pk.TRIAD_ZEN_O3),
           ("skl", pk.PI_O1), ("zen", pk.PI_O1),
           ("skl", pk.PI_O2), ("zen", pk.PI_O2),
           ("skl", pk.PI_SKL_O3), ("zen", pk.PI_ZEN_O3)]


def _request_pool() -> list[ServiceRequest]:
    pool = []
    for arch, src in MATCHED:
        for mode in ("analytic", "simulate"):
            for sched in ("uniform", "balanced"):
                for backend in (None, "numpy"):
                    pool.append(ServiceRequest(
                        analysis=AnalysisRequest(
                            kernel=src, arch=arch, scheduler=sched,
                            mode=mode),
                        backend=backend, tenant="t%d" % (len(pool) % 3)))
    for text in (HLO_A, HLO_B):
        for ici in (1.0, 2.0):
            for dtype in ("bf16", "f32"):
                pool.append(ServiceRequest(
                    hlo=HloRequest(text=text, ici_links=ici,
                                   flop_dtype=dtype),
                    tenant="hlo"))
    return pool


POOL = _request_pool()


def _signature(sreq: ServiceRequest, result) -> tuple:
    if sreq.analysis is not None:
        return (result.predicted_cycles, result.port_bound_cycles,
                result.lcd_cycles, result.bound_sim, result.binding)
    t = result.terms
    return (t.bound_combined, t.bound_overlap, t.critical_path_s)


@settings(max_examples=60, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=len(POOL) - 1),
                min_size=0, max_size=40),
       st.one_of(st.none(), st.integers(min_value=1, max_value=5)))
def test_cohorts_partition_and_never_mix(idxs, max_cohort):
    """form_cohorts partitions any traffic mix; members always agree
    on the full cohort key; max_cohort caps cohort size."""
    requests = [POOL[i] for i in idxs]
    cohorts = form_cohorts(SERVICE, requests, max_cohort=max_cohort)

    assert is_partition(cohorts, len(requests))
    seen = sorted(i for _, members in cohorts for i in members)
    assert seen == list(range(len(requests)))

    for key, members in cohorts:
        assert members, "empty cohort emitted"
        if max_cohort is not None:
            assert len(members) <= max_cohort
        for i in members:
            assert cohort_key(SERVICE, requests[i]) == key

    # no two cohorts share a key unless forced apart by max_cohort
    if max_cohort is None:
        keys = [k for k, _ in cohorts]
        assert len(keys) == len(set(keys))


@settings(max_examples=60, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=len(POOL) - 1),
                min_size=1, max_size=40))
def test_cohorts_never_mix_incompatible(idxs):
    """Explicit incompatibility axes: kind, machine digest, mode,
    backend (and HLO pricing knobs) are constant within a cohort."""
    requests = [POOL[i] for i in idxs]
    for _, members in form_cohorts(SERVICE, requests):
        group = [requests[i] for i in members]
        kinds = {r.kind for r in group}
        assert len(kinds) == 1
        if kinds == {"x86"}:
            digests = {SERVICE.resolve_machine(r.analysis.arch).digest
                       for r in group}
            modes = {r.analysis.mode for r in group}
        else:
            digests = {SERVICE.resolve_machine(r.hlo.machine).digest
                       for r in group}
            modes = {r.hlo.mode for r in group}
            assert len({(r.hlo.ici_links, r.hlo.flop_dtype,
                         r.hlo.working_set) for r in group}) == 1
        assert len(digests) == 1
        assert len(modes) == 1
        assert len({r.backend for r in group}) == 1


@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(
    st.integers(min_value=0, max_value=len(MATCHED) - 1),
    st.sampled_from(["uniform", "balanced"])),
    min_size=1, max_size=8))
def test_analytic_batch_identical_to_per_request(cells):
    """predict_batch == predict, field for field, on fresh engines."""
    reqs = [AnalysisRequest(kernel=MATCHED[i][1], arch=MATCHED[i][0],
                            scheduler=sched) for i, sched in cells]
    batched = AnalysisService().predict_batch(reqs)
    serial_engine = AnalysisService()
    for req, got in zip(reqs, batched):
        want = serial_engine.predict(req)
        assert _signature(ServiceRequest(analysis=req), got) == \
            _signature(ServiceRequest(analysis=req), want)


def test_service_batched_results_bit_identical():
    """The full queue -> cohort -> simulate_many service path returns
    bit-identical results to per-request predict on a fresh engine,
    for a mixed simulate/analytic/HLO traffic burst."""
    traffic = []
    for i, (arch, src) in enumerate(MATCHED[:4]):
        traffic.append((0.0, ServiceRequest(
            analysis=AnalysisRequest(kernel=src, arch=arch,
                                     mode="simulate"),
            tenant="a" if i % 2 else "b")))
    traffic.append((0.0, ServiceRequest(
        analysis=AnalysisRequest(kernel=pk.PI_O1, arch="skl"),
        tenant="a")))
    traffic.append((0.0, ServiceRequest(hlo=HloRequest(text=HLO_A),
                                        tenant="b")))

    svc = PredictionService(config=ServiceConfig(
        batch_window_s=0.01, backend="numpy"))
    resps = replay(svc, traffic)
    assert all(r.ok for r in resps), [r.error for r in resps]
    # batching actually happened: the 4 simulate cells form 2 cohorts
    # (one per machine model), not 4 singleton dispatches
    sim_sizes = [r.cohort_size for r in resps[:4]]
    assert max(sim_sizes) >= 2

    engine = AnalysisService()
    for (_, sreq), resp in zip(traffic, resps):
        if sreq.analysis is not None:
            want = engine.predict(sreq.analysis)
        else:
            want = engine.predict_hlo(sreq.hlo.text)
        assert _signature(sreq, resp.result) == _signature(sreq, want)


def test_cohort_key_distinguishes_machines_and_modes():
    r_skl = ServiceRequest(analysis=AnalysisRequest(kernel=pk.PI_O1,
                                                    arch="skl"))
    r_zen = ServiceRequest(analysis=AnalysisRequest(kernel=pk.PI_O1,
                                                    arch="zen"))
    r_sim = ServiceRequest(analysis=AnalysisRequest(
        kernel=pk.PI_O1, arch="skl", mode="simulate"))
    r_hlo = ServiceRequest(hlo=HloRequest(text=HLO_A))
    keys = {cohort_key(SERVICE, r) for r in
            (r_skl, r_zen, r_sim, r_hlo)}
    assert len(keys) == 4

    # same machine resolved under an alias must share a cohort
    assert cohort_key(SERVICE, r_skl) == cohort_key(
        SERVICE, ServiceRequest(analysis=AnalysisRequest(
            kernel=pk.PI_O1, arch="skylake")))


def test_oversized_cohort_split_is_stable():
    requests = [ServiceRequest(analysis=AnalysisRequest(
        kernel=pk.PI_O1, arch="skl", unroll_factor=1 + i))
        for i in range(7)]
    cohorts = form_cohorts(SERVICE, requests, max_cohort=3)
    assert [len(m) for _, m in cohorts] == [3, 3, 1]
    assert is_partition(cohorts, len(requests))
    flat = [i for _, m in cohorts for i in m]
    assert flat == list(range(7))


# ---------------------------------------------------------------------
# every cohort size answered by the device recurrence
# ---------------------------------------------------------------------
_FORMS = ("vaddsd %xmm{a}, %xmm{b}, %xmm{c}",
          "vmulsd %xmm{a}, %xmm{b}, %xmm{c}",
          "vaddpd %xmm{a}, %xmm{b}, %xmm{c}",
          "vmulpd %xmm{a}, %xmm{b}, %xmm{c}",
          "vfmadd132pd %xmm{a}, %xmm{b}, %xmm{c}",
          "vdivsd %xmm{a}, %xmm{b}, %xmm{c}",
          "vmovaps {d}(%r13,%rax), %xmm{c}",
          "vmovaps %xmm{a}, {d}(%r12,%rax)")


def _body(i: int) -> str:
    """Loop body ``i``: 1-12 drawn forms and the loop control, distinct
    for every ``i``."""
    import random
    rng = random.Random(f"body/{i}")
    lines = [rng.choice(_FORMS).format(a=rng.randrange(8),
                                       b=rng.randrange(8),
                                       c=rng.randrange(8),
                                       d=16 * rng.randrange(4))
             for _ in range(1 + i % 12)]
    return "\n".join([f".L{i}:"] + lines + ["addq $8, %rax",
                                            "cmpq %rbp, %rax",
                                            f"jne .L{i}"]) + "\n"


def _sim_request(i: int) -> AnalysisRequest:
    return AnalysisRequest(kernel=_body(i), arch="zen", mode="simulate")


def _answer(res) -> tuple:
    return (res.predicted_cycles, res.bound_sim, res.binding,
            res.sim_result)


class _Spans:
    """A span factory that records every span opened, with its stats
    and the span open around it on the same thread."""

    def __init__(self):
        import threading
        self.opened: list[tuple[str, dict, str | None]] = []
        self._open = threading.local()

    def __call__(self, name, **meta):
        import contextlib
        stack = self._open.__dict__.setdefault("stack", [])
        self.opened.append((name, dict(meta),
                            stack[-1] if stack else None))

        @contextlib.contextmanager
        def cm():
            stack.append(name)
            try:
                yield None
            finally:
                stack.pop()
        return cm()

    def named(self, name):
        return [stats for n, stats, _ in self.opened if n == name]

    def parents(self, name):
        return [p for n, _, p in self.opened if n == name]


@pytest.mark.parametrize("size", [1, 2, 15, 16, 17, 63, 64, 65])
def test_every_cohort_size_is_answered_by_the_device_recurrence(
        monkeypatch, size):
    """A cohort of ``size`` bodies, then the same bodies again from the
    cross-request cache: every answer equals the numpy recurrence's
    exactly, from the jit rung, not degraded; each submit, the cohort
    and the one engine dispatch are ``repro.service.*`` spans."""
    from repro.core import engine as engine_mod
    from repro.service import service as service_mod

    spans = _Spans()
    monkeypatch.setattr(service_mod, "span", spans)
    monkeypatch.setattr(engine_mod, "span", spans)
    reqs = [_sim_request(1000 * size + i) for i in range(size)]
    engine = AnalysisService(sim_backend="jit")
    svc = PredictionService(engine, ServiceConfig(
        batch_window_s=0.05,
        default_policy=TenantPolicy(max_in_flight=128, burst=128.0)))
    first = replay(svc, [(0.0, ServiceRequest(analysis=r, tenant="t"))
                         for r in reqs])
    want = AnalysisService(sim_backend="numpy").predict_batch(reqs)
    assert all(r.ok for r in first), [repr(r.error) for r in first
                                      if not r.ok]
    assert [r.cohort_size for r in first] == [size] * size
    assert svc.telemetry.engine_dispatches == 1

    again = replay(svc, [(0.0, ServiceRequest(analysis=r, tenant="u"))
                         for r in reqs])
    assert all(r.ok and r.cache_hit for r in again)
    for resp, w in zip(first + again, want + want):
        assert _answer(resp.result) == _answer(w)
        assert resp.backend_used == "jit" and not resp.degraded

    assert len(spans.named("repro.service.submit")) == 2 * size
    assert spans.named("repro.service.cohort") == [{"requests": size}]
    assert spans.named("repro.service.dispatch") == [{"requests": size}]
    # the engine's batched call, with its ``call``, nests inside the
    # dispatch span (the numpy engine's own call follows, outside it)
    assert spans.parents("repro.predict_batch") == [
        "repro.service.dispatch", None]
    assert "call" in spans.named("repro.predict_batch")[0]


def test_batch_of_one_goes_through_the_planner():
    """``predict_batch([r])`` is a batched call: one grouped dispatch on
    the requested rung, never the tick loop ``predict`` runs."""
    req = _sim_request(7)
    engine = AnalysisService(sim_backend="jit")
    (res,) = engine.predict_batch([req])
    assert res.backend_used == "jit" and not res.degraded
    assert engine.stats.sim_group_dispatches == 1
    assert "tick" not in engine.stats.rung_attempts
    assert engine.stats.sim_lanes == 1
    (want,) = AnalysisService(sim_backend="numpy").predict_batch([req])
    assert _answer(res) == _answer(want)
    assert engine.predict_batch([]) == []


@pytest.mark.parametrize("size,rung", [(1, "numpy"), (15, "numpy"),
                                       (16, "jit")])
def test_auto_group_below_the_jit_minimum_runs_numpy(size, rung):
    """Under ``"auto"`` a group below AUTO_JIT_MIN_BATCH (16) runs the
    numpy recurrence, not the tick loop; its answers equal the
    compiled recurrence's."""
    reqs = [_sim_request(50_000 + i) for i in range(size)]
    engine = AnalysisService(sim_backend="auto")
    got = engine.predict_batch(reqs)
    assert {r.backend_used for r in got} == {rung}
    assert set(engine.stats.rung_attempts) == {rung}
    jit = AnalysisService(sim_backend="jit").predict_batch(reqs)
    assert [_answer(r) for r in got] == [_answer(r) for r in jit]
