"""JIT-compiled vectorized sweep engine: numpy/jit/pallas backend
parity (1e-9), the grouped predict_batch/sweep planner, memoized
preprocessing counters, and the bounded steady-state detector."""
import functools

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:          # optional dev dependency
    from repro.testing import given, settings, st

from repro.core import (AnalysisRequest, AnalysisService, extract_kernel)
from repro.core import paper_kernels as pk
from repro.core.arch.skylake import build_skylake_db
from repro.core.arch.zen import build_zen_db
from repro.core.scheduler import SCHEDULERS
from repro.core.sim import (SimProgram, SimUop, compile_program,
                            simulate, simulate_many)
from repro.core.sim import batch
from repro.core.sim.batch import (JIT_SHARD, TIGHT_MIN_LANES, _bucket,
                                  _chain, _composed_edges,
                                  _jit_compatible, _steady_state)

SKL = build_skylake_db()
ZEN = build_zen_db()

PAPER_KERNELS = {
    "triad_skl": pk.TRIAD_SKL_O3, "triad_zen": pk.TRIAD_ZEN_O3,
    "pi_o1": pk.PI_O1, "pi_o2": pk.PI_O2,
    "pi_skl_o3": pk.PI_SKL_O3, "pi_zen_o3": pk.PI_ZEN_O3,
}

def _paper_programs():
    progs = []
    for src in PAPER_KERNELS.values():
        for db in (SKL, ZEN):
            progs.append(compile_program(extract_kernel(src), db))
    return progs


# ------------------------------------------------------------------ #
# Backend parity: numpy vs jit (vs pallas) to 1e-9
# ------------------------------------------------------------------ #
def test_driver_parity_numpy_vs_jit_on_paper_kernels():
    progs = _paper_programs()
    rn = simulate_many(progs, backend="numpy")
    rj = simulate_many(progs, backend="jit")
    for n, j in zip(rn, rj):
        assert abs(n.cycles_per_iteration - j.cycles_per_iteration) \
            <= 1e-9
        assert n.converged == j.converged
        assert n.bottleneck == j.bottleneck


def test_driver_parity_pallas_interpret():
    """The Pallas arbitration step (interpreter mode off-TPU) must be
    arithmetically identical to the inline lax formulation."""
    progs = [compile_program(extract_kernel(pk.PI_O1), SKL),
             compile_program(extract_kernel(pk.PI_O2), SKL)]
    rj = simulate_many(progs, backend="jit")
    rp = simulate_many(progs, backend="pallas")
    for j, p in zip(rj, rp):
        assert abs(j.cycles_per_iteration - p.cycles_per_iteration) \
            <= 1e-9


@pytest.mark.parametrize("scheduler", sorted(SCHEDULERS))
@pytest.mark.parametrize("arch", ["skl", "zen"])
def test_service_sweep_parity_all_kernels(arch, scheduler):
    """Service-level parity: every paper kernel, each architecture and
    every registered scheduler — numpy and jit sweeps agree to 1e-9 on
    the simulated bound and bit-for-bit on the analytic bounds."""
    svc_np = AnalysisService(sim_backend="numpy")
    svc_jit = AnalysisService(sim_backend="jit")
    gn = svc_np.sweep(PAPER_KERNELS, archs=(arch,),
                      schedulers=(scheduler,), mode="simulate")
    gj = svc_jit.sweep(PAPER_KERNELS, archs=(arch,),
                       schedulers=(scheduler,), mode="simulate")
    assert gn.keys() == gj.keys()
    for key in gn:
        a, b = gn[key], gj[key]
        assert abs(a.bound_sim - b.bound_sim) <= 1e-9, key
        assert a.port_bound_cycles == b.port_bound_cycles
        assert a.lcd_cycles == b.lcd_cycles
        assert a.binding == b.binding


def test_sweep_backend_numpy_matches_legacy_pi_anchor():
    """The grouped numpy sweep still reproduces the paper anchors
    (pi -O1: 9.0 cy/it SKL, ~11.5 Zen)."""
    svc = AnalysisService(sim_backend="numpy")
    grid = svc.sweep({"pi_o1": pk.PI_O1}, archs=("skl", "zen"),
                     mode="simulate")
    assert grid[("pi_o1", "skl", "uniform")].bound_sim == \
        pytest.approx(9.0)
    assert grid[("pi_o1", "zen", "uniform")].bound_sim >= 11.0


# ------------------------------------------------------------------ #
# Property test: random padded batches mixing architectures
# ------------------------------------------------------------------ #
def _random_program(draw, db):
    n_instr = draw(st.integers(min_value=1, max_value=5))
    model = db.model
    uops = []
    latency = []
    for idx in range(n_instr):
        latency.append(float(draw(st.integers(1, 5))))
        for _ in range(draw(st.integers(0, 2))):
            ports = draw(st.sets(st.sampled_from(model.ports),
                                 min_size=1, max_size=2))
            uops.append(SimUop(instr_index=idx,
                               ports=tuple(sorted(ports)),
                               cycles=float(draw(st.integers(1, 2)))))
    edges = []
    for _ in range(draw(st.integers(0, 4))):
        src = draw(st.integers(0, n_instr - 1))
        dst = draw(st.integers(0, n_instr - 1))
        w = float(draw(st.integers(0, 4)))
        wrap = draw(st.booleans())
        if src == dst and not wrap:
            continue            # intra self-loop is not a dependency
        if src > dst and not wrap:
            src, dst = dst, src  # intra edges point forward
        edges.append((src, dst, w, wrap))
    return SimProgram(model=model, n_instructions=n_instr,
                      uops=tuple(uops), latency=tuple(latency),
                      edges=tuple(edges))


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_property_random_mixed_arch_batches(data):
    """numpy and jit agree to 1e-9 on random padded batches that mix
    machine models, uop counts, port sets and dependency shapes."""
    n = data.draw(st.integers(min_value=2, max_value=6))
    progs = [_random_program(data.draw,
                             data.draw(st.sampled_from([SKL, ZEN])))
             for _ in range(n)]
    rn = simulate_many(progs, backend="numpy", n_iterations=48)
    rj = simulate_many(progs, backend="jit", n_iterations=48)
    for a, b in zip(rn, rj):
        assert abs(a.cycles_per_iteration - b.cycles_per_iteration) \
            <= 1e-9
        assert a.converged == b.converged


# ------------------------------------------------------------------ #
# Grouped planner: dispatch counts, dedupe, caches
# ------------------------------------------------------------------ #
def test_sweep_dispatches_once_per_machine_group():
    svc = AnalysisService(sim_backend="numpy")
    grid = svc.sweep(PAPER_KERNELS, archs=("skl", "zen"),
                     schedulers=("uniform", "balanced"), mode="simulate")
    assert len(grid) == len(PAPER_KERNELS) * 4
    # 24 cells -> 12 unique (arch, kernel) programs -> 2 model groups
    assert svc.stats.sim_runs == len(PAPER_KERNELS) * 2
    assert svc.stats.sim_group_dispatches == 2
    assert svc.stats.program_misses == len(PAPER_KERNELS) * 2
    # the analytic LCD pass and the simulator share the edge memo
    assert svc.stats.edge_hits > 0
    assert svc.stats.hit_rate("edge") > 0


def test_predict_batch_dedupes_and_fills_result_cache():
    svc = AnalysisService(sim_backend="numpy")
    req = AnalysisRequest(kernel=pk.PI_O1, arch="skl", mode="simulate")
    out = svc.predict_batch([req, req, req])
    assert out[0] is out[1] is out[2]
    # mirrors the sequential path: the simulate cell plus its implicit
    # analytic base are the two misses; the duplicates are hits
    assert svc.stats.result_misses == 2
    assert svc.stats.result_hits == 2
    # the single-request path now serves the batch-computed cell
    assert svc.predict(req) is out[0]


def test_predict_batch_mixed_modes_preserves_order():
    svc = AnalysisService(sim_backend="numpy")
    reqs = [AnalysisRequest(kernel=pk.PI_O1, arch="skl"),
            AnalysisRequest(kernel=pk.PI_O2, arch="skl",
                            mode="simulate"),
            AnalysisRequest(kernel=pk.PI_O1, arch="zen")]
    out = svc.predict_batch(reqs)
    assert [r.model.name for r in out] == \
        ["Intel Skylake", "Intel Skylake", "AMD Zen"]
    assert out[0].sim_result is None
    assert out[1].sim_result is not None
    assert out[1].bound_sim > 0


def test_planner_falls_back_for_exotic_programs():
    """Programs the compiled driver cannot take (non-contiguous
    same-instruction slots) run on the reference path instead."""
    model = SKL.model
    prog = SimProgram(
        model=model, n_instructions=2,
        uops=(SimUop(0, ("0",)), SimUop(1, ("1",)), SimUop(0, ("0",))),
        latency=(1.0, 1.0), edges=())
    assert not _jit_compatible([prog], model.pipeline)
    contiguous = SimProgram(
        model=model, n_instructions=2,
        uops=(SimUop(0, ("0",)), SimUop(0, ("0",)), SimUop(1, ("1",))),
        latency=(1.0, 1.0), edges=())
    assert _jit_compatible([contiguous], model.pipeline)
    # simulate_many routes the exotic program to numpy — individually,
    # without downgrading compatible programs sharing its group
    paper = compile_program(extract_kernel(pk.PI_O1), SKL)
    out = simulate_many([prog, paper, contiguous], backend="jit")
    ref = simulate_many([prog, paper, contiguous], backend="numpy")
    for o, r in zip(out, ref):
        assert abs(o.cycles_per_iteration - r.cycles_per_iteration) \
            <= 1e-9
    assert out[1].cycles_per_iteration == pytest.approx(9.0)


def test_counters_count_shards_slots_and_escalation():
    """70 lanes make two 64-lane shards; the one lane still in its
    transient at 16 iterations re-runs alone at 64, and its shard
    counts in the calls and the slots."""
    pi = compile_program(extract_kernel(pk.PI_O1), SKL)         # 15 uops
    triad = compile_program(extract_kernel(pk.TRIAD_SKL_O3), SKL)  # 9
    progs = [triad] + [pi] * 69
    counters = {}
    out = simulate_many(progs, backend="jit", n_iterations=16,
                        counters=counters)
    assert out == simulate_many(progs, backend="numpy", n_iterations=16)
    assert out[0].iterations == 64 and out[1].iterations == 16
    # a group this small pads to the chain shapes: U = 16 for both
    # shards (pi has 15 uops) and for triad's (9 uops) escalation shard
    assert counters == {
        "dispatches": 1, "sim_lanes": 70, "sim_host_lanes": 0,
        "sim_device_calls": 2 + 1, "sim_escalated_lanes": 1,
        "sim_slot_capacity": 2 * 16 * 64 * 16 + 16 * 64 * 64,
        "sim_slot_steps": (9 + 69 * 15) * 16 + 9 * 64}


# ------------------------------------------------------------------ #
# Length-sorted sharding: shards of like-length lanes, input order kept
# ------------------------------------------------------------------ #
def _seeded_body(rng, n_uops):
    """A contiguous loop body of ``n_uops`` uops on the Skylake ports."""
    model = SKL.model
    uops, latency = [], []
    while len(uops) < n_uops:
        idx = len(latency)
        for _ in range(min(int(rng.integers(1, 3)), n_uops - len(uops))):
            ports = rng.choice(model.ports, size=int(rng.integers(1, 3)),
                               replace=False)
            uops.append(SimUop(idx, tuple(sorted(ports)),
                               float(rng.integers(1, 3))))
        latency.append(float(rng.integers(1, 6)))
    n_instr = len(latency)
    edges = []
    for _ in range(int(rng.integers(0, n_instr + 1))):
        src, dst = sorted(int(i) for i in rng.integers(0, n_instr, 2))
        w, wrap = float(rng.integers(0, 4)), bool(rng.integers(2))
        edges.append((src, dst, w, wrap or src == dst))
    return SimProgram(model=model, n_instructions=n_instr,
                      uops=tuple(uops), latency=tuple(latency),
                      edges=tuple(edges))


@functools.lru_cache(maxsize=None)
def _heavy_tailed_batch():
    """201 lanes in drawn (unsorted) order: log-normal lengths (median
    6 uops, sigma 0.75, 1-48), one exotic lane at position 57, and one
    lane (seeded) still in its transient at 96 iterations."""
    rng = np.random.default_rng(1)
    lengths = np.clip(np.rint(rng.lognormal(np.log(6), 0.75, 200)), 1, 48)
    progs = [_seeded_body(rng, int(n)) for n in lengths]
    exotic = SimProgram(
        model=SKL.model, n_instructions=2,
        uops=(SimUop(0, ("0",)), SimUop(1, ("1",)), SimUop(0, ("0",))),
        latency=(1.0, 1.0), edges=())
    progs.insert(57, exotic)
    counters = {}
    out = simulate_many(progs, backend="jit", counters=counters)
    return progs, out, counters


def _capacity(progs, T):
    """``sim_slot_capacity`` of one pass over ``progs`` in this order,
    in a group small enough for the chain shapes."""
    return T * JIT_SHARD * sum(
        _chain(max(len(p.uops) for p in progs[s:s + JIT_SHARD]),
               max(len(_composed_edges(p))
                   for p in progs[s:s + JIT_SHARD]))[0]
        for s in range(0, len(progs), JIT_SHARD))


def test_sorted_shards_match_numpy_in_input_order():
    progs, out, counters = _heavy_tailed_batch()
    assert len(progs) >= 192
    assert out == simulate_many(progs, backend="numpy")
    assert [r.iterations for r in out].count(4 * 96) == 1
    assert counters["sim_host_lanes"] == 1
    assert counters["sim_escalated_lanes"] == 1
    assert counters["sim_lanes"] == len(progs) - 1


def test_sorted_shards_scan_fewer_padded_slots():
    progs, out, counters = _heavy_tailed_batch()
    params = SKL.model.pipeline
    lanes = [p for p in progs if _jit_compatible([p], params)]
    grown = [p for p, r in zip(progs, out)
             if r.iterations == 4 * 96 and _jit_compatible([p], params)]

    def by_length(ps):
        return sorted(ps, key=lambda p: (len(p.uops),
                                         len(_composed_edges(p))))

    assert counters["sim_slot_capacity"] == \
        _capacity(by_length(lanes), 96) + _capacity(by_length(grown), 384)
    assert counters["sim_slot_capacity"] < \
        _capacity(lanes, 96) + _capacity(grown, 384)


def test_one_shard_batch_keeps_its_shape(monkeypatch):
    """At most 64 lanes make one shard, packed to the chain shape of
    the whole batch, as in arrival order."""
    progs = _heavy_tailed_batch()[0][:64]
    params = SKL.model.pipeline
    lanes = [p for p in progs if _jit_compatible([p], params)]
    pack_shards, shapes = batch._pack_shards, []

    def spy(*args):
        shards = pack_shards(*args)
        shapes.extend((pk["U"], pk["E"]) for pk in shards)
        return shards

    monkeypatch.setattr(batch, "_pack_shards", spy)
    out = simulate_many(progs, backend="jit")
    assert all(r.iterations == 96 for r in out)
    assert shapes == [_chain(
        max(len(p.uops) for p in lanes),
        max(len(_composed_edges(p)) for p in lanes))]


def _spy_shapes(monkeypatch):
    """Record the ``(U, E, T)`` of every shard the jit driver runs."""
    run_shard, shapes = batch._run_shard, []

    def spy(pk, ports, params, T, flavor):
        shapes.append((pk["U"], pk["E"], T))
        return run_shard(pk, ports, params, T, flavor)

    monkeypatch.setattr(batch, "_run_shard", spy)
    return shapes


def test_large_group_keeps_tight_shapes_small_group_takes_the_chain(
        monkeypatch):
    """A group of TIGHT_MIN_LANES lanes or more (a corpus study) pads
    each shard to its own ``_bucket``, its escalation shard too; the
    same lanes in a smaller group pad to the chain shapes.  Either way
    the answers equal numpy's."""
    params = SKL.model.pipeline
    lanes = [p for p in _heavy_tailed_batch()[0]
             if _jit_compatible([p], params)]
    big = (lanes * 3)[:TIGHT_MIN_LANES]
    shapes = _spy_shapes(monkeypatch)
    out = simulate_many(big, backend="jit", n_iterations=48)
    assert out == simulate_many(big, backend="numpy", n_iterations=48)
    lanes = sorted(big, key=lambda p: (len(p.uops),
                                       len(_composed_edges(p))))
    want = [(_bucket(max(len(p.uops) for p in ch)),
             _bucket(max(len(_composed_edges(p)) for p in ch)), 48)
            for ch in (lanes[s:s + JIT_SHARD]
                       for s in range(0, len(lanes), JIT_SHARD))]
    assert shapes[:len(want)] == want
    grown = [p for p, r in zip(big, out) if r.iterations == 4 * 48]
    assert grown and shapes[len(want):] == [(
        _bucket(max(len(p.uops) for p in grown)),
        _bucket(max(len(_composed_edges(p)) for p in grown)), 4 * 48)]

    shapes.clear()
    small = big[:TIGHT_MIN_LANES - 1]
    assert simulate_many(small, backend="jit", n_iterations=48) == \
        out[:TIGHT_MIN_LANES - 1]
    chain = {_chain(u, e) for u in range(1, 300) for e in range(600)}
    assert shapes and all((U, E) in chain for U, E, _ in shapes)


def test_chain_shapes_are_few_and_hold_their_lanes():
    assert [_chain(u, 0) for u in (1, 8, 9, 16, 17, 100, 224)] == [
        (8, 16), (8, 16), (16, 32), (16, 32), (32, 64), (128, 256),
        (256, 512)]
    # edges are a vector width: a lane with many edges steps up a level
    assert _chain(5, 17) == (16, 32) and _chain(5, 16) == (8, 16)
    for u in range(1, 257):
        for e in range(0, 513, 7):
            U, E = _chain(u, e)
            assert U >= max(u, _bucket(u)) and E >= e
            assert U < 2 * max(u, 8) or E < 2 * e


def test_after_warm_chain_a_small_group_builds_nothing(monkeypatch):
    """``warm_chain`` builds every chain shape up to the largest lane
    at both horizons; a small group of lanes no larger then reaches no
    executable that is not built yet."""
    from repro.core.sim import warm_chain

    progs = [p for p in _heavy_tailed_batch()[0]
             if _jit_compatible([p], SKL.model.pipeline)][:100]
    top = (max(len(p.uops) for p in progs),
           max(len(_composed_edges(p)) for p in progs))
    built = warm_chain(SKL.model, 24, up_to=top)
    U = _chain(*top)[0]
    levels = [8 << k for k in range(U.bit_length() - 3)]
    assert built == [(u, 2 * u, T) for T in (24, 96) for u in levels]
    shapes = _spy_shapes(monkeypatch)
    for size in (1, 2, 15, 17, 64, 65, 100):
        assert simulate_many(progs[:size], backend="jit",
                             n_iterations=24) == \
            simulate_many(progs[:size], backend="numpy", n_iterations=24)
    assert shapes and set(shapes) <= set(built)


def test_counters_of_exotic_lanes():
    model = SKL.model
    exotic = SimProgram(
        model=model, n_instructions=2,
        uops=(SimUop(0, ("0",)), SimUop(1, ("1",)), SimUop(0, ("0",))),
        latency=(1.0, 1.0), edges=())
    paper = compile_program(extract_kernel(pk.PI_O1), SKL)
    counters = {}
    simulate_many([exotic, paper], backend="jit", counters=counters)
    # the reference driver and the compiled one are a dispatch each
    assert counters["dispatches"] == 2
    assert counters["sim_host_lanes"] == 1
    assert counters["sim_lanes"] == counters["sim_device_calls"] == 1


def test_planner_surfaces_sim_counters_in_stats():
    svc = AnalysisService(sim_backend="jit")
    svc.sweep(PAPER_KERNELS, archs=("skl",), mode="simulate")
    progs = [compile_program(extract_kernel(src), SKL)
             for src in PAPER_KERNELS.values()]
    uops = [len(p.uops) for p in progs]
    U = _chain(max(uops), max(len(_composed_edges(p)) for p in progs))[0]
    s = svc.stats
    assert s.sim_group_dispatches == 1
    assert (s.sim_lanes, s.sim_device_calls, s.sim_host_lanes,
            s.sim_escalated_lanes) == (len(progs), 1, 0, 0)
    assert s.sim_slot_steps == sum(uops) * 96
    assert s.sim_slot_capacity == U * 64 * 96


def test_sim_program_digest_is_content_addressed():
    p1 = compile_program(extract_kernel(pk.PI_O1), SKL)
    p2 = compile_program(extract_kernel(pk.PI_O1), SKL)
    p3 = compile_program(extract_kernel(pk.PI_O2), SKL)
    assert p1.digest == p2.digest
    assert p1.digest != p3.digest


# ------------------------------------------------------------------ #
# Memoized preprocessing + machine resolution
# ------------------------------------------------------------------ #
def test_service_dependency_edges_memoized():
    svc = AnalysisService()
    e1 = svc.dependency_edges(pk.PI_O1, "skl")
    assert svc.stats.edge_misses == 1 and svc.stats.edge_hits == 0
    e2 = svc.dependency_edges(pk.PI_O1, "skl")
    assert e2 is e1
    assert svc.stats.edge_hits == 1
    # alias spelling resolves to the same machine digest
    assert svc.dependency_edges(pk.PI_O1, "skylake") is e1


def _count_parses(monkeypatch):
    """Wrap the engine's extract_kernel with a call counter."""
    import repro.core.engine as engine
    calls = []
    parse = engine.extract_kernel

    def counted(*args, **kwargs):
        calls.append(args[0])
        return parse(*args, **kwargs)

    monkeypatch.setattr(engine, "extract_kernel", counted)
    return calls


@pytest.mark.parametrize("arch,other", [("skl", "zen"), ("zen", "skl")])
def test_predict_batch_parses_each_body_once(monkeypatch, arch, other):
    texts = list(dict.fromkeys(PAPER_KERNELS.values()))
    calls = _count_parses(monkeypatch)
    svc = AnalysisService()
    reqs = [AnalysisRequest(kernel=t, arch=arch, mode="simulate")
            for t in texts]
    svc.predict_batch(reqs)
    n = len(texts)
    # the analytic pass, the dependency edges and the program compile
    # draw one parse
    assert len(calls) == n and sorted(calls) == sorted(texts)
    assert svc.stats.parse_misses == n
    assert svc.stats.parse_hits >= 2 * n
    svc.predict_batch(reqs)
    # the parse is machine-independent: another arch reuses it too
    hits = svc.stats.parse_hits
    svc.predict_batch([AnalysisRequest(kernel=t, arch=other,
                                       mode="simulate") for t in texts])
    assert len(calls) == n and svc.stats.parse_misses == n
    assert svc.stats.parse_hits >= hits + 2 * n


def test_parse_memo_keys_on_syntax(monkeypatch):
    text = "add rax, rbx\nimul rax, rcx\n"
    calls = _count_parses(monkeypatch)
    svc = AnalysisService()
    att = svc._kernel_of(AnalysisRequest(kernel=text, syntax="att"))
    intel = svc._kernel_of(AnalysisRequest(kernel=text, syntax="intel"))
    assert len(calls) == 2 and svc.stats.parse_misses == 2
    assert att != intel
    assert att == tuple(extract_kernel(text, syntax="att"))
    assert intel == tuple(extract_kernel(text, syntax="intel"))
    assert svc._kernel_of(
        AnalysisRequest(kernel=text, syntax="intel")) is intel
    assert svc.stats.parse_hits == 1


def test_cache_clear_drops_parse_memo(monkeypatch):
    calls = _count_parses(monkeypatch)
    svc = AnalysisService()
    svc.dependency_edges(pk.PI_O1, "skl")
    svc.dependency_edges(pk.PI_O2, "skl")
    assert svc.stats.parse_misses == 2 and len(calls) == 2
    svc.cache_clear()
    assert svc._parse_cache == {}
    assert (svc.stats.parse_hits, svc.stats.parse_misses) == (0, 0)
    svc.dependency_edges(pk.PI_O1, "skl")
    assert svc.stats.parse_misses == 1 and len(calls) == 3


def test_classify_memo_counts():
    svc = AnalysisService()
    assert svc._classify_memo(9.0, 2.0, 4.75) == "dependencies"
    assert svc._classify_memo(9.0, 2.0, 4.75) == "dependencies"
    assert svc.stats.classify_misses == 1
    assert svc.stats.classify_hits == 1


def test_resolve_machine_memoized_and_invalidated():
    from repro.core import MachineModel, get_model
    svc = AnalysisService()
    m1 = svc.resolve_machine("skl")
    m2 = svc.resolve_machine("skl")
    assert m1 is m2
    assert svc.stats.machine_misses == 1
    assert svc.stats.machine_hits == 1
    # registering over the id drops the resolution cache
    svc.register(MachineModel.from_json(get_model("zen").to_json())
                 .derive("skl"))
    m3 = svc.resolve_machine("skl")
    assert m3.name == m1.name or m3 is not m1


def test_predict_hlo_batch_single_resolution_and_dedupe():
    hlo = """
HloModule test, entry_computation_layout={()->f32[64,64]{1,0}}

ENTRY %main.1 () -> f32[64,64] {
  %a = f32[64,64]{1,0} constant({...})
  ROOT %d = f32[64,64]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    svc = AnalysisService()
    out = svc.predict_hlo_batch([hlo, hlo, hlo])
    assert out[0] is out[1] is out[2]
    assert svc.stats.hlo_misses == 1 and svc.stats.hlo_hits == 0
    assert svc.stats.machine_misses == 1   # resolved once per batch


# ------------------------------------------------------------------ #
# Steady-state detector
# ------------------------------------------------------------------ #
def test_steady_state_caps_scan_and_reports_non_convergence():
    """A trajectory with no periodic pattern must come back with an
    explicit ``converged=False`` and the documented tail-slope
    fallback, not a silently promoted plateau."""
    rng = np.random.RandomState(0)
    drift = np.cumsum(1.0 + rng.rand(64))      # aperiodic deltas
    periodic = np.arange(64) * 3.0             # exact period-1 pattern
    iter_end = np.stack([drift, periodic])
    cpi, conv = _steady_state(iter_end, warmup=4, max_period=4)
    assert not conv[0]
    deltas = np.diff(iter_end[0, 4:])
    assert cpi[0] == pytest.approx(deltas[len(deltas) // 2:].mean())
    assert conv[1]
    assert cpi[1] == pytest.approx(3.0)


def test_pipeline_detector_bounded_history_same_results():
    """The bounded-deque rework of the reference detector must not
    change any steady state (paper anchor: pi -O1 at 9.0 on SKL)."""
    res = simulate(compile_program(extract_kernel(pk.PI_O1), SKL))
    assert res.converged
    assert res.cycles_per_iteration == pytest.approx(9.0)
    # long non-periodic run: detector terminates with explicit flag
    prog = compile_program(extract_kernel(pk.TRIAD_SKL_O3), SKL)
    res2 = simulate(prog, max_iterations=8)
    assert res2.iterations <= 8 or not res2.converged
