"""Differential suite for the front-end model: the three batch backends
(numpy slot sweep, ``jax.jit`` scan, Pallas arbitration step) must agree
to 1e-9 on randomly generated programs with the front end enabled, and
turning every front-end feature off must reproduce the pre-front-end
simulator's numbers *bit-exactly* on the paper kernels.

Random programs are exercised twice: a seeded deterministic sweep that
always runs, and a hypothesis property test that runs when the optional
``[dev]`` dependency is installed.
"""
import dataclasses
import random

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:      # optional [dev] dependency
    from repro.testing import given, settings, st

from repro.core import extract_kernel, get_model
from repro.core import paper_kernels as pk
from repro.core.ports import PipelineParams, PortModel
from repro.core.sim import (SimProgram, SimUop, compile_program,
                            simulate, simulate_many)

RAND_MODEL = PortModel(name="rand", ports=("0", "1", "2", "3"))

#: a fully enabled SKL-flavoured front end for the random sweeps
FE_PARAMS = PipelineParams(
    issue_width=4, rob_size=64, scheduler_size=40, retire_width=4,
    predecode_width=5, decode_width=4, complex_decode_width=1,
    dsb_width=6, dsb_size=1536, lsd_size=64, macro_fusion=True,
    micro_fusion=True, move_elimination=True, mispredict_penalty=17.0)


def frontend_off(params: PipelineParams) -> PipelineParams:
    """The same backend windows with every front-end feature disabled —
    by construction the pre-front-end simulator's parameter set."""
    return dataclasses.replace(
        params, predecode_width=0, decode_width=0,
        complex_decode_width=1, dsb_width=0, dsb_size=0, lsd_size=0,
        macro_fusion=False, micro_fusion=False, move_elimination=False,
        mispredict_penalty=0.0)


def random_program(rng: random.Random) -> SimProgram:
    """A small random loop body with random fusion capabilities."""
    n_instr = rng.randint(2, 6)
    uops, fuse_prev, eliminable, lat, macro_prev = [], [], [], [], []
    for i in range(n_instr):
        n_u = rng.choice((1, 1, 1, 2, 2, 3))
        for j in range(n_u):
            ports = tuple(sorted(rng.sample(
                RAND_MODEL.ports, rng.randint(1, 2))))
            uops.append(SimUop(i, ports, rng.choice((0.5, 1.0, 1.0))))
            # second uop of an instruction may laminate with the first
            fuse_prev.append(j == 1 and rng.random() < 0.5)
            eliminable.append(n_u == 1 and rng.random() < 0.2)
        lat.append(float(rng.randint(1, 5)))
        macro_prev.append(i > 0 and rng.random() < 0.2)
    edges = [(i, i + 1, lat[i], False) for i in range(n_instr - 1)
             if rng.random() < 0.6]
    if rng.random() < 0.7:   # loop-carried chain
        edges.append((n_instr - 1, 0, lat[-1], True))
    return SimProgram(
        model=RAND_MODEL, n_instructions=n_instr, uops=tuple(uops),
        latency=tuple(lat), edges=tuple(edges),
        fuse_prev=tuple(fuse_prev), eliminable=tuple(eliminable),
        macro_prev=tuple(macro_prev))


def _assert_backends_agree(programs, params):
    ref = simulate_many(programs, params, backend="numpy")
    for backend in ("jit", "pallas"):
        got = simulate_many(programs, params, backend=backend)
        for prog, r, g in zip(programs, ref, got):
            assert g.cycles_per_iteration == pytest.approx(
                r.cycles_per_iteration, abs=1e-9), (
                backend, prog.digest[:12], r.cycles_per_iteration,
                g.cycles_per_iteration)
            assert g.converged == r.converged, (backend, prog.digest)


# ------------------------------------------------------------------ #
# Random differential sweep (seeded, always runs)
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("seed", range(6))
def test_random_programs_backends_agree_frontend_on(seed):
    rng = random.Random(1000 + seed)
    programs = [random_program(rng) for _ in range(4)]
    _assert_backends_agree(programs, FE_PARAMS)


def test_random_programs_backends_agree_frontend_off():
    rng = random.Random(7)
    programs = [random_program(rng) for _ in range(8)]
    _assert_backends_agree(programs, frontend_off(FE_PARAMS))


@pytest.mark.parametrize("seed", range(4))
def test_random_program_frontend_off_ignores_capabilities(seed):
    """With every feature flag off, the recorded fusion capabilities are
    inert: stripping them from the program must not move the numpy
    sweep's result at all."""
    rng = random.Random(2000 + seed)
    prog = random_program(rng)
    bare = dataclasses.replace(
        prog, fuse_prev=(), eliminable=(), macro_prev=())
    off = frontend_off(FE_PARAMS)
    a = simulate_many([prog], off, backend="numpy")[0]
    b = simulate_many([bare], off, backend="numpy")[0]
    assert a.cycles_per_iteration == b.cycles_per_iteration
    assert a.bottleneck == b.bottleneck


# ------------------------------------------------------------------ #
# Hypothesis property form (runs when the [dev] extra is installed)
# ------------------------------------------------------------------ #
@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=0, max_value=2**31))
def test_property_backends_agree(seed):
    rng = random.Random(seed)
    _assert_backends_agree([random_program(rng)], FE_PARAMS)


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=0, max_value=2**31))
def test_property_frontend_off_is_inert(seed):
    rng = random.Random(seed)
    prog = random_program(rng)
    bare = dataclasses.replace(
        prog, fuse_prev=(), eliminable=(), macro_prev=())
    off = frontend_off(FE_PARAMS)
    a = simulate_many([prog], off, backend="numpy")[0]
    b = simulate_many([bare], off, backend="numpy")[0]
    assert a.cycles_per_iteration == b.cycles_per_iteration


# ------------------------------------------------------------------ #
# Features-off reproduces the pre-front-end simulator bit-exactly
# ------------------------------------------------------------------ #
PAPER_CASES = {
    "triad_skl": ("skl", pk.TRIAD_SKL_O3),
    "triad_zen": ("zen", pk.TRIAD_ZEN_O3),
    "pi_skl_O1": ("skl", pk.PI_O1),
    "pi_skl_O2": ("skl", pk.PI_O2),
    "pi_skl_O3": ("skl", pk.PI_SKL_O3),
    "pi_zen_O1": ("zen", pk.PI_O1),
    "pi_zen_O2": ("zen", pk.PI_O2),
    "pi_zen_O3": ("zen", pk.PI_ZEN_O3),
}

#: cycles/iteration of the simulator *before* the front-end model
#: existed (captured at the pre-front-end commit); the reference tick
#: loop and the numpy sweep differed on triad_skl already (documented
#: arbitration-order divergence), so both baselines are pinned
PRE_FRONTEND_TICK = {
    "triad_skl": 2.5, "triad_zen": 2.0, "pi_skl_O1": 9.0,
    "pi_skl_O2": 4.0, "pi_skl_O3": 16.0, "pi_zen_O1": 12.0,
    "pi_zen_O2": 4.0, "pi_zen_O3": 4.0,
}
PRE_FRONTEND_NUMPY = dict(PRE_FRONTEND_TICK, triad_skl=2.25)


@pytest.mark.parametrize("name", list(PAPER_CASES))
def test_features_off_reproduces_pre_frontend_cycles(name):
    arch, src = PAPER_CASES[name]
    prog = compile_program(extract_kernel(src), arch)
    off = frontend_off(get_model(arch).pipeline)
    tick = simulate(prog, params=off, max_iterations=200)
    assert tick.cycles_per_iteration == PRE_FRONTEND_TICK[name], name
    assert tick.converged
    sweep = simulate_many([prog], off, backend="numpy")[0]
    assert sweep.cycles_per_iteration == PRE_FRONTEND_NUMPY[name], name
    assert sweep.converged


@pytest.mark.parametrize("name", ["triad_skl", "pi_zen_O2"])
def test_features_off_jit_matches_numpy_baseline(name):
    arch, src = PAPER_CASES[name]
    prog = compile_program(extract_kernel(src), arch)
    off = frontend_off(get_model(arch).pipeline)
    for backend in ("jit", "pallas"):
        res = simulate_many([prog], off, backend=backend)[0]
        assert res.cycles_per_iteration == PRE_FRONTEND_NUMPY[name], \
            (name, backend)


# ------------------------------------------------------------------ #
# Front end ON: the paper kernels across all three batch backends
# ------------------------------------------------------------------ #
def test_paper_kernels_backends_agree_frontend_on():
    programs = [compile_program(extract_kernel(src), arch)
                for arch, src in PAPER_CASES.values()]
    _assert_backends_agree(programs, None)
