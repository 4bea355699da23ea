"""Plain reference, part 2: the batched dataflow recurrence that the
device driver computes, in numpy, one Python step per uop slot.

A frozen copy of the predictor's numpy slot sweep (the semantics the
compiled ``"jit"`` driver promises to reproduce bit for bit), with the
arithmetic type as a parameter: ``float64`` is the reference, a lower
precision is the control (``bench/control.py``).  Lanes are independent,
so bodies run in chunks of similar length to keep padding small.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .x86 import Pipeline, Program, classify, frontend

N_ITERATIONS = 96
WARMUP = 4
MAX_PERIOD = 8
CHUNK = 256


@dataclass(frozen=True)
class Answer:
    """What the predictor reports for one body."""

    cycles_per_iteration: float
    converged: bool
    bottleneck: str


def composed_edges(prog: Program) -> list[tuple[int, int, float, bool]]:
    """Edges with zero-uop producers composed away (``s -w1-> z -w2->
    d`` becomes ``s -(w1+w2)-> d``; wrap hops saturate at one iteration;
    zero-uop self-loops are dropped)."""
    has_uops = [False] * prog.n_instructions
    for u in prog.uops:
        has_uops[u.instr_index] = True
    edges = [(s, d, w, bool(h)) for s, d, w, h in prog.edges]
    for _ in range(prog.n_instructions):
        if all(has_uops[s] for s, _, _, _ in edges):
            break
        in_by: dict[int, list] = {}
        for e in edges:
            in_by.setdefault(e[1], []).append(e)
        out: dict[tuple[int, int, bool], float] = {}
        for s, d, w, h in edges:
            if has_uops[s]:
                k = (s, d, h)
                out[k] = max(out.get(k, float("-inf")), w)
                continue
            for s2, _, w2, h2 in in_by.get(s, ()):
                if s2 != s:
                    k = (s2, d, h or h2)
                    out[k] = max(out.get(k, float("-inf")), w + w2)
        edges = [(s, d, w, h) for (s, d, h), w in out.items()]
    return [e for e in edges if has_uops[e[0]]]


def _run(progs: list[Program], ports: tuple[str, ...], p: Pipeline,
         n_iterations: int, dtype) -> np.ndarray:
    """Retire time of each iteration's last uop, ``[B, T]``."""
    B, P = len(progs), len(ports)
    pindex = {q: i for i, q in enumerate(ports)}
    edge_lists = [composed_edges(q) for q in progs]
    U = max(len(q.uops) for q in progs)
    I = max(max(q.n_instructions for q in progs), 1)
    E = max(len(es) for es in edge_lists)
    f = lambda v: np.asarray(v, dtype)               # noqa: E731

    active = np.zeros((B, U), bool)
    is_first = np.zeros((B, U), bool)
    instr_of = np.zeros((B, U), np.int64)
    has_port = np.zeros((B, U), bool)
    elig = np.zeros((B, U, P), bool)
    cyc = np.ones((B, U), dtype)
    lat = np.ones((B, U), dtype)
    slot_start = np.zeros((B, U), bool)
    phase_u = np.zeros((B, U), dtype)
    fe_cpi = np.zeros(B, dtype)
    e_valid = np.zeros((B, E), bool)
    e_src = np.zeros((B, E), np.int64)
    e_dst = np.zeros((B, E), np.int64)
    e_w = np.zeros((B, E), dtype)
    e_wrap = np.zeros((B, E), bool)
    for b, prog in enumerate(progs):
        fe = frontend(prog, p)
        fe_cpi[b] = fe.cpi
        seen: set[int] = set()
        for u, uop in enumerate(prog.uops):
            active[b, u] = True
            instr_of[b, u] = uop.instr_index
            if uop.instr_index not in seen:
                seen.add(uop.instr_index)
                is_first[b, u] = True
            if uop.ports and not fe.eliminated[u]:
                has_port[b, u] = True
                for q in uop.ports:
                    elig[b, u, pindex[q]] = True
            cyc[b, u] = max(1.0, uop.cycles)
            lat[b, u] = max(1.0, prog.latency[uop.instr_index])
            slot_start[b, u] = fe.slot_start[u]
            if fe.cpi:
                phase_u[b, u] = fe.phase[fe.slot_of[u]]
        for e, (src, dst, w, wrap) in enumerate(edge_lists[b]):
            e_valid[b, e] = True
            e_src[b, e], e_dst[b, e], e_w[b, e] = src, dst, w
            e_wrap[b, e] = wrap

    rng = np.arange(B)
    zero, one = f(0.0), f(1.0)
    penalty = f(p.mispredict_penalty)
    port_cap = np.zeros((B, P), dtype)
    exec_prev = np.zeros((B, I), dtype)
    valid_prev = np.zeros((B, I), bool)
    last_issue = np.zeros(B, dtype)
    last_retire = np.zeros(B, dtype)
    issue_ring = np.zeros((B, p.issue_width), dtype)
    rob_ring = np.zeros((B, p.rob_size), dtype)
    disp_ring = np.zeros((B, p.scheduler_size), dtype)
    rw_ring = np.zeros((B, p.retire_width), dtype)
    g_ctr = np.zeros(B, np.int64)
    gp_ctr = np.zeros(B, np.int64)
    s_ctr = np.zeros(B, np.int64)
    iter_end = np.zeros((B, n_iterations), dtype)
    inf = f(np.inf)
    for it in range(n_iterations):
        exec_cur = np.zeros((B, I), dtype)
        valid_cur = np.zeros((B, I), bool)
        ready_cur = np.zeros((B, I), dtype)
        deliv_base = f(it) * fe_cpi
        for u in range(U):
            a = active[:, u]
            if not a.any():
                continue
            i_b = instr_of[:, u]
            hp = has_port[:, u]
            ss = slot_start[:, u]
            # issue: in order, gated by width, delivery, ROB, scheduler
            t = np.maximum(last_issue, zero)
            t = np.maximum(t, np.where(
                ss & (s_ctr >= p.issue_width),
                issue_ring[rng, s_ctr % p.issue_width] + one, zero))
            t = np.maximum(t, np.where(
                ss, deliv_base + phase_u[:, u]
                + np.where(fe_cpi > 0, penalty, zero), zero))
            t = np.maximum(t, np.where(g_ctr == 0, penalty, zero))
            t = np.maximum(t, np.where(
                g_ctr >= p.rob_size, rob_ring[rng, g_ctr % p.rob_size],
                zero))
            t = np.maximum(t, np.where(
                hp & (gp_ctr >= p.scheduler_size),
                disp_ring[rng, gp_ctr % p.scheduler_size], zero))
            t = np.ceil(t)
            issue_t = np.where(a, t, last_issue)
            # operand readiness at an instruction's first slot
            need = a & is_first[:, u]
            if need.any() and E:
                m = e_valid & (e_dst == i_b[:, None]) & need[:, None]
                src_exec = np.where(
                    e_wrap, np.take_along_axis(exec_prev, e_src, axis=1),
                    np.take_along_axis(exec_cur, e_src, axis=1))
                src_ok = np.where(
                    e_wrap, np.take_along_axis(valid_prev, e_src, axis=1),
                    np.take_along_axis(valid_cur, e_src, axis=1))
                contrib = np.where(m & src_ok, src_exec + e_w, zero)
                ready = np.maximum(contrib, zero).max(axis=1)
                ready_cur[need, i_b[need]] = ready[need]
            ready_t = ready_cur[rng, i_b]
            # dispatch: least-loaded eligible port
            pf = np.where(elig[:, u], port_cap, inf)
            choice = pf.argmin(axis=1)
            lb = np.maximum(issue_t + one, np.ceil(ready_t))
            start = np.where(hp, np.maximum(lb, pf[rng, choice]), issue_t)
            disp = np.where(a, start, zero)
            upd = a & hp
            port_cap[rng[upd], choice[upd]] += cyc[:, u][upd]
            cur = exec_cur[rng, i_b]
            new_exec = np.where(valid_cur[rng, i_b],
                                np.maximum(cur, disp), disp)
            exec_cur[rng[a], i_b[a]] = new_exec[a]
            valid_cur[rng[a], i_b[a]] = True
            # retire: in order, bandwidth counted in issue slots
            r = np.maximum(disp + lat[:, u], last_retire)
            r = np.maximum(r, np.where(
                ss & (s_ctr >= p.retire_width),
                rw_ring[rng, s_ctr % p.retire_width] + one, zero))
            retire_t = np.where(a, r, last_retire)
            su = a & ss
            issue_ring[rng[su], (s_ctr % p.issue_width)[su]] = issue_t[su]
            rob_ring[rng[a], (g_ctr % p.rob_size)[a]] = retire_t[a]
            slot_idx = np.where(ss, s_ctr, s_ctr - 1)
            rw_ring[rng[a], (slot_idx % p.retire_width)[a]] = retire_t[a]
            disp_ring[rng[upd], (gp_ctr % p.scheduler_size)[upd]] = \
                disp[upd]
            last_issue, last_retire = issue_t, retire_t
            g_ctr = g_ctr + a
            gp_ctr = gp_ctr + upd
            s_ctr = s_ctr + su
        iter_end[:, it] = last_retire
        exec_prev, valid_prev = exec_cur, valid_cur
    return iter_end


def _steady_state(iter_end: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-lane cycles per iteration: the mean of a delta pattern that
    repeats three times at the end of the trajectory (and agrees with
    the tail slope), else the tail slope with ``converged=False``."""
    iter_end = iter_end.astype(np.float64)
    deltas = np.diff(iter_end[:, WARMUP:], axis=1)
    span = deltas.shape[1]
    cpi = deltas[:, span // 2:].mean(axis=1)
    slope = cpi.copy()
    converged = np.zeros(len(cpi), bool)
    for per in range(1, MAX_PERIOD + 1):
        if span < 3 * per:
            continue
        pval = deltas[:, -per:].mean(axis=1)
        match = np.all((deltas[:, -per:] == deltas[:, -2 * per:-per])
                       & (deltas[:, -per:] == deltas[:, -3 * per:-2 * per]),
                       axis=1)
        match &= np.abs(pval - slope) <= 0.25 + 0.02 * np.abs(slope)
        cpi = np.where(match & ~converged, pval, cpi)
        converged |= match
    return cpi, converged


def _answers(progs: list[Program], ports: tuple[str, ...], p: Pipeline,
             n_iterations: int, dtype, grown: bool) -> list[Answer]:
    cpi, converged = _steady_state(_run(progs, ports, p, n_iterations,
                                        dtype))
    out: list[Answer | None] = [None] * len(progs)
    retry = [b for b in range(len(progs)) if not converged[b]]
    if retry and not grown:
        # an unconverged lane runs once more at four times the horizon
        sub = _answers([progs[b] for b in retry], ports, p,
                       4 * n_iterations, dtype, True)
        for b, ans in zip(retry, sub):
            out[b] = ans
    for b, prog in enumerate(progs):
        if out[b] is not None:
            continue
        fe = frontend(prog, p)
        fe_cycles = fe.n_slots / p.issue_width
        c = float(cpi[b])
        out[b] = Answer(c, bool(converged[b]),
                        classify(c, fe_cycles, prog.port_bound, fe.cpi,
                                 fe.mode))
    return out  # type: ignore[return-value]


def simulate(progs: list[Program], ports: tuple[str, ...], p: Pipeline,
             dtype=np.float64) -> list[Answer]:
    """The answer for every program (none may be empty), in order."""
    order = sorted(range(len(progs)), key=lambda i: len(progs[i].uops))
    out: list[Answer | None] = [None] * len(progs)
    for k in range(0, len(order), CHUNK):
        idx = order[k:k + CHUNK]
        for i, ans in zip(idx, _answers([progs[i] for i in idx], ports, p,
                                        N_ITERATIONS, dtype, False)):
            out[i] = ans
    return out  # type: ignore[return-value]
