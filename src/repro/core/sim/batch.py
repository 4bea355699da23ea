"""Vectorized batch drivers for the pipeline simulator.

``pipeline.simulate`` steps one kernel cycle by cycle — the reference
semantics.  This module simulates *many* kernels at once in a
struct-of-arrays pass: every per-uop quantity (issue cycle, operand
readiness, dispatch cycle, retire cycle) becomes a ``[batch]`` vector,
and the driver sweeps the padded uop slots of all kernels in lockstep,
iteration by iteration.  Padding is explicit: every slot, edge and
instruction row carries a validity *mask* (``active`` / ``e_valid`` /
the ``valid_*`` execution masks), and window constraints gate on the
issued-uop counters instead of sentinel timestamps, so the recurrence
is a pure, shape-static function of the packed arrays.

Two interchangeable backends run that function (``backend=``):

* ``"numpy"`` — the reference slot sweep, a Python loop over uop slots
  with ``[batch]``-vectorized numpy ops per slot.
* ``"jit"`` — the same recurrence compiled with ``jax.jit``:
  ``lax.scan`` over iterations and over uop slots, operating on
  ``[shard, ...]`` arrays in float64 (``jax.enable_x64``) so the two
  backends agree to 1e-9 (``tests/test_sweep_engine.py`` locks this).
  Batches are cut into fixed-size, cache-resident shards (padded with
  empty lanes), so one compiled executable per (shape bucket, machine)
  serves every sweep size, and shards run concurrently on a small
  thread pool (XLA releases the GIL).  Three structural facts make the
  compiled step cheap: the uop counters — hence every ring index and
  window-gate boolean — depend only on the static active-slot pattern
  and are precomputed host-side; ROB/scheduler ring traffic hoists out
  of the slot loop (their windows exceed one iteration's uops, so all
  reads hit previous iterations: one gather at iteration start, one
  masked scatter at iteration end); and same-instruction slots are
  contiguous, so per-instruction execute/ready state collapses to
  running scalars plus an incrementally-maintained per-edge source
  vector (no gather/scatter in the inner step at all).
* ``"pallas"`` — the jit driver with the port-arbitration inner step
  swapped for a Pallas kernel (``sim/pallas_step.py``), run in
  interpret mode (slow, exact).  The TPU's kernel compiler takes no
  float64, so on a TPU this backend is refused up front.

The reformulation replaces the per-cycle oldest-ready arbitration with
its program-order dataflow equivalent: each uop books the eligible port
with the least cumulative occupation, and a port's occupation total acts
as its earliest back-to-back start time (``start = max(ready,
cap[port])``, ``cap[port] += cycles``).  This models every port as
perfectly packable — gaps left by dependency-delayed uops can be filled
by younger work, which is what the tick loop's out-of-order dispatch
achieves explicitly.  The cost of that simplification is a longer
transient on kernels whose dependency chain initially outpaces a
saturated port (idle port time is "banked" until the backlog catches
up), so the driver runs more iterations than the reference simulator
and requires the delta pattern to repeat three times before declaring a
steady state; ``tests/test_simulator.py`` locks the two drivers'
agreement on the paper kernels.  Front-end width, ROB and scheduler
occupancy, and retirement bandwidth are modelled identically, as
ring-buffer recurrences:

    issue[s]  >= issue[s - issue_width] + 1          (issue slots)
    issue[s]  >= it * fe_cpi + fe_phase[s]           (fetch/decode)
    issue[g]  >= retire[g - rob_size]                (finite ROB)
    issue[g]  >= dispatch[g' - scheduler_size]       (finite scheduler)
    retire[g] >= retire[g - retire_width] + 1        (retire bandwidth)

where ``s`` counts issue *slots* (micro-fused uop pairs share one; with
the front end disabled every uop is its own slot and the delivery term
vanishes, reproducing the pre-front-end recurrence exactly) and the
delivery term is the static per-iteration schedule computed by
:func:`repro.core.sim.pipeline.frontend_schedule` — the loop body ends
in a taken branch, so fetch restarts at the loop head each iteration.
ROB and retirement stay in the uop domain; a laminated pair keeps its
two scheduler entries.  Rename-eliminated moves become port-less uops
(issue slot + ROB entry, no scheduler entry); the branch-mispredict
recovery penalty delays the first issue of the stream, which cancels
out of every steady-state delta.

Batches mixing architectures are grouped by machine model internally;
each group runs as one vectorized pass.  Kernels whose delta pattern
never repeats within ``n_iterations`` are reported with an explicit
``converged=False`` (the ``cycles_per_iteration`` then is the mean
slope of the simulated tail, a documented fallback — not a silently
promoted plateau).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable

import numpy as np

from ..ports import PipelineParams
from ..spans import span
from .pipeline import (DEFAULT_PARAMS, SimProgram, SimResult, _classify,
                       frontend_schedule)

#: smallest per-group batch for which ``backend="auto"`` picks the
#: compiled driver (below it, numpy's per-slot loop is cheaper than a
#: compile-cache lookup + device transfer)
AUTO_JIT_MIN_BATCH = 16


@dataclass
class _Group:
    """Programs sharing one machine model, padded to common shapes."""

    programs: list[SimProgram]
    indices: list[int]                # positions in the caller's batch


@dataclass
class _Packed:
    """One machine-model group packed as padded struct-of-arrays
    (the numpy reference layout; the compiled backend uses the
    slot-major :func:`_pack_lean` layout instead).

    Validity is carried by masks (``active`` for uop slots, ``e_valid``
    for dependency edges); padding rows are all-False and provably
    identity under the recurrence, which is what lets the drivers pad
    shapes without changing results.
    """

    ports: tuple[str, ...]
    params: PipelineParams
    active: np.ndarray          # [B, U] bool — real (non-padding) slots
    is_first: np.ndarray        # [B, U] bool — first slot of its instr
    instr_of: np.ndarray        # [B, U] int64
    has_port: np.ndarray        # [B, U] bool
    elig: np.ndarray            # [B, U, P] bool
    cyc: np.ndarray             # [B, U] f64 — port occupation cycles
    lat: np.ndarray             # [B, U] f64 — instruction latency
    slot_start: np.ndarray      # [B, U] bool — first uop of its issue slot
    phase_u: np.ndarray         # [B, U] f64 — delivery offset of the slot
    fe_cpi: np.ndarray          # [B] f64 — delivery cycles per iteration
    e_valid: np.ndarray         # [B, E] bool
    e_src: np.ndarray           # [B, E] int64
    e_dst: np.ndarray           # [B, E] int64
    e_w: np.ndarray             # [B, E] f64
    e_wrap: np.ndarray          # [B, E] bool
    n_instr: int                # padded instruction-row count (>= 1)

    @property
    def batch(self) -> int:
        return self.active.shape[0]

    @property
    def slots(self) -> int:
        return self.active.shape[1]


def _composed_edges(prog: SimProgram) -> list[tuple[int, int, float, bool]]:
    """Dependency edges with zero-uop producers composed away.

    The slot sweep only learns execution times at uop slots, so an edge
    whose producer compiled to zero uops (unmatched form) would never
    see a valid execution mask and silently vanish.  The reference
    simulator treats such producers as executing the moment their own
    operands are ready; the dataflow equivalent is edge composition:
    ``s -w1-> z -w2-> d`` with zero-uop ``z`` becomes ``s -(w1+w2)-> d``.
    Wrap hops saturate at one iteration (the consumer looks back exactly
    one iteration, which can only over-delay — conservative), and
    self-loops on zero-uop nodes are dropped to keep the rewrite finite.
    """
    has_uops = [False] * prog.n_instructions
    for u in prog.uops:
        has_uops[u.instr_index] = True
    edges = [(s, d, w, bool(h)) for s, d, w, h in prog.edges]
    for _ in range(prog.n_instructions):
        if all(has_uops[s] for s, _, _, _ in edges):
            break
        in_by: dict[int, list[tuple[int, int, float, bool]]] = {}
        for e in edges:
            in_by.setdefault(e[1], []).append(e)
        out: dict[tuple[int, int, bool], float] = {}

        def keep(s: int, d: int, w: float, h: bool) -> None:
            k = (s, d, h)
            out[k] = max(out.get(k, float("-inf")), w)

        for s, d, w, h in edges:
            if has_uops[s]:
                keep(s, d, w, h)
                continue
            for s2, _, w2, h2 in in_by.get(s, ()):
                if s2 == s:
                    continue          # zero-uop self-loop: drop
                keep(s2, d, w + w2, h or h2)
        edges = [(s, d, w, h) for (s, d, h), w in out.items()]
    return [e for e in edges if has_uops[e[0]]]


def _bucket(n: int) -> int:
    """Shape bucket for the compile cache: next multiple of 4 (padding
    slots cost real scan steps, so the bucket stays tight; multiples of
    4 still let kernels of similar size share one executable)."""
    return max(4, -(-n // 4) * 4)


def _chain(u: int, e: int) -> tuple[int, int]:
    """The coarse ``(U, E)`` holding ``u`` uop slots and ``e`` edges:
    the first of (8, 16), (16, 32), (32, 64), ... that holds both.  At
    most twice the scan steps of the tight ``_bucket`` shape (edges
    are a vector width, not scan steps), from a handful of shapes."""
    U = 8
    while U < u or 2 * U < e:
        U *= 2
    return U, 2 * U


def _pack(programs: list[SimProgram], ports: tuple[str, ...],
          params: PipelineParams) -> _Packed:
    B = len(programs)
    P = len(ports)
    pindex = {p: i for i, p in enumerate(ports)}
    edge_lists = [_composed_edges(p) for p in programs]
    U = max((len(p.uops) for p in programs), default=0)
    I = max((p.n_instructions for p in programs), default=0)
    E = max((len(es) for es in edge_lists), default=0)

    active = np.zeros((B, U), bool)
    is_first = np.zeros((B, U), bool)
    instr_of = np.zeros((B, U), np.int64)
    has_port = np.zeros((B, U), bool)
    elig = np.zeros((B, U, P), bool)
    cyc = np.ones((B, U))
    lat = np.ones((B, U))
    slot_start = np.zeros((B, U), bool)
    phase_u = np.zeros((B, U))
    fe_cpi = np.zeros(B)
    e_valid = np.zeros((B, E), bool)
    e_src = np.zeros((B, E), np.int64)
    e_dst = np.zeros((B, E), np.int64)
    e_w = np.zeros((B, E))
    e_wrap = np.zeros((B, E), bool)
    for b, prog in enumerate(programs):
        fe = frontend_schedule(prog, params)
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
                for pt in uop.ports:
                    elig[b, u, pindex[pt]] = True
            cyc[b, u] = max(1.0, uop.cycles)
            lat[b, u] = max(1.0, prog.latency[uop.instr_index])
            slot_start[b, u] = fe.slot_start[u]
            if fe.cpi:
                phase_u[b, u] = fe.phase[fe.slot_of[u]]
        for e, (src, dst, w, wrap) in enumerate(edge_lists[b]):
            e_valid[b, e] = True
            e_src[b, e], e_dst[b, e], e_w[b, e] = src, dst, w
            e_wrap[b, e] = wrap
    return _Packed(ports=ports, params=params, active=active,
                   is_first=is_first, instr_of=instr_of,
                   has_port=has_port, elig=elig, cyc=cyc, lat=lat,
                   slot_start=slot_start, phase_u=phase_u,
                   fe_cpi=fe_cpi, e_valid=e_valid, e_src=e_src,
                   e_dst=e_dst, e_w=e_w, e_wrap=e_wrap,
                   n_instr=max(I, 1))


# --------------------------------------------------------------------------
# Reference backend: numpy slot sweep
# --------------------------------------------------------------------------

def _run_numpy(pk: _Packed, n_iterations: int) -> np.ndarray:
    """Run the masked recurrence in numpy; returns ``iter_end [B, T]``
    (the retire timestamp of each iteration's last uop)."""
    params = pk.params
    B, U, I = pk.batch, pk.slots, pk.n_instr
    E = pk.e_valid.shape[1]
    rng = np.arange(B)

    port_cap = np.zeros((B, len(pk.ports)))
    exec_prev = np.zeros((B, I))
    valid_prev = np.zeros((B, I), bool)
    last_issue = np.zeros(B)
    last_retire = np.zeros(B)
    issue_ring = np.zeros((B, params.issue_width))
    rob_ring = np.zeros((B, params.rob_size))
    disp_ring = np.zeros((B, params.scheduler_size))
    rw_ring = np.zeros((B, params.retire_width))
    g_ctr = np.zeros(B, np.int64)           # uops issued (ROB/retire)
    gp_ctr = np.zeros(B, np.int64)          # port uops issued (scheduler)
    s_ctr = np.zeros(B, np.int64)           # issue slots (front-end width)
    iter_end = np.zeros((B, n_iterations))

    for it in range(n_iterations):
        exec_cur = np.zeros((B, I))
        valid_cur = np.zeros((B, I), bool)
        ready_cur = np.zeros((B, I))
        for u in range(U):
            a = pk.active[:, u]
            if not a.any():
                continue
            i_b = pk.instr_of[:, u]
            hp = pk.has_port[:, u]
            ss = pk.slot_start[:, u]

            # -- issue: in-order, front-end width (counted in issue
            #    slots — micro-fused pairs share one), fetch/decode
            #    delivery, finite ROB/scheduler; a ring entry constrains
            #    only once the counter has wrapped past it (mask),
            #    never via a sentinel timestamp
            t = np.maximum(last_issue, 0.0)
            t = np.maximum(t, np.where(
                ss & (s_ctr >= params.issue_width),
                issue_ring[rng, s_ctr % params.issue_width] + 1.0, 0.0))
            t = np.maximum(t, np.where(
                ss, it * pk.fe_cpi + pk.phase_u[:, u]
                + np.where(pk.fe_cpi > 0,
                           params.mispredict_penalty, 0.0), 0.0))
            t = np.maximum(t, np.where(
                g_ctr == 0, params.mispredict_penalty, 0.0))
            t = np.maximum(t, np.where(
                g_ctr >= params.rob_size,
                rob_ring[rng, g_ctr % params.rob_size], 0.0))
            t = np.maximum(t, np.where(
                hp & (gp_ctr >= params.scheduler_size),
                disp_ring[rng, gp_ctr % params.scheduler_size], 0.0))
            t = np.ceil(t)
            issue_t = np.where(a, t, last_issue)

            # -- operand readiness (first slot of each instruction)
            need = a & pk.is_first[:, u]
            if need.any() and E:
                m = pk.e_valid & (pk.e_dst == i_b[:, None]) & need[:, None]
                src_exec = np.where(
                    pk.e_wrap,
                    np.take_along_axis(exec_prev, pk.e_src, axis=1),
                    np.take_along_axis(exec_cur, pk.e_src, axis=1))
                src_ok = np.where(
                    pk.e_wrap,
                    np.take_along_axis(valid_prev, pk.e_src, axis=1),
                    np.take_along_axis(valid_cur, pk.e_src, axis=1))
                contrib = np.where(m & src_ok, src_exec + pk.e_w, 0.0)
                contrib = np.maximum(contrib, 0.0)
                ready = contrib.max(axis=1)
                ready_cur[need, i_b[need]] = ready[need]
            ready_t = ready_cur[rng, i_b]

            # -- dispatch: least-loaded eligible port; the port's booked
            #    capacity is its earliest back-to-back start time
            pf = np.where(pk.elig[:, u], port_cap, np.inf)
            choice = pf.argmin(axis=1)
            lb = np.maximum(issue_t + 1.0, np.ceil(ready_t))
            start = np.maximum(lb, pf[rng, choice])
            start = np.where(hp, start, issue_t)
            disp = np.where(a, start, 0.0)
            upd = a & hp
            port_cap[rng[upd], choice[upd]] += pk.cyc[:, u][upd]
            cur = exec_cur[rng, i_b]
            new_exec = np.where(valid_cur[rng, i_b],
                                np.maximum(cur, disp), disp)
            exec_cur[rng[a], i_b[a]] = new_exec[a]
            valid_cur[rng[a], i_b[a]] = True

            # -- retire: in-order, bounded bandwidth counted in
            #    fused-domain slots (a micro-fused continuation uop
            #    leaves with its slot for free)
            complete = disp + pk.lat[:, u]
            r = np.maximum(complete, last_retire)
            r = np.maximum(r, np.where(
                ss & (s_ctr >= params.retire_width),
                rw_ring[rng, s_ctr % params.retire_width] + 1.0, 0.0))
            retire_t = np.where(a, r, last_retire)

            # -- commit state for active elements (the issue ring only
            #    advances on slot starts: width is a slot resource)
            su = a & ss
            issue_ring[rng[su], (s_ctr % params.issue_width)[su]] = \
                issue_t[su]
            rob_ring[rng[a], (g_ctr % params.rob_size)[a]] = retire_t[a]
            # the retire ring holds *slot* retire times: a continuation
            # uop overwrites its own slot's entry (s_ctr has not
            # advanced past it yet only for slot starts)
            slot_idx = np.where(ss, s_ctr, s_ctr - 1)
            rw_ring[rng[a], (slot_idx % params.retire_width)[a]] = \
                retire_t[a]
            disp_ring[rng[upd], (gp_ctr % params.scheduler_size)[upd]] = \
                disp[upd]
            last_issue = issue_t
            last_retire = retire_t
            g_ctr = g_ctr + a
            gp_ctr = gp_ctr + upd
            s_ctr = s_ctr + su
        iter_end[:, it] = last_retire
        exec_prev, valid_prev = exec_cur, valid_cur
    return iter_end


# --------------------------------------------------------------------------
# Compiled backend: jax.jit over the same recurrence, sharded
# --------------------------------------------------------------------------

#: lanes per compiled shard: small enough that the per-step working set
#: stays cache-resident, large enough to amortize dispatch; a group's
#: lanes are sorted by (uops, edges) before they are cut into shards, so
#: a shard pads only to the longest of lanes of like length; every batch
#: is padded (with empty lanes) to a multiple of this, so one compiled
#: executable per (shape bucket, machine) serves all sweep sizes
JIT_SHARD = 64

#: a group of at least this many compiled lanes (a corpus study, a
#: large sweep) pads each shard to its own tight ``_bucket`` shape: its
#: scan steps are most of the call, and such calls recur with like
#: shapes, so set-up can build them.  A smaller group (a service cohort:
#: its shapes follow whatever requests share it) pads to the ``_chain``
#: shape instead, one of the few that :func:`warm_chain` builds, so a
#: served process builds no executable after set-up
TIGHT_MIN_LANES = 8 * JIT_SHARD

#: threads used to run shards concurrently (XLA releases the GIL)
_POOL_WORKERS = max(1, min(4, __import__("os").cpu_count() or 1))
_POOL = None


def _pool():
    global _POOL
    if _POOL is None:
        from concurrent.futures import ThreadPoolExecutor
        _POOL = ThreadPoolExecutor(max_workers=_POOL_WORKERS)
    return _POOL


def _jit_compatible(programs: list[SimProgram],
                    params: PipelineParams) -> bool:
    """The lean compiled recurrence assumes (a) same-instruction uop
    slots are contiguous (``compile_program`` always emits them so) and
    (b) one iteration's uops fit inside the ROB/scheduler windows, so
    every ring read references a previous iteration.  Programs violating
    either run on the numpy reference path (individually — they do not
    downgrade the rest of their group)."""
    for prog in programs:
        seen: set[int] = set()
        prev = -1
        n = n_p = 0
        for u in prog.uops:
            if u.instr_index != prev and u.instr_index in seen:
                return False                      # non-contiguous slots
            seen.add(u.instr_index)
            prev = u.instr_index
            n += 1
            n_p += bool(u.ports)
        if n > params.rob_size or n_p > params.scheduler_size:
            return False
    return True


def _pack_lean(programs: list[SimProgram],
               edge_lists: list[list[tuple[int, int, float, bool]]],
               ports: tuple[str, ...], params: PipelineParams,
               n_iterations: int,
               shape: tuple[int, int] | None = None) -> dict:
    """Pack one shard for the compiled recurrence; ``edge_lists`` holds
    each program's :func:`_composed_edges`.  The padded ``(U, E)`` is
    ``shape``, by default the tight ``_bucket`` of the longest lane.

    Slot-major ``[U, B]`` layout (scan consumes leading-axis slices);
    window-gate booleans and ring index bases are precomputed here
    because the uop counters depend only on the static active pattern.
    """
    B = len(programs)
    P = len(ports)
    T = n_iterations
    pindex = {p: i for i, p in enumerate(ports)}
    U, E = shape or (
        _bucket(max(max((len(p.uops) for p in programs), default=0), 1)),
        _bucket(max(max((len(es) for es in edge_lists), default=0), 1)))

    active = np.zeros((U, B), bool)
    first = np.zeros((U, B), bool)
    same_prev = np.zeros((U, B), bool)
    has_port = np.zeros((U, B), bool)
    elig = np.zeros((U, B, P), bool)
    cyc_upd = np.zeros((U, B))          # booked cycles (0 = no port)
    lat = np.ones((U, B))
    slot_start = np.zeros((U, B), bool)
    phase_u = np.zeros((U, B))
    fe_cpi = np.zeros(B)
    m_dst = np.zeros((U, B, E), bool)   # edges feeding this slot's instr
    m_src = np.zeros((U, B, E), bool)   # edges sourced at this slot's
    e_w = np.zeros((B, E))              # instr
    e_wrap = np.zeros((B, E), bool)
    n_uops = np.zeros(B, np.int64)
    n_puops = np.zeros(B, np.int64)
    n_slots = np.zeros(B, np.int64)
    pre_g = np.zeros((U, B), np.int64)
    pre_gp = np.zeros((U, B), np.int64)
    pre_s = np.zeros((U, B), np.int64)
    for b, prog in enumerate(programs):
        fe = frontend_schedule(prog, params)
        fe_cpi[b] = fe.cpi
        es = edge_lists[b]
        for e, (_, _, w, wrap) in enumerate(es):
            e_w[b, e] = w
            e_wrap[b, e] = wrap
        seen: set[int] = set()
        g = gp = s = 0
        prev_instr = -1
        for u, uop in enumerate(prog.uops):
            active[u, b] = True
            pre_g[u, b] = g
            pre_gp[u, b] = gp
            pre_s[u, b] = s
            slot_start[u, b] = fe.slot_start[u]
            if fe.cpi:
                phase_u[u, b] = fe.phase[fe.slot_of[u]]
            if uop.instr_index not in seen:
                seen.add(uop.instr_index)
                first[u, b] = True
            same_prev[u, b] = (uop.instr_index == prev_instr)
            prev_instr = uop.instr_index
            if uop.ports and not fe.eliminated[u]:
                has_port[u, b] = True
                cyc_upd[u, b] = max(1.0, uop.cycles)
                for pt in uop.ports:
                    elig[u, b, pindex[pt]] = True
                gp += 1
            lat[u, b] = max(1.0, prog.latency[uop.instr_index])
            for e, (src, dst, _, _) in enumerate(es):
                if dst == uop.instr_index:
                    m_dst[u, b, e] = True
                if src == uop.instr_index:
                    m_src[u, b, e] = True
            g += 1
            s += fe.slot_start[u]
        n_uops[b] = g
        n_puops[b] = gp
        n_slots[b] = s
    # window gates per (iteration, slot, lane): the issued-uop counters
    # are static, so "has the ring wrapped yet" is data, not control;
    # the issue-width ring is a *slot* resource, so its gate also
    # requires a slot start
    it_ = np.arange(T)[:, None, None]
    g_abs = it_ * n_uops[None, None, :] + pre_g[None]       # [T, U, B]
    gp_abs = it_ * n_puops[None, None, :] + pre_gp[None]
    s_abs = it_ * n_slots[None, None, :] + pre_s[None]
    gm = np.stack([(s_abs >= params.issue_width) & slot_start[None],
                   g_abs >= params.rob_size,
                   (gp_abs >= params.scheduler_size) & has_port[None]],
                  axis=-1)                                  # [T, U, B, 3]
    # retire bandwidth is a fused-domain (slot) resource too
    g_rw = (s_abs >= params.retire_width) & slot_start[None]  # [T, U, B]
    # static fetch/decode delivery floor per (iteration, slot, lane),
    # anchored after the mispredict recovery penalty (fetch restarts
    # once the mispredicted loop branch resolves); on unconstrained
    # lanes the penalty still delays the very first issue
    deliv = np.where(slot_start[None],
                     it_ * fe_cpi[None, None, :] + phase_u[None]
                     + np.where(fe_cpi > 0.0,
                                params.mispredict_penalty,
                                0.0)[None, None, :], 0.0)
    deliv[0, 0, :] = np.maximum(deliv[0, 0, :],
                                params.mispredict_penalty)
    return dict(active=active, first=first, same_prev=same_prev,
                has_port=has_port, elig=elig, cyc_upd=cyc_upd, lat=lat,
                slot_start=slot_start, deliv=deliv,
                m_dst=m_dst, m_src=m_src, e_w=e_w, e_wrap=e_wrap,
                gm=gm, g_rw=g_rw, n_uops=n_uops, n_puops=n_puops,
                pre_g=pre_g.T, pre_gp=pre_gp.T, U=U, E=E)


_LEAN_ARGS = ("active", "first", "same_prev", "has_port", "elig",
              "cyc_upd", "lat", "slot_start", "deliv", "m_dst", "m_src",
              "e_w", "e_wrap", "gm", "g_rw", "n_uops", "n_puops",
              "pre_g", "pre_gp")


@functools.lru_cache(maxsize=128)
def _compiled_run(U: int, E: int, P: int, T: int,
                  params: PipelineParams, flavor: str):
    """Build (and cache) the compiled shard recurrence for one shape
    bucket.  ``flavor`` selects the port-arbitration implementation
    (``"lax"`` or ``"pallas"``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    Wi, R = params.issue_width, params.rob_size
    S, Wr = params.scheduler_size, params.retire_width
    NEG = -jnp.inf

    if flavor == "pallas":
        from .pallas_step import make_arbitration_step
        arbitrate = make_arbitration_step(P)
    else:
        def arbitrate(port_cap, elig, cyc_upd):
            pf = jnp.where(elig, port_cap, jnp.inf)
            pmin = jnp.min(pf, axis=1)
            choice = jnp.argmin(pf, axis=1)     # first index on ties
            oh = jnp.arange(P)[None, :] == choice[:, None]
            return port_cap + jnp.where(oh, cyc_upd[:, None], 0.0), pmin

    def run(active, first, same_prev, has_port, elig, cyc_upd, lat,
            slot_start, deliv, m_dst, m_src, e_w, e_wrap, gm, g_rw,
            n_uops, n_puops, pre_g, pre_gp):
        B = active.shape[1]
        zeros = jnp.zeros((B,))
        rngB = jnp.arange(B)[:, None]

        def slot_step(carry, x):
            (port_cap, cur_e, prev_e, last_issue, last_retire,
             run_exec, run_ready, reg_i, reg_rw) = carry
            (a, fi, sp, hp, el, cu, lt, ssx, dlx, md, gmx, grw,
             rob_v, sch_v, ms) = x

            # issue: in-order, gated on the front-end / ROB / scheduler
            # ring heads (gm masks rings that have not wrapped yet —
            # the issue-width gate additionally requires a slot start)
            # plus the static fetch/decode delivery floor
            heads = jnp.concatenate(
                [reg_i[:, :1] + 1.0, rob_v[:, None], sch_v[:, None]],
                axis=1)
            t = jnp.maximum(
                last_issue,
                jnp.max(heads * gmx.astype(heads.dtype), axis=1))
            t = jnp.maximum(t, dlx)
            t = jnp.ceil(t)
            issue_t = jnp.where(a, t, last_issue)

            # operand readiness: evaluated at an instruction's first
            # slot from the per-edge source-execute vector; -inf is the
            # identity for "no producer yet" (exact under max/clamp)
            src = jnp.where(e_wrap, prev_e, cur_e) + e_w
            ready = jnp.maximum(
                jnp.max(jnp.where(md, src, NEG), axis=1), 0.0)
            ready_t = jnp.where(fi, ready, run_ready)

            # dispatch: least-loaded eligible port
            lb = jnp.maximum(issue_t + 1.0, jnp.ceil(ready_t))
            port_cap, pmin = arbitrate(port_cap, el, cu)
            start = jnp.where(hp, jnp.maximum(lb, pmin), issue_t)
            disp = jnp.where(a, start, 0.0)

            # execute: running per-instruction max (same-instruction
            # slots are contiguous), pushed onto outgoing edges
            new_exec = jnp.maximum(disp, jnp.where(sp, run_exec, NEG))
            cur_e = jnp.where(ms, new_exec[:, None], cur_e)

            # retire: in-order, bounded bandwidth
            complete = disp + lt
            r = jnp.maximum(complete, last_retire)
            r = jnp.maximum(r, jnp.where(grw, reg_rw[:, 0] + 1.0, 0.0))
            retire_t = jnp.where(a, r, last_retire)

            # the issue/retire rings hold *slot* times: they only
            # advance when a slot starts (fused continuation uops are
            # free); a continuation instead overwrites its own slot's
            # retire entry (retire_t is monotone, so this is its max)
            su1 = (a & ssx)[:, None]
            reg_i = jnp.where(su1, jnp.concatenate(
                [reg_i[:, 1:], issue_t[:, None]], axis=1), reg_i)
            reg_rw = jnp.where(su1, jnp.concatenate(
                [reg_rw[:, 1:], retire_t[:, None]], axis=1),
                jnp.where(a[:, None], reg_rw.at[:, -1].set(retire_t),
                          reg_rw))
            return (port_cap, cur_e, prev_e, issue_t, retire_t,
                    new_exec, ready_t, reg_i, reg_rw), (retire_t, disp)

        def iter_body(carry, g_it):
            (port_cap, prev_e, last_issue, last_retire,
             reg_i, reg_rw, rob_ring, sch_ring, it) = carry
            gmx, grw, dlv = g_it
            # ROB/scheduler ring traffic hoisted out of the slot loop:
            # one iteration's uops fit inside both windows (checked by
            # _jit_compatible), so every read hits a previous iteration
            # — gather them all up front, scatter the writes at the end
            g0 = it * n_uops[:, None] + pre_g               # [B, U]
            gp0 = it * n_puops[:, None] + pre_gp
            rob_v = rob_ring[rngB, (g0 - R) % R]
            sch_v = sch_ring[rngB, jnp.maximum(gp0 - S, 0) % S]
            c = (port_cap, jnp.full_like(prev_e, NEG), prev_e,
                 last_issue, last_retire, zeros, zeros, reg_i, reg_rw)
            xs = (active, first, same_prev, has_port, elig, cyc_upd,
                  lat, slot_start, dlv, m_dst, gmx, grw, rob_v.T,
                  sch_v.T, m_src)
            c, (ret_ts, disp_ts) = lax.scan(slot_step, c, xs, unroll=2)
            (port_cap, cur_e, _, last_issue, last_retire,
             _, _, reg_i, reg_rw) = c
            # masked scatter: padding slots write out of bounds -> drop
            w_idx = jnp.where(active.T, g0 % R, R)
            rob_ring = rob_ring.at[rngB, w_idx].set(ret_ts.T,
                                                    mode="drop")
            wp_idx = jnp.where((active & has_port).T, gp0 % S, S)
            sch_ring = sch_ring.at[rngB, wp_idx].set(disp_ts.T,
                                                     mode="drop")
            return (port_cap, cur_e, last_issue, last_retire,
                    reg_i, reg_rw, rob_ring, sch_ring,
                    it + 1), last_retire

        E_ = m_dst.shape[2]
        init = (jnp.zeros((B, P)), jnp.full((B, E_), NEG), zeros, zeros,
                jnp.zeros((B, Wi)), jnp.zeros((B, Wr)),
                jnp.zeros((B, R)), jnp.zeros((B, S)),
                jnp.zeros((), jnp.int64))
        _, iter_end = lax.scan(iter_body, init, (gm, g_rw, deliv))
        return iter_end.T                                   # [B, T]

    return jax.jit(run)


def _empty_program(model) -> SimProgram:
    return SimProgram(model=model, n_instructions=0, uops=(),
                      latency=(), edges=())


def _pack_shards(programs: list[SimProgram],
                 edge_lists: list[list[tuple[int, int, float, bool]]],
                 ports: tuple[str, ...], params: PipelineParams,
                 n_iterations: int, tight: bool = True) -> list[dict]:
    """Cut ``programs`` (sorted by the caller by uop and edge count, so
    lanes of like length share a shard and its padded ``U`` and ``E``)
    into ``JIT_SHARD``-lane shards, the last padded with empty lanes,
    and pack each for the compiled recurrence, at the tight ``_bucket``
    shape of its longest lane or, unless ``tight``, its ``_chain``
    shape."""
    model = programs[0].model
    shards = []
    for s in range(0, len(programs), JIT_SHARD):
        chunk, edges = programs[s:s + JIT_SHARD], edge_lists[s:s + JIT_SHARD]
        pad = JIT_SHARD - len(chunk)
        shape = None if tight else _chain(
            max(max(len(p.uops) for p in chunk), 1),
            max(max(len(es) for es in edges), 1))
        shards.append(_pack_lean(chunk + [_empty_program(model)] * pad,
                                 edges + [[]] * pad, ports, params,
                                 n_iterations, shape))
    return shards


def _run_jax(shards: list[dict], n_lanes: int, ports: tuple[str, ...],
             params: PipelineParams, n_iterations: int, flavor: str,
             meta: dict | None = None) -> np.ndarray:
    """Run packed shards on the compiled recurrence and return the
    first ``n_lanes`` lanes' retire trajectories; agrees with
    :func:`_run_numpy` to 1e-9 because it executes the identical
    arithmetic in float64 (``jax.enable_x64``).  Each shard (argument
    transfer, execution, fetch) is one ``repro.sim.shard`` span on the
    thread that runs it, carrying ``meta``."""
    import jax

    def run_shard(s: int) -> np.ndarray:
        pk = shards[s]
        with span("repro.sim.shard", **(meta or {}), shard=s, U=pk["U"],
                  E=pk["E"], T=n_iterations), jax.enable_x64(True):
            return _run_shard(pk, ports, params, n_iterations, flavor)

    if len(shards) == 1:
        outs = [run_shard(0)]
    else:
        outs = list(_pool().map(run_shard, range(len(shards))))
    return np.concatenate(outs, axis=0)[:n_lanes]


def _run_shard(pk: dict, ports: tuple[str, ...], params: PipelineParams,
               n_iterations: int, flavor: str) -> np.ndarray:
    import jax.numpy as jnp

    fn = _compiled_run(pk["U"], pk["E"], len(ports), n_iterations, params,
                       flavor)
    return np.asarray(fn(*[jnp.asarray(pk[k]) for k in _LEAN_ARGS]))


def warm_chain(model, n_iterations: int = 96,
               up_to: tuple[int, int] | None = None
               ) -> list[tuple[int, int, int]]:
    """Build (or load from the compile cache) the compiled recurrence of
    every ``_chain`` shape that a group smaller than
    :data:`TIGHT_MIN_LANES` can reach on ``model``, at ``n_iterations``
    and at the 4x horizon unconverged lanes escalate to, by running one
    empty shard of each.  ``up_to`` is the ``(uops, composed edges)``
    of the largest lane the caller will send, by default a full ROB of
    uops.  Returns the ``(U, E, T)`` built; after it, such groups build
    no executable."""
    import jax

    params = model.pipeline or DEFAULT_PARAMS
    top, _ = _chain(*(up_to or (params.rob_size, 0)))
    empty = [_empty_program(model)] * JIT_SHARD
    built = []
    for T in (n_iterations, 4 * n_iterations):
        U = 8
        while U <= top:
            pk = _pack_lean(empty, [[]] * JIT_SHARD, model.ports, params,
                            T, (U, 2 * U))
            with jax.enable_x64(True):
                _run_shard(pk, model.ports, params, T, "lax")
            built.append((pk["U"], pk["E"], T))
            U *= 2
    return built


# --------------------------------------------------------------------------
# Steady state + entry point
# --------------------------------------------------------------------------

def _steady_state(iter_end: np.ndarray, warmup: int, max_period: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Per-lane steady-state cycles/iteration from the retire
    trajectory.  The periodic-pattern scan is bounded: only the last
    ``3 * max_period`` deltas are ever examined (the pattern must repeat
    three times — the capacity accumulator can plateau mid-transient,
    and a 2x match would mistake that plateau for the steady state).
    Lanes with no repeating pattern get an explicit ``converged=False``
    and fall back to the mean slope of the simulated tail.
    """
    B = iter_end.shape[0]
    deltas = np.diff(iter_end[:, warmup:], axis=1)
    span = deltas.shape[1]
    cpi = deltas[:, span // 2:].mean(axis=1) if span else \
        iter_end[:, -1].copy()
    # the tail-mean slope vetoes aliased matches: a long-period pattern
    # (e.g. a scheduler backlog that stalls every Nth iteration) can
    # end on p identical deltas without them being the steady state
    slope = cpi.copy()
    converged = np.zeros(B, bool)
    for p in range(1, max_period + 1):
        if span >= 3 * p:
            pval = deltas[:, -p:].mean(axis=1)
            match = np.all(
                (deltas[:, -p:] == deltas[:, -2 * p:-p])
                & (deltas[:, -p:] == deltas[:, -3 * p:-2 * p]), axis=1)
            match &= np.abs(pval - slope) <= 0.25 + 0.02 * np.abs(slope)
            new = match & ~converged
            if new.any():   # converged at period p: periodic mean
                cpi = np.where(new, pval, cpi)
            converged |= match
    return cpi, converged


def _resolve_backend(backend: str, batch: int) -> str:
    if backend == "auto":
        return "jit" if batch >= AUTO_JIT_MIN_BATCH else "numpy"
    if backend == "pallas":
        import jax
        if jax.default_backend() == "tpu":
            raise NotImplementedError(
                "backend='pallas' cannot run on a TPU: the recurrence is "
                "float64 and the TPU kernel compiler (Mosaic) takes "
                "float32 only; use backend='jit'")
        return backend
    if backend in ("numpy", "jit"):
        return backend
    raise ValueError(f"unknown backend {backend!r} "
                     "(expected 'auto', 'numpy', 'jit' or 'pallas')")


def simulate_many(programs: list[SimProgram],
                  params: PipelineParams | None = None, *,
                  n_iterations: int = 96,
                  warmup_iterations: int = 4,
                  max_period: int = 8,
                  backend: str = "auto",
                  classify: Callable[..., str] | None
                  = None,
                  counters: dict | None = None) -> list[SimResult]:
    """Simulate every program; results match the input order.

    Args:
        programs: compiled loop bodies (see
            :func:`repro.core.sim.pipeline.compile_program`); mixed
            architectures are allowed.
        params: pipeline parameters forced for the whole batch;
            default: each program's own ``model.pipeline``.
        n_iterations: loop bodies simulated per kernel (the vectorized
            pass has no early exit; lanes that fail to converge within
            the horizon are re-run once at ``4 * n_iterations``).
        warmup_iterations: iterations excluded from the steady-state
            slope.
        max_period: longest periodic delta pattern accepted as
            convergence.
        backend: ``"numpy"`` (reference slot sweep), ``"jit"``
            (``jax.jit`` + ``vmap``, shape-bucketed), ``"pallas"``
            (jit with the Pallas arbitration step, interpret mode
            only; refused on a TPU), or ``"auto"`` (jit for groups of
            ≥ :data:`AUTO_JIT_MIN_BATCH`, else numpy).  See
            docs/performance.md.
        classify: optional replacement for the bottleneck classifier
            (the :class:`~repro.core.engine.AnalysisService` passes a
            memoized one).
        counters: optional dict the engine surfaces in
            :class:`~repro.core.engine.ServiceStats`.  ``"dispatches"``
            is incremented once per driver invocation actually issued
            (a group split between the reference driver and the
            compiled one counts each; a sharded jit dispatch counts
            once; the escalation pass does not count):
            ``stats.sim_group_dispatches``.  The ``sim_*`` entries
            count the compiled recurrence's work, escalation included
            unless noted: ``sim_lanes`` (lanes sent to it, first pass),
            ``sim_device_calls`` (shard executions), ``sim_slot_steps``
            (real lanes' uop slots × iterations, summed over shards),
            ``sim_slot_capacity`` (the same for the shards' padded
            ``U × JIT_SHARD`` slots), ``sim_escalated_lanes`` (lanes
            re-run at 4× the horizon, any driver) and
            ``sim_host_lanes`` (lanes asked of a compiled backend that
            ran on the reference driver as exotic, first pass).  A
            ``"call"`` entry, when present, is attached to every
            ``repro.sim.*`` profiler span as its ``call`` stat.
    """
    classify = classify or _classify
    groups: dict[tuple, _Group] = {}
    for pos, prog in enumerate(programs):
        p = params or prog.model.pipeline or DEFAULT_PARAMS
        key = (prog.model.ports, p)
        g = groups.setdefault(key, _Group([], []))
        g.programs.append(prog)
        g.indices.append(pos)

    out: list[SimResult | None] = [None] * len(programs)
    for (ports, p), g in groups.items():
        results = _simulate_group(
            g.programs, ports, p, n_iterations, warmup_iterations,
            max_period, _resolve_backend(backend, len(g.programs)),
            classify, counters)
        for pos, res in zip(g.indices, results):
            out[pos] = res
    return out  # type: ignore[return-value]


def _count(counters: dict | None, **increments: int) -> None:
    if counters is not None:
        for key, n in increments.items():
            counters[key] = counters.get(key, 0) + n


def _simulate_group(programs: list[SimProgram], ports: tuple[str, ...],
                    params: PipelineParams, n_iterations: int,
                    warmup: int, max_period: int, backend: str,
                    classify: Callable[..., str],
                    counters: dict | None = None, *,
                    _grown: bool = False,
                    _tight: bool | None = None) -> list[SimResult]:
    if max((len(p.uops) for p in programs), default=0) == 0:
        return [SimResult(0.0, 0, True, "empty", 0.0, {}, params)
                for _ in programs]
    # the planner call these spans belong to, as their ``call`` stat
    meta = {"call": counters["call"]} if counters and "call" in counters \
        else {}
    B, T = len(programs), n_iterations
    host = list(range(B))        # lanes on the numpy reference driver
    lanes: list[int] = []        # lanes on the compiled recurrence
    shards: list[dict] = []
    if backend != "numpy":
        with span("repro.sim.pack", **meta):
            # exotic programs (non-contiguous slots / iteration larger
            # than a window) take the reference driver, individually,
            # so one of them does not downgrade the whole group
            ok = [_jit_compatible([p], params) for p in programs]
            host = [b for b in range(B) if not ok[b]]
            lanes = [b for b in range(B) if ok[b]]
            if any(programs[b].uops for b in lanes):
                # lanes of like length share a shard, so few scan steps
                # are padding; lanes are independent, so the order
                # changes no answer, and ``iter_end[lanes]`` below puts
                # the trajectories back in input order
                edges = {b: _composed_edges(programs[b]) for b in lanes}
                lanes.sort(key=lambda b: (len(programs[b].uops),
                                          len(edges[b])))
                # escalated lanes keep the padding rule of their group
                if _tight is None:
                    _tight = len(lanes) >= TIGHT_MIN_LANES
                shards = _pack_shards([programs[b] for b in lanes],
                                      [edges[b] for b in lanes], ports,
                                      params, T, _tight)
    if not _grown:
        # one dispatch per driver invocation (the reference driver for
        # exotic lanes and the compiled one each count); the
        # escalation pass below is not a dispatch of its own
        _count(counters, dispatches=bool(host) + bool(shards),
               sim_lanes=len(lanes) if shards else 0,
               sim_host_lanes=len(host) if backend != "numpy" else 0)
    iter_end = np.zeros((B, T))
    if host:
        with span("repro.sim.numpy", **meta):
            iter_end[host] = _run_numpy(
                _pack([programs[b] for b in host], ports, params), T)
    if shards:
        _count(counters, sim_device_calls=len(shards),
               sim_slot_steps=T * sum(int(pk["n_uops"].sum())
                                      for pk in shards),
               sim_slot_capacity=T * JIT_SHARD * sum(pk["U"]
                                                     for pk in shards))
        iter_end[lanes] = _run_jax(
            shards, len(lanes), ports, params, T,
            "pallas" if backend == "pallas" else "lax", meta)
    with span("repro.sim.steady_state", **meta):
        cpi, converged = _steady_state(iter_end, warmup, max_period)

    # one escalation pass: a lane whose transient outlasts the horizon
    # (e.g. a divider backlog that takes ~scheduler_size iterations to
    # fill) re-runs with 4x the iterations; converged lanes keep their
    # first-pass numbers bit-exactly
    retry: dict[int, SimResult] = {}
    if not _grown:
        retry_idx = [b for b, prog in enumerate(programs)
                     if prog.uops and not converged[b]]
        if retry_idx:
            _count(counters, sim_escalated_lanes=len(retry_idx))
            with span("repro.sim.escalate", **meta):
                sub = _simulate_group(
                    [programs[b] for b in retry_idx], ports, params,
                    4 * n_iterations, warmup, max_period, backend,
                    classify, counters, _grown=True, _tight=_tight)
            retry = dict(zip(retry_idx, sub))

    results = []
    with span("repro.sim.finish", **meta):
        for b, prog in enumerate(programs):
            if not prog.uops:
                results.append(SimResult(0.0, 0, True, "empty", 0.0, {},
                                         params))
                continue
            if b in retry:
                results.append(retry[b])
                continue
            sched = frontend_schedule(prog, params)
            fe = sched.n_slots / params.issue_width
            results.append(SimResult(
                cycles_per_iteration=float(cpi[b]),
                iterations=n_iterations, converged=bool(converged[b]),
                bottleneck=classify(float(cpi[b]), fe,
                                    prog.port_bound_cycles, sched.cpi,
                                    sched.mode),
                frontend_cycles=fe, port_busy={}, params=params,
                delivery_cycles=sched.cpi, fe_mode=sched.mode))
    return results
