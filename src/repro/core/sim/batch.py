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
#: stays cache-resident, large enough to amortize dispatch; every batch
#: is padded (with empty lanes) to a multiple of this, so one compiled
#: executable per (shape bucket, machine) serves all sweep sizes
JIT_SHARD = 64

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


def _pack_lean(programs: list[SimProgram], ports: tuple[str, ...],
               params: PipelineParams, n_iterations: int) -> dict:
    """Pack one shard for the compiled recurrence.

    Slot-major ``[U, B]`` layout (scan consumes leading-axis slices);
    window-gate booleans and ring index bases are precomputed here
    because the uop counters depend only on the static active pattern.
    """
    B = len(programs)
    P = len(ports)
    T = n_iterations
    pindex = {p: i for i, p in enumerate(ports)}
    edge_lists = [_composed_edges(p) for p in programs]
    U = _bucket(max(max((len(p.uops) for p in programs), default=0), 1))
    E = _bucket(max(max((len(es) for es in edge_lists), default=0), 1))

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


def _run_jax(programs: list[SimProgram], ports: tuple[str, ...],
             params: PipelineParams, n_iterations: int,
             flavor: str) -> np.ndarray:
    """Shard + run the compiled recurrence; agrees with
    :func:`_run_numpy` to 1e-9 because it executes the identical
    arithmetic in float64 (``jax.enable_x64``)."""
    import jax
    import jax.numpy as jnp

    B = len(programs)
    model = programs[0].model
    n_shards = -(-B // JIT_SHARD)
    shards = []
    for s in range(n_shards):
        chunk = programs[s * JIT_SHARD:(s + 1) * JIT_SHARD]
        chunk = chunk + [_empty_program(model)] * (JIT_SHARD - len(chunk))
        shards.append(_pack_lean(chunk, ports, params, n_iterations))

    def run_shard(pk: dict) -> np.ndarray:
        with jax.enable_x64(True):
            fn = _compiled_run(pk["U"], pk["E"], len(ports),
                               n_iterations, params, flavor)
            args = [jnp.asarray(pk[k]) for k in _LEAN_ARGS]
            return np.asarray(fn(*args))

    if len(shards) == 1:
        outs = [run_shard(shards[0])]
    else:
        outs = list(_pool().map(run_shard, shards))
    return np.concatenate(outs, axis=0)[:B]


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
        counters: optional dict whose ``"dispatches"`` entry is
            incremented once per driver invocation actually issued
            (split groups count each sub-invocation; a sharded jit
            dispatch counts once) — the engine surfaces this as
            ``stats.sim_group_dispatches``.
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


def _simulate_group(programs: list[SimProgram], ports: tuple[str, ...],
                    params: PipelineParams, n_iterations: int,
                    warmup: int, max_period: int, backend: str,
                    classify: Callable[..., str],
                    counters: dict | None = None, *,
                    _grown: bool = False) -> list[SimResult]:
    if max((len(p.uops) for p in programs), default=0) == 0:
        return [SimResult(0.0, 0, True, "empty", 0.0, {}, params)
                for _ in programs]
    if backend != "numpy":
        ok = [_jit_compatible([p], params) for p in programs]
        if not all(ok):
            # exotic programs (non-contiguous slots / iteration larger
            # than a window) take the reference path — individually,
            # so one of them does not downgrade the whole group
            exotic = [p for p, k in zip(programs, ok) if not k]
            rest = [p for p, k in zip(programs, ok) if k]
            sub = _simulate_group(exotic, ports, params, n_iterations,
                                  warmup, max_period, "numpy",
                                  classify, counters, _grown=_grown)
            out = iter(sub)
            if rest:
                sub2 = iter(_simulate_group(
                    rest, ports, params, n_iterations, warmup,
                    max_period, backend, classify, counters,
                    _grown=_grown))
                return [next(out) if not k else next(sub2)
                        for k in ok]
            return sub
    if counters is not None:
        counters["dispatches"] = counters.get("dispatches", 0) + 1
    if backend == "numpy":
        iter_end = _run_numpy(_pack(programs, ports, params),
                              n_iterations)
    else:
        iter_end = _run_jax(programs, ports, params, n_iterations,
                            "pallas" if backend == "pallas" else "lax")
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
            sub = _simulate_group(
                [programs[b] for b in retry_idx], ports, params,
                4 * n_iterations, warmup, max_period, backend,
                classify, None, _grown=True)
            retry = dict(zip(retry_idx, sub))

    results = []
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
