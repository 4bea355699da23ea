"""Unified throughput (+) critical-path analysis (paper Sec. III, and the
OSACA follow-up arXiv:1910.00214): map every kernel instruction to its DB
entry, schedule uops onto ports, sum per-port occupation, and combine the
port-occupation bound with the loop-carried-dependency (LCD) bound —

    predicted = max(port_bound, loop_carried_dependency)

The paper's own worst mispredictions (pi at -O1, Table V: measurement ~2x
the port-bound estimate) are exactly the cases where the LCD term binds.
Both bounds and the binding constraint are reported by ``render()``.

Implements the Zen store/load AGU pairing: each store instruction hides one
load instruction's AGU uops (displayed parenthesised, excluded from totals) —
paper Sec. III-A, Table IV.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

from .database import InstructionDB, MissingForm
from .isa import Instruction
from .latency import LatencyResult, analyze_latency
from .machine import as_database
from .ports import PortModel, merge_occupation
from .scheduler import SCHEDULERS, ScheduledUop


@dataclass
class InstructionReport:
    instruction: Instruction
    occupation: dict[str, float]          # visible occupation per port
    hidden_occupation: dict[str, float]   # parenthesised (hidden) occupation
    throughput: float | None
    latency: float | None
    matched: bool

    def total(self) -> float:
        return sum(self.occupation.values())


@dataclass
class AnalysisResult:
    """Combined throughput + critical-path prediction for one kernel.

    The headline number, ``predicted_cycles``, is the *combined* bound
    ``max(port_bound_cycles, lcd_cycles)`` per assembly iteration; the two
    constituent bounds are always reported alongside so callers (and
    ``render()``) can see which constraint binds.
    """

    model: PortModel
    rows: list[InstructionReport]
    port_totals: dict[str, float]         # visible occupation per port
    bottleneck_port: str                  # argmax of port_totals
    predicted_cycles: float               # combined bound, per asm iteration
    missing: list[MissingForm]
    scheduler: str
    unroll_factor: int = 1
    # --- constituent bounds (per assembly iteration) -------------------
    port_bound_cycles: float = 0.0        # pure throughput (paper) bound
    lcd_cycles: float = 0.0               # loop-carried dependency bound
    latency_result: LatencyResult | None = None
    binding: str = "throughput"           # "throughput" | "latency"
    #                                       | "simulation" | "memory"
    # --- cycle-level simulation (mode="simulate" only) -----------------
    bound_sim: float = 0.0                # steady-state cy/asm-it; 0 = not
    #                                       simulated
    sim_result: object | None = None      # repro.core.sim.SimResult
    # --- ECM memory-hierarchy composition (working_set= requests) ------
    bound_ecm: float = 0.0                # max(in-core, T_nOL + transfers);
    #                                       0 = not composed
    ecm_result: object | None = None      # repro.core.mem.EcmResult
    # --- degradation provenance (docs/robustness.md) --------------------
    degraded: bool = False                # a cheaper backend answered after
    #                                       the requested one failed
    backend_used: str = ""                # rung that answered ("" = none)
    fault_trace_id: int = 0               # FaultInjector event id (0 = none)
    routed_from: str = ""                 # rung the HealthRouter skipped
    #                                       pre-dispatch ("" = not routed)
    probe: bool = False                   # answered by a scheduled
    #                                       half-open probe dispatch

    @property
    def cycles_per_source_iteration(self) -> float:
        """Combined bound scaled back to one *source* loop iteration."""
        return self.predicted_cycles / self.unroll_factor

    @property
    def sim_per_source_iteration(self) -> float:
        """The simulated bound per source iteration (0 if not simulated)."""
        return self.bound_sim / self.unroll_factor

    @property
    def ecm_per_source_iteration(self) -> float:
        """The ECM-composed bound per source iteration (0 if no ECM)."""
        return self.bound_ecm / self.unroll_factor

    @property
    def port_bound_per_source_iteration(self) -> float:
        """The paper's pure port-occupation bound per source iteration."""
        return self.port_bound_cycles / self.unroll_factor

    @property
    def lcd_per_source_iteration(self) -> float:
        """The loop-carried-dependency bound per source iteration."""
        return self.lcd_cycles / self.unroll_factor

    # ------------------------------------------------------------------
    def render(self, precision: int = 2) -> str:
        headers = []
        for p in self.model.ports:
            headers.append(f"{p} - DV" if p in self.model.divider_ports
                           else p)
        width = max(6, max(len(h) for h in headers) + 1)

        def fmt(v: float, hidden: float = 0.0) -> str:
            if v <= 1e-12 and hidden <= 1e-12:
                return " " * width
            if hidden > 1e-12:
                return f"({hidden:.{precision}f})".rjust(width)
            return f"{v:.{precision}f}".rjust(width)

        lines = ["| " + " | ".join(h.rjust(width) for h in headers)
                 + " | Assembly Instructions"]
        lines.append("|" + "-" * (len(lines[0]) - 1))
        for row in self.rows:
            cells = [fmt(row.occupation.get(p, 0.0),
                         row.hidden_occupation.get(p, 0.0))
                     for p in self.model.ports]
            marker = "" if row.matched else "   # NOT IN DB"
            lines.append("| " + " | ".join(cells) + " | "
                         + row.instruction.text + marker)
        totals = [f"{self.port_totals[p]:.{precision}f}".rjust(width)
                  for p in self.model.ports]
        lines.append("|" + "-" * (len(lines[0]) - 1))
        lines.append("| " + " | ".join(totals) + " |")
        unit = self.model.unit
        lines.append(
            f"Port (throughput) bound: {self.port_bound_cycles:.{precision}f}"
            f" {unit}/asm-it   (bottleneck port {self.bottleneck_port})")
        if self.latency_result is not None:
            lines.append(
                f"Loop-carried dependency: {self.lcd_cycles:.{precision}f} "
                f"{unit}/asm-it"
                + ("" if not self.latency_result.chain else
                   "   (critical chain: "
                   + " -> ".join(i.mnemonic
                                 for i in self.latency_result.chain) + ")"))
        if self.sim_result is not None:
            lines.append(
                f"Simulated (cycle-level): {self.bound_sim:.{precision}f} "
                f"{unit}/asm-it"
                + (f"   ({self.sim_result.bottleneck}-limited)"
                   if getattr(self.sim_result, "bottleneck", "") else ""))
        if self.ecm_result is not None:
            lines.append(
                f"ECM composition: {self.bound_ecm:.{precision}f} {unit}"
                f"/asm-it   {self.ecm_result.notation()}"
                f"   (working set {self.ecm_result.working_set:.0f} B, "
                f"{self.ecm_result.resident}-resident)")
        rule = "ECM" if self.ecm_result is not None \
            else "simulation" if self.sim_result is not None \
            else "max(port, LCD)"
        lines.append(
            f"Predicted: {self.predicted_cycles:.{precision}f} {unit}/asm-it"
            f" = {rule}"
            + (f"   ({self.cycles_per_source_iteration:.{precision}f} "
               f"{unit}/src-it @ unroll "
               f"{self.unroll_factor})" if self.unroll_factor != 1 else "")
            + f"   [{self.binding}-bound, scheduler={self.scheduler}]")
        if self.missing:
            lines.append("Missing forms (benchmarks auto-generated):")
            for m in self.missing:
                lines.append("  - " + m.instruction.form)
        return "\n".join(lines)


def hidden_instruction_indices(model: PortModel,
                               entries: list) -> set[int]:
    """Zen store/load AGU pairing (paper Sec. III-A, Table IV): each
    store instruction lets one load's hideable AGU uops execute in its
    shadow; OSACA hides the first loads in program order.  Shared by the
    analytic pipeline and the simulator so both model the same machine.

    Args:
        model: the port model (only ``store_hides_load`` matters).
        entries: DB entry (or None) per kernel instruction.
    Returns:
        indices of instructions whose hideable-load uops are hidden.
    """
    hidden: set[int] = set()
    if not model.store_hides_load:
        return hidden
    n_stores = sum(
        1 for e in entries
        if e is not None and any(u.kind == "store-agu" for u in e.uops))
    budget = n_stores
    for idx, e in enumerate(entries):
        if budget == 0:
            break
        if e is not None and any(u.hideable_load for u in e.uops):
            hidden.add(idx)
            budget -= 1
    return hidden


def analyze(kernel: list[Instruction], db: InstructionDB,
            scheduler: str = "uniform",
            unroll_factor: int = 1, *,
            latency_bound: bool = True,
            store_forward_latency: float | None = None,
            schedule_fn: Callable | None = None,
            lookup: Callable | None = None,
            edges: "list[tuple[int, int, float, bool]] | None" = None,
            ) -> AnalysisResult:
    """Predict kernel runtime as ``max(port_bound, loop-carried dep)``.

    Args:
        kernel: instructions of one assembly loop iteration (see
            :func:`repro.core.kernel.extract_kernel`).
        db: the machine to analyze on — an instruction-form database, a
            :class:`~repro.core.machine.MachineModel`, or an arch
            id/alias resolved through the default registry.
        scheduler: ``"uniform"`` (paper assumption 2) or ``"balanced"``
            (IACA-like min-max LP).
        unroll_factor: assembly-iterations per source iteration; only
            affects the ``*_per_source_iteration`` properties.
        latency_bound: when True (default) also run the critical-path /
            LCD analysis and fold it into ``predicted_cycles``; when
            False, reproduce the paper's pure throughput model.
        store_forward_latency: override for the architecture's
            store->load forwarding latency (defaults to the PortModel's).
        schedule_fn: override for ``SCHEDULERS[scheduler]`` — the batched
            :class:`repro.core.engine.AnalysisService` injects a
            memoizing wrapper around the balanced-scheduler LP here.
        lookup: override for ``db.lookup`` (memoized by the service).
        edges: precomputed :func:`repro.core.latency.dependency_edges`
            result for the LCD pass (memoized by the service); ignored
            when ``store_forward_latency`` overrides the model value.
    """
    db = as_database(db)
    model = db.model
    if schedule_fn is None:
        schedule_fn = SCHEDULERS[scheduler]
    if lookup is None:
        lookup = db.lookup

    # 1. match instruction forms
    matched: list[tuple[Instruction, object]] = []
    missing: list[MissingForm] = []
    for ins in kernel:
        entry = lookup(ins)
        if entry is None and not _is_ignorable(ins):
            missing.append(MissingForm(ins))
        matched.append((ins, entry))

    # 2. Zen AGU pairing: each store hides one load instruction's
    #    hideable AGU uops (the first loads in program order, as OSACA does)
    hidden_instrs = hidden_instruction_indices(model,
                                               [e for _, e in matched])

    # 3. flatten uops and schedule
    visible_uops: list[tuple[int, object]] = []
    hidden_uops: list[tuple[int, object]] = []
    for idx, (ins, e) in enumerate(matched):
        if e is None:
            continue
        for uop in e.uops:
            if idx in hidden_instrs and uop.hideable_load:
                hidden_uops.append((idx, uop))
            else:
                visible_uops.append((idx, uop))
    scheduled = schedule_fn(model, visible_uops)
    scheduled_hidden = SCHEDULERS["uniform"](model, hidden_uops)

    # 4. accumulate per-instruction and per-port occupation
    rows: list[InstructionReport] = []
    per_instr: dict[int, dict[str, float]] = {}
    per_instr_hidden: dict[int, dict[str, float]] = {}
    for s in scheduled:
        merge_occupation(per_instr.setdefault(s.instr_index, {}),
                         s.assignment)
    for s in scheduled_hidden:
        merge_occupation(per_instr_hidden.setdefault(s.instr_index, {}),
                         s.assignment)
    port_totals = model.zero_occupation()
    for idx, (ins, e) in enumerate(matched):
        occ = per_instr.get(idx, {})
        merge_occupation(port_totals, occ)
        rows.append(InstructionReport(
            instruction=ins, occupation=occ,
            hidden_occupation=per_instr_hidden.get(idx, {}),
            throughput=getattr(e, "throughput", None),
            latency=getattr(e, "latency", None),
            matched=e is not None))

    bottleneck = max(port_totals, key=lambda p: port_totals[p])
    port_bound = port_totals[bottleneck]

    # 5. critical-path / loop-carried-dependency bound (arXiv:1910.00214):
    #    the headline prediction is max(throughput bound, LCD).
    lat_res: LatencyResult | None = None
    lcd = 0.0
    if latency_bound:
        if store_forward_latency is not None:
            edges = None          # override invalidates injected edges
        lat_res = analyze_latency(
            kernel, db, store_forward_latency=store_forward_latency,
            lookup=lookup, edges=edges)
        lcd = lat_res.loop_carried_cycles
    combined = max(port_bound, lcd)
    binding = "latency" if lcd > port_bound + 1e-9 else "throughput"

    return AnalysisResult(
        model=model, rows=rows, port_totals=port_totals,
        bottleneck_port=bottleneck,
        predicted_cycles=combined,
        missing=missing, scheduler=scheduler, unroll_factor=unroll_factor,
        port_bound_cycles=port_bound, lcd_cycles=lcd,
        latency_result=lat_res, binding=binding)


def _is_ignorable(ins: Instruction) -> bool:
    return ins.mnemonic in ("nop", "vzeroupper", "endbr64", "ret", "leave")
