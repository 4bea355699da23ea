"""Batched multi-architecture analysis service (the unified prediction
engine).

One :class:`AnalysisService` owns every per-architecture instruction
database and serves *batches* of kernels x architectures x schedulers
through a single memoized pipeline:

* **DB construction** — architectures resolve through an
  :class:`~repro.core.arch.registry.ArchRegistry` (a private child of
  the process-wide registry, so runtime ``register()`` calls stay
  service-local); each database is built once per registry layer and
  shared across the batch.
* **Form lookups** — ``db.lookup`` results are cached per
  ``(arch, mnemonic, signature)``; a sweep re-resolving the same triad
  kernel on three schedulers pays for the progressive-generalisation
  walk only once.
* **Balanced-scheduler LP solves** — ``schedule_balanced`` is an exact
  min-max flow LP; its result depends only on the (ordered) uop spec, so
  identical kernels across the batch reuse the solve.
* **Whole results** — ``predict()`` itself is memoized on
  ``(arch, kernel, scheduler, unroll, latency_bound)``; ``render()``
  variations, table generators and tests all hit the same entry.
* **HLO analyses** — ``predict_hlo`` caches by module-text digest, so the
  serving dry-run and the roofline benchmark share one pass per program.

Entry points: :meth:`AnalysisService.predict` (one request),
:meth:`~AnalysisService.predict_batch` (many, optionally threaded),
:meth:`~AnalysisService.predict_async` (awaitable), and
:meth:`~AnalysisService.sweep` (full kernels x archs x schedulers grid).

Every analytic prediction is the *combined* bound ``max(port_bound,
LCD)`` from :func:`repro.core.analysis.analyze`; ``mode="simulate"``
requests additionally run the cycle-level pipeline simulator
(``repro.core.sim``) and report its steady state as ``bound_sim`` —
see docs/prediction-model.md and docs/simulation.md.
"""
from __future__ import annotations

import asyncio
import hashlib
import threading
import warnings
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Iterable, Mapping, Sequence

from .analysis import AnalysisResult, analyze
from .arch.registry import ArchRegistry, UnknownArchError, default_registry
from .database import InstructionDB
from .degrade import (BreakerBoard, BreakerConfig, HealthRouter,
                      contained_faults, ladder_from, validate_sims)
from .faults import (FaultAbort, FaultInjector, FaultPlan, InjectedFault,
                     ResultValidationError)
from .isa import Instruction
from .kernel import extract_kernel
from .machine import MachineModel
from .ports import PortModel, Uop
from .scheduler import SCHEDULERS, ScheduledUop


@dataclass(frozen=True)
class AnalysisRequest:
    """One cell of a batch: a kernel analyzed on one architecture.

    Attributes:
        kernel: assembly source text (markers/loop detection handled by
            :func:`repro.core.kernel.extract_kernel`) or an already-parsed
            tuple of :class:`~repro.core.isa.Instruction`.
        arch: architecture id or alias resolved through the service's
            :class:`~repro.core.arch.registry.ArchRegistry`
            (``"skl"``/``"skylake"``, ``"zen"``/``"zen1"``/``"znver1"``,
            any shipped ``arch/models/*.json`` id, or a model registered
            via :meth:`AnalysisService.register`).
        scheduler: ``"uniform"`` or ``"balanced"``.
        unroll_factor: assembly iterations per source iteration.
        latency_bound: fold the LCD bound into the prediction (default).
        syntax: ``"att"`` or ``"intel"`` when ``kernel`` is text.
        mode: ``"analytic"`` (the combined ``max(port_bound, LCD)``
            bound, default) or ``"simulate"`` (additionally run the
            cycle-level pipeline simulator, ``repro.core.sim`` — the
            result then carries ``bound_sim``/``sim_result``, and
            ``predicted_cycles`` is the simulated steady state floored
            at the LCD bound).
        working_set: total bytes the kernel streams over per repetition
            of its outer loop.  ``None`` (default) keeps the paper's
            infinite-L1 assumption.  A size, on an arch whose
            :class:`~repro.core.machine.MachineModel` carries a
            ``hierarchy`` block, composes the in-core bound with
            per-level cache/memory transfer terms into an ECM
            prediction (``AnalysisResult.bound_ecm`` /
            ``ecm_result``, see docs/ecm.md); on a hierarchy-less
            model the request behaves exactly like ``None``.
        traffic_model: ``"analytic"`` (streaming/layer-condition miss
            model, default) or ``"cachesim"`` (LRU set-associative
            cache simulation of the access streams).
    """

    kernel: str | tuple[Instruction, ...]
    arch: str = "skl"
    scheduler: str = "uniform"
    unroll_factor: int = 1
    latency_bound: bool = True
    syntax: str = "att"
    mode: str = "analytic"
    working_set: float | None = None
    traffic_model: str = "analytic"


@dataclass
class ServiceStats:
    """Cache-effectiveness counters for one :class:`AnalysisService`."""

    result_hits: int = 0
    result_misses: int = 0
    lookup_hits: int = 0
    lookup_misses: int = 0
    lp_hits: int = 0
    lp_misses: int = 0
    hlo_hits: int = 0
    hlo_misses: int = 0
    sim_runs: int = 0        # cycle-level simulations actually executed
    #                          (cache hits are counted in result_hits)
    edge_hits: int = 0       # memoized latency.dependency_edges
    edge_misses: int = 0
    program_hits: int = 0    # memoized sim.compile_program
    program_misses: int = 0
    classify_hits: int = 0   # memoized sim.pipeline._classify
    classify_misses: int = 0
    machine_hits: int = 0    # memoized machine-model resolution
    machine_misses: int = 0
    sim_group_dispatches: int = 0   # compiled batch dispatches issued by
    #                                 the sweep planner (one per
    #                                 machine-model group)
    traffic_hits: int = 0    # memoized ECM traffic predictions
    traffic_misses: int = 0
    degraded_results: int = 0   # results answered below the requested
    #                             backend (docs/robustness.md)
    journal_hits: int = 0    # machine groups replayed from a sweep
    #                          journal (zero re-dispatch on resume)
    journal_records: int = 0    # live records in the last journal used
    journal_segments: int = 0   # sealed segments in that journal
    journal_bytes: int = 0      # its on-disk footprint (bytes)
    rung_attempts: dict = field(default_factory=dict)
    #                          dispatch attempts actually paid per
    #                          ladder rung (a breaker-skipped or
    #                          router-skipped rung never counts here —
    #                          the routing-probe gate in service_bench)
    routed_groups: int = 0   # dispatch groups the HealthRouter started
    #                          below the requested rung
    probe_dispatches: int = 0   # scheduled half-open probe dispatches

    def as_dict(self) -> dict[str, int]:
        d = dict(vars(self))
        d["rung_attempts"] = dict(self.rung_attempts)
        return d

    def hit_rate(self, kind: str) -> float:
        """Hit rate in [0, 1] for one counter pair (``"result"``,
        ``"lookup"``, ``"lp"``, ``"hlo"``, ``"edge"``, ``"program"``,
        ``"classify"``, ``"machine"`` or ``"traffic"``); 0.0 when
        never exercised."""
        hits = getattr(self, f"{kind}_hits")
        misses = getattr(self, f"{kind}_misses")
        total = hits + misses
        return hits / total if total else 0.0


class AnalysisService:
    """Memoizing, thread-safe front end over the prediction pipeline.

    A single instance can be shared by benchmarks, examples, the HLO
    analyzer and the serving engine; all of them then draw from the same
    database/lookup/LP/result caches.  All public methods are safe to
    call from multiple threads (``predict_batch(parallel=True)`` does).
    """

    def __init__(self, max_workers: int = 8,
                 registry: ArchRegistry | None = None,
                 sim_backend: str = "auto",
                 faults: "FaultPlan | FaultInjector | None" = None,
                 breaker_config: BreakerConfig | None = None,
                 router: HealthRouter | None = None):
        self._lock = threading.RLock()
        # a private child of the (shared) registry: this service's
        # register() calls shadow the parent without leaking into other
        # services, while built-in model/DB caches stay shared
        self._arch = ArchRegistry(parent=registry or default_registry())
        self._lookups: dict[str, Callable[[Instruction], object]] = {}
        self._lp_cache: dict[tuple, list[ScheduledUop]] = {}
        self._results: dict[tuple, AnalysisResult] = {}
        self._sim_cache: dict[tuple, object] = {}   # SimResult by kernel
        self._hlo_cache: dict[tuple, object] = {}
        self._edge_cache: dict[tuple, tuple] = {}   # dependency edges
        self._program_cache: dict[tuple, object] = {}   # SimProgram
        self._classify_cache: dict[tuple, str] = {}
        self._machine_cache: dict[str, MachineModel] = {}
        self._traffic_cache: dict[tuple, tuple] = {}    # ECM traffic
        self._max_workers = max_workers
        #: batch-simulation driver for sweeps: "auto" | "numpy" | "jit"
        #: | "pallas" (see repro.core.sim.batch and docs/performance.md)
        self.sim_backend = sim_backend
        self.stats = ServiceStats()
        #: armed fault injector (None = disarmed: every hook is a single
        #: `is not None` test, so the no-plan instruction stream — and
        #: therefore the golden tables — is bit-identical to before the
        #: fault layer existed; docs/robustness.md)
        self.faults: FaultInjector | None = None
        if isinstance(faults, FaultPlan):
            self.faults = FaultInjector(faults)
        elif faults is not None:
            self.faults = faults
        #: per-(machine digest x backend) circuit breakers driving the
        #: degradation ladder pallas -> jit -> numpy -> analytic-only
        self.breakers = BreakerBoard(breaker_config)
        #: breaker-aware routing policy (None = reactive-only PR 9
        #: behavior, bit-identical: the ladder still demotes on
        #: failure but never skips a rung pre-dispatch)
        self.router = router
        # provenance of every simulated cell: sim_key -> (rung that
        # answered, degraded, fault event id, routed_from, probe)
        self._sim_provenance: dict[tuple, tuple[str, bool, int, str,
                                                bool]] = {}
        # registry epoch at the last cache fill: a replacing
        # registration anywhere in the layer chain bumps it, and
        # _check_epoch() then drops every arch-keyed cache
        self._arch_epoch = self._arch.epoch

    # ------------------------------------------------------------------
    # architectures
    # ------------------------------------------------------------------
    @property
    def registry(self) -> ArchRegistry:
        """This service's architecture registry (a private child of the
        process-wide :func:`repro.core.arch.registry.default_registry`)."""
        return self._arch

    def register(self, model: MachineModel, *,
                 aliases: Sequence[str] | None = None,
                 replace: bool = True) -> str:
        """Register a :class:`MachineModel` with this service.

        The model's id (and aliases) become valid ``AnalysisRequest.arch``
        values for this service only.  Re-registering an id — including
        shadowing a built-in like ``"skl"`` — drops every cached lookup
        and result for it, so subsequent predictions use the new model.
        An ``arch_id`` that is an *alias spelling* of an existing id
        (``"skylake"``) shadows the canonical id (``"skl"``) rather than
        splitting the alias from it.  Returns the canonical id.
        """
        try:
            canonical = self._arch.resolve(model.arch_id)
        except UnknownArchError:
            canonical = model.arch_id
        if canonical != model.arch_id:
            model = model.derive(canonical, aliases=model.aliases)
        key = self._arch.register(model, aliases=aliases, replace=replace)
        self._invalidate_arch(key)
        return key

    def register_db(self, name: str, db: InstructionDB) -> None:
        """Deprecated: wrap ``db`` in a :class:`MachineModel` and call
        :meth:`register` instead.  This shim does exactly that (via
        :meth:`MachineModel.from_db`) and keeps the old semantics:
        re-registering a name (or an alias spelling of it) shadows the
        built-in and drops its cached results."""
        warnings.warn(
            "AnalysisService.register_db is deprecated; use "
            "register(MachineModel.from_db(...)) or register a "
            "MachineModel directly", DeprecationWarning, stacklevel=2)
        try:
            key = self._arch.resolve(name)
        except UnknownArchError:
            key = name.lower()
        self.register(MachineModel.from_db(key, db))
        # keep the caller's exact database object (old register_db
        # semantics), not a rebuild from the extracted form table
        self._arch.prime_database(key, db)

    def _invalidate_arch(self, key: str) -> None:
        with self._lock:
            self._lookups.pop(key, None)
            # alias spellings may map to the re-registered id, so the
            # (cheap to refill) resolution cache is dropped wholesale
            self._machine_cache.clear()
            for k in [k for k in self._results if k[0] == key]:
                del self._results[k]
            for k in [k for k in self._sim_cache if k[0] == key]:
                del self._sim_cache[k]
            for k in [k for k in self._sim_provenance if k[0] == key]:
                del self._sim_provenance[k]
            # edge/program/classify caches are keyed by machine *digest*
            # (content addresses), so entries for a replaced model can
            # never be served for the new one — no invalidation needed

    def _check_epoch(self) -> None:
        """Drop arch-keyed caches if any registry layer re-registered a
        model since the last fill.

        Runs at every public prediction entry; the common case is one
        integer compare.  Digest-keyed caches (edges, programs, traffic)
        survive — a superseded model's digest can never be resolved
        again, so those entries are unreachable rather than stale."""
        ep = self._arch.epoch
        if ep == self._arch_epoch:
            return
        with self._lock:
            if ep == self._arch_epoch:
                return
            self._arch_epoch = ep
            self._lookups.clear()
            self._machine_cache.clear()
            self._results.clear()
            self._sim_cache.clear()
            self._sim_provenance.clear()
            self._hlo_cache.clear()

    def database(self, arch: str) -> InstructionDB:
        """The (registry-cached) instruction DB for ``arch``, built on
        first use."""
        return self._arch.database(arch)

    def resolve_machine(self, machine: "str | MachineModel",
                        ) -> MachineModel:
        """Memoized machine-model resolution (id/alias →
        :class:`MachineModel`).

        ``predict_hlo``, the sweep planner and
        ``ServingEngine.dryrun_estimate`` all route through this, so a
        sweep resolves each model once instead of per call; hit/miss
        counts land in ``stats.machine_hits`` / ``machine_misses``.
        """
        if isinstance(machine, MachineModel):
            return machine
        with self._lock:
            hit = self._machine_cache.get(machine)
            if hit is not None:
                self.stats.machine_hits += 1
                return hit
            self.stats.machine_misses += 1
        model = self._arch.model(machine)
        with self._lock:
            self._machine_cache[machine] = model
        return model

    def _lookup_fn(self, arch: str) -> Callable[[Instruction], object]:
        """Memoized ``db.lookup`` keyed by (mnemonic, signature)."""
        key = self._arch.resolve(arch)
        with self._lock:
            fn = self._lookups.get(key)
            if fn is not None:
                return fn
            db = self.database(key)
            cache: dict[tuple, object] = {}

            def lookup(ins: Instruction):
                k = (ins.mnemonic, ins.signature)
                with self._lock:
                    if k in cache:
                        self.stats.lookup_hits += 1
                        return cache[k]
                    self.stats.lookup_misses += 1
                entry = db.lookup(ins)
                with self._lock:
                    cache[k] = entry
                return entry

            self._lookups[key] = lookup
            return lookup

    # ------------------------------------------------------------------
    # balanced-scheduler LP memoization
    # ------------------------------------------------------------------
    def _schedule_fn(self, model: PortModel, scheduler: str) -> Callable:
        base = SCHEDULERS[scheduler]
        if scheduler != "balanced":
            return base  # uniform is O(n); caching would only add overhead

        def cached(model_: PortModel,
                   uops: list[tuple[int, Uop]]) -> list[ScheduledUop]:
            # the LP solution is a deterministic function of the port
            # list + uop spec, so keying on both stays correct even when
            # two registered databases share a model name
            key = (model_.ports,
                   tuple((idx, u.ports, u.cycles) for idx, u in uops))
            with self._lock:
                hit = self._lp_cache.get(key)
                if hit is not None:
                    self.stats.lp_hits += 1
                    return hit
                self.stats.lp_misses += 1
            out = base(model_, uops)
            with self._lock:
                self._lp_cache[key] = out
            return out

        return cached

    # ------------------------------------------------------------------
    # memoized per-uop preprocessing (shared by the single-request path
    # and the sweep planner; keys are (machine digest, kernel id) /
    # (machine digest, program digest) content addresses)
    # ------------------------------------------------------------------
    def dependency_edges(self, kernel: "str | tuple[Instruction, ...]",
                         arch: str = "skl", syntax: str = "att",
                         ) -> tuple[tuple[int, int, float, bool], ...]:
        """Memoized :func:`repro.core.latency.dependency_edges`.

        The edge list depends only on the kernel text and the machine
        model, so sweeps re-analyzing one kernel across schedulers,
        unrolls or modes pay for the read/write scan once;
        ``stats.edge_hits`` / ``edge_misses`` track effectiveness.
        """
        machine = self.resolve_machine(arch)
        req = AnalysisRequest(kernel=kernel, arch=arch, syntax=syntax)
        key = (machine.digest, self._kernel_id(req))
        with self._lock:
            hit = self._edge_cache.get(key)
            if hit is not None:
                self.stats.edge_hits += 1
                return hit
            self.stats.edge_misses += 1
        from .latency import dependency_edges as _edges
        out = tuple(_edges(list(self._kernel_of(req)),
                           self.database(arch),
                           lookup=self._lookup_fn(arch)))
        with self._lock:
            self._edge_cache[key] = out
        return out

    def _sim_program(self, request: AnalysisRequest):
        """Memoized ``sim.compile_program`` for one request, built on
        the memoized dependency edges."""
        machine = self.resolve_machine(request.arch)
        key = (machine.digest, self._kernel_id(request))
        with self._lock:
            hit = self._program_cache.get(key)
            if hit is not None:
                self.stats.program_hits += 1
                return hit
            self.stats.program_misses += 1
        if self.faults is not None:
            # armed compile faults hit real compilation work only —
            # a program-cache hit above never fires
            self.faults.fire("engine.compile", machine=machine.digest)
        from .sim import compile_program
        edges = self.dependency_edges(request.kernel, request.arch,
                                      request.syntax)
        prog = compile_program(
            list(self._kernel_of(request)), self.database(request.arch),
            lookup=self._lookup_fn(request.arch), edges=edges)
        with self._lock:
            self._program_cache[key] = prog
        return prog

    def _classify_memo(self, cpi: float, frontend: float,
                       port_bound: float, delivery: float = 0.0,
                       fe_mode: str = "ideal") -> str:
        """Memoized ``sim.pipeline._classify``: the bottleneck label is
        a pure function of (steady state, front-end bounds, port
        bound), so identical programs re-simulated across sweep
        dispatches reuse the verdict; the planner passes this as the
        batch driver's ``classify`` hook."""
        from .sim.pipeline import _classify

        key = (cpi, frontend, port_bound, delivery, fe_mode)
        with self._lock:
            hit = self._classify_cache.get(key)
            if hit is not None:
                self.stats.classify_hits += 1
                return hit
            self.stats.classify_misses += 1
        label = _classify(cpi, frontend, port_bound, delivery, fe_mode)
        with self._lock:
            self._classify_cache[key] = label
        return label

    # ------------------------------------------------------------------
    # prediction entry points
    # ------------------------------------------------------------------
    def _kernel_of(self, req: AnalysisRequest) -> tuple[Instruction, ...]:
        if isinstance(req.kernel, str):
            return tuple(extract_kernel(req.kernel, syntax=req.syntax))
        return tuple(req.kernel)

    @staticmethod
    def _kernel_id(req: AnalysisRequest) -> tuple:
        if isinstance(req.kernel, str):
            # raw source keys by (text, syntax): the same bytes parse
            # differently under AT&T vs Intel, and keying pre-parse also
            # skips extract_kernel entirely on a hit
            return ("src", req.kernel, req.syntax)
        # Instruction is a frozen dataclass: hashing the instances
        # themselves keys on the full parse (operand order included),
        # not just the source text, so e.g. the same reg-reg move
        # parsed under AT&T vs Intel order cannot collide
        return ("parsed", tuple(req.kernel))

    def predict(self, request: AnalysisRequest) -> AnalysisResult:
        """Run the prediction pipeline for one request, drawing every
        sub-step from the service caches.

        ``mode="analytic"``: the combined ``max(port_bound, LCD)``
        bound.  ``mode="simulate"``: the analytic pass (cached and
        shared with analytic requests) plus the cycle-level pipeline
        simulation; the returned result carries ``bound_sim`` and a
        three-way ``binding``.
        """
        self._check_epoch()
        key = self._result_key(request)
        with self._lock:
            hit = self._results.get(key)
            if hit is not None:
                self.stats.result_hits += 1
                return hit
            self.stats.result_misses += 1
        if request.mode == "simulate":
            res = self._predict_simulated(request)
        else:
            res = self._compute_analytic(request)
        res = self._apply_ecm(res, request)
        with self._lock:
            self._results[key] = res
        return res

    def request_key(self, request: AnalysisRequest) -> tuple:
        """Public content-address of one request.

        Like the internal result key but keyed by the *machine digest*
        instead of the arch id, so it stays valid across registries and
        can be shared by out-of-process caches
        (``repro.service.PredictionService`` keys its cross-request
        TTL cache on this).
        """
        machine = self.resolve_machine(request.arch)
        key = self._result_key(request)
        return (machine.digest,) + key[1:]

    def _result_key(self, request: AnalysisRequest) -> tuple:
        if request.mode not in ("analytic", "simulate"):
            raise ValueError(f"unknown mode {request.mode!r} "
                             "(expected 'analytic' or 'simulate')")
        if request.traffic_model not in ("analytic", "cachesim"):
            raise ValueError(f"unknown traffic_model "
                             f"{request.traffic_model!r} "
                             "(expected 'analytic' or 'cachesim')")
        if request.working_set is not None and request.working_set <= 0:
            raise ValueError("working_set must be positive (bytes) or "
                             "None")
        return (self._arch.resolve(request.arch),
                self._kernel_id(request), request.scheduler,
                request.unroll_factor, request.latency_bound,
                request.mode, request.working_set, request.traffic_model)

    def _compute_analytic(self, request: AnalysisRequest
                          ) -> AnalysisResult:
        """The uncached analytic pipeline for one request (all
        sub-steps still draw from the service caches)."""
        kernel = self._kernel_of(request)
        db = self.database(request.arch)
        edges = None
        if request.latency_bound:
            edges = list(self.dependency_edges(
                request.kernel, request.arch, request.syntax))
        return analyze(
            list(kernel), db, scheduler=request.scheduler,
            unroll_factor=request.unroll_factor,
            latency_bound=request.latency_bound,
            schedule_fn=self._schedule_fn(db.model, request.scheduler),
            lookup=self._lookup_fn(request.arch), edges=edges)

    def _predict_simulated(self, request: AnalysisRequest
                           ) -> AnalysisResult:
        """The ``mode="simulate"`` pipeline: analytic result (served
        from / stored in the shared cache) refined by the cycle-level
        simulator.

        The tick-loop driver is its own single-rung ladder: a fault in
        compile or simulation (:func:`~.degrade.contained_faults`) is
        contained and the cell degrades to the analytic floor with
        ``degraded`` provenance rather than failing the request; any
        other exception is a bug and propagates — the analytic and
        simulated predictors are redundant estimates of the same
        quantity (docs/robustness.md)."""
        import dataclasses

        from .sim import simulate

        analytic = self.predict(
            dataclasses.replace(request, mode="analytic"))
        # the simulation depends only on (arch, kernel) — not on the
        # scheduler / unroll / latency_bound knobs of the analytic pass —
        # so it is cached on its own key and shared across e.g. a
        # multi-scheduler sweep.  Like the result cache, there is no
        # in-flight deduplication: identical cold-cache cells submitted
        # concurrently may each simulate (correctly) — see predict_batch.
        sim_key = (self._arch.resolve(request.arch),
                   self._kernel_id(request))
        with self._lock:
            sim = self._sim_cache.get(sim_key)
        if sim is None:
            machine = self.resolve_machine(request.arch)
            breaker = self.breakers.breaker(machine.digest, "tick")
            probe = False
            if self.router is not None:
                # tick is its own single-rung ladder: an unhealthy rung
                # routes straight to the analytic floor with no dispatch
                route = self.router.plan(self.breakers, machine.digest,
                                         ("tick",))
                probe = route.probe
                if not route.rungs:
                    with self._lock:
                        self.stats.degraded_results += 1
                    return self._analytic_floor(analytic, 0)
                if probe:
                    with self._lock:
                        self.stats.probe_dispatches += 1
            event_id = 0
            try:
                prog = self._sim_program(request)
                if not breaker.allow():
                    raise ResultValidationError(
                        "tick-rung breaker open for "
                        f"{machine.digest[:12]}")
                if self.faults is not None:
                    self.faults.fire("engine.dispatch", backend="tick",
                                     machine=machine.digest)
                with self._lock:
                    self.stats.sim_runs += 1
                    self.stats.rung_attempts["tick"] = \
                        self.stats.rung_attempts.get("tick", 0) + 1
                sim = simulate(prog)
                if self.faults is not None:
                    cpi, ev = self.faults.corrupt(
                        "engine.dispatch", sim.cycles_per_iteration,
                        backend="tick", machine=machine.digest)
                    if ev:
                        sim = dataclasses.replace(
                            sim, cycles_per_iteration=cpi)
                problems = validate_sims([sim], [prog])
                if problems:
                    raise ResultValidationError("; ".join(problems))
                breaker.record_success()
                with self._lock:
                    self._sim_cache[sim_key] = sim
                    self._sim_provenance[sim_key] = (
                        "tick", False, 0, "", probe)
            except FaultAbort:
                raise               # simulated process kill: never contained
            except contained_faults() as exc:
                breaker.record_failure()
                event_id = getattr(exc, "event_id", 0)
                with self._lock:
                    self.stats.degraded_results += 1
                return self._analytic_floor(analytic, event_id)
        res = self._combine_sim(analytic, sim)
        with self._lock:
            prov = self._sim_provenance.get(sim_key)
        return res if prov is None else self._with_provenance(res, prov)

    @staticmethod
    def _with_provenance(res: AnalysisResult, prov: tuple
                         ) -> AnalysisResult:
        """Stamp a combined ``mode="simulate"`` result with the rung
        that answered it and, when it was degraded, routed or a probe,
        with that provenance too."""
        import dataclasses

        backend_used, degraded, event_id, routed_from, probe = prov
        res = dataclasses.replace(res, backend_used=backend_used)
        if degraded:
            res = dataclasses.replace(res, degraded=True,
                                      fault_trace_id=event_id)
        if routed_from or probe:
            res = dataclasses.replace(res, routed_from=routed_from,
                                      probe=probe)
        return res

    @staticmethod
    def _analytic_floor(analytic: AnalysisResult,
                        event_id: int) -> AnalysisResult:
        """The bottom ladder rung: answer a ``mode="simulate"`` request
        with its (already computed) analytic base, flagged ``degraded``.

        Any ECM composition the base carries is stripped the same way
        :meth:`_combine_sim` does — ``predict``/``predict_batch``
        re-apply it afterwards, so the floor result equals the plain
        analytic prediction bit-for-bit."""
        import dataclasses

        if analytic.ecm_result is None:
            return dataclasses.replace(
                analytic, degraded=True, backend_used="analytic",
                fault_trace_id=event_id)
        # same binding rule as analyze(): the pre-ECM label
        binding = ("latency" if analytic.lcd_cycles
                   > analytic.port_bound_cycles + 1e-9 else "throughput")
        return dataclasses.replace(
            analytic,
            predicted_cycles=max(analytic.port_bound_cycles,
                                 analytic.lcd_cycles),
            binding=binding, bound_ecm=0.0, ecm_result=None,
            degraded=True, backend_used="analytic",
            fault_trace_id=event_id)

    def _run_ladder(self, digest: str, progs: list, start: str,
                    small: bool) -> tuple:
        """Dispatch one machine group down the degradation ladder.

        Walks the sim rungs from ``start`` (``("tick",)`` for the
        small-batch reference loop), skipping rungs whose circuit
        breaker is open, validating every rung's output, and demoting
        on any contained fault (:func:`~.degrade.contained_faults`).
        When a :class:`HealthRouter` is
        installed it is consulted *before* the walk: rungs with an
        open breaker are dropped without paying a dispatch and at
        most one scheduled probe per cooldown window reaches a rung
        that is due one.  Returns ``(sims | None, backend_used,
        degraded, dispatches, fault event id, routed_from, probe)`` —
        ``sims is None`` means every rung failed and the group takes
        the analytic floor.  :class:`FaultAbort` (a simulated process
        kill) and every exception that is not a contained fault (a bug
        or a bad request) propagate."""
        import dataclasses

        from .sim import simulate, simulate_many

        rungs = ("tick",) if small else ladder_from(start)
        routed_from, probe = "", False
        if self.router is not None:
            route = self.router.plan(self.breakers, digest, rungs)
            rungs = route.rungs
            routed_from, probe = route.routed_from, route.probe
            with self._lock:
                if routed_from:
                    self.stats.routed_groups += 1
                if probe:
                    self.stats.probe_dispatches += 1
        # a dispatch answered below the rung the caller asked for is
        # degraded provenance, whether the skip happened reactively
        # (breaker.allow() refused) or proactively (router)
        demoted = bool(routed_from)
        event_id = 0
        for rung in rungs:
            # only the first routed rung can be the scheduled probe; if
            # it does not answer, whatever answers below is not one
            if rung != rungs[0]:
                probe = False
            breaker = self.breakers.breaker(digest, rung)
            if not breaker.allow():
                demoted = True
                continue
            with self._lock:
                self.stats.rung_attempts[rung] = \
                    self.stats.rung_attempts.get(rung, 0) + 1
            try:
                if self.faults is not None:
                    self.faults.fire("engine.dispatch", backend=rung,
                                     machine=digest)
                counters = {"dispatches": 0}
                if rung == "tick":
                    sims = [simulate(p) for p in progs]
                else:
                    sims = simulate_many(progs, backend=rung,
                                         classify=self._classify_memo,
                                         counters=counters)
                if self.faults is not None:
                    poisoned = []
                    for sim in sims:
                        cpi, ev = self.faults.corrupt(
                            "engine.dispatch", sim.cycles_per_iteration,
                            backend=rung, machine=digest)
                        if ev:
                            event_id = ev
                            sim = dataclasses.replace(
                                sim, cycles_per_iteration=cpi)
                        poisoned.append(sim)
                    sims = poisoned
                problems = validate_sims(sims, progs)
                if problems:
                    raise ResultValidationError("; ".join(problems))
                breaker.record_success()
                return (sims, rung, demoted, counters["dispatches"],
                        event_id, routed_from, probe)
            except FaultAbort:
                raise
            except contained_faults() as exc:
                breaker.record_failure()
                event_id = getattr(exc, "event_id", event_id)
                demoted = True
                continue
        # the floor answered: nothing dispatched, so no probe either
        return None, "analytic", True, 0, event_id, routed_from, False

    @staticmethod
    def _journal_lookup(session: dict | None, digest: str,
                        progs: list) -> tuple | None:
        """Replay one machine group from a sweep-journal session
        (``sweep(resume_from=...)``); None when the group is not
        journaled.  Returns ``(sims | None, backend_used, degraded,
        event id)`` — the same shape the ladder produces, so a resumed
        sweep is bit-identical with zero re-dispatch."""
        if session is None or not session.get("resume"):
            return None
        record = session["resume"].get(
            (digest, tuple(p.digest for p in progs)))
        if record is None:
            return None
        from .journal import sim_from_record
        from .sim.pipeline import DEFAULT_PARAMS
        if record["sims"] is None:
            sims = None
        else:
            sims = [sim_from_record(sr, p.model.pipeline or DEFAULT_PARAMS)
                    for sr, p in zip(record["sims"], progs)]
        return sims, record["backend_used"], record["degraded"], 0

    @staticmethod
    def _journal_record(session: dict | None, digest: str, progs: list,
                        sims, backend_used: str, degraded: bool) -> None:
        if session is None or session.get("writer") is None:
            return
        session["writer"].record_group(
            session["plan"], digest, [p.digest for p in progs],
            sims, backend_used, degraded)

    @staticmethod
    def _combine_sim(analytic: AnalysisResult, sim) -> AnalysisResult:
        """Fold a cycle-level simulation into an analytic result (the
        ``mode="simulate"`` combination rule, shared by the single
        path and the sweep planner)."""
        import dataclasses

        bound_sim = sim.cycles_per_iteration
        analytic_bound = max(analytic.port_bound_cycles,
                             analytic.lcd_cycles)
        predicted = max(bound_sim, analytic.lcd_cycles)
        # three-way binding: "simulation" whenever the simulated steady
        # state materially deviates from the analytic bound — above it
        # (front-end / finite-window effects) or below it (discrete
        # dispatch beating the uniform averaging, paper Sec. III-B);
        # otherwise the analytic label still names the constraint that
        # produces the headline
        if abs(bound_sim - analytic_bound) > analytic_bound * 0.02 + 1e-9:
            binding = "simulation"
        else:
            binding = analytic.binding
        # the analytic base may itself carry an ECM composition (its
        # cache key includes working_set); the combined result is a pure
        # in-core bound again — predict()/predict_batch re-apply ECM on
        # top of the simulated bound afterwards
        return dataclasses.replace(
            analytic, bound_sim=bound_sim, sim_result=sim,
            predicted_cycles=predicted, binding=binding,
            bound_ecm=0.0, ecm_result=None)

    # ------------------------------------------------------------------
    # ECM memory-hierarchy composition (working_set= requests)
    # ------------------------------------------------------------------
    def _traffic(self, request: AnalysisRequest, machine: MachineModel):
        """Memoized per-level traffic + T_nOL for one (machine, kernel,
        working_set, traffic_model) — the sim cache's sibling: its key
        excludes scheduler/unroll/mode, so an ECM sweep across those
        knobs predicts traffic once per working set."""
        key = (machine.digest, self._kernel_id(request),
               float(request.working_set), request.traffic_model)
        with self._lock:
            hit = self._traffic_cache.get(key)
            if hit is not None:
                self.stats.traffic_hits += 1
                return hit
            self.stats.traffic_misses += 1
        if self.faults is not None:
            self.faults.fire("engine.traffic", machine=machine.digest,
                             traffic_model=request.traffic_model)
        from .mem import (extract_streams, memory_port_occupation,
                          predict_traffic, simulate_traffic)
        kernel = self._kernel_of(request)
        streams = extract_streams(kernel)
        estimator = simulate_traffic if request.traffic_model == \
            "cachesim" else predict_traffic
        traffic = estimator(streams, machine.hierarchy,
                            float(request.working_set))
        lookup = self._lookup_fn(request.arch)
        entries = [lookup(ins) for ins in kernel]
        t_nol = memory_port_occupation(
            self.database(request.arch).model, entries)
        out = (traffic, t_nol)
        with self._lock:
            self._traffic_cache[key] = out
        return out

    def _apply_ecm(self, res: AnalysisResult,
                   request: AnalysisRequest) -> AnalysisResult:
        """Compose the in-core result with the memory-hierarchy terms.

        No-op when the request has no ``working_set`` or the machine
        has no ``hierarchy`` block — the existing bounds pass through
        bit-exactly (the documented compatibility guarantee).
        """
        if request.working_set is None:
            return res
        machine = self.resolve_machine(request.arch)
        if machine.hierarchy is None:
            return res
        import dataclasses

        from .mem import compose_ecm

        try:
            traffic, t_nol = self._traffic(request, machine)
        except FaultAbort:
            raise
        except InjectedFault as exc:
            # contained: the in-core bound stands, flagged degraded —
            # the memory-hierarchy terms are a refinement, not a
            # prerequisite (docs/robustness.md)
            with self._lock:
                self.stats.degraded_results += 1
            return dataclasses.replace(
                res, degraded=True,
                backend_used=res.backend_used or "incore",
                fault_trace_id=exc.event_id)
        # T_nOL is by definition part of the in-core time: the uniform
        # split of the memory uops alone can exceed the balanced overall
        # bottleneck on asymmetric port sets, so clamp — this also makes
        # working_set <= L1 reproduce the in-core bound bit-exactly.
        if res.port_bound_cycles > 0:
            t_nol = min(t_nol, res.port_bound_cycles)
        ecm = compose_ecm(t_incore=res.predicted_cycles, t_nol=t_nol,
                          traffic=traffic)
        binding = "memory" if ecm.cycles > res.predicted_cycles + 1e-9 \
            else res.binding
        return dataclasses.replace(
            res, bound_ecm=ecm.cycles, ecm_result=ecm,
            predicted_cycles=ecm.cycles, binding=binding)

    def predict_batch(self, requests: Sequence[AnalysisRequest],
                      parallel: bool = False,
                      backend: str | None = None,
                      _journal: dict | None = None) -> list[AnalysisResult]:
        """Predict every request; order of results matches the input.

        Batches run through a three-stage planner instead of a
        loop-over-requests:

        1. **plan** — every request resolves to its result-cache key;
           duplicates collapse to one cell, cached cells are served
           immediately.
        2. **analytic pass** — the unique analytic cells (including the
           analytic base of every ``mode="simulate"`` cell) compute
           once each, drawing parses/lookups/LP solves from the
           memoized sub-steps (``parallel=True`` spreads them over a
           thread pool).
        3. **grouped simulation** — the ``mode="simulate"`` cells that
           miss the simulation cache compile to :class:`SimProgram`\\ s
           (memoized by (machine digest, kernel)) and dispatch as *one*
           vectorized :func:`repro.core.sim.simulate_many` call per
           machine-model group (``stats.sim_group_dispatches``), on
           ``backend`` (default: the service's ``sim_backend``;
           ``"auto"`` compiles with ``jax.jit`` for large groups, see
           docs/performance.md).  A 1k-point sweep is a handful of
           compiled dispatches, not 1k tick-loop runs.

        The batch path and the single-request :meth:`predict` share all
        caches; for ``mode="simulate"`` they run different drivers of
        the same machine (vectorized dataflow recurrence vs reference
        tick loop), so whichever computes a cell first fills the cache
        for both (the drivers' agreement on the paper kernels is locked
        by ``tests/test_simulator.py`` / ``tests/test_sweep_engine.py``).

        Each machine group's dispatch walks the degradation ladder
        (requested rung, then every cheaper one whose circuit breaker
        admits it, then the analytic floor) — see docs/robustness.md;
        ``_journal`` is the private sweep-journal session plumbed
        through :meth:`sweep` for crash-safe resume.
        """
        self._check_epoch()
        if len(requests) <= 1:
            return [self.predict(r) for r in requests]

        # ---- plan: dedupe on result keys -----------------------------
        keys = [self._result_key(r) for r in requests]
        unique: dict[tuple, AnalysisRequest] = {}
        for key, req in zip(keys, requests):
            unique.setdefault(key, req)
        with self._lock:
            done = {k: self._results[k] for k in unique
                    if k in self._results}
        todo = {k: r for k, r in unique.items() if k not in done}
        with self._lock:
            self.stats.result_hits += len(requests) - len(todo)

        # ---- analytic pass (also the base of every simulate cell) ----
        analytic_reqs: dict[tuple, AnalysisRequest] = {}
        for key, req in todo.items():
            if req.mode == "simulate":
                import dataclasses
                base = dataclasses.replace(req, mode="analytic")
                analytic_reqs[self._result_key(base)] = base
            else:
                analytic_reqs[key] = req
        with self._lock:
            analytic_todo = {k: r for k, r in analytic_reqs.items()
                             if k not in self._results}
            # stats mirror the sequential path: each uncached cell is
            # one miss — including the analytic base a simulate cell
            # computes implicitly — everything else a hit
            self.stats.result_misses += len(todo) + sum(
                1 for k in analytic_todo if k not in todo)
        if parallel and len(analytic_todo) > 1:
            with ThreadPoolExecutor(max_workers=self._max_workers) as ex:
                computed = list(ex.map(self._compute_analytic,
                                       analytic_todo.values()))
        else:
            computed = [self._compute_analytic(r)
                        for r in analytic_todo.values()]
        computed = [self._apply_ecm(res, r)
                    for res, r in zip(computed, analytic_todo.values())]
        with self._lock:
            for k, res in zip(analytic_todo, computed):
                self._results.setdefault(k, res)

        # ---- grouped simulation dispatch -----------------------------
        sim_cells = {k: r for k, r in todo.items()
                     if r.mode == "simulate"}
        if sim_cells:
            sim_keys = {k: (self._arch.resolve(r.arch),
                            self._kernel_id(r))
                        for k, r in sim_cells.items()}
            # sim_key -> fault event id for cells the ladder bottomed
            # out on (compile fault or every sim rung exhausted): they
            # get the analytic floor in the combine loop below
            floor_cells: dict[tuple, int] = {}
            # sim_key -> (routed_from, probe) for floor cells the
            # router sent straight to the floor (every rung unhealthy)
            floor_route: dict[tuple, tuple[str, bool]] = {}
            with self._lock:
                missing = {sk: r for k, r in sim_cells.items()
                           if (sk := sim_keys[k]) not in self._sim_cache}
            if missing:
                from .sim import AUTO_JIT_MIN_BATCH
                from .sim.batch import _resolve_backend
                chosen = backend or self.sim_backend
                # compile per request, containing injected compile
                # faults per cell (a cell whose program cannot compile
                # degrades alone; the rest of its group still simulates)
                compiled: dict[tuple, tuple[str, object]] = {}
                for sk, r in missing.items():
                    machine = self.resolve_machine(r.arch)
                    try:
                        compiled[sk] = (machine.digest,
                                        self._sim_program(r))
                    except FaultAbort:
                        raise
                    except InjectedFault as exc:
                        floor_cells[sk] = exc.event_id
                        with self._lock:
                            self.stats.degraded_results += 1
                # the small-batch tick-loop decision and the "auto"
                # rung both resolve on the *total* missing count, as
                # the single simulate_many call they replace did
                small = (chosen == "auto"
                         and len(compiled) < AUTO_JIT_MIN_BATCH)
                start = chosen if chosen != "auto" else \
                    _resolve_backend("auto", len(compiled))
                groups: dict[str, list[tuple]] = {}
                for sk, (digest, _prog) in compiled.items():
                    groups.setdefault(digest, []).append(sk)
                for digest, sks in groups.items():
                    progs = [compiled[sk][1] for sk in sks]
                    replay = self._journal_lookup(_journal, digest, progs)
                    if replay is not None:
                        sims, backend_used, degraded, event_id = replay
                        dispatches = 0
                        routed_from, probe = "", False
                        with self._lock:
                            self.stats.journal_hits += 1
                    else:
                        sims, backend_used, degraded, dispatches, \
                            event_id, routed_from, probe = \
                            self._run_ladder(digest, progs, start, small)
                        self._journal_record(_journal, digest, progs,
                                             sims, backend_used, degraded)
                    with self._lock:
                        if sims is None:
                            # every sim rung failed or was breaker-open:
                            # the whole group takes the analytic floor
                            self.stats.degraded_results += len(sks)
                            for sk in sks:
                                floor_cells.setdefault(sk, event_id)
                                if routed_from:
                                    floor_route[sk] = (routed_from, False)
                            continue
                        if replay is None:
                            self.stats.sim_runs += len(progs)
                            self.stats.sim_group_dispatches += dispatches
                        for sk, sim in zip(sks, sims):
                            self._sim_cache.setdefault(sk, sim)
                        if degraded:
                            self.stats.degraded_results += len(sks)
                        for sk in sks:
                            self._sim_provenance[sk] = (
                                backend_used, degraded, event_id,
                                routed_from, probe)
            # combine analytic base + simulation per cell
            import dataclasses
            for k, req in sim_cells.items():
                base_key = self._result_key(
                    dataclasses.replace(req, mode="analytic"))
                with self._lock:
                    analytic = self._results.get(base_key)
                    sim = self._sim_cache.get(sim_keys[k])
                    prov = self._sim_provenance.get(sim_keys[k])
                if analytic is not None and sim is None \
                        and sim_keys[k] in floor_cells:
                    res = self._apply_ecm(
                        self._analytic_floor(analytic,
                                             floor_cells[sim_keys[k]]),
                        req)
                    fr = floor_route.get(sim_keys[k])
                    if fr is not None:
                        res = dataclasses.replace(
                            res, routed_from=fr[0], probe=fr[1])
                elif analytic is None or sim is None:
                    # a concurrent register()/cache_clear() dropped the
                    # cell mid-batch: recompute through the (race-free)
                    # single-request path
                    res = self.predict(req)
                else:
                    res = self._apply_ecm(self._combine_sim(analytic, sim),
                                          req)
                    if prov is not None:
                        res = self._with_provenance(res, prov)
                with self._lock:
                    self._results.setdefault(k, res)

        out = []
        for key, req in zip(keys, requests):
            with self._lock:
                res = self._results.get(key)
            # concurrent invalidation between fill and gather: recompute
            out.append(res if res is not None else self.predict(req))
        return out

    async def predict_async(self, request: AnalysisRequest, *,
                            timeout: float | None = None,
                            retries: int = 0,
                            backoff_s: float = 0.05) -> AnalysisResult:
        """Awaitable ``predict`` (runs on the default executor), with
        graceful-degradation semantics for long-lived callers:

        * ``timeout`` — seconds per attempt; a dispatch that exceeds it
          raises :class:`asyncio.TimeoutError` to the caller instead of
          hanging it (the abandoned executor thread finishes in the
          background and still fills the result cache).
        * ``retries`` — extra attempts after a timeout *or* an engine
          exception, with exponential backoff starting at
          ``backoff_s`` (doubled per retry).  Invalid-request errors
          (``ValueError``) are never retried — they are deterministic.
        * **Cancellation**: cancelling the awaiting task propagates
          :class:`asyncio.CancelledError` immediately (no retry).  An
          in-flight executor call cannot be interrupted mid-compute;
          it completes in the background and populates the caches, so
          a re-submit of the same request is a cache hit.
        """
        loop = asyncio.get_running_loop()
        delay = backoff_s
        for attempt in range(1 + max(0, retries)):
            try:
                fut = loop.run_in_executor(None, self.predict, request)
                if timeout is None:
                    return await fut
                return await asyncio.wait_for(fut, timeout)
            except (asyncio.CancelledError, ValueError):
                raise
            except Exception:      # timeout or transient engine error
                if attempt >= retries:
                    raise
                await asyncio.sleep(delay)
                delay *= 2
        raise RuntimeError("unreachable")    # pragma: no cover

    def sweep(self, kernels: Mapping[str, str | tuple[Instruction, ...]],
              archs: Iterable[str] = ("skl", "zen"),
              schedulers: Iterable[str] = ("uniform",),
              unroll_factors: Mapping[str, int] | None = None,
              parallel: bool = False,
              mode: str = "analytic",
              backend: str | None = None,
              working_set: float | None = None,
              traffic_model: str = "analytic",
              journal: str | None = None,
              resume_from: str | None = None,
              journal_segment_size: int | None = None,
              ) -> dict[tuple[str, str, str], AnalysisResult]:
        """Full grid: ``{(kernel_name, arch, scheduler): AnalysisResult}``.

        ``unroll_factors`` optionally maps kernel names to their unroll
        factor (default 1); ``mode="simulate"`` runs the whole grid
        through the cycle-level simulator backend, planned and
        dispatched in machine-model groups (see :meth:`predict_batch`;
        ``backend`` picks the batch-simulation driver).
        ``working_set`` / ``traffic_model`` apply the ECM
        memory-hierarchy composition to every cell (see
        :class:`AnalysisRequest`); the underlying analytic passes and
        simulations are cached independently of the working set, so an
        ECM sweep over an already-swept grid adds zero sim dispatches.
        This is the bulk entry point used by
        ``benchmarks/paper_tables.py``-style sweeps.

        ``journal`` names a directory to journal completed
        machine-group results into (one crash-safe record per group,
        scoped by a plan digest over the full request grid);
        ``resume_from`` replays matching records from such a directory
        so a killed sweep resumes with zero re-dispatch of journaled
        groups and bit-identical output — see docs/robustness.md.
        ``journal_segment_size`` bounds the journal's live file count:
        every time that many loose record files accumulate they are
        folded into one sealed digest-verified segment
        (docs/robustness.md#journal-segments); the journal's shape
        after the sweep is surfaced in ``stats.journal_records`` /
        ``journal_segments`` / ``journal_bytes``.
        """
        unroll_factors = unroll_factors or {}
        names, reqs = [], []
        for name, kern in kernels.items():
            for arch in archs:
                for sched in schedulers:
                    names.append((name, arch, sched))
                    reqs.append(AnalysisRequest(
                        kernel=kern, arch=arch, scheduler=sched,
                        unroll_factor=unroll_factors.get(name, 1),
                        mode=mode, working_set=working_set,
                        traffic_model=traffic_model))
        session = None
        if journal is not None or resume_from is not None:
            from .journal import SweepJournal, plan_digest
            plan = plan_digest([self.request_key(r) for r in reqs],
                               backend or self.sim_backend)
            session = {
                "plan": plan,
                "writer": SweepJournal(journal,
                                       segment_size=journal_segment_size)
                          if journal is not None else None,
                "resume": SweepJournal(resume_from).load(plan)
                          if resume_from is not None else {},
            }
        results = self.predict_batch(reqs, parallel=parallel,
                                     backend=backend, _journal=session)
        if session is not None and session["writer"] is not None:
            jstats = session["writer"].stats()
            with self._lock:
                self.stats.journal_records = jstats["records"]
                self.stats.journal_segments = jstats["segments"]
                self.stats.journal_bytes = jstats["bytes"]
        return dict(zip(names, results))

    # ------------------------------------------------------------------
    # HLO (TPU) path
    # ------------------------------------------------------------------
    def predict_hlo(self, text: str, *, ici_links: float = 1.0,
                    flop_dtype: str = "bf16", mode: str = "analytic",
                    machine: "str | MachineModel | None" = None,
                    working_set: float | None = None):
        """Memoized :func:`repro.core.hlo.analyzer.analyze_hlo`.

        Results carry the combined ``max(overlap, critical-path)`` bound
        (``HloAnalysis.terms.bound_combined``); ``mode="simulate"``
        additionally list-schedules the entry ops onto the TPU ports
        (``repro.core.sim.dag``) and fills ``terms.sim_s`` /
        ``terms.bound_sim``.  ``machine`` selects the accelerator model
        (an arch id/alias resolved through this service's registry, or a
        :class:`MachineModel` whose ``constants`` carry the hardware
        numbers; default ``"tpu_v5e"``).  ``working_set`` selects the
        memory level that prices the roofline memory term from the
        model's ``constants["mem_levels"]`` table (``None`` keeps the
        flat HBM assumption — see docs/ecm.md).  The cache key is the
        module-text digest plus the machine digest, so the serving
        dry-run and roofline sweeps share one pass per compiled program.
        """
        if mode not in ("analytic", "simulate"):
            raise ValueError(f"unknown mode {mode!r} "
                             "(expected 'analytic' or 'simulate')")
        self._check_epoch()
        machine = self.resolve_machine(machine or "tpu_v5e")
        digest = hashlib.sha256(text.encode()).hexdigest()
        key = (digest, ici_links, flop_dtype, mode, machine.digest,
               working_set)
        with self._lock:
            hit = self._hlo_cache.get(key)
            if hit is not None:
                self.stats.hlo_hits += 1
                return hit
            self.stats.hlo_misses += 1
        if self.faults is not None:
            # parse faults are *not* contained: there is no cheaper
            # predictor for an unparsed module, so the typed error
            # propagates (the service maps it to a DispatchError)
            self.faults.fire("engine.hlo_parse", module=digest[:12])
        from .hlo.analyzer import analyze_hlo
        res = analyze_hlo(text, ici_links=ici_links, flop_dtype=flop_dtype,
                          simulate=(mode == "simulate"), machine=machine,
                          working_set=working_set)
        with self._lock:
            self._hlo_cache[key] = res
        return res

    def predict_hlo_batch(self, texts: Sequence[str], *,
                          ici_links: float = 1.0,
                          flop_dtype: str = "bf16",
                          mode: str = "analytic",
                          machine: "str | MachineModel | None" = None,
                          working_set: float | None = None,
                          ) -> list:
        """Batched :meth:`predict_hlo` through the sweep planner's
        discipline: the machine model resolves *once* for the whole
        batch, duplicate modules collapse onto one cache cell, and each
        unique module analyzes once.  ``ServingEngine.dryrun_estimate``
        sends its prefill + decode programs through here, so a serving
        sweep over prompt lengths re-resolves nothing.
        """
        machine = self.resolve_machine(machine or "tpu_v5e")
        out: dict[str, object] = {}
        for text in texts:
            if text not in out:
                out[text] = self.predict_hlo(
                    text, ici_links=ici_links, flop_dtype=flop_dtype,
                    mode=mode, machine=machine, working_set=working_set)
        return [out[text] for text in texts]

    # ------------------------------------------------------------------
    def drop_results(self) -> None:
        """Drop the *volatile* caches (results, simulations, HLO
        analyses) while keeping the compiled artifacts — dependency
        edges, :class:`SimProgram`\\ s, LP solves, lookups, traffic,
        machine resolutions.

        This is the expiry operation a persistent service applies when
        result TTLs lapse: the next sweep re-simulates (fresh numbers)
        but reuses every compiled program, which is what makes
        ``stats.program_hits`` nonzero across successive sweeps —
        ``benchmarks/sweep_bench.py`` gates exactly that.
        """
        with self._lock:
            self._results.clear()
            self._sim_cache.clear()
            self._sim_provenance.clear()
            self._hlo_cache.clear()

    def cache_clear(self) -> None:
        """Drop every cache (databases are kept) and reset the stats."""
        with self._lock:
            self._lookups.clear()
            self._lp_cache.clear()
            self._results.clear()
            self._sim_cache.clear()
            self._sim_provenance.clear()
            self._hlo_cache.clear()
            self._edge_cache.clear()
            self._program_cache.clear()
            self._classify_cache.clear()
            self._machine_cache.clear()
            self._traffic_cache.clear()
            self.stats = ServiceStats()


_DEFAULT: AnalysisService | None = None
_DEFAULT_LOCK = threading.Lock()


def default_service() -> AnalysisService:
    """Process-wide shared service (benchmarks, examples and the serving
    dry-run all use this one so their caches compose)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = AnalysisService()
        return _DEFAULT
