"""The persistent multi-tenant prediction service.

:class:`PredictionService` is a long-lived asyncio front over the
in-process :class:`~repro.core.engine.AnalysisService` planner:

    submit -> admission control -> request queue -> cohort former
           -> batched engine dispatch -> response (+ telemetry)

* **Admission** (``repro.service.admission``): bounded global and
  per-tenant queue depth plus token-bucket rate limits; rejected
  submits raise :class:`AdmissionError` immediately instead of queueing
  unboundedly.
* **Batching** (``repro.service.cohort``): the dispatcher drains the
  queue after a tunable ``batch_window_s``, partitions the in-flight
  set by ``(kind, machine digest, mode, backend)`` and issues *one*
  ``predict_batch`` / ``predict_hlo_batch`` per cohort — the grouped
  planner then turns a cohort into a handful of compiled dispatches.
* **Cross-request cache** (``repro.service.cache``): responses are
  kept in a TTL+size-bounded cache keyed by the same content digests
  the engine memoizes on, shared across tenants; hits return at submit
  time without touching the queue.
* **Robustness**: per-request deadlines (submit-relative,
  propagated to the dispatcher which skips expired work), per-dispatch
  timeout with *governed* retries — capped full-jitter backoff from a
  seeded RNG, per-tenant retry budgets (exhausted budget fails fast
  with an explicit reason), sleeps clamped to the tightest remaining
  request deadline — optional hedged dispatch for straggler cohorts
  (docs/robustness.md#retry-budgets), a pre-dispatch
  :class:`~repro.core.degrade.HealthRouter` consult when the engine
  carries one (docs/robustness.md#health-aware-routing), and a
  documented cancellation path (cancel the task awaiting
  :meth:`submit`; the dispatcher notices and drops the request from
  its cohort).
* **Observability** (``repro.service.telemetry``): per-stage latency
  histograms, queue-depth/batch-size distributions, per-tenant and
  per-cohort-class counters, trace events — ``export_stats()`` returns
  one JSON dict, which also feeds the analytic SLO self-model
  (``repro.service.slo``).  Submit, cohort formation and each engine
  dispatch are ``repro.service.*`` profiler spans
  (``repro.core.spans``), beside the engine's own.

See docs/serving-service.md for the worked example and
``benchmarks/service_bench.py`` for the load-generation harness.
"""
from __future__ import annotations

import asyncio
import hashlib
import random
from dataclasses import dataclass, field as dc_field
from typing import Any, Sequence

from repro.core.degrade import LADDER, ladder_from
from repro.core.engine import AnalysisService
from repro.core.spans import span

from .admission import AdmissionController, AdmissionError, TenantPolicy
from .cache import TTLCache
from .cohort import form_cohorts
from .request import (DeadlineExceeded, DispatchError, HloRequest,
                      ServiceClosed, ServiceRequest, ServiceResponse)
from .slo import SloModel, SloPrediction
from .telemetry import Telemetry, class_name


@dataclass
class ServiceConfig:
    """Tunables of one :class:`PredictionService` (see
    docs/serving-service.md#admission-control-knobs)."""

    batch_window_s: float = 0.002       # cohort formation window
    max_queue_depth: int = 256          # global in-flight ceiling
    default_policy: TenantPolicy = dc_field(default_factory=TenantPolicy)
    tenant_policies: dict[str, TenantPolicy] = dc_field(
        default_factory=dict)
    default_timeout_s: float = 60.0     # per-request deadline
    dispatch_timeout_s: float = 60.0    # one engine dispatch attempt
    max_retries: int = 1                # extra dispatch attempts
    retry_backoff_s: float = 0.05       # backoff base, doubled per retry
    retry_backoff_cap_s: float = 1.0    # backoff ceiling (the doubling
    #                                     can never sleep longer)
    retry_seed: int = 0                 # full-jitter RNG seed: replays
    #                                     are deterministic
    hedge: bool = False                 # hedged dispatch: after the
    #                                     hedge delay, race the next
    #                                     ladder rung against a
    #                                     straggling primary dispatch
    hedge_delay_s: float | None = None  # None = p99 of the measured
    #                                     dispatch histogram
    max_cohort: int = 1024              # split larger cohorts
    cache_entries: int = 4096           # cross-request cache size bound
    cache_ttl_s: float = float("inf")   # cross-request cache TTL
    backend: str | None = None          # default sim batch driver


class PredictionService:
    """Async, batching, caching, admission-controlled prediction front.

    One instance wraps one :class:`AnalysisService` (its planner and
    memo caches are shared by every tenant).  Lifecycle::

        service = PredictionService()
        await service.start()
        resp = await service.submit(ServiceRequest(analysis=req,
                                                   tenant="alice"))
        await service.stop()

    or synchronously via :func:`replay`.  ``submit`` raises
    :class:`AdmissionError` / :class:`ServiceClosed` at submit time;
    every other failure (deadline, dispatch error) comes back *inside*
    the :class:`ServiceResponse` so telemetry and partial batches stay
    consistent.
    """

    _STOP = object()

    def __init__(self, engine: AnalysisService | None = None,
                 config: ServiceConfig | None = None):
        self.engine = engine or AnalysisService()
        self.config = config or ServiceConfig()
        self.admission = AdmissionController(
            max_queue_depth=self.config.max_queue_depth,
            default_policy=self.config.default_policy,
            per_tenant=self.config.tenant_policies)
        # the engine's fault injector (when armed) also covers the
        # cross-request cache, so one FaultPlan exercises the whole stack
        self.cache = TTLCache(max_entries=self.config.cache_entries,
                              ttl_s=self.config.cache_ttl_s,
                              faults=self.engine.faults)
        self.telemetry = Telemetry()
        # full-jitter backoff RNG: seeded so a replayed fault schedule
        # produces the same retry timing (docs/robustness.md)
        self._retry_rng = random.Random(self.config.retry_seed)
        self._queue: asyncio.Queue | None = None
        self._dispatcher: asyncio.Task | None = None
        self._closed = True
        # registry epoch at the last cache fill: a machine-model
        # re-registration invalidates every cross-request entry (they
        # key on digests of models that may no longer be resolvable)
        self._registry_epoch = self.engine.registry.epoch

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    async def start(self) -> None:
        """Start the dispatcher; idempotent while running."""
        if self._dispatcher is not None and not self._dispatcher.done():
            return
        self._queue = asyncio.Queue()
        self._closed = False
        loop = asyncio.get_running_loop()
        if self.telemetry.started_at is None:
            self.telemetry.started_at = loop.time()
        self._dispatcher = asyncio.create_task(self._run())

    async def stop(self, drain: bool = True) -> None:
        """Stop accepting work; ``drain=True`` (default) serves every
        already-queued request first, ``False`` fails them with
        :class:`ServiceClosed`."""
        if self._closed and self._dispatcher is None:
            return
        self._closed = True
        if self._queue is not None:
            self._queue.put_nowait(self._STOP)
        if self._dispatcher is not None:
            if not drain:
                self._dispatcher.cancel()
            try:
                await self._dispatcher
            except asyncio.CancelledError:
                pass
            self._dispatcher = None
        if not drain and self._queue is not None:
            while not self._queue.empty():
                item = self._queue.get_nowait()
                if item is not self._STOP:
                    self._finalize_error(item, ServiceClosed("stopped"))
        self.telemetry.stopped_at = asyncio.get_running_loop().time()

    # ------------------------------------------------------------------
    # submit
    # ------------------------------------------------------------------
    def _cache_key(self, sreq: ServiceRequest) -> tuple:
        if sreq.analysis is not None:
            return ("x86", self.engine.request_key(sreq.analysis),
                    sreq.backend)
        h = sreq.hlo
        digest = hashlib.sha256(h.text.encode()).hexdigest()
        machine = self.engine.resolve_machine(h.machine)
        return ("hlo", machine.digest, digest, h.mode, h.ici_links,
                h.flop_dtype, h.working_set)

    async def submit(self, sreq: ServiceRequest) -> ServiceResponse:
        """Admit, enqueue and await one request.

        Cache hits return immediately (no admission cost — the cached
        answer consumes no queue capacity).  Cancellation: cancelling
        the task awaiting ``submit`` abandons the request; the
        dispatcher drops it from its cohort (counted per tenant as
        ``cancelled``) and its admission slot is released when the
        cohort containing it is finalized.
        """
        loop = asyncio.get_running_loop()
        now = loop.time()
        tc = self.telemetry.tenant(sreq.tenant)
        tc.submitted += 1
        if self._closed or self._queue is None:
            raise ServiceClosed("service not started or stopped")
        with span("repro.service.submit"):
            epoch = self.engine.registry.epoch
            if epoch != self._registry_epoch:
                self._registry_epoch = epoch
                self.cache.clear()
                self.telemetry.trace("cache_invalidated", epoch=epoch)
            key = self._cache_key(sreq)
            hit = self.cache.get(key, now)
            if hit is not None:
                tc.cache_hits += 1
                tc.completed += 1
                dt = loop.time() - now
                self.telemetry.total.observe(dt)
                return ServiceResponse(
                    request=sreq, result=hit, cache_hit=True, total_s=dt,
                    **ServiceResponse.provenance_of(hit))
            try:
                self.admission.admit(sreq.tenant, now)
            except AdmissionError:
                tc.rejected += 1
                self.telemetry.trace("rejected", tenant=sreq.tenant,
                                     tag=sreq.tag)
                raise
            tc.admitted += 1
            timeout = sreq.timeout_s if sreq.timeout_s is not None \
                else self.config.default_timeout_s
            pending = _Pending(request=sreq, future=loop.create_future(),
                               cache_key=key, t_submit=now,
                               deadline=now + timeout)
            self._queue.put_nowait(pending)
        try:
            return await asyncio.wait_for(
                asyncio.shield(pending.future), timeout)
        except asyncio.TimeoutError:
            pending.abandoned = True
            tc.deadline_exceeded += 1
            return ServiceResponse(
                request=sreq, error=DeadlineExceeded(
                    f"timeout {timeout}s elapsed in queue/dispatch"),
                total_s=loop.time() - now)
        except asyncio.CancelledError:
            pending.abandoned = True
            tc.cancelled += 1
            raise

    # ------------------------------------------------------------------
    # dispatcher
    # ------------------------------------------------------------------
    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        stop = False
        while not stop:
            item = await self._queue.get()
            if item is self._STOP:
                break
            batch = [item]
            if self.config.batch_window_s > 0:
                await asyncio.sleep(self.config.batch_window_s)
            while True:
                try:
                    nxt = self._queue.get_nowait()
                except asyncio.QueueEmpty:
                    break
                if nxt is self._STOP:
                    stop = True
                    break
                batch.append(nxt)
            self.telemetry.queue_depth.observe(float(len(batch)))
            t_form = loop.time()
            with span("repro.service.cohort", requests=len(batch)):
                cohorts = form_cohorts(
                    self.engine, [p.request for p in batch],
                    max_cohort=self.config.max_cohort)
            self.telemetry.trace(
                "batch_formed", requests=len(batch),
                cohorts=len(cohorts))
            for key, idxs in cohorts:
                await self._dispatch_cohort(
                    key, [batch[i] for i in idxs], t_form)
            self.cache.purge(loop.time())

    def _finalize_error(self, pending: "_Pending",
                        err: BaseException) -> None:
        tc = self.telemetry.tenant(pending.request.tenant)
        if isinstance(err, DeadlineExceeded):
            tc.deadline_exceeded += 1
        else:
            tc.failed += 1
        self.admission.release(pending.request.tenant)
        if not pending.future.done():
            pending.future.set_result(ServiceResponse(
                request=pending.request, error=err))

    def _engine_dispatch_fn(self, key: tuple,
                            sreqs: list[ServiceRequest],
                            backend: str | None = None):
        """The blocking engine call for one cohort (runs on the
        default executor).  ``backend`` overrides the cohort's batch
        driver — the routing consult and hedged dispatch use it.  An x86
        call is one ``repro.service.dispatch`` span on the executor
        thread, with the cohort's ``requests``; the engine's
        ``repro.predict_batch`` span (its ``call``) nests inside it."""
        if key[0] == "x86":
            backend = backend or key[3] or self.config.backend
            reqs = [s.analysis for s in sreqs]

            def dispatch():
                with span("repro.service.dispatch", requests=len(reqs)):
                    return self.engine.predict_batch(reqs, backend=backend)
            return dispatch
        h0 = sreqs[0].hlo
        texts = [s.hlo.text for s in sreqs]
        machine = self.engine.resolve_machine(h0.machine)
        return lambda: self.engine.predict_hlo_batch(
            texts, ici_links=h0.ici_links, flop_dtype=h0.flop_dtype,
            mode=h0.mode, machine=machine,
            working_set=h0.working_set)

    def _backoff_s(self, attempt: int) -> float:
        """Full-jitter capped exponential backoff for retry ``attempt``
        (>= 1): uniform in ``[0, min(cap, base * 2**(attempt-1))]``
        from the seeded RNG, so retries decorrelate across cohorts but
        a replay is deterministic and no sleep exceeds the cap."""
        ceiling = min(self.config.retry_backoff_cap_s,
                      self.config.retry_backoff_s * (2 ** (attempt - 1)))
        return self._retry_rng.uniform(0.0, ceiling)

    def _hedge_delay_s(self) -> float:
        """The straggler threshold for hedged dispatch: configured, or
        derived from the measured dispatch-latency p99 (hedging only
        fires for dispatches already slower than ~99% of history)."""
        if self.config.hedge_delay_s is not None:
            return self.config.hedge_delay_s
        p99 = self.telemetry.dispatch.percentile(0.99)
        return p99 if p99 > 0 else max(self.config.batch_window_s, 0.01)

    def _route_start(self, key: tuple) -> str | None:
        """Pre-dispatch routing consult for one cohort: the healthiest
        start rung, or None to dispatch as requested.

        Uses the router's non-consuming :meth:`HealthRouter.preview` —
        the engine's own ``plan()`` at dispatch time stays the single
        scheduler of half-open probes, so the service consult can never
        double-spend a probe slot."""
        router = self.engine.router
        if router is None or key[0] != "x86" or key[2] != "simulate":
            return None
        requested = key[3] or self.config.backend or self.engine.sim_backend
        if requested not in LADDER:
            return None     # "auto"/None resolve on batch size downstream
        route = router.preview(self.engine.breakers, key[1],
                               ladder_from(requested))
        if route.routed_from and route.rungs:
            return route.rungs[0]
        return None

    async def _dispatch_attempt(self, key: tuple, fn, hedge_fn):
        """One governed dispatch attempt, optionally hedged.

        Without a hedge fn this is a plain bounded executor call.  With
        one, the primary runs alone for the hedge delay; if it is still
        going, the next-rung duplicate is launched and the first
        successful result wins — the loser's asyncio future is
        cancelled (the executor thread runs to completion; its result
        is discarded) and accounted in cohort-class telemetry."""
        loop = asyncio.get_running_loop()
        timeout = self.config.dispatch_timeout_s
        primary = asyncio.ensure_future(loop.run_in_executor(None, fn))
        if hedge_fn is None:
            return await asyncio.wait_for(primary, timeout)
        cls = self.telemetry.cohort_class(key)
        t0 = loop.time()
        done, _ = await asyncio.wait(
            {primary}, timeout=min(self._hedge_delay_s(), timeout))
        if primary in done:
            return primary.result()
        cls.hedges += 1
        self.telemetry.trace("hedge", cohort=class_name(key))
        hedge = asyncio.ensure_future(
            loop.run_in_executor(None, hedge_fn))
        tasks = {primary, hedge}
        last_exc: BaseException | None = None
        while tasks:
            remaining = timeout - (loop.time() - t0)
            if remaining <= 0:
                break
            done, tasks = await asyncio.wait(
                tasks, timeout=remaining,
                return_when=asyncio.FIRST_COMPLETED)
            if not done:
                break
            for t in done:
                if t.exception() is None:
                    for loser in tasks:
                        loser.cancel()
                    if t is hedge:
                        cls.hedge_wins += 1
                    return t.result()
                last_exc = t.exception()
        if tasks:       # timed out with dispatches still in flight
            for t in tasks:
                t.cancel()
            raise asyncio.TimeoutError
        assert last_exc is not None     # both completed, both failed
        raise last_exc

    async def _dispatch_cohort(self, key: tuple,
                               pendings: list["_Pending"],
                               t_form: float) -> None:
        loop = asyncio.get_running_loop()
        live: list[_Pending] = []
        for p in pendings:
            if p.abandoned:
                # submit() already counted deadline/cancel; just free
                # the admission slot
                self.admission.release(p.request.tenant)
            elif t_form > p.deadline:
                self._finalize_error(p, DeadlineExceeded(
                    "deadline elapsed before dispatch"))
            else:
                live.append(p)
        if not live:
            return
        cls = self.telemetry.cohort_class(key)
        cls.requests += len(live)
        self.telemetry.batch_size.observe(float(len(live)))
        # breaker-aware routing consult: where will this cohort start?
        # The consult is a pure preview — the engine's own plan() at
        # dispatch time performs the actual skip (and emits the
        # routed_from/probe provenance); the service records the
        # decision in telemetry and picks the hedge rung from it.
        start = self._route_start(key)
        if start is not None:
            cls.routed += 1
            self.telemetry.trace("routed", cohort=class_name(key),
                                 start=start)
        hedge_fn = None
        if self.config.hedge and key[0] == "x86" and key[2] == "simulate":
            healthiest = start or key[3] or self.config.backend \
                or self.engine.sim_backend
            rungs = ladder_from(healthiest) if healthiest in LADDER else ()
            if len(rungs) > 1:
                hedge_fn = self._engine_dispatch_fn(
                    key, [p.request for p in live], backend=rungs[1])
        fn = self._engine_dispatch_fn(key, [p.request for p in live])
        stats = self.engine.stats
        before = (stats.sim_group_dispatches, stats.hlo_misses)
        err: BaseException | None = None
        results = None
        t0 = loop.time()
        for attempt in range(1 + self.config.max_retries):
            if attempt:
                # per-tenant retry budget: a tenant out of budget fails
                # fast instead of amplifying a failing backend's load
                now_b = loop.time()
                granted: list[_Pending] = []
                for p in live:
                    if self.admission.try_retry(p.request.tenant, now_b):
                        granted.append(p)
                    else:
                        tc = self.telemetry.tenant(p.request.tenant)
                        tc.retry_budget_exhausted += 1
                        self.telemetry.trace(
                            "retry_budget_exhausted",
                            tenant=p.request.tenant,
                            cohort=class_name(key))
                        self._finalize_error(p, DispatchError(
                            "retry budget exhausted for tenant "
                            f"{p.request.tenant!r} (fail-fast; see "
                            "TenantPolicy.retry_rate_per_s)"))
                if len(granted) != len(live):
                    live = granted
                    if not live:
                        return
                    fn = self._engine_dispatch_fn(
                        key, [p.request for p in live])
                cls.retries += 1
                self.telemetry.trace("retry", cohort=class_name(key),
                                     attempt=attempt)
                # deadline-aware jittered sleep: never sleep past any
                # live request's remaining deadline
                sleep = self._backoff_s(attempt)
                sleep = max(0.0, min(
                    sleep, min(p.deadline for p in live) - loop.time()))
                self.telemetry.retry_sleep.observe(sleep)
                if sleep > 0:
                    await asyncio.sleep(sleep)
            try:
                results = await self._dispatch_attempt(key, fn, hedge_fn)
                err = None
                break
            except asyncio.TimeoutError as e:
                err = DispatchError(
                    f"dispatch timed out after "
                    f"{self.config.dispatch_timeout_s}s")
                err.__cause__ = e
            except Exception as e:        # engine-side failure
                err = DispatchError(str(e))
                err.__cause__ = e
        dt = loop.time() - t0
        cls.dispatches += 1
        cls.cost.observe(dt)
        self.telemetry.dispatch.observe(dt)
        after = (self.engine.stats.sim_group_dispatches,
                 self.engine.stats.hlo_misses)
        # one simulate_many driver invocation = one dispatch, whatever
        # the cohort's size; each unique HLO module analyzed = one
        self.telemetry.engine_dispatches += sum(
            a - b for a, b in zip(after, before))
        now = loop.time()
        if err is not None:
            self.telemetry.trace("dispatch_failed",
                                 cohort=class_name(key), error=str(err))
            for p in live:
                self._finalize_error(p, err)
            return
        for p, result in zip(live, results):
            self.cache.put(p.cache_key, result, now)
            if not p.abandoned:    # abandoned = accounted at submit
                self.telemetry.tenant(p.request.tenant).completed += 1
            self.admission.release(p.request.tenant)
            queue_s = t_form - p.t_submit
            total_s = now - p.t_submit
            self.telemetry.queue_wait.observe(queue_s)
            self.telemetry.total.observe(total_s)
            if not p.future.done():
                p.future.set_result(ServiceResponse(
                    request=p.request, result=result,
                    queue_s=queue_s, dispatch_s=dt, total_s=total_s,
                    cohort_size=len(live),
                    **ServiceResponse.provenance_of(result)))

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def export_stats(self, now: float | None = None) -> dict[str, Any]:
        """Telemetry + cross-request cache + engine cache counters as
        one JSON-serializable dict."""
        out = self.telemetry.export(now)
        out["cache"] = self.cache.stats()
        out["engine"] = self.engine.stats.as_dict()
        out["engine_hit_rates"] = {
            k: self.engine.stats.hit_rate(k)
            for k in ("result", "lookup", "lp", "hlo", "edge",
                      "program", "classify", "machine")}
        # degradation-ladder state: breaker opening/half-opening is
        # visible here (and in the bounded transition event log)
        out["breakers"] = self.engine.breakers.snapshot()
        out["faults"] = (self.engine.faults.summary()
                         if self.engine.faults is not None else None)
        # routing-policy state: plan/probe/floor counts + pending
        # probe windows (None when the engine has no router installed)
        out["router"] = (self.engine.router.snapshot()
                         if self.engine.router is not None else None)
        return out

    def slo_model(self) -> SloModel:
        """The analytic SLO self-model calibrated from this service's
        own telemetry (see repro.service.slo)."""
        return SloModel.from_telemetry(self.telemetry.export(),
                                       self.config.batch_window_s)

    def predict_slo(self) -> SloPrediction:
        """Shorthand: build the self-model and predict p50/p99."""
        return self.slo_model().predict()


@dataclass
class _Pending:
    request: ServiceRequest
    future: asyncio.Future
    cache_key: tuple
    t_submit: float
    deadline: float
    abandoned: bool = False


def replay(service: PredictionService,
           traffic: Sequence[tuple[float, ServiceRequest]],
           ) -> list[ServiceResponse]:
    """Synchronous mixed-traffic replay (the load harness entry point).

    ``traffic`` is ``[(offset_s, request), ...]`` with offsets relative
    to service start.  Starts the service, submits every request at its
    offset, drains, stops, and returns the responses in input order —
    admission rejections come back as error responses rather than
    raising, so a replay is never torn down by one throttled tenant.
    """
    async def _go() -> list[ServiceResponse]:
        await service.start()
        out: list[ServiceResponse | None] = [None] * len(traffic)

        async def one(i: int, offset: float, sreq: ServiceRequest):
            await asyncio.sleep(offset)
            try:
                out[i] = await service.submit(sreq)
            except (AdmissionError, ServiceClosed) as e:
                out[i] = ServiceResponse(request=sreq, error=e)

        await asyncio.gather(*(one(i, off, sreq)
                               for i, (off, sreq) in enumerate(traffic)))
        await service.stop()
        return out                    # type: ignore[return-value]

    return asyncio.run(_go())
