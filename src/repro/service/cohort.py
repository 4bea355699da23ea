"""Cohort formation: coalesce compatible in-flight requests.

The batch engine (``AnalysisService.predict_batch`` /
``simulate_many``) amortizes compilation and dispatch overhead only
when every request in a batch shares the same machine model, mode and
batch-simulation backend — the planner groups by machine internally,
but mixing modes or backends would force it back onto per-point paths.
The cohort former therefore *partitions* the in-flight set by

    ``(kind, machine digest, mode, backend [, HLO pricing knobs])``

and dispatches each cohort as one batched engine call.  Partitioning
(every request in exactly one cohort, no cohort mixing keys) is the
correctness property ``tests/test_service_cohorts.py`` locks with
hypothesis.  A request's answer does not depend on its cohort: every
cohort size, one included, goes through the engine's batched planner
and the same recurrence.

The functions here are pure (no clocks, no I/O): the service hands
them its drained queue, the tests hand them synthetic request lists.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, Iterable, Sequence

from .request import ServiceRequest

if TYPE_CHECKING:                       # pragma: no cover
    from repro.core.engine import AnalysisService


def cohort_key(engine: "AnalysisService", req: ServiceRequest) -> tuple:
    """The compatibility class of one request.

    x86 requests batch when they agree on (machine digest, mode,
    backend); HLO requests additionally carry their pricing knobs
    (``ici_links``/``flop_dtype``/``working_set``) because
    ``predict_hlo_batch`` applies them batch-wide.  The machine model
    resolves through the engine's memoized ``resolve_machine``, so key
    computation is cheap after the first request per arch.
    """
    if req.analysis is not None:
        a = req.analysis
        digest = engine.resolve_machine(a.arch).digest
        return ("x86", digest, a.mode, req.backend)
    h = req.hlo
    digest = engine.resolve_machine(h.machine).digest
    return ("hlo", digest, h.mode, req.backend,
            h.ici_links, h.flop_dtype, h.working_set)


def form_cohorts(engine: "AnalysisService",
                 requests: Sequence[ServiceRequest],
                 max_cohort: int | None = None,
                 ) -> list[tuple[tuple, list[int]]]:
    """Partition ``requests`` into dispatch cohorts.

    Returns ``[(key, indices), ...]`` in first-seen order; ``indices``
    index into ``requests`` and preserve arrival order within a cohort
    (the engine planner dedupes identical cells itself, so duplicates
    stay in the cohort).  ``max_cohort`` splits oversized cohorts so a
    tenant flooding one key cannot make a single dispatch arbitrarily
    large (and arbitrarily late for everyone in it).
    """
    buckets: dict[tuple, list[int]] = {}
    order: list[tuple] = []
    for i, req in enumerate(requests):
        key = cohort_key(engine, req)
        if key not in buckets:
            buckets[key] = []
            order.append(key)
        buckets[key].append(i)
    out: list[tuple[tuple, list[int]]] = []
    for key in order:
        idxs = buckets[key]
        if max_cohort is None or len(idxs) <= max_cohort:
            out.append((key, idxs))
        else:
            for lo in range(0, len(idxs), max_cohort):
                out.append((key, idxs[lo:lo + max_cohort]))
    return out


def is_partition(cohorts: Iterable[tuple[tuple, list[int]]],
                 n_requests: int) -> bool:
    """True when the cohorts cover each request index exactly once."""
    seen: list[int] = []
    for _, idxs in cohorts:
        seen.extend(idxs)
    return sorted(seen) == list(range(n_requests))
