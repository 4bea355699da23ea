"""Service observability: histograms, counters, traces — exported as JSON.

Everything the SLO self-model (``repro.service.slo``) and the load
harness (``benchmarks/service_bench.py``) consume comes from here:

* :class:`LatencyHistogram` — log-spaced fixed buckets (counting, not
  sampling: thousands of requests cost a few hundred ints) with exact
  ``count``/``sum`` and interpolated percentiles;
* :class:`Telemetry` — per-stage latency histograms (queue wait,
  dispatch, end-to-end), queue-depth and batch-size distributions,
  per-tenant counters, per-cohort-class dispatch accounting (the SLO
  model's flow inputs), and a bounded ring of structured trace events.

``export()`` returns one plain-JSON dict; nothing here imports the
engine, so the module stays importable in minimal environments.
"""
from __future__ import annotations

import math
from collections import deque
from dataclasses import dataclass, field
from typing import Any


class LatencyHistogram:
    """Fixed log-spaced buckets from ``lo_s`` to ``hi_s``.

    Percentiles interpolate within the matched bucket (log-linear), so
    p99 error is bounded by the bucket ratio (default ~7% per decade
    with 36 buckets over 9 decades) — tight enough for an SLO gate at
    +/-50%.
    """

    def __init__(self, lo_s: float = 1e-6, hi_s: float = 1e3,
                 buckets_per_decade: int = 4):
        self.lo_s = lo_s
        self.hi_s = hi_s
        decades = math.log10(hi_s / lo_s)
        self.n = max(1, int(round(decades * buckets_per_decade)))
        self.ratio = (hi_s / lo_s) ** (1.0 / self.n)
        self.counts = [0] * (self.n + 2)    # +underflow +overflow
        self.count = 0
        self.sum = 0.0
        self.max = 0.0

    def _index(self, v: float) -> int:
        if v < self.lo_s:
            return 0
        if v >= self.hi_s:
            return self.n + 1
        return 1 + int(math.log(v / self.lo_s) / math.log(self.ratio))

    def observe(self, v: float) -> None:
        self.counts[self._index(v)] += 1
        self.count += 1
        self.sum += v
        self.max = max(self.max, v)

    def mean(self) -> float:
        return self.sum / self.count if self.count else 0.0

    def percentile(self, q: float) -> float:
        """Interpolated quantile ``q`` in [0, 1]; 0.0 when empty."""
        if self.count == 0:
            return 0.0
        target = q * self.count
        acc = 0.0
        for i, c in enumerate(self.counts):
            if c == 0:
                continue
            if acc + c >= target:
                frac = (target - acc) / c
                if i == 0:
                    return self.lo_s * frac
                if i == self.n + 1:
                    return self.max
                lo = self.lo_s * self.ratio ** (i - 1)
                hi = min(lo * self.ratio, self.max if self.max else
                         lo * self.ratio)
                return lo + (hi - lo) * frac
            acc += c
        return self.max

    def as_dict(self) -> dict[str, float]:
        return {"count": self.count, "mean_s": self.mean(),
                "p50_s": self.percentile(0.50),
                "p90_s": self.percentile(0.90),
                "p99_s": self.percentile(0.99),
                "max_s": self.max}


@dataclass
class TenantCounters:
    submitted: int = 0
    admitted: int = 0
    rejected: int = 0            # AdmissionError at submit
    completed: int = 0
    failed: int = 0              # dispatch errors after retries
    deadline_exceeded: int = 0
    cancelled: int = 0
    cache_hits: int = 0          # served from the cross-request cache
    retry_budget_exhausted: int = 0   # failed fast: no retry budget left

    def as_dict(self) -> dict[str, int]:
        return dict(vars(self))


@dataclass
class CohortClassStats:
    """Per cohort-class dispatch accounting — the SLO model's flows."""

    dispatches: int = 0
    requests: int = 0
    retries: int = 0
    routed: int = 0              # cohorts started below the requested
    #                              rung by the pre-dispatch consult
    hedges: int = 0              # hedged (duplicate) dispatches issued
    hedge_wins: int = 0          # hedges that answered before primary
    cost: LatencyHistogram = field(default_factory=LatencyHistogram)

    def as_dict(self) -> dict[str, Any]:
        return {"dispatches": self.dispatches,
                "requests": self.requests, "retries": self.retries,
                "routed": self.routed, "hedges": self.hedges,
                "hedge_wins": self.hedge_wins,
                "cost": self.cost.as_dict()}


class Telemetry:
    """All measured state of one :class:`PredictionService`."""

    def __init__(self, trace_capacity: int = 512):
        self.queue_wait = LatencyHistogram()
        self.dispatch = LatencyHistogram()
        self.total = LatencyHistogram()
        self.retry_sleep = LatencyHistogram()   # governed backoff sleeps
        self.batch_size = LatencyHistogram(lo_s=1.0, hi_s=4096.0,
                                           buckets_per_decade=8)
        self.queue_depth = LatencyHistogram(lo_s=1.0, hi_s=65536.0,
                                            buckets_per_decade=8)
        self.tenants: dict[str, TenantCounters] = {}
        self.cohort_classes: dict[str, CohortClassStats] = {}
        self.engine_dispatches = 0       # sim driver + HLO dispatches
        self.traces: deque[dict] = deque(maxlen=trace_capacity)
        self.started_at: float | None = None
        self.stopped_at: float | None = None

    def tenant(self, name: str) -> TenantCounters:
        tc = self.tenants.get(name)
        if tc is None:
            tc = self.tenants[name] = TenantCounters()
        return tc

    def cohort_class(self, key: tuple | str) -> CohortClassStats:
        name = key if isinstance(key, str) else class_name(key)
        cc = self.cohort_classes.get(name)
        if cc is None:
            cc = self.cohort_classes[name] = CohortClassStats()
        return cc

    def trace(self, event: str, **fields: Any) -> None:
        self.traces.append({"event": event, **fields})

    def elapsed_s(self, now: float | None = None) -> float:
        if self.started_at is None:
            return 0.0
        end = self.stopped_at if self.stopped_at is not None else now
        return max(0.0, (end or self.started_at) - self.started_at)

    def export(self, now: float | None = None) -> dict[str, Any]:
        """One JSON-serializable dict with every counter/histogram."""
        return {
            "elapsed_s": self.elapsed_s(now),
            "stages": {"queue_wait": self.queue_wait.as_dict(),
                       "dispatch": self.dispatch.as_dict(),
                       "retry_sleep": self.retry_sleep.as_dict(),
                       "total": self.total.as_dict()},
            "batch_size": self.batch_size.as_dict(),
            "queue_depth": self.queue_depth.as_dict(),
            "engine_dispatches": self.engine_dispatches,
            "tenants": {t: c.as_dict()
                        for t, c in sorted(self.tenants.items())},
            "cohort_classes": {n: c.as_dict() for n, c in
                               sorted(self.cohort_classes.items())},
            "traces": list(self.traces),
        }


def class_name(key: tuple) -> str:
    """Human-readable cohort-class label: ``kind/digest8/mode/backend``."""
    kind, digest, mode, backend = key[0], key[1], key[2], key[3]
    return f"{kind}/{str(digest)[:8]}/{mode}/{backend or 'auto'}"
