"""Service request/response envelopes.

A :class:`ServiceRequest` wraps one unit of work for the prediction
service: either an x86 :class:`~repro.core.engine.AnalysisRequest`
(single point or sweep cell) or an HLO module text (the serving
dry-run path), plus the multi-tenant envelope — tenant id, per-request
timeout, and the batch-dispatch backend hint.

Responses carry the raw engine result plus per-stage timing so the
load harness (``benchmarks/service_bench.py``) and the observability
layer can attribute latency to queueing vs batching vs dispatch.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

from repro.core.engine import AnalysisRequest


class DeadlineExceeded(Exception):
    """The request's timeout elapsed before a result was produced."""


class DispatchError(Exception):
    """The engine dispatch failed after the configured retries."""


class ServiceClosed(Exception):
    """submit() after stop(): the service no longer accepts work."""


@dataclass(frozen=True)
class HloRequest:
    """One HLO dry-run cell (the TPU analogue of AnalysisRequest)."""

    text: str
    machine: str = "tpu_v5e"
    mode: str = "analytic"
    ici_links: float = 1.0
    flop_dtype: str = "bf16"
    working_set: float | None = None


@dataclass(frozen=True)
class ServiceRequest:
    """One tenant-attributed unit of work.

    Exactly one of ``analysis`` / ``hlo`` must be set.  ``timeout_s``
    is the caller's deadline measured from submit; ``None`` means the
    service default.  ``backend`` overrides the batch-simulation driver
    for the cohort this request lands in (requests with different
    backends never share a cohort).
    """

    analysis: AnalysisRequest | None = None
    hlo: HloRequest | None = None
    tenant: str = "default"
    timeout_s: float | None = None
    backend: str | None = None
    tag: str = ""            # free-form label echoed into trace events

    def __post_init__(self):
        if (self.analysis is None) == (self.hlo is None):
            raise ValueError("exactly one of analysis=/hlo= must be set")

    @property
    def kind(self) -> str:
        return "x86" if self.analysis is not None else "hlo"


@dataclass
class ServiceResponse:
    """Result envelope: the engine result plus latency attribution."""

    request: ServiceRequest
    result: Any = None               # AnalysisResult | HloAnalysis
    error: BaseException | None = None
    cache_hit: bool = False          # served from the cross-request cache
    queue_s: float = 0.0             # submit -> cohort formation
    dispatch_s: float = 0.0          # engine batch dispatch (shared)
    total_s: float = 0.0             # submit -> response
    cohort_size: int = 0             # batch the request dispatched in
    # degradation provenance, copied from the engine result — a
    # degraded answer is never silently indistinguishable from a
    # full-fidelity one (docs/robustness.md)
    degraded: bool = False
    backend_used: str = ""           # rung that answered ("" = none)
    fault_trace_id: int = 0          # FaultInjector event id (0 = none)
    routed_from: str = ""            # rung the HealthRouter skipped
    #                                  pre-dispatch ("" = not routed)
    probe: bool = False              # answered by a scheduled half-open
    #                                  probe dispatch

    @property
    def ok(self) -> bool:
        return self.error is None

    @classmethod
    def provenance_of(cls, result: Any) -> dict[str, Any]:
        """The degradation fields carried by an engine result (empty
        defaults for result types without them, e.g. HloAnalysis)."""
        return {
            "degraded": bool(getattr(result, "degraded", False)),
            "backend_used": str(getattr(result, "backend_used", "")),
            "fault_trace_id": int(getattr(result, "fault_trace_id", 0)),
            "routed_from": str(getattr(result, "routed_from", "")),
            "probe": bool(getattr(result, "probe", False)),
        }
