"""Backend degradation ladder: circuit breakers + result validation.

The analytic bound (the source paper) and the cycle-level simulator are
redundant predictors of the same quantity, which is exactly the
structure graceful degradation needs: when an expensive backend fails,
a cheaper one still answers, and the analytic bound is the floor that
never goes away.  The rung sequence is

    pallas -> jit -> numpy -> analytic-only

(`tick` — the per-program reference interpreter used for small batches
— is its own single-rung ladder above the analytic floor).

Per-(machine digest x backend) :class:`CircuitBreaker` state machines
stop the engine from hammering a rung that keeps failing:

    closed ──failures >= threshold──> open ──cooldown──> half_open
      ^                                                      │
      └──────────── probe succeeds ──────────────────────────┤
                                                             │
                    probe fails ──> open (cooldown restarts) ─┘

All clocks are injectable so the chaos suite can step time without
sleeping.  The :class:`BreakerBoard` keeps a bounded transition log —
the telemetry that makes breaker opening/half-opening visible in
``service.export_stats()``.

:class:`HealthRouter` turns the breakers from *reactive* containment
into *proactive* routing: instead of attempting a rung and demoting on
failure, the dispatcher asks the router for a :class:`RoutePlan` first
— an open rung is skipped before any dispatch is paid, and a rung due
for a half-open probe gets at most one scheduled probe dispatch per
cooldown window while all other traffic routes below it
(docs/robustness.md#health-aware-routing).
"""

from __future__ import annotations

import json
import math
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, Sequence

from .faults import InjectedFault, ResultValidationError

__all__ = [
    "LADDER", "ladder_from", "contained_faults", "BreakerConfig", "CircuitBreaker",
    "BreakerBoard", "validate_sims", "RouterConfig", "RoutePlan",
    "HealthRouter",
]

# sim rungs, most to least expensive; "analytic" is the implicit floor
LADDER: tuple[str, ...] = ("pallas", "jit", "numpy")


def ladder_from(backend: str) -> tuple[str, ...]:
    """The sim rungs at or below ``backend``.

    ``tick`` (the small-batch reference interpreter) has no cheaper sim
    rung — its only fallback is the analytic floor."""
    if backend == "tick":
        return ("tick",)
    try:
        i = LADDER.index(backend)
    except ValueError:
        raise ValueError(f"unknown sim backend {backend!r}; "
                         f"known: {', '.join(LADDER)} or 'tick'") from None
    return LADDER[i:]


def contained_faults() -> tuple[type[Exception], ...]:
    """The failures a rung contains by demoting: injected faults,
    validator rejections and device runtime errors.

    Anything else — ``ImportError``, ``AttributeError``, ``TypeError``,
    ``NotImplementedError``, ``ValueError`` — is a bug or a bad request
    and propagates; demoting it would turn a broken device path into
    quiet answers from a cheaper rung.  :class:`~.faults.FaultAbort` is
    an ``InjectedFault`` too, so handlers re-raise it first.  Called in
    the ``except`` clause, so jax is imported only once a rung fails."""
    from jax.errors import JaxRuntimeError
    return (InjectedFault, ResultValidationError, JaxRuntimeError)


@dataclass(frozen=True)
class BreakerConfig:
    """``failure_threshold`` consecutive failures open the breaker;
    after ``cooldown_s`` one half-open probe is allowed through."""

    failure_threshold: int = 3
    cooldown_s: float = 30.0

    def __post_init__(self):
        if self.failure_threshold < 1:
            raise ValueError("failure_threshold must be >= 1")
        if self.cooldown_s < 0:
            raise ValueError("cooldown_s must be >= 0")


class CircuitBreaker:
    """closed / open / half_open with cooldown; injectable clock."""

    def __init__(self, config: BreakerConfig,
                 clock: Callable[[], float] = time.monotonic,
                 on_transition: Callable[[str, str, float], None] | None = None):
        self.config = config
        self._clock = clock
        self._on_transition = on_transition
        self._state = "closed"
        self._failures = 0
        self._opened_at = 0.0

    @property
    def state(self) -> str:
        return self._state

    @property
    def failures(self) -> int:
        return self._failures

    def _set(self, state: str) -> None:
        if state == self._state:
            return
        prev, self._state = self._state, state
        if self._on_transition is not None:
            self._on_transition(prev, state, self._clock())

    def peek(self, now: float | None = None) -> str:
        """Effective state at ``now`` *without* transitioning.

        Unlike :meth:`allow`, this never mutates the breaker, so a
        routing policy can look before it leaps: ``"closed"`` /
        ``"half_open"`` / ``"open"`` mirror :attr:`state`, and
        ``"due_probe"`` reports an open breaker whose cooldown has
        elapsed — the next :meth:`allow` call would admit one probe."""
        if self._state == "open":
            t = self._clock() if now is None else now
            if t - self._opened_at >= self.config.cooldown_s:
                return "due_probe"
        return self._state

    def allow(self) -> bool:
        """May a dispatch be attempted on this rung right now?

        An open breaker whose cooldown has elapsed transitions to
        half_open and lets exactly one probe through."""
        if self._state == "closed":
            return True
        if self._state == "open":
            if self._clock() - self._opened_at >= self.config.cooldown_s:
                self._set("half_open")
                return True
            return False
        # half_open: a probe is already in flight (or just allowed);
        # further calls wait for its verdict
        return False

    def record_success(self) -> None:
        self._failures = 0
        self._set("closed")

    def record_failure(self) -> None:
        self._failures += 1
        if self._state == "half_open" or self._failures >= self.config.failure_threshold:
            self._opened_at = self._clock()
            self._set("open")

    def snapshot(self) -> dict:
        return {"state": self._state, "failures": self._failures,
                "opened_at": self._opened_at}


class BreakerBoard:
    """Lazily-created breakers keyed (machine digest, backend), plus a
    bounded transition-event log.  Thread-safe."""

    def __init__(self, config: BreakerConfig | None = None,
                 clock: Callable[[], float] = time.monotonic,
                 event_capacity: int = 256):
        self.config = config or BreakerConfig()
        self._clock = clock
        self._lock = threading.Lock()
        self._breakers: dict[tuple[str, str], CircuitBreaker] = {}
        self._events: deque[dict] = deque(maxlen=event_capacity)

    def breaker(self, machine_digest: str, backend: str) -> CircuitBreaker:
        key = (machine_digest, backend)
        with self._lock:
            br = self._breakers.get(key)
            if br is None:
                label = f"{machine_digest[:12]}/{backend}"

                def log(prev: str, new: str, t: float, _label=label) -> None:
                    self._events.append(
                        {"breaker": _label, "from": prev, "to": new, "t": t})

                br = CircuitBreaker(self.config, clock=self._clock,
                                    on_transition=log)
                self._breakers[key] = br
            return br

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "breakers": {f"{d[:12]}/{b}": br.snapshot()
                             for (d, b), br in sorted(self._breakers.items())},
                "events": list(self._events),
            }

    def reset(self) -> None:
        with self._lock:
            self._breakers.clear()
            self._events.clear()


# ----------------------------------------------------------------------
# health-aware dispatch routing
# ----------------------------------------------------------------------
@dataclass(frozen=True)
class RouterConfig:
    """Tunables of one :class:`HealthRouter`.

    ``probe_interval_s`` is the minimum spacing between half-open probe
    dispatches per (machine digest, rung); ``None`` (default) uses the
    breaker's own cooldown, so at most one probe is scheduled per
    cooldown window."""

    probe_interval_s: float | None = None

    def __post_init__(self):
        if self.probe_interval_s is not None and self.probe_interval_s < 0:
            raise ValueError("probe_interval_s must be >= 0 or None")

    def to_dict(self) -> dict:
        return {"probe_interval_s": self.probe_interval_s}

    @classmethod
    def from_dict(cls, d) -> "RouterConfig":
        return cls(probe_interval_s=d.get("probe_interval_s"))


@dataclass(frozen=True)
class RoutePlan:
    """One routing decision: the rungs to walk (healthiest first),
    where the dispatch was routed *from* (``""`` when it starts at the
    requested rung), and whether the first rung is a scheduled
    half-open probe.  An empty ``rungs`` means every rung is unhealthy
    and the group should take the analytic floor without paying a
    single dispatch."""

    rungs: tuple[str, ...] = ()
    routed_from: str = ""
    probe: bool = False


class HealthRouter:
    """Breaker-aware routing policy: pick the healthiest rung *before*
    dispatch instead of demoting after a failure.

    Serializable (:meth:`to_json` round-trips the policy config; the
    probe bookkeeping is runtime state) with an injectable clock so the
    chaos suite can step time.  Thread-safe: the probe ledger is
    lock-protected.

    Routing semantics per rung, walked healthiest-first from the
    requested rung down (:func:`ladder_from`):

    * ``closed`` — dispatch here.
    * ``open`` (cooldown pending) — skip without paying a dispatch.
    * ``due_probe`` (open, cooldown elapsed) — at most one scheduled
      probe dispatch per ``probe_interval_s`` window is routed here
      (``RoutePlan.probe=True``); all other traffic routes below.
    * ``half_open`` — a probe is already in flight; route below.
    """

    def __init__(self, config: RouterConfig | None = None,
                 clock: Callable[[], float] = time.monotonic):
        self.config = config or RouterConfig()
        self._clock = clock
        self._lock = threading.Lock()
        # (machine digest, rung) -> time of the last scheduled probe
        self._last_probe: dict[tuple[str, str], float] = {}
        self.stats = {"plans": 0, "routed": 0, "probes": 0,
                      "floor_routes": 0}

    # -- serialization (policy config only) ---------------------------
    def to_dict(self) -> dict:
        return {"config": self.config.to_dict()}

    @classmethod
    def from_dict(cls, d, clock: Callable[[], float] = time.monotonic,
                  ) -> "HealthRouter":
        return cls(RouterConfig.from_dict(d.get("config", {})),
                   clock=clock)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    @classmethod
    def from_json(cls, text: str,
                  clock: Callable[[], float] = time.monotonic,
                  ) -> "HealthRouter":
        return cls.from_dict(json.loads(text), clock=clock)

    # -- routing ------------------------------------------------------
    def _route(self, board: BreakerBoard, digest: str,
               rungs: Sequence[str], consume: bool) -> RoutePlan:
        now = self._clock()
        rungs = tuple(rungs)
        for i, rung in enumerate(rungs):
            br = board.breaker(digest, rung)
            state = br.peek(now)
            if state == "closed":
                routed = rungs[0] if i else ""
                if consume:
                    with self._lock:
                        self.stats["plans"] += 1
                        self.stats["routed"] += bool(routed)
                return RoutePlan(rungs[i:], routed, False)
            if state == "due_probe":
                interval = (self.config.probe_interval_s
                            if self.config.probe_interval_s is not None
                            else br.config.cooldown_s)
                key = (digest, rung)
                with self._lock:
                    last = self._last_probe.get(key)
                    due = last is None or now - last >= interval
                    if due and consume:
                        self._last_probe[key] = now
                        self.stats["plans"] += 1
                        self.stats["probes"] += 1
                        self.stats["routed"] += bool(i)
                if due:
                    return RoutePlan(rungs[i:], rungs[0] if i else "",
                                     True)
            # open / half_open / probe-slot taken: route below
        if consume:
            with self._lock:
                self.stats["plans"] += 1
                self.stats["floor_routes"] += 1
        return RoutePlan((), rungs[0] if rungs else "", False)

    def plan(self, board: BreakerBoard, digest: str,
             rungs: Sequence[str]) -> RoutePlan:
        """Commit to a routing decision for one dispatch (a returned
        probe consumes the probe slot for its window)."""
        return self._route(board, digest, rungs, consume=True)

    def preview(self, board: BreakerBoard, digest: str,
                rungs: Sequence[str]) -> RoutePlan:
        """The decision :meth:`plan` *would* make, without consuming a
        probe slot or touching the stats — the service's pre-dispatch
        consult (the engine's :meth:`plan` at dispatch time stays the
        single probe scheduler)."""
        return self._route(board, digest, rungs, consume=False)

    def snapshot(self) -> dict:
        with self._lock:
            return {"config": self.config.to_dict(),
                    "stats": dict(self.stats),
                    "pending_probes": {f"{d[:12]}/{r}": t for (d, r), t
                                       in sorted(self._last_probe.items())}}

    def reset(self) -> None:
        with self._lock:
            self._last_probe.clear()
            for k in self.stats:
                self.stats[k] = 0


# ----------------------------------------------------------------------
# post-dispatch result validation
# ----------------------------------------------------------------------
def validate_sims(sims: Sequence, progs: Sequence,
                  divergence_factor: float = 50.0) -> list[str]:
    """Problems with a backend's output, empty when clean.

    Rejects non-finite or negative cycle counts outright, and flags
    implausible divergence from each program's analytic port bound —
    the sim models *more* constraints than port pressure (front end,
    dependencies), so it can exceed the bound, but not by 50x; and it
    cannot undercut a positive bound by 50x either.  Corrupt output is
    thereby treated exactly like a dispatch fault."""
    problems: list[str] = []
    for sim, prog in zip(sims, progs):
        cpi = sim.cycles_per_iteration
        label = prog.digest[:12]
        if not math.isfinite(cpi):
            problems.append(f"{label}: non-finite cycles ({cpi})")
            continue
        if cpi < 0:
            problems.append(f"{label}: negative cycles ({cpi})")
            continue
        bound = prog.port_bound_cycles
        if bound > 0:
            if cpi > bound * divergence_factor:
                problems.append(
                    f"{label}: {cpi:.3f} cy/it diverges above "
                    f"{divergence_factor:.0f}x the {bound:.3f} port bound")
            elif cpi * divergence_factor < bound:
                problems.append(
                    f"{label}: {cpi:.3f} cy/it diverges below "
                    f"1/{divergence_factor:.0f}x the {bound:.3f} port bound")
    return problems
