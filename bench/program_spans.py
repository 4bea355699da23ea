"""Reduction of the program's own ``repro.*`` host spans in a profiler
trace, beside the device record that ``bench/trace.py`` reduces.

The program marks each phase of a ``predict_batch`` call with a
``repro.*`` span (``repro.core.spans``; docs/performance.md) carrying
the call's sequence number as its ``call`` stat, on whichever thread
runs the phase (the shards run on worker threads).  From those spans
and the device's program executions this computes, over the traced
window that ``trace.reduce`` settles from the benchmark's own spans
(program spans never move it):

- the idle stretches of the first device, each split at program-span
  boundaries and labelled with the innermost span open then: the
  latest-started of the ``repro.*`` and ``bench.*`` spans, on any
  thread, and of those the shortest (with no program spans,
  ``trace.reduce``'s gaps);
- each program span's self time, its duration less the part its
  children on the same thread cover, summed by name over the calls
  that lie inside the window;
- the share of device busy time inside ``repro.sim.escalate``, and the
  share of idle time that no program span covers.
"""
from __future__ import annotations

import glob
from dataclasses import dataclass

from bench import trace

PREFIX = "repro."
CALL_SPAN = "repro.predict_batch"
ESCALATE_SPAN = "repro.sim.escalate"
NO_SPAN = "outside any bench span"


@dataclass(frozen=True)
class Span:
    name: str
    start: float      # seconds, on the device events' clock
    end: float
    thread: int       # host trace line the span was recorded on
    call: int | None  # the ``call`` stat: the batched call it belongs to


def read(trace_dir: str) -> list[Span]:
    """The ``repro.*`` spans of the newest ``.xplane.pb`` under
    ``trace_dir``; each host line (one per thread) is a thread."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        return []
    out: list[Span] = []
    thread = 0
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    call = dict(e.stats).get("call")
                    out.append(Span(e.name, e.start_ns * 1e-9,
                                    e.end_ns * 1e-9, thread,
                                    None if call is None else int(call)))
            thread += 1
    return out


def _overlap(a: list[tuple[float, float]],
             b: list[tuple[float, float]]) -> float:
    """Length of the intersection of two unions of intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _self_times(spans: list[Span]) -> dict[str, float]:
    by_thread: dict[int, list[tuple[float, float, str]]] = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).append((s.start, s.end, s.name))
    out: dict[str, float] = {}
    for ops in by_thread.values():
        for name, d in trace._self_times(ops):
            out[name] = out.get(name, 0.0) + d
    return out


def reduce(host_spans: list[trace.Event],
           device_modules: list[list[trace.Event]],
           spans: list[Span], top: int = 10) -> dict:
    """Reduce the program spans ``spans`` against the benchmark's host
    spans and each device's program executions (one clock)."""
    settled = trace.reduce(host_spans, device_modules)
    if not settled:
        return {}
    w0 = next(s.start for s in host_spans if s.name == trace.WINDOW_SPAN)
    w1 = w0 + settled["window_s"]
    busy = [trace._union([(max(e.start, w0), min(e.end, w1))
                          for e in mods if e.end > w0 and e.start < w1])
            for mods in device_modules]
    busy_s = sum(e - s for dev in busy for s, e in dev) / len(busy)

    calls = {s.call for s in spans if s.name == CALL_SPAN
             and s.start >= w0 and s.end <= w1}
    mine = [s for s in spans if s.call in calls]
    escalate = trace._union([(s.start, s.end) for s in mine
                             if s.name == ESCALATE_SPAN])
    esc_busy = sum(_overlap(dev, escalate) for dev in busy) / len(busy)

    labels = [(s.start, s.end, s.name) for s in host_spans
              if s.name != trace.WINDOW_SPAN]
    labels += [(s.start, s.end, s.name) for s in spans]
    covered = trace._union([(s.start, s.end) for s in spans])
    gaps, unattributed = [], 0.0
    edge = w0
    for s, e in busy[0] + [(w1, w1)]:
        if s > edge:
            cuts = sorted({t for sp in spans for t in (sp.start, sp.end)
                           if edge < t < s})
            for a, b in zip([edge] + cuts, cuts + [s]):
                mid = (a + b) / 2
                open_ = [lb for lb in labels if lb[0] <= mid <= lb[1]]
                gaps.append((max(open_, key=lambda lb: (lb[0], -lb[1]))[2]
                             if open_ else NO_SPAN, b - a))
            unattributed += (s - edge) - _overlap([(edge, s)], covered)
        edge = max(edge, e)
    idle_s = sum(d for _, d in gaps)
    idle_by_span: dict[str, float] = {}
    for label, d in gaps:
        idle_by_span[label] = idle_by_span.get(label, 0.0) + d
    return {
        "window_s": settled["window_s"],
        "calls": len(calls),
        "idle_gaps": [[n, d] for n, d in sorted(gaps,
                                                key=lambda x: -x[1])[:top]],
        "idle_by_span": idle_by_span,
        "self_s": _self_times(mine),
        "escalation_busy_share": esc_busy / busy_s if busy_s else 0.0,
        "idle_unattributed_share": unattributed / idle_s if idle_s
        else 0.0,
    }
