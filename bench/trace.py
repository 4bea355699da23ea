"""Reduction of one profiler trace to device busy and idle time, the
device operations that took most time, and the idle gaps labelled with
the benchmark's host span open at the time.

Busy time is the union of the intervals in which a program ran on a
device (the ``XLA Modules`` line of each ``/device:TPU:<n>`` plane),
clipped to the benchmark's ``bench.window`` span and averaged over the
devices.  The operation breakdown reads the first ``OPS_SAMPLE``
operations of the ``XLA Ops`` line: operations nest (a ``while`` loop
spans its body's operations), so each is charged its self time, and
they are grouped by kind, the HLO instruction name without its number
(``%while.53 = ...`` is ``while``).  A gap is a stretch of the window
in which no operation ran on the first device; its label is the
innermost ``bench.*`` host span covering its midpoint.  Where the
profiler's buffer filled and the device record stops early, the window
is cut back to the last benchmark span that ends inside the record
(``truncated``).
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass

_DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: operations read back for the breakdown: the first this many
OPS_SAMPLE = 300_000
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
CALL_SPAN = "bench.predict_batch"
#: a device record that stops this long before the window closes was cut
#: short by the profiler, not by an idle device
TRUNCATION_GAP_S = 2.0


@dataclass(frozen=True)
class Event:
    name: str
    start: float      # seconds
    end: float


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_kind(name: str) -> str:
    """``%fusion.880 = (f32[...]) fusion(...)`` -> ``fusion``."""
    return re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


def _self_times(ops: list[tuple[float, float, str]]
                ) -> list[tuple[str, float]]:
    """Each operation's duration less that of the operations directly
    nested in it."""
    out: list[tuple[str, float]] = []
    stack: list[list] = []           # [end, name, start, children]
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and s >= stack[-1][0]:
            end, n, start, kids = stack.pop()
            out.append((n, end - start - kids))
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, s, 0.0])
    out += [(n, end - start - kids) for end, n, start, kids in stack]
    return out


def reduce(host_spans: list[Event], device_modules: list[list[Event]],
           device_ops: list[list[Event]] | None = None,
           top: int = 10) -> dict:
    """Reduce a trace given as the host's ``bench.*`` spans, each
    device's program executions (``XLA Modules``) and, for the
    breakdown, a sample of the first device's operations (all on one
    clock)."""
    windows = [s for s in host_spans if s.name == WINDOW_SPAN]
    if not windows or not device_modules:
        return {}
    w0, w1 = windows[0].start, windows[0].end
    # the profiler stops recording once its buffer is full; a window
    # whose device record ends long before the window does is cut back
    # to the last benchmark span that ends inside the record
    last = max((e.end for mods in device_modules for e in mods
                if w0 < e.end <= w1), default=w0)
    truncated = w1 - last > TRUNCATION_GAP_S
    if truncated:
        w1 = max((s.end for s in host_spans if s.name != WINDOW_SPAN
                  and w0 < s.end <= last), default=last)
    busy = [_union([(max(e.start, w0), min(e.end, w1)) for e in mods
                    if e.end > w0 and e.start < w1])
            for mods in device_modules]
    busy_s = sum(e - s for dev in busy for s, e in dev) / len(busy)
    by_op: dict[str, float] = {}
    ops = [(max(e.start, w0), min(e.end, w1), e.name)
           for e in (device_ops or [[]])[0] if e.end > w0 and e.start < w1]
    for name, d in _self_times(ops):
        kind = op_kind(name)
        by_op[kind] = by_op.get(kind, 0.0) + d
    spans = [s for s in host_spans if s.name != WINDOW_SPAN]
    calls = sum(1 for s in spans if s.name == CALL_SPAN and s.end <= w1)
    gaps = []
    edge = w0
    for s, e in busy[0] + [(w1, w1)]:
        if s > edge:
            mid = (edge + s) / 2
            open_ = [sp for sp in spans if sp.start <= mid <= sp.end]
            label = max(open_, key=lambda sp: sp.start).name if open_ \
                else "outside any bench span"
            gaps.append((label, s - edge))
        edge = max(edge, e)
    idle_by_label: dict[str, float] = {}
    for label, d in gaps:
        idle_by_label[label] = idle_by_label.get(label, 0.0) + d
    return {
        "window_s": w1 - w0,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (w1 - w0),
        "device_ops": sorted(([n, d] for n, d in by_op.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[n, d] for n, d in sorted(gaps,
                                                key=lambda x: -x[1])[:top]],
        "idle_by_span": idle_by_label,
        "truncated": truncated,
        "calls": calls,
        "n_modules": sum(len(m) for m in device_modules),
        "n_ops_sampled": len(ops),
    }


def _events(line, limit: int | None = None) -> list[Event]:
    out = []
    for e in line.events:
        if limit is not None and len(out) >= limit:
            break
        out.append(Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9))
    return out


def read_profile(trace_dir: str) -> tuple[list[Event], list[list[Event]],
                                          list[list[Event]]]:
    """Host ``bench.*`` spans, each device's program executions and the
    first ``OPS_SAMPLE`` operations of each device, from the newest
    ``.xplane.pb`` under ``trace_dir``.  Reading every operation back
    (several million a second of device time) would take minutes."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        return [], [], []
    data = ProfileData.from_file(paths[-1])
    spans: list[Event] = []
    devices: list[tuple[int, list[Event], list[Event]]] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
        elif _DEVICE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            devices.append((
                int(plane.name.rsplit(":", 1)[1]),
                _events(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
                _events(lines[OPS_LINE], OPS_SAMPLE) if OPS_LINE in lines
                else []))
    devices.sort(key=lambda d: d[0])
    return spans, [m for _, m, _ in devices], [o for _, _, o in devices]
