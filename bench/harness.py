"""What every cell shares: finding a cell's files by name, the device
check, compile counting, the output check and the result line.

Nothing here knows a configuration, a traffic mix or a metric by name:
``BENCHMARK.json`` names them, and each lives in a file of its own
(``configs/<config>.json``, ``traffic/<traffic>.json`` with the driver
it names in ``drivers/<driver>.py``, ``metrics/<metric>.py``).
"""
from __future__ import annotations

import importlib.util
import json
import random
import sys
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import one file by path (metric files carry dots in their
    names, so the import statement cannot name them)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    driver: ModuleType
    end_to_end: list[dict]
    per_layer: list[dict]


def _applies(metric: dict, cell: str, reported: set[str]) -> bool:
    """A per-layer metric is read in the cells it lists, or, listing
    none, in every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in reported


def find_cell(name: str) -> Cell:
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    config = load_json(ROOT / next(c["file"] for c in bench["configs"]
                                   if c["name"] == entry["config"]))
    traffic = load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, name, reported)]
    return Cell(name, int(entry["chips"]), config, traffic, driver, e2e,
                layer)


def read_metric(metric: dict, run) -> float | None:
    mod = load_module(BENCH / "metrics" / f"{metric['name']}.py")
    return mod.read(run)


class NoAccelerator(RuntimeError):
    pass


def device_info(chips: int) -> dict:
    """The devices JAX reports; raises :class:`NoAccelerator` where
    there is no TPU or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoAccelerator(
            f"JAX found {len(devs)} {devs[0].platform!r} device(s); this "
            f"cell needs {chips} TPU chip(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, where reported."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


class CompileCounter:
    """Executables JAX built or loaded from its persistent cache, with
    their seconds and the time of each, from JAX's own events."""

    def __init__(self, clock):
        import jax
        self.clock = clock
        self.events: list[tuple[float, float]] = []   # (end time, seconds)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((self.clock(), duration))

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.events if t0 <= t <= t1)

    def seconds(self) -> float:
        return sum(d for _, d in self.events)


@dataclass
class Context:
    """What a driver gets: the cell's files, the seed, the window, the
    machine model (as the reference reads it and as the program takes
    it), a clock, a span factory and a log."""

    config: dict
    traffic: dict
    seed: int
    seconds: float
    machine: object           # reference.x86.Machine
    model: object             # the program's MachineModel
    clock: object
    span: object              # name -> context manager (host trace span)
    t_window: float | None = None

    def start_window(self) -> float:
        """Mark the first timed request; returns its time."""
        self.t_window = self.clock()
        return self.t_window

    @staticmethod
    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


@dataclass
class Item:
    """One answer the user received, as the check needs it."""

    source: str
    answer: tuple            # (cycles per iteration, converged, bottleneck)
    rung: str                # backend that answered
    degraded: bool


@dataclass
class Run:
    """What a run measured, for the metric readers."""

    cell: str
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    values: dict = field(default_factory=dict)     # driver's own numbers
    trace: dict | None = None                      # reduced device trace
    compiles_in_window: int = 0


def sample_items(items: list[Item], n: int, seed: int,
                 length=lambda src: src.count("\n")) -> list[Item]:
    """Up to ``n`` answers drawn from the seed, the longest body always
    among them."""
    pool = sorted(items, key=lambda it: it.source)
    if len(pool) <= n:
        return pool
    longest = max(pool, key=lambda it: length(it.source))
    rest = [it for it in pool if it is not longest]
    return [longest] + random.Random(f"check/{seed}").sample(rest, n - 1)


def check(items: list[Item], machine) -> dict:
    """Compare each sampled answer with the plain reference: the batched
    numpy recurrence over programs compiled independently from the same
    source.  An answer that is degraded or not from the device
    recurrence (``jit``) is not compared and counts as off rung.
    Returns the counts compared, each with its limit."""
    from .reference.batch import simulate
    from .reference.x86 import compile_source

    off = [it for it in items if it.degraded or it.rung != "jit"]
    on = [it for it in items if not (it.degraded or it.rung != "jit")]
    refs = simulate([compile_source(it.source, machine) for it in on],
                    machine.ports, machine.pipeline)
    mismatches = []
    for it, ref in zip(on, refs):
        want = (ref.cycles_per_iteration, ref.converged, ref.bottleneck)
        if it.answer != want:
            mismatches.append((it, want))
    for it, want in mismatches[:10]:
        first = it.source.splitlines()[0]
        print(f"MISMATCH {first} program {it.answer} reference {want}",
              file=sys.stderr)
    for it in off[:10]:
        print(f"OFF RUNG {it.source.splitlines()[0]} rung {it.rung!r} "
              f"degraded {it.degraded}", file=sys.stderr)
    return {"mismatches": {"value": len(mismatches), "limit": 0},
            "off_rung_or_degraded": {"value": len(off), "limit": 0}}
