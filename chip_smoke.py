#!/usr/bin/env python3
"""Smoke run of the predictor's main path on one TPU chip.

Drives the path users call — ``PredictionService`` -> ``AnalysisService``
-> ``simulate_many(backend="jit")`` — at a realistic corpus size and
holds every answer bit-identical to the numpy reference driver:

* phase A, bulk sweep: ``AnalysisService(sim_backend="jit")`` predicts
  ``mode="simulate"`` for ``--per-arch`` distinct loop bodies on each of
  Skylake and Zen, under the uniform scheduler and (every 16th body) the
  balanced one, cold (compiling) and warm;
* phase B, served path: a ``PredictionService`` over the same engine
  answers 64 requests for fresh loop bodies.

Loop bodies are drawn from ``--seed``: 4-48 instruction forms from the
architecture's own database, with register dependency chains and the
loop branch, each inside the ROB and scheduler windows so the compiled
driver takes all of them.

This is a smoke run, not a benchmark: its times include compilation
and host work and are printed for orientation only.  It needs a TPU.
On any other platform it exits non-zero at once, or with
``--rehearse`` runs every phase there and still exits non-zero; it
never prints ``"ok": true`` off the chip.  Run from the repository root::

    python3 chip_smoke.py                                  # on the chip
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse --per-arch 128

The last line of a passing chip run is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import json
import random
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCHS = ("skl", "zen")
SCHEDULERS = ("uniform", "balanced")
# the balanced scheduler's min-max LP costs ~0.1 s of host time per body
# (pure-Python max-flow), so phase A pairs it with every 16th body only
BALANCED_EVERY = 16
SERVED_REQUESTS = 64
REFERENCE_CHUNK = 256

# operand types the generator can spell in AT&T syntax, and the
# registers it spells them with; %rax (induction), %rbp (trip count)
# and the memory bases are left out so the loop control stays intact
_GPR64 = ("rbx", "rcx", "rdx", "r8", "r9", "r10", "r11", "r12", "r13",
          "r14", "r15")
_GPR32 = ("ebx", "ecx", "edx", "r8d", "r9d", "r10d", "r11d", "r12d",
          "r13d", "r14d", "r15d")
_BASES = ("rsi", "rdi")
_TOKENS = {"xmm", "ymm", "mem", "imm", "r", "r64", "r32"}


def _usable_forms(db) -> list:
    """The database forms the generator can spell and that parse back
    to a form of the same database (branches excluded)."""
    from repro.core import extract_kernel
    from repro.core.isa import is_branch

    rng = random.Random(0)
    forms = []
    for form in db.entries():
        sig = form.signature
        if not sig or not set(sig) <= _TOKENS or is_branch(form.mnemonic) \
                or form.mnemonic in ("push", "pop", "call", "ret"):
            continue
        text = _spell(form, rng, [], [])
        body = extract_kernel(f".L0:\n{text}\njne .L0\n")
        if len(body) == 2 and db.lookup(body[0]) is not None:
            forms.append(form)
    return forms


def _spell(form, rng: random.Random, vec_live: list, gpr_live: list
           ) -> str:
    """One instruction of ``form`` in AT&T syntax.  Sources read a
    recently written register most of the time, so bodies carry
    dependency chains (and loop-carried ones through reuse)."""
    ops = []
    for pos, tok in enumerate(form.signature):      # Intel order
        src = pos > 0
        if tok in ("xmm", "ymm"):
            if src and vec_live and rng.random() < 0.6:
                n = rng.choice(vec_live[-3:])
            else:
                n = rng.randrange(16)
            if not src:
                vec_live.append(n)
            ops.append(f"%{tok}{n}")
        elif tok in ("r", "r64", "r32"):
            names = _GPR32 if tok == "r32" else _GPR64
            if src and gpr_live and rng.random() < 0.6:
                i = rng.choice(gpr_live[-3:])
            else:
                i = rng.randrange(len(names))
            if not src:
                gpr_live.append(i)
            ops.append(f"%{names[i]}")
        elif tok == "mem":
            ops.append(f"{8 * rng.randrange(8)}(%{rng.choice(_BASES)},"
                       f"%rax,8)")
        else:
            ops.append(f"${rng.randrange(1, 64)}")
    return f"{form.mnemonic} {', '.join(reversed(ops))}".strip()


def make_corpus(arch: str, n: int, seed: int) -> list[tuple[str, object]]:
    """``n`` distinct loop bodies for ``arch`` as ``(source, program)``;
    the programs are compiled independently of the engine and feed the
    numpy reference."""
    from repro.core import compile_program, extract_kernel, get_model
    from repro.core.arch.registry import default_registry
    from repro.core.sim.batch import _jit_compatible

    db = default_registry().database(arch)
    params = get_model(arch).pipeline
    forms = _usable_forms(db)
    rng = random.Random(f"{seed}/{arch}")
    out, seen = [], set()
    while len(out) < n:
        vec_live: list = []
        gpr_live: list = []
        lines = [f".L{len(out)}:"]
        for _ in range(rng.randint(4, 48)):
            lines.append(_spell(rng.choice(forms), rng, vec_live,
                                gpr_live))
        lines += ["addq $8, %rax", "cmpq %rbp, %rax",
                  f"jne .L{len(out)}"]
        src = "\n".join(lines) + "\n"
        prog = compile_program(extract_kernel(src), arch)
        if prog.digest in seen or not _jit_compatible([prog], params):
            continue
        seen.add(prog.digest)
        out.append((src, prog))
    return out


class CompileCounter:
    """Backend compiles and their seconds, from JAX's own events."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def _check(results, reqs, reference, what: str) -> None:
    """Every result on the jit rung, not degraded, and bit-identical
    to the numpy reference driver."""
    bad = []
    for req, res in zip(reqs, results):
        ref = reference[(req.arch, req.kernel)]
        sim = res.sim_result
        if res.degraded or res.backend_used != "jit" or sim is None:
            bad.append(f"{req.arch}: degraded={res.degraded} "
                       f"backend_used={res.backend_used!r}")
        elif (sim.cycles_per_iteration != ref.cycles_per_iteration
              or sim.converged != ref.converged
              or sim.bottleneck != ref.bottleneck):
            bad.append(f"{req.arch}: jit {sim.cycles_per_iteration!r} "
                       f"{sim.converged} {sim.bottleneck} != numpy "
                       f"{ref.cycles_per_iteration!r} {ref.converged} "
                       f"{ref.bottleneck}")
    if bad:
        for line in bad[:20]:
            print(f"MISMATCH {what}: {line}", file=sys.stderr)
        raise SystemExit(f"{what}: {len(bad)} of {len(results)} results "
                         "off the jit rung or not equal to numpy")


def _reference(corpus: dict) -> dict:
    """The numpy driver's answer for every body.  Lanes are independent,
    so bodies run in chunks of similar length: every lane of a call pads
    to its longest body, and one call over the whole corpus would cost
    about three times as much host time."""
    from repro.core.sim import simulate_many
    ref = {}
    for arch, items in corpus.items():
        items = sorted(items, key=lambda item: len(item[1].uops))
        for i in range(0, len(items), REFERENCE_CHUNK):
            chunk = items[i:i + REFERENCE_CHUNK]
            sims = simulate_many([p for _, p in chunk], backend="numpy")
            ref.update(((arch, src), s) for (src, _), s in zip(chunk, sims))
    return ref


def phase_a(corpus: dict, reference: dict, counter: CompileCounter):
    from repro.core import AnalysisRequest, AnalysisService
    from repro.core.sim.batch import _compiled_run

    reqs = [AnalysisRequest(kernel=src, arch=arch, mode="simulate",
                            scheduler=sched)
            for arch, items in corpus.items()
            for i, (src, _) in enumerate(items)
            for sched in SCHEDULERS[:1 + (i % BALANCED_EVERY == 0)]]
    engine = AnalysisService(sim_backend="jit")
    for label in ("cold", "warm"):
        # warm: results expire, compiled programs and LP solves stay
        if label == "warm":
            engine.drop_results()
        st = engine.stats
        runs, groups, rungs = (st.sim_runs, st.sim_group_dispatches,
                               dict(st.rung_attempts))
        c0, s0 = counter.count, counter.seconds
        t0 = time.perf_counter()
        results = engine.predict_batch(reqs)
        wall = time.perf_counter() - t0
        _check(results, reqs, reference, f"phase A ({label})")
        tried = {k: v - rungs.get(k, 0)
                 for k, v in st.rung_attempts.items()}
        print(f"phase A {label}: {len(reqs)} cells, "
              f"{st.sim_runs - runs} simulations in "
              f"{st.sim_group_dispatches - groups} group dispatches, "
              f"rung attempts {tried}, {counter.count - c0} compiles "
              f"({counter.seconds - s0:.1f} s), wall {wall:.1f} s",
              flush=True)
    print(f"phase A: {_compiled_run.cache_info().currsize} compiled shape "
          f"buckets; every result on jit, not degraded, equal to numpy",
          flush=True)
    return engine


def phase_b(engine, fresh: dict, reference: dict) -> None:
    from repro.core import AnalysisRequest
    from repro.service import (PredictionService, ServiceConfig,
                               ServiceRequest, replay)

    traffic = [(0.0, ServiceRequest(
        analysis=AnalysisRequest(kernel=src, arch=arch, mode="simulate"),
        tenant=f"t{i % 2}"))
        for arch, items in fresh.items()
        for i, (src, _) in enumerate(items)]
    service = PredictionService(engine, ServiceConfig(batch_window_s=0.05))
    t0 = time.perf_counter()
    resps = replay(service, traffic)
    wall = time.perf_counter() - t0
    failed = [r for r in resps if not r.ok]
    if failed:
        raise SystemExit(f"phase B: {len(failed)} responses failed, "
                         f"first: {failed[0].error!r}")
    cohorts = [r.cohort_size for r in resps]
    if max(cohorts) < 16:
        raise SystemExit(f"phase B: largest cohort {max(cohorts)} < 16")
    _check([r.result for r in resps], [r.request.analysis for r in resps],
           reference, "phase B")
    print(f"phase B: {len(resps)} served requests, largest cohort "
          f"{max(cohorts)}, wall {wall:.2f} s; every response ok, on jit, "
          f"not degraded, equal to numpy", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--per-arch", type=int, default=4096,
                    help="distinct loop bodies per architecture")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases off the chip; still exits 1")
    args = ap.parse_args()

    import jax

    from repro.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU: JAX found {dev.platform!r}; pass --rehearse to "
              "run the phases here anyway", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    counter = CompileCounter()
    print(f"smoke run (not a benchmark) on {dev.platform} "
          f"{dev.device_kind!r} x{len(jax.devices())}, jax "
          f"{jax.__version__}, compile cache {cache}", flush=True)

    # one draw per arch, split: phase B's bodies are fresh to the engine
    t0 = time.perf_counter()
    served = SERVED_REQUESTS // len(ARCHS)
    drawn = {arch: make_corpus(arch, args.per_arch + served, args.seed)
             for arch in ARCHS}
    reference = _reference(drawn)
    print(f"corpus: {args.per_arch} + {served} distinct loop bodies per "
          f"arch (seed {args.seed}), numpy reference in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)

    engine = phase_a({a: d[:args.per_arch] for a, d in drawn.items()},
                     reference, counter)
    phase_b(engine, {a: d[args.per_arch:] for a, d in drawn.items()},
            reference)

    if dev.platform != "tpu":
        print(f"rehearsal on {dev.platform}: every phase passed, but "
              "this is not a chip run", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
