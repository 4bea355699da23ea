"""Closed-loop served traffic: compiler autotuners and interactive users
against one ``PredictionService`` on one asyncio loop.

The configuration's ``clients`` block sets the mix.  Each autotune
client submits a round of distinct, never-repeated candidate bodies, one
``ServiceRequest`` each, all at once, awaits them all, pauses for its
search step and repeats.  Each interactive user submits one body drawn
Zipf from a pool by popularity rank, awaits it, thinks for an
exponential time and repeats.  Every body, draw, pause, think time and
start offset follows from the seed; which requests share a cohort
follows from the timing.

Set-up generates every body the window may use (a client pauses at
least ``pause_s`` between rounds, so it starts no more than the window
over that), builds every compiled shape a service cohort can reach
(``warm_chain``, up to the largest body generated), and replays the
traffic's first round or two on a throwaway service.  The window then
runs the same loop on a fresh service and engine: a prediction is a
response completed inside it, cache hits included, and those answers
are the ones the check samples.

Where the event loop stalls for ``STALL_S`` or more, every thread's
stack goes to standard error (``faulthandler``, whose watchdog needs
no interpreter lock), so a slow run says where it waited.
"""
from __future__ import annotations

import asyncio
import collections
import faulthandler
import itertools
import math
import random
import sys

from bench.corpus import Corpus, length_table
from bench.harness import Item
from bench.reference.batch import composed_edges

#: the set-up replay's length: a round or two of each autotune client,
#: enough to run every service and engine path once before the window
REPLAY_S = 1.0
#: an event-loop stall this long dumps every thread's stack
STALL_S = 0.5


def _lengths(ctx, n: int, key: str) -> list[int]:
    """``n`` quantiles of the configuration's length distribution in an
    order drawn from the seed."""
    order = length_table(ctx.config["corpus"]["length"], n)
    random.Random(f"{key}/{ctx.seed}").shuffle(order)
    return order


def population(ctx) -> dict:
    """The interactive pool, each autotune client's rounds (sources) and
    the ``(uops, composed edges)`` bound of every body among them."""
    at = ctx.config["clients"]["autotune"]
    ia = ctx.config["clients"]["interactive"]
    seen: set[str] = set()
    top = [0, 0]

    def body(corpus: Corpus, index: int, n: int) -> str:
        src, prog = corpus.body(index, (n, n))
        while src in seen:               # distinct across the whole run
            index += 1 << 40
            src, prog = corpus.body(index, (n, n))
        seen.add(src)
        top[0] = max(top[0], len(prog.uops))
        top[1] = max(top[1], len(composed_edges(prog)))
        return src

    corpus = Corpus(ctx.config, ctx.machine, ctx.seed, "interactive")
    pool = [body(corpus, i, n)
            for i, n in enumerate(_lengths(ctx, ia["pool"], "pool"))]
    corpus = Corpus(ctx.config, ctx.machine, ctx.seed, "autotune")
    per_client = math.ceil(ctx.seconds / at["pause_s"])
    rounds = []
    for c in range(at["clients"]):
        mine = []
        for k in range(per_client):
            base = (c * per_client + k) * at["round"]
            mine.append([body(corpus, base + j, n) for j, n in enumerate(
                _lengths(ctx, at["round"], f"round/{c}/{k}"))])
        rounds.append(mine)
    return {"pool": pool, "rounds": rounds, "top": tuple(top)}


def sources(ctx) -> list[str]:
    pop = population(ctx)
    return pop["pool"] + [s for mine in pop["rounds"] for rnd in mine
                          for s in rnd]


def _histogram(h) -> dict[str, int]:
    """A telemetry histogram's non-empty buckets as whole-number
    ranges, ``{"lo-hi": count}``."""
    def edge(i: int) -> int:
        return math.ceil(round(h.lo_s * h.ratio ** i, 9))

    out = {}
    for i, n in enumerate(h.counts):
        if n:
            lo, hi = edge(max(i - 1, 0)), edge(i) - 1
            out[f"{lo}-{hi}" if hi > lo else f"{lo}"] = n
    return out


def _service(ctx):
    from repro.core import AnalysisService
    from repro.service import PredictionService, ServiceConfig, TenantPolicy

    svc = ctx.config["service"]
    engine = AnalysisService(sim_backend=svc["sim_backend"])
    engine.register(ctx.model)
    return PredictionService(engine, ServiceConfig(
        batch_window_s=svc["batch_window_s"],
        max_queue_depth=svc["max_queue_depth"],
        max_cohort=svc["max_cohort"], cache_entries=svc["cache_entries"],
        tenant_policies={name: TenantPolicy(
            max_in_flight=p["max_in_flight"], burst=float(p["burst"]))
            for name, p in svc["tenants"].items()}))


async def _loop(ctx, st, service, start, seconds: float) -> dict:
    """Run every client against ``service`` from ``start()`` (called
    once the service is up; returns the window's start) for
    ``seconds``; clients finish what they have in flight.  Returns the
    records ``(t_done, source, response)``, the submits begun, the
    rejections, the clients' wake-up lateness and, as at the window's
    end, the engine's device calls and the dispatches with their
    requests."""
    from repro.service import AdmissionError, ServiceClosed

    at = ctx.config["clients"]["autotune"]
    ia = ctx.config["clients"]["interactive"]
    clock = ctx.clock
    out = {"records": [], "begun": 0, "rejected": 0, "late": []}
    await service.start()
    t0 = start()
    t_end = t0 + seconds

    async def nap(delay: float) -> None:
        due = clock() + delay
        await asyncio.sleep(delay)
        out["late"].append(clock() - due)

    async def ask(req):
        out["begun"] += 1
        try:
            resp = await service.submit(req)
        except (AdmissionError, ServiceClosed):
            out["rejected"] += 1
            return
        out["records"].append((clock(), req.analysis.kernel, resp))

    async def autotune(c: int) -> None:
        rng = random.Random(f"autotune/{ctx.seed}/{c}")
        await nap(rng.uniform(0.0, at["pause_s"]))
        for rnd in st["rounds"][c]:
            if clock() >= t_end:
                return
            await asyncio.gather(*(ask(r) for r in rnd))
            await nap(at["pause_s"])
        ctx.log(f"autotune client {c} used all its rounds")

    weights = list(itertools.accumulate(
        1.0 / (r + 1) ** ia["zipf"] for r in range(len(st["pool"]))))

    async def interactive(c: int) -> None:
        rng = random.Random(f"interactive/{ctx.seed}/{c}")
        await nap(rng.uniform(0.0, ia["think_s"]))
        while clock() < t_end:
            rank = rng.choices(range(len(st["pool"])), cum_weights=weights)
            await ask(st["pool"][rank[0]])
            await nap(rng.expovariate(1.0 / ia["think_s"]))

    async def at_end() -> None:
        await asyncio.sleep(max(0.0, t_end - clock()))
        sizes = service.telemetry.batch_size
        out.update(dispatches=sizes.count, dispatched_requests=sizes.sum,
                   sim_device_calls=service.engine.stats.sim_device_calls,
                   longest_dispatch_s=service.telemetry.dispatch.max,
                   cohort_sizes=_histogram(sizes))

    async def watchdog() -> None:
        try:
            while True:
                faulthandler.dump_traceback_later(STALL_S, file=sys.stderr)
                await asyncio.sleep(STALL_S / 5)
        finally:
            faulthandler.cancel_dump_traceback_later()

    guard = asyncio.ensure_future(watchdog())
    await asyncio.gather(
        at_end(), *(autotune(c) for c in range(at["clients"])),
        *(interactive(c) for c in range(ia["clients"])))
    guard.cancel()
    await service.stop()
    out["t0"], out["t_end"] = t0, t_end
    return out


def prepare(ctx):
    from repro.core import AnalysisRequest
    from repro.core.sim import warm_chain
    from repro.service import ServiceRequest

    req = ctx.config["request"]

    def request(src: str, tenant: str) -> ServiceRequest:
        return ServiceRequest(analysis=AnalysisRequest(
            kernel=src, arch=ctx.model.arch_id, mode=req["mode"],
            scheduler=req["scheduler"]), tenant=tenant)

    with ctx.span("bench.generate"):
        pop = population(ctx)
        st = {"pool": [request(s, "interactive") for s in pop["pool"]],
              "rounds": [[[request(s, "autotune") for s in rnd]
                          for rnd in mine] for mine in pop["rounds"]]}
    ctx.log(f"generated {len(pop['pool'])} pool bodies and "
            f"{sum(len(m) for m in pop['rounds'])} rounds; largest body "
            f"{pop['top'][0]} uops, {pop['top'][1]} edges")
    with ctx.span("bench.warm"):
        built = warm_chain(ctx.model, req["n_iterations"], up_to=pop["top"])
        ctx.log(f"warmed {len(built)} cohort shapes (U, E, T): {built}")
        asyncio.run(_loop(ctx, st, _service(ctx), ctx.clock, REPLAY_S))
    st["service"] = _service(ctx)
    return st


def window(ctx, st, run) -> None:
    from repro.core.sim import AUTO_JIT_MIN_BATCH

    with ctx.span("bench.served"):
        out = asyncio.run(_loop(ctx, st, st["service"], ctx.start_window,
                                ctx.seconds))
    t_end = out["t_end"]
    done = [(t, src, r) for t, src, r in out["records"]
            if t <= t_end and r.ok]
    engine = [r for _, _, r in done if not r.cache_hit]
    # the answers the window counts are the answers the check samples
    st["answers"] = [(src, r) for _, src, r in done]
    run.attempted = out["begun"]
    run.failed = out["rejected"] + sum(
        1 for _, _, r in out["records"] if not r.ok)
    run.values.update(
        predictions=len(done),
        elapsed_s=max((t for t, _, _ in done), default=t_end) - out["t0"],
        queue_s=[r.queue_s for r in engine],
        cache_hits=len(done) - len(engine),
        engine_predictions=len(engine),
        dispatches=out["dispatches"],
        dispatched_requests=out["dispatched_requests"],
        sim_device_calls=out["sim_device_calls"])
    late = sorted(out["late"]) or [0.0]
    ctx.log(f"served {len(done)} in the window ({len(engine)} from the "
            f"engine, {out['dispatches']} dispatches of "
            f"{out['dispatched_requests']:.0f} requests, the longest "
            f"{out['longest_dispatch_s']:.3f} s); client wake-ups late by "
            f"{sum(late) / len(late) * 1e3:.2f} ms mean, "
            f"{late[int(0.99 * (len(late) - 1))] * 1e3:.2f} ms p99, "
            f"{late[-1] * 1e3:.2f} ms at most")
    # a cohort of k requests gives k responses, each with its size
    small = collections.Counter(r.cohort_size for r in engine
                                if r.cohort_size < AUTO_JIT_MIN_BATCH)
    ctx.log(f"dispatches by requests: {out['cohort_sizes']}; cohorts of "
            f"fewer than {AUTO_JIT_MIN_BATCH} requests answered "
            f"{sum(small.values())} of the window's engine answers, in "
            f"about {sum(n / k for k, n in small.items()):.0f} dispatches; "
            f"the smallest cohort "
            f"{min((r.cohort_size for r in engine), default=0)}")


def release(st) -> None:
    st.pop("service", None)


def items(st) -> list[Item]:
    out = []
    for src, resp in st["answers"]:
        sim = resp.result.sim_result
        out.append(Item(src, (sim.cycles_per_iteration, sim.converged,
                              sim.bottleneck) if sim is not None
                        else (None, None, None),
                        resp.backend_used, resp.degraded))
    return out
