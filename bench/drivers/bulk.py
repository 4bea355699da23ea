"""Closed-loop bulk traffic: one client predicts batches of distinct,
never-repeated loop bodies with ``AnalysisService.predict_batch``, back
to back, each call timed whole with its results in hand.

Set-up draws the batches the window may use (``batches_per_s`` times
the window's length, rounded up) and warms the compiled shapes by
predicting all of them once on a throwaway engine: the shapes the
program compiles follow from the bodies and their order alone, so the
window's own engine then compiles nothing.
"""
from __future__ import annotations

import math
import random

from bench.corpus import Corpus, length_table
from bench.harness import Item


def _engine(ctx):
    from repro.core import AnalysisService
    engine = AnalysisService(sim_backend="jit")
    engine.register(ctx.model)
    return engine


def batches(ctx) -> list[list[str]]:
    """The source of every body the window may use, batch by batch.

    Every batch holds the same multiset of lengths (quantiles of the
    configuration's length distribution) in an order drawn from the
    seed, and each body is drawn freely at its length."""
    corpus = Corpus(ctx.config, ctx.machine, ctx.seed, "bulk")
    size = ctx.traffic["batch_size"]
    lengths = length_table(ctx.config["corpus"]["length"], size)
    out, seen = [], set()
    for b in range(math.ceil(ctx.seconds * ctx.traffic["batches_per_s"])):
        order = lengths[:]
        random.Random(f"order/{ctx.seed}/{b}").shuffle(order)
        batch = []
        for n in order:
            index = b * size + len(batch)
            src, _ = corpus.body(index, (n, n))
            while src in seen:            # distinct across the whole run
                index += 1 << 40
                src, _ = corpus.body(index, (n, n))
            seen.add(src)
            batch.append(src)
        out.append(batch)
    return out


def sources(ctx) -> list[str]:
    return [src for batch in batches(ctx) for src in batch]


def prepare(ctx):
    from repro.core import AnalysisRequest
    req = ctx.config["request"]
    with ctx.span("bench.generate"):
        work = [[AnalysisRequest(kernel=src, arch=ctx.model.arch_id,
                                 mode=req["mode"],
                                 scheduler=req["scheduler"])
                 for src in batch] for batch in batches(ctx)]
    ctx.log(f"generated {len(work)} batches of "
            f"{ctx.traffic['batch_size']} bodies")
    with ctx.span("bench.warm"):
        warm = _engine(ctx)
        for batch in work:
            warm.predict_batch(batch)
    return {"batches": work, "engine": _engine(ctx), "answers": []}


def window(ctx, st, run) -> None:
    calls = []
    t_end = None
    for batch in st["batches"]:
        now = ctx.clock()
        if t_end is None:
            t_end = ctx.start_window() + ctx.seconds
        elif now >= t_end:
            break
        with ctx.span("bench.predict_batch"):
            t0 = ctx.clock()
            results = st["engine"].predict_batch(batch)
            t1 = ctx.clock()
        calls.append((t0, t1, len(batch)))
        st["answers"].append((batch, results))
    if ctx.clock() < t_end:
        ctx.log("all prepared batches used before the window closed")
    run.attempted = sum(n for _, _, n in calls)
    run.values.update(predictions=run.attempted,
                      batch_size=ctx.traffic["batch_size"],
                      calls_s=[t1 - t0 for t0, t1, _ in calls],
                      elapsed_s=sum(t1 - t0 for t0, t1, _ in calls))
    ctx.log("calls (s): " + " ".join(f"{t1 - t0:.3f}"
                                     for t0, t1, _ in calls))


def release(st) -> None:
    st.pop("engine", None)


def items(st) -> list[Item]:
    return [Item(req.kernel, (res.sim_result.cycles_per_iteration,
                              res.sim_result.converged,
                              res.sim_result.bottleneck)
                 if res.sim_result is not None else (None, None, None),
                 res.backend_used, res.degraded)
            for batch, results in st["answers"]
            for req, res in zip(batch, results)]

