"""Named host spans on the JAX profiler's clock.

The batched prediction path (``AnalysisService.predict_batch`` and
``simulate_many``) marks its phases with ``repro.*`` spans
(docs/performance.md, "Tracing a ``predict_batch`` call").  A span is
a ``jax.profiler.TraceAnnotation``: it is recorded only while a
profiler trace runs, on the clock of the device's own events, and
costs about a microsecond otherwise.  Metadata passed as keywords
(``call=``, ``shard=``) becomes event stats of the recorded span.
"""
from __future__ import annotations

import contextlib
import sys


def span(name: str, **meta):
    """A context manager recording one ``name`` span with ``meta``.

    ``repro.core`` does not import JAX, and no trace can run before
    ``jax.profiler`` is imported, so until then this opens nothing and
    imports nothing."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.TraceAnnotation(name, **meta)

