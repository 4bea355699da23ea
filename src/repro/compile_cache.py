"""JAX's persistent compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``benchmarks/sweep_bench.py``, ``benchmarks/service_bench.py``) call
:func:`enable_compile_cache` before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache lives in one fixed directory of
the checkout, ``.jax_cache`` (listed in ``.gitignore``): a directory
that moved between runs — a temporary name, a PID, a time — would never
be hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the in-checkout cache directory used when the environment names none
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its
    directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
