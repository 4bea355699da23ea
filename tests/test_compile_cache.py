"""The persistent compilation cache honours ``JAX_COMPILATION_CACHE_DIR``
and otherwise sits at one fixed directory of the checkout.

Each case runs in a fresh interpreter: JAX reads the variable when it
is imported, and turning the cache on inside a test worker would leave
it on for every later test in that worker."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

PROBE = """
import json, jax
from repro.compile_cache import DEFAULT_DIR, enable_compile_cache
first, second = enable_compile_cache(), enable_compile_cache()
print(json.dumps({"first": first, "second": second,
                  "jax": jax.config.jax_compilation_cache_dir,
                  "default": str(DEFAULT_DIR)}))
"""


def _probe(cache_env: str | None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run([sys.executable, "-c", PROBE], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_environment_directory_is_honoured(tmp_path):
    where = str(tmp_path / "placed-from-outside")
    got = _probe(where)
    assert got["first"] == got["second"] == got["jax"] == where


@pytest.mark.parametrize("unset", [None, ""])
def test_default_directory_is_fixed_inside_the_checkout(unset):
    got = _probe(unset)
    assert got["first"] == got["second"] == got["jax"] == got["default"]
    assert Path(got["default"]) == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
