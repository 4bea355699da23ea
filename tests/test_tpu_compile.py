"""Compile the device programs of the main path for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  What interpret mode and the
CPU backend cannot show — a block layout the TPU kernel compiler
(Mosaic) refuses, a dtype it does not take, a program that does not fit
the chip's memory — fails here.  Nothing runs, so these tests say
nothing about results or times.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import compile_program, extract_kernel, get_model
from repro.core import paper_kernels as pk
from repro.core.sim.batch import (JIT_SHARD, _LEAN_ARGS, _chain,
                                  _compiled_run, _composed_edges,
                                  _empty_program, _pack_lean)

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    if saved_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


def _fits_one_chip(compiled) -> None:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES


# the default horizon of simulate_many, and the 4x horizon its
# unconverged lanes escalate to
@pytest.mark.parametrize("T", [96, 384])
@pytest.mark.parametrize("arch,kernels", [
    ("skl", (pk.TRIAD_SKL_O3, pk.PI_O1, pk.PI_SKL_O3)),
    ("zen", (pk.TRIAD_ZEN_O3, pk.PI_O1, pk.PI_ZEN_O3)),
])
def test_lax_recurrence_compiles_in_float64(one_chip, arch, kernels, T):
    """The jit driver's shard recurrence, float64 as it runs: one
    64-lane shard."""
    model = get_model(arch)
    progs = [compile_program(extract_kernel(src), arch) for src in kernels]
    progs += [_empty_program(progs[0].model)] * (JIT_SHARD - len(progs))
    shard = _pack_lean(progs, [_composed_edges(p) for p in progs],
                       model.ports, model.pipeline, T)
    with jax.enable_x64(True):
        args = _shapes(one_chip, *((np.shape(shard[k]),
                                    np.asarray(shard[k]).dtype)
                                   for k in _LEAN_ARGS))
        assert any(a.dtype == jnp.float64 for a in args)
        run = _compiled_run(shard["U"], shard["E"], len(model.ports), T,
                            model.pipeline, "lax")
        compiled = run.lower(*args).compile()
    _fits_one_chip(compiled)


@pytest.mark.parametrize("T", [96, 384])
def test_largest_chain_shape_compiles_in_float64(one_chip, T):
    """The largest shape ``warm_chain`` builds for a served Zen
    process (the chain level that holds a full ROB of uops) compiles
    and fits the chip."""
    model = get_model("zen")
    U, E = _chain(model.pipeline.rob_size, 0)
    empty = [_empty_program(model)] * JIT_SHARD
    shard = _pack_lean(empty, [[]] * JIT_SHARD, model.ports,
                       model.pipeline, T, (U, E))
    assert (shard["U"], shard["E"]) == (256, 512)
    with jax.enable_x64(True):
        args = _shapes(one_chip, *((np.shape(shard[k]),
                                    np.asarray(shard[k]).dtype)
                                   for k in _LEAN_ARGS))
        compiled = _compiled_run(U, E, len(model.ports), T,
                                 model.pipeline, "lax").lower(
                                     *args).compile()
    _fits_one_chip(compiled)


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention

    cfg = get_config("qwen2.5-3b")
    d_head = cfg.d_model // cfg.n_heads
    q, k, v = _shapes(one_chip,
                      ((1, 4096, cfg.n_heads, d_head), jnp.bfloat16),
                      ((1, 4096, cfg.n_kv_heads, d_head), jnp.bfloat16),
                      ((1, 4096, cfg.n_kv_heads, d_head), jnp.bfloat16))
    compiled = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, interpret=False)).lower(q, k, v).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


def test_moe_gmm_compiles(one_chip):
    from repro.kernels.moe_gmm.ops import grouped_matmul

    cfg = get_config("kimi-k2-1t-a32b")
    # eight of the experts, each with a 512-token capacity buffer
    x, w = _shapes(one_chip,
                   ((8, 512, cfg.d_model), jnp.bfloat16),
                   ((8, cfg.d_model, cfg.d_ff_expert), jnp.bfloat16))
    compiled = jax.jit(lambda x, w: grouped_matmul(
        x, w, interpret=False)).lower(x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


def test_ssd_scan_compiles(one_chip):
    from repro.kernels.ssd_scan.ops import ssd_scan

    cfg = get_config("mamba2-370m")
    H = cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
    S, N, P = 2048, cfg.ssm_state, cfg.ssm_head_dim
    args = _shapes(one_chip,
                   ((1, S, H, P), jnp.bfloat16),
                   ((1, S, H), jnp.float32), ((1, S, H), jnp.float32),
                   ((1, S, N), jnp.bfloat16), ((1, S, N), jnp.bfloat16))
    compiled = jax.jit(lambda *a: ssd_scan(
        *a, chunk=128, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)
