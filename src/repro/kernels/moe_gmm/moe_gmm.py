"""Grouped expert GEMM (MoE) as a Pallas TPU kernel.

Computes y[e] = x[e] @ w[e] for every expert's capacity buffer in one
launch — the TPU analogue of MegaBlocks' grouped GEMM (arXiv:2211.15841):
instead of CUDA block-scheduling over a ragged CSR structure, the
fixed-capacity dispatch (repro.models.moe) gives a dense (E, C, d) layout
and the kernel tiles (C, d, f) per expert through VMEM with a sequential
reduction over d-tiles accumulated in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, y_ref, acc_ref, *, n_d: int):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)       # (bc, bd)
    w = w_ref[0].astype(jnp.float32)       # (bd, bf)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())))

    @pl.when(di == n_d - 1)
    def _finish():
        y_ref[0] = acc_ref[...].astype(y_ref.dtype)


def grouped_matmul(x: jax.Array, w: jax.Array, *, block_c: int = 128,
                   block_d: int = 512, block_f: int = 512,
                   interpret: bool = False) -> jax.Array:
    """x: (E, C, d); w: (E, d, f) -> (E, C, f)."""
    E, C, d = x.shape
    f = w.shape[-1]
    bc = min(block_c, C)
    bd = min(block_d, d)
    bf = min(block_f, f)
    assert C % bc == 0 and d % bd == 0 and f % bf == 0
    grid = (E, C // bc, f // bf, d // bd)
    kernel = functools.partial(_kernel, n_d=d // bd)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bd, bf), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        interpret=interpret,
    )(x, w)
