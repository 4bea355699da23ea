"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

The GPU implementation (mamba_ssm) is a fused selective-scan CUDA kernel
built around warp-parallel prefix scans.  The TPU-native formulation
(state-space duality, arXiv:2405.21060) re-expresses each chunk as two
MXU matmuls (intra-chunk quadratic form + state projection) plus a small
recurrent state carried across chunks; the TPU grid executes the chunk
dimension sequentially per (batch, head), so the (P x N) state lives in
VMEM scratch between grid steps — no cross-core scan primitive needed.

Layouts: x (B,H,nc,Q,P), dt/dA (B,H,nc,1,Q), Bm/Cm (B,nc,Q,N) shared
across heads (single SSD group).  The unit axis gives dt/dA blocks whose
last two dims span (1, Q), which the TPU lowering accepts: a (1, Q)
block of a (nc, Q) array would break its 8x128 tiling rule.  Q (chunk)
and P, N are 128-aligned by config.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(x_ref, da_ref, dt_ref, b_ref, c_ref, y_ref, h_ref, *,
            chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)        # (Q, P)
    da = da_ref[0, 0, 0].astype(jnp.float32)      # (1, Q) row
    dt = dt_ref[0, 0, 0].astype(jnp.float32)      # (1, Q) row
    bm = b_ref[0, 0].astype(jnp.float32)          # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)          # (Q, N)

    # everything stays 2-D (Mosaic tiles the last two dims): the
    # inclusive prefix sum is a masked lane reduction, and column forms
    # of the per-step vectors come from masked reductions too
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = ii >= jj
    da_b = jnp.broadcast_to(da, (chunk, chunk))   # [i, j] = da_j
    cum = jnp.sum(jnp.where(lower, da_b, 0.0), axis=1,
                  keepdims=True)                  # (Q, 1) inclusive
    dt_col = jnp.sum(jnp.where(ii == jj, jnp.broadcast_to(
        dt, (chunk, chunk)), 0.0), axis=1, keepdims=True)       # (Q, 1)
    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for j <= i
    cum_b = jnp.broadcast_to(cum, (chunk, chunk))
    seg = jnp.where(lower, cum_b - cum_b.T, NEG_INF)
    L = jnp.exp(seg)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))  # (Q,Q)
    w = cb * L * dt
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())))     # (Q,P)

    # inter-chunk: contribution of carried state h (P,N)
    c_scaled = cm * jnp.exp(cum)                                # (Q,N)
    y = y + jax.lax.dot_general(
        c_scaled, h_ref[...], (((1,), (1,)), ((), ())))         # (Q,P)

    # state update: h' = exp(total) h + sum_j exp(total-cum_j) dt_j x_j B_j
    total = jnp.sum(da, axis=1, keepdims=True)                  # (1, 1)
    decay = jnp.exp(total - cum) * dt_col                       # (Q, 1)
    dS = jax.lax.dot_general(
        x * decay, bm, (((0,), (0,)), ((), ())))                # (P,N)
    h_ref[...] = h_ref[...] * jnp.exp(total) + dS

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)


def ssd_scan_bhcqp(x, da, dt, bm, cm, *, interpret: bool = False):
    """x: (B,H,nc,Q,P); da, dt: (B,H,nc,1,Q); bm, cm: (B,nc,Q,N).
    Returns y: (B,H,nc,Q,P) (the D-skip/gating epilogue stays in the
    caller)."""
    B, H, nc, Q, P = x.shape
    N = bm.shape[-1]
    kernel = functools.partial(_kernel, chunk=Q)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, Q, P),
                               lambda b, h, c: (b, h, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nc, Q, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, da, dt, bm, cm)
