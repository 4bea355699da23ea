"""jit'd wrapper for the SSD Pallas kernel: model layout (B,S,H,P) plus
per-head decay -> kernel layout, chunking, interpret auto-select."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .ssd_scan import ssd_scan_bhcqp


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, da, dt, bm, cm, *, chunk: int = 128,
             interpret: bool | None = None):
    """x: (B,S,H,P); da, dt: (B,S,H); bm, cm: (B,S,N) -> (B,S,H,P)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, S, H, P = x.shape
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    xk = x.transpose(0, 2, 1, 3).reshape(B, H, nc, Q, P)
    dak = da.transpose(0, 2, 1).reshape(B, H, nc, 1, Q)
    dtk = dt.transpose(0, 2, 1).reshape(B, H, nc, 1, Q)
    bk = bm.reshape(B, nc, Q, -1)
    ck = cm.reshape(B, nc, Q, -1)
    y = ssd_scan_bhcqp(xk, dak, dtk, bk, ck, interpret=bool(interpret))
    return y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
