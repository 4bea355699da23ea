"""Pallas implementation of the port-arbitration inner step.

The hottest sub-step of the vectorized sweep recurrence
(``repro.core.sim.batch``) is port arbitration: mask the per-port
capacity accumulators with the uop's eligibility set, pick the
least-loaded port (first index on ties, matching ``np.argmin``), and
book the uop's cycles onto it.  ``backend="pallas"`` swaps the ``lax``
formulation for this kernel.

The kernel processes one whole shard (``JIT_SHARD`` lanes × ``P``
ports, a few KB) as a single block, always in interpreter mode (exact,
float64-capable, slow), which is what the parity tests exercise.  The
TPU's kernel compiler (Mosaic) refuses float64 (``Only float32 is
supported``), so ``simulate_many`` refuses ``backend="pallas"`` on a
TPU before anything compiles; the ``jit`` driver is the device path
there (see docs/performance.md).
"""
from __future__ import annotations


def make_arbitration_step(n_ports: int):
    """Build the arbitration step for a ``n_ports``-wide machine.

    Returns ``step(port_cap, elig, cyc_upd) -> (new_cap, pmin)`` for a
    ``[lanes, n_ports]`` shard: ``pmin`` is each lane's least booked
    eligible capacity (``inf`` when no port is eligible) and
    ``new_cap`` books ``cyc_upd`` onto the winning port (``cyc_upd`` is
    0 for slots that occupy no port, so the booking is a no-op there).
    Semantically identical to the inline ``lax`` version in
    ``batch._compiled_run`` (the parity suite asserts it).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(cap_ref, elig_ref, cyc_ref, cap_out, pmin_out):
        cap = cap_ref[...]
        pf = jnp.where(elig_ref[...], cap, jnp.inf)
        pmin_out[...] = jnp.min(pf, axis=1)
        choice = jnp.argmin(pf, axis=1)         # first index on ties
        oh = jax.lax.broadcasted_iota(
            jnp.int32, cap.shape, 1) == choice[:, None].astype(jnp.int32)
        cap_out[...] = cap + jnp.where(oh, cyc_ref[...][:, None], 0.0)

    def step(port_cap, elig, cyc_upd):
        return pl.pallas_call(
            kernel,
            out_shape=(
                jax.ShapeDtypeStruct(port_cap.shape, port_cap.dtype),
                jax.ShapeDtypeStruct((port_cap.shape[0],),
                                     port_cap.dtype),
            ),
            interpret=True,
        )(port_cap, elig, cyc_upd)

    return step
