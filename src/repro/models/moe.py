"""Mixture-of-Experts FFN: token-choice top-k routing with sort-based
dispatch into fixed-capacity expert buffers (static shapes throughout, so
the same code path serves real execution, AD, and the dry-run).

Sharding modes (picked in repro.parallel.sharding):
  * EP — experts sharded over the `model` axis (n_experts % model == 0);
  * TP — expert d_ff sharded over `model` (few-expert models, e.g. grok-1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.parallel.sharding import constrain, moe_sharding_mode

from .config import ModelConfig
from .schema import PSpec


def moe_schema(cfg: ModelConfig) -> dict:
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    sch = {
        "router": PSpec((d, E), ("embed", None), dtype=jnp.float32,
                        scale=0.02),
        "w_gate": PSpec((E, d, f), ("experts", "embed", "ff")),
        "w_up": PSpec((E, d, f), ("experts", "embed", "ff")),
        "w_out": PSpec((E, f, d), ("experts", "ff", "embed")),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        sch["shared"] = {
            "w_gate": PSpec((d, fs), ("embed", "ff")),
            "w_up": PSpec((d, fs), ("embed", "ff")),
            "w_out": PSpec((fs, d), ("ff", "embed")),
        }
    return sch


def _activation(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(kind)


def _dispatch_compute_combine(params: dict, x: jax.Array,
                              cfg: ModelConfig, e_base: int,
                              e_local: int, capacity: int
                              ) -> tuple[jax.Array, jax.Array]:
    """Local (per-shard) token-choice dispatch for experts
    [e_base, e_base + e_local).  x: (T, d) local tokens.  Returns the
    partial output (zero rows for tokens routed elsewhere) and the local
    load-balance statistics term."""
    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    logits = x.astype(jnp.float32) @ params["router"]          # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = lax.top_k(gates, K)                          # (T, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # load-balance stats (combined into the global aux loss by caller)
    me = jnp.mean(gates, axis=0)                                # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(
        1.0 / (T * K))
    aux = E * jnp.sum(me * ce)

    # ---- sort local (token, expert) pairs by expert --------------------
    flat_e = top_i.reshape(-1)                                  # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = top_w.reshape(-1)
    order = jnp.argsort(flat_e)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    within = jnp.arange(T * K) - jnp.searchsorted(se, se, side="left")
    local_e = se - e_base
    mine = (local_e >= 0) & (local_e < e_local)
    keep = (within < capacity) & mine
    slot = jnp.where(keep, local_e * capacity + within,
                     e_local * capacity)

    # ---- dispatch into (e_local, capacity, d) ---------------------------
    xs = jnp.take(x, st, axis=0)                                # (T*K, d)
    buf = jnp.zeros((e_local * capacity, d), x.dtype)
    buf = buf.at[slot].set(xs, mode="drop")
    buf = buf.reshape(e_local, capacity, d)

    # ---- grouped expert FFN (einsum over the local expert dim) ---------
    g = _activation(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"]),
                    cfg.activation)
    if cfg.glu:
        g = g * jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    y = jnp.einsum("ecf,efd->ecd", g, params["w_out"])

    # ---- combine back to token order ------------------------------------
    y_flat = y.reshape(e_local * capacity, d)
    contrib = jnp.take(y_flat, jnp.where(keep, slot, 0), axis=0)
    contrib = contrib * (sw * keep)[:, None].astype(y_flat.dtype)
    out = jnp.zeros((T, d), jnp.float32).at[st].add(
        contrib.astype(jnp.float32))
    return out, aux


def moe_ffn(params: dict, x: jax.Array, cfg: ModelConfig
            ) -> tuple[jax.Array, jax.Array]:
    """x: (T, d) flattened tokens -> (out (T, d), aux_loss ()).

    Distributed path (inside an activation-sharding context): shard_map
    over the mesh — tokens stay local to their data shard; each model
    shard dispatches only to its own experts (EP) or computes a d_ff
    slice of every expert (TP), and one psum over "model" combines
    expert outputs.  Communication per layer = one (T_local, d)
    all-reduce, like a Megatron FFN — no global sort/gather (the naive
    SPMD lowering of token dispatch all-gathered activations; see
    EXPERIMENTS.md §Perf iteration log)."""
    from repro.parallel.sharding import active_rules
    rules = active_rules()
    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k

    if rules is not None and rules.stationary_weights:
        # decode-stationary: expert weights stay sharded on their
        # contraction ("embed") dim; auto-SPMD turns the handful of
        # decode tokens into partial matmuls + tiny psums, with zero
        # weight movement — the shard_map path would re-gather weights.
        rules = None

    if rules is None:
        capacity = max(8, -(-int(T * K / E * cfg.capacity_factor)
                            ) // 8 * 8)
        out, aux = _dispatch_compute_combine(params, x, cfg, 0, E,
                                             capacity)
        out = out.astype(x.dtype)
    else:
        from jax.sharding import PartitionSpec as P
        mode = moe_sharding_mode(E)
        msize = rules.model_size
        dsize = rules.data_size
        e_local = E // msize if mode == "ep" else E
        T_loc = T // dsize if T % dsize == 0 else T
        capacity = max(8, -(-int(T_loc * K / E * cfg.capacity_factor)
                            ) // 8 * 8)
        t_spec = P(rules.data_axes) if T % dsize == 0 else P()
        if mode == "ep":
            w_spec = {"router": P(), "w_gate": P("model",),
                      "w_up": P("model",), "w_out": P("model",)}
        else:
            w_spec = {"router": P(), "w_gate": P(None, None, "model"),
                      "w_up": P(None, None, "model"),
                      "w_out": P(None, "model", None)}
        if cfg.n_shared_experts:
            w_spec["shared"] = {"w_gate": P(None, "model"),
                                "w_up": P(None, "model"),
                                "w_out": P("model", None)}
        routed = {k: params[k] for k in w_spec if k in params}

        def local_fn(w, xl):
            if mode == "ep":
                e_base = lax.axis_index("model") * e_local
            else:
                e_base = 0
            Tl = xl.shape[0]
            chunk = min(cfg.moe_token_chunk, Tl)
            if Tl % chunk == 0 and Tl // chunk > 1:
                cap = max(8, -(-int(chunk * K / E * cfg.capacity_factor)
                               ) // 8 * 8)

                def one_chunk(xc):
                    o, a = _dispatch_compute_combine(w, xc, cfg, e_base,
                                                     e_local, cap)
                    return o, a
                outs, auxs = lax.map(
                    one_chunk, xl.reshape(Tl // chunk, chunk, d))
                out = outs.reshape(Tl, d)
                aux = auxs.mean()
            else:
                out, aux = _dispatch_compute_combine(w, xl, cfg,
                                                     e_base, e_local,
                                                     capacity)
            if cfg.n_shared_experts:
                spw = w["shared"]
                h = _activation(xl @ spw["w_gate"], cfg.activation)
                if cfg.glu:
                    h = h * (xl @ spw["w_up"])
                out = out + (h @ spw["w_out"]).astype(jnp.float32)
            # reduce in bf16: per-shard partials are already f32-
            # accumulated; the cross-shard sum in bf16 halves ICI bytes
            # (§Perf iteration B1)
            out = lax.psum(out.astype(xl.dtype), "model")
            aux = lax.pmean(aux, rules.data_axes) if T % dsize == 0 \
                else aux
            aux = lax.pmean(aux, "model")
            return out.astype(xl.dtype), aux

        mapped = jax.shard_map(
            local_fn, mesh=rules.mesh,
            in_specs=(w_spec, P(*t_spec)),
            out_specs=(P(*t_spec), P()),
            check_vma=False)
        out, aux = mapped(routed, x)
        return out, aux

    if cfg.n_shared_experts:
        sp = params["shared"]
        h = _activation(x @ sp["w_gate"], cfg.activation)
        if cfg.glu:
            h = h * (x @ sp["w_up"])
        out = out + (h @ sp["w_out"]).astype(out.dtype)
    return out.astype(x.dtype), aux


def dense_ffn(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: (B, S, d)."""
    from repro.parallel.sharding import active_rules
    rules = active_rules()
    if cfg.tp_shard_map and rules is not None \
            and not rules.stationary_weights \
            and params["w_out"].shape[0] % rules.model_size == 0:
        return _dense_ffn_tp(params, x, cfg, rules)
    h = _activation(x @ params["w_gate"], cfg.activation)
    if cfg.glu:
        h = h * (x @ params["w_up"])
    h = constrain(h, "batch", None, "model")
    return (h @ params["w_out"]).astype(x.dtype)


def _dense_ffn_tp(params: dict, x: jax.Array, cfg: ModelConfig,
                  rules) -> jax.Array:
    """Explicit Megatron-SP TP: the sequence-sharded residual is
    all-gathered (bf16) on entry, the column/row-parallel FFN computes
    locally, and the row-parallel partial sums leave through a bf16
    reduce-scatter back to sequence sharding — replacing auto-SPMD's
    f32 all-reduce + reshard pair (half the bytes twice over)."""
    from jax.sharding import PartitionSpec as P
    B, S, d = x.shape
    batch_ok = B % rules.data_size == 0
    seq_sp = cfg.seq_shard_residual and S % rules.model_size == 0
    x_spec = P(rules.data_axes if batch_ok else None,
               "model" if seq_sp else None)
    w_spec = {"w_gate": P(None, "model"), "w_out": P("model", None)}
    if cfg.glu:
        w_spec["w_up"] = P(None, "model")

    def local_fn(w, xl):
        if seq_sp:
            xl = lax.all_gather(xl, "model", axis=1, tiled=True)
        h = _activation(xl @ w["w_gate"], cfg.activation)
        if cfg.glu:
            h = h * (xl @ w["w_up"])
        partial = (h @ w["w_out"]).astype(xl.dtype)   # bf16 partials
        if seq_sp:
            return lax.psum_scatter(partial, "model",
                                    scatter_dimension=1, tiled=True)
        return lax.psum(partial, "model")

    routed = {k: params[k] for k in w_spec}
    return jax.shard_map(local_fn, mesh=rules.mesh,
                         in_specs=(w_spec, x_spec), out_specs=x_spec,
                         check_vma=False)(routed, x)


def dense_ffn_schema(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    sch = {
        "w_gate": PSpec((d, f), ("embed", "ff")),
        "w_out": PSpec((f, d), ("ff", "embed")),
    }
    if cfg.glu:
        sch["w_up"] = PSpec((d, f), ("embed", "ff"))
    return sch
