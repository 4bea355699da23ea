"""Logical-axis -> mesh sharding rules (DP x TP x EP + FSDP/ZeRO-3).

Every parameter dimension carries a logical name (see repro.models.schema).
Rules map logical names to mesh axes:

  vocab / q_heads / kv_heads / ff / experts -> "model"  (tensor/expert par.)
  embed                                     -> FSDP axes (ZeRO-3 over data
                                               [+ pod]); all-gathered at use
  layers                                    -> replicated (scan dim)

A divisibility guard demotes any mapping whose dimension does not divide by
the axis size (e.g. Qwen1.5's 40 heads on a 16-way model axis, or Grok's 8
experts) to replication — those cells then surface as collective-/memory-
heavy rows in the roofline table and are hillclimb targets (EXPERIMENTS.md
§Perf)."""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.schema import PSpec, is_pspec


@dataclass(frozen=True)
class ShardingRules:
    mesh: Mesh
    data_axes: tuple[str, ...]          # batch / FSDP axes
    model_axis: str = "model"
    fsdp: bool = True                   # ZeRO-3 param sharding over data
    # Decode-stationary mode (§Perf iteration C): weights stay fully
    # sharded at use time — the "embed" (contraction) dim of every matrix
    # is computed sharded over the data axes and the tiny per-token
    # partial sums are reduced, instead of all-gathering every weight for
    # every generated token.  Used when the decode batch cannot occupy
    # the data axes (long-context, batch 1).
    stationary_weights: bool = False

    @property
    def data_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.data_axes]))

    @property
    def model_size(self) -> int:
        return int(self.mesh.shape[self.model_axis])


def make_rules(mesh: Mesh, fsdp: bool = True,
               stationary_weights: bool = False) -> ShardingRules:
    axes = mesh.axis_names
    data_axes = tuple(a for a in axes if a != "model")
    return ShardingRules(mesh=mesh, data_axes=data_axes, fsdp=fsdp,
                         stationary_weights=stationary_weights)


# mapping logical name -> candidate mesh assignment builder
def _logical_assignment(rules: ShardingRules):
    m = rules.model_axis
    fsdp_axes = rules.data_axes if rules.fsdp else ()
    return {
        "vocab": m,
        "q_heads": m,
        "kv_heads": m,
        "ff": m,
        "experts": m,
        "heads": m,            # ssm per-head params / dt projection
        "embed": fsdp_axes,    # ZeRO-3
        "layers": None,
        None: None,
    }


def _axis_size(mesh: Mesh, assignment) -> int:
    if assignment is None:
        return 1
    if isinstance(assignment, tuple):
        return int(np.prod([mesh.shape[a] for a in assignment])) \
            if assignment else 1
    return int(mesh.shape[assignment])


def spec_for(pspec: PSpec, rules: ShardingRules) -> P:
    """PartitionSpec for one parameter, with divisibility demotion and
    first-wins axis allocation (a mesh axis may appear only once — e.g.
    stacked MoE weights (layers, experts, embed, ff) map experts->model
    and must then leave ff unsharded)."""
    table = _logical_assignment(rules)
    out: list = []
    used: set = set()
    for dim, logical in zip(pspec.shape, pspec.logical):
        assignment = table.get(logical, None)
        size = _axis_size(rules.mesh, assignment)
        axes = assignment if isinstance(assignment, tuple) \
            else (assignment,) if assignment else ()
        if assignment in (None, ()) or size <= 1 or dim % size != 0 \
                or any(a in used for a in axes):
            out.append(None)
        else:
            out.append(assignment)
            used.update(axes)
    # trim trailing Nones (canonical form)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_shardings(schema, rules: ShardingRules):
    """Pytree of NamedSharding mirroring the params tree."""
    return jax.tree.map(
        lambda s: NamedSharding(rules.mesh, spec_for(s, rules)),
        schema, is_leaf=is_pspec)


# ---------------------------------------------------------------------- #
# Activations / inputs
# ---------------------------------------------------------------------- #

def batch_spec(rules: ShardingRules) -> P:
    return P(rules.data_axes)


def batch_shardings(batch_tree, rules: ShardingRules):
    """Shard dim 0 (global batch) over the data axes; demote if indivisible
    (long_500k has batch 1 -> fully replicated inputs, the cache carries
    the parallelism instead)."""
    def one(x):
        dim0 = x.shape[0] if getattr(x, "shape", ()) else 0
        if dim0 and dim0 % max(rules.data_size, 1) == 0:
            return NamedSharding(rules.mesh, P(rules.data_axes))
        return NamedSharding(rules.mesh, P())
    return jax.tree.map(one, batch_tree)


def cache_shardings(cache_tree, rules: ShardingRules, batch: int,
                    stacked: bool = True):
    """KV/SSM cache sharding.  Batch >= data axes: shard batch.  batch==1
    (long-context): shard the sequence/window dim of attention caches over
    the data axes (flash-decoding style) and SSM heads over model."""
    m = rules.model_axis
    msize = rules.model_size
    dsize = rules.data_size
    shard_batch = batch % dsize == 0

    def one(x):
        shape = x.shape
        off = 1 if stacked and len(shape) >= 1 else 0  # leading n_groups
        spec: list = [None] * len(shape)
        dims = shape[off:]
        if len(dims) == 4 and not hasattr(x, "_ssm"):  # (B,W,H,D) or (B,H,P,N)
            pass
        # identify attention kv (B,W,Hkv,Dh) vs ssm h (B,H,P,N) vs conv
        if shard_batch:
            if len(dims) >= 1 and dims[0] % dsize == 0:
                spec[off] = rules.data_axes
        elif len(dims) == 4 and dims[1] % dsize == 0 and dims[1] >= dsize:
            # batch==1 attention cache: shard window dim over data
            spec[off + 1] = rules.data_axes
        # model axis on the head-ish dim when divisible
        if len(dims) == 4:
            # attention cache (B,W,Hkv,Dh): dims[2]=Hkv; ssm h (B,H,P,N):
            # dims[1]=H.  Try Hkv first, else H.
            if spec[off + 2] is None and dims[2] % msize == 0 \
                    and dims[2] >= msize:
                spec[off + 2] = m
            elif spec[off + 1] is None and dims[1] % msize == 0 \
                    and dims[1] >= msize:
                spec[off + 1] = m
        elif len(dims) == 3 and dims[2] % msize == 0 and dims[2] >= msize:
            spec[off + 2] = m  # conv state (B,K-1,di)
        while spec and spec[-1] is None:
            spec.pop()
        return NamedSharding(rules.mesh, P(*spec))

    return jax.tree.map(one, cache_tree)


def replicated(rules: ShardingRules):
    return NamedSharding(rules.mesh, P())


# ---------------------------------------------------------------------- #
# Activation-sharding context: model code annotates intermediate tensors
# with logical names; outside a context (smoke tests on one device) the
# annotation is a no-op.  XLA's sharding propagation degrades badly
# through lax.scan layer stacks without these constraints (first dry-run
# measured 84 GiB/dev temp on qwen2.5-3b; with constraints ~5 GiB).
# ---------------------------------------------------------------------- #

_ACTIVE_RULES: list[ShardingRules | None] = [None]


class activation_sharding:
    def __init__(self, rules: ShardingRules | None):
        self.rules = rules

    def __enter__(self):
        _ACTIVE_RULES.append(self.rules)
        return self.rules

    def __exit__(self, *exc):
        _ACTIVE_RULES.pop()


def active_rules() -> ShardingRules | None:
    return _ACTIVE_RULES[-1]


def compute_spec_for(pspec: PSpec, rules: ShardingRules,
                     drop_layers: bool = True) -> P:
    """PartitionSpec for a parameter *at use time* inside a block: FSDP
    ("embed") axes are gathered (None); tensor/expert-parallel axes stay.
    With drop_layers the leading scan ("layers") dim is removed — the spec
    then matches the per-layer slice seen inside the scan body."""
    table = _logical_assignment(rules)
    out: list = []
    used: set = set()
    for dim, logical in zip(pspec.shape, pspec.logical):
        if logical == "layers" and drop_layers:
            continue
        if logical == "embed":
            if rules.stationary_weights and \
                    dim % max(rules.data_size, 1) == 0 and \
                    not any(a in used for a in rules.data_axes):
                out.append(rules.data_axes)
                used.update(rules.data_axes)
            else:
                out.append(None)
            continue
        assignment = table.get(logical, None)
        size = _axis_size(rules.mesh, assignment)
        axes = assignment if isinstance(assignment, tuple) \
            else (assignment,) if assignment else ()
        if assignment in (None, ()) or size <= 1 or dim % size != 0 \
                or any(a in used for a in axes):
            out.append(None)
        else:
            out.append(assignment)
            used.update(axes)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def compute_specs(schema, rules: ShardingRules, drop_layers: bool = True):
    """Pytree of use-time PartitionSpecs mirroring the params tree."""
    import jax as _jax
    from repro.models.schema import is_pspec as _is_pspec
    return _jax.tree.map(
        lambda s: compute_spec_for(s, rules, drop_layers), schema,
        is_leaf=_is_pspec)


def gather_params(params, specs):
    """FSDP just-in-time weight gather: constrain each param leaf to its
    use-time spec (inside a scan body this inserts one all-gather per
    layer, the ZeRO-3 pattern).  No-op outside an activation context."""
    rules = _ACTIVE_RULES[-1]
    if rules is None or specs is None:
        return params
    import jax as _jax

    def one(x, spec):
        return _jax.lax.with_sharding_constraint(
            x, NamedSharding(rules.mesh, spec))
    return _jax.tree.map(one, params, specs,
                         is_leaf=lambda x: isinstance(x, P))


def moe_sharding_mode(n_experts: int) -> str:
    """"ep" when experts divide the model axis (shard experts), else "tp"
    (shard each expert's d_ff) — e.g. Grok-1's 8 experts on a 16-way
    model axis."""
    rules = _ACTIVE_RULES[-1]
    if rules is None:
        return "ep"
    return "ep" if n_experts % rules.model_size == 0 else "tp"


def row_parallel_matmul(x, w, enabled: bool = True):
    """x: (..., k) with k sharded over the model axis (e.g. attention
    heads or d_inner), w: (k, d) row-sharded.  Explicit Megatron
    row-parallel: local partial matmul, bf16 cast, psum over "model" —
    auto-SPMD emits the same all-reduce but in f32 (2x ICI bytes)."""
    import jax as _jax
    rules = _ACTIVE_RULES[-1]
    k = w.shape[0]
    if not enabled or rules is None or k % rules.model_size != 0 \
            or rules.stationary_weights:
        return x @ w
    B = x.shape[0]
    batch_ok = B % rules.data_size == 0 and B >= rules.data_size
    lead = (rules.data_axes,) if batch_ok else (None,)
    x_spec = P(*(lead + (None,) * (x.ndim - 2) + ("model",)))
    out_spec = P(*(lead + (None,) * (x.ndim - 2)))

    def local_fn(wl, xl):
        return _jax.lax.psum((xl @ wl).astype(xl.dtype), "model")

    return _jax.shard_map(local_fn, mesh=rules.mesh,
                          in_specs=(P("model"), x_spec), out_specs=out_spec,
                          check_vma=False)(w, x)


def constrain(x, *logical):
    """logical per dim: "batch" -> data axes, "model" -> model axis,
    None -> unsharded.  Dims that do not divide are demoted."""
    rules = _ACTIVE_RULES[-1]
    if rules is None:
        return x
    spec = []
    for dim, name in zip(x.shape, logical):
        if name == "batch" and dim % rules.data_size == 0:
            spec.append(rules.data_axes)
        elif name == "model" and dim % rules.model_size == 0:
            spec.append(rules.model_axis)
        elif name == "seq" and dim % rules.data_size == 0:
            spec.append(rules.data_axes)
        else:
            spec.append(None)
    while spec and spec[-1] is None:
        spec.pop()
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, P(*spec)))
