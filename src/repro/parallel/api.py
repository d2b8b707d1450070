"""Logical-axis sharding: the distribution layer of the framework.

Models annotate tensors with *logical* axis names ("batch", "seq", "embed",
"heads", "kv_heads", "mlp", "experts", "vocab", ...).  A ``ShardingRules``
context maps logical names to mesh axes; ``shard(x, *axes)`` applies
``with_sharding_constraint`` when a mesh is active and is a no-op otherwise,
so the same model code runs single-device smoke tests and 512-chip SPMD.

Default production rules (see DESIGN.md section 5):
  batch   -> ('pod', 'data')     DP across pods and the data axis
  seq     -> 'model'             sequence-parallel residual stream
  heads/mlp/experts/vocab -> 'model'   Megatron TP / expert parallelism
  embed   -> None (activations) ; parameters get FSDP over 'data' via the
  parameter-spec rules in ``param_specs``.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Optional

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    mesh: Optional[Mesh] = None
    # logical name -> mesh axis (or tuple of mesh axes) or None
    rules: dict = dataclasses.field(default_factory=dict)
    # FSDP: shard the largest non-TP parameter axis over these mesh axes.
    fsdp_axes: tuple = ()
    enabled: bool = False

    # Identity hash (the rules dict is unhashable) so a ShardingRules may
    # ride jit-hashable carriers like lowering.Plan.mesh: equality stays
    # field-wise, so distinct-but-equal bindings cost at most a cache
    # miss, never a wrong lookup.
    __hash__ = object.__hash__

    def to_spec(self, logical_axes) -> P:
        out = []
        for name in logical_axes:
            ax = self.rules.get(name) if name else None
            out.append(ax)
        return P(*out)

    def axis_extent(self, ax) -> int:
        """Total device count behind a rules entry (1 for None)."""
        if ax is None or self.mesh is None:
            return 1
        flat = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in flat:
            size *= self.mesh.shape[a]
        return size


_RULES = contextvars.ContextVar("sharding_rules", default=ShardingRules())


def default_rules(mesh: Mesh) -> ShardingRules:
    axes = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in axes)
    dp = dp if len(dp) > 1 else (dp[0] if dp else None)
    tp = "model" if "model" in axes else None
    return ShardingRules(
        mesh=mesh,
        rules={
            "batch": dp,
            "seq": tp,            # sequence-parallel residual
            "seq_kv": tp,         # decode KV cache: seq over model
            "heads": tp,
            "kv_heads": tp,
            "mlp": tp,
            "experts": tp,
            "vocab": tp,
            "embed": None,
            "ssm_heads": tp,
            "state": None,
        },
        fsdp_axes=(("data",) if "data" in axes else ()),
        enabled=True,
    )


def current() -> ShardingRules:
    return _RULES.get()


@contextlib.contextmanager
def use_rules(rules: ShardingRules):
    token = _RULES.set(rules)
    try:
        yield rules
    finally:
        _RULES.reset(token)


def activation_spec(shape, logical_axes, rules: ShardingRules) -> P:
    """to_spec with divisibility + uniqueness guards: a logical axis whose
    dimension does not divide the mesh axis (e.g. 24 SSM heads over 16-way
    TP) or whose mesh axis is already taken degrades to replicated."""
    out, used = [], set()
    for i, name in enumerate(logical_axes):
        ax = rules.rules.get(name) if name else None
        if ax is None:
            out.append(None)
            continue
        flat = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in flat:
            size *= rules.mesh.shape[a]
        if any(a in used for a in flat) or shape[i] % size != 0:
            out.append(None)
            continue
        used.update(flat)
        out.append(ax)
    return P(*out)


def shard(x, *logical_axes):
    """Annotate an activation with logical axes (no-op without a mesh)."""
    r = current()
    if not r.enabled or r.mesh is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(
            f"shard() got {len(logical_axes)} axes for rank-{x.ndim} value")
    spec = activation_spec(x.shape, logical_axes, r)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(r.mesh, spec))


# ----------------------------------------------------------------------
# Parameter sharding: TP axis from the param's logical axes + FSDP on the
# largest remaining axis (ZeRO-3-style weight sharding so 67B/176B-class
# models fit 16 GB/chip HBM).
# ----------------------------------------------------------------------

def param_spec(shape, logical_axes, rules: ShardingRules,
               fsdp: bool = True) -> P:
    assert len(shape) == len(logical_axes), (shape, logical_axes)
    mesh_axes = [None] * len(shape)
    used = set()
    for i, name in enumerate(logical_axes):
        ax = rules.rules.get(name) if name else None
        if ax is None:
            continue
        flat = ax if isinstance(ax, tuple) else (ax,)
        if any(a in used for a in flat):
            continue
        size = 1
        for a in flat:
            size *= rules.mesh.shape[a]
        if shape[i] % size != 0:
            continue  # unshardable (e.g. 2 kv heads over 16-way TP)
        mesh_axes[i] = ax
        used.update(flat)
    if fsdp and rules.fsdp_axes:
        free = [a for a in rules.fsdp_axes if a not in used]
        if free:
            size = 1
            for a in free:
                size *= rules.mesh.shape[a]
            # biggest unsharded divisible axis
            cands = [i for i in range(len(shape))
                     if mesh_axes[i] is None and shape[i] % size == 0]
            if cands:
                i = max(cands, key=lambda j: shape[j])
                mesh_axes[i] = free[0] if len(free) == 1 else tuple(free)
    return P(*mesh_axes)


def tree_param_specs(abstract_params, axes_tree, rules: ShardingRules,
                     fsdp: bool = True):
    """Zip a params pytree with its logical-axes tree into PartitionSpecs."""
    return jax.tree.map(
        lambda p, ax: param_spec(p.shape, ax, rules, fsdp=fsdp),
        abstract_params, axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


# ----------------------------------------------------------------------
# Sanctioned collectives: the only raw shard_map / lax.all_to_all surface
# above core/lowering (analysis rule ``collective-purity``).  Layers that
# need an explicit exchange (models/moe.py's expert dispatch) call these
# helpers instead of reaching for the collective primitives themselves.
# ----------------------------------------------------------------------

def expert_exchange(buf, params, fn):
    """All-to-all expert dispatch: exchange a slot-sharded ``(E, C, ...)``
    dispatch buffer against the expert axis, run ``fn`` on each shard's
    expert slab, and exchange the result back.

    ``buf`` is the capacity-dispatch buffer (experts x capacity-slots x
    features) with its *slot* dim sharded over the expert-parallel mesh
    axis (tokens live where they were routed from); ``params`` is a
    pytree of per-expert tensors with experts leading (sharded over the
    same axis).  Inside the exchange each shard holds ``(E/P, C, ...)`` —
    every peer's slots for *its* experts — so ``fn(slab, params)`` runs
    the per-shard batched expert GEMMs on resident weights.  The return
    value is exchanged back to slot sharding and reassembled, so the
    global result is exactly the unsharded ``fn(buf, params)``: the
    all_to_all is a pure permutation of slots.

    Degrades to a plain ``fn(buf, params)`` call when no expert-parallel
    axis is active or E/C do not divide it — the caller never branches.
    ``fn`` runs inside a shard_map trace: contracts it issues must bind
    ``Plan(mesh=False)`` and it must not call :func:`shard`.
    """
    r = current()
    ax = r.rules.get("experts") if r.enabled and r.mesh is not None \
        else None
    p = r.axis_extent(ax)
    e, c = buf.shape[0], buf.shape[1]
    if p <= 1 or e % p or c % p:
        return fn(buf, params)
    from repro.runtime import faults as _faults
    _faults.maybe_inject(_faults.COLLECTIVE)
    flat = ax if isinstance(ax, tuple) else (ax,)
    name = flat if len(flat) > 1 else flat[0]

    def body(b, ps):
        b = lax.all_to_all(b, name, split_axis=0, concat_axis=1,
                           tiled=True)
        out = fn(b, ps)
        return lax.all_to_all(out, name, split_axis=1, concat_axis=0,
                              tiled=True)

    return jax.shard_map(
        body, mesh=r.mesh,
        in_specs=(P(None, ax), P(ax)), out_specs=P(None, ax),
        check_vma=False)(buf, params)
