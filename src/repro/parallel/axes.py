"""Logical-axis sharding: context-managed rules + constraint helpers.

A :class:`ShardingPlan` binds a mesh to a rule table
``logical axis name → mesh axis (or tuple of mesh axes, or None)``.
Model code calls ``shard(x, "batch", "seq", "embed")`` at layer
boundaries; outside a plan context this is a no-op, so the same model
runs unsharded on one CPU device and sharded under pjit on a pod.

Divisibility guard: a mesh axis is silently dropped from a dim's spec if
it does not divide the dim (e.g. 8 KV heads over a 16-way model axis) —
the standard MaxText-style fallback to replication for that dim.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    mesh: Mesh
    rules: dict[str, Any]  # logical name -> mesh axis | tuple | None

    def mesh_axes_for(self, logical: str | None):
        if logical is None:
            return None
        return self.rules.get(logical, None)

    def with_rules(self, **overrides) -> "ShardingPlan":
        rules = dict(self.rules)
        rules.update(overrides)
        return ShardingPlan(mesh=self.mesh, rules=rules)


def current_plan() -> ShardingPlan | None:
    return getattr(_STATE, "plan", None)


@contextlib.contextmanager
def use_plan(plan: ShardingPlan | None):
    prev = current_plan()
    _STATE.plan = plan
    try:
        yield plan
    finally:
        _STATE.plan = prev


def under_plan(fn: Callable, plan: ShardingPlan | None) -> Callable:
    """``fn`` run inside ``use_plan(plan)`` — wrap a function before
    `jax.jit` so the model code it traces (`shard`, `batch_shard_map`)
    sees the plan."""

    @functools.wraps(fn)
    def planned(*args, **kwargs):
        with use_plan(plan):
            return fn(*args, **kwargs)

    return planned


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def sanitize_spec(shape: Sequence[int], spec: P, mesh: Mesh) -> P:
    """Make a raw spec legal for (shape, mesh):

    * mesh axes absent from the mesh are dropped (single-pod meshes have
      no "pod" axis);
    * axes that do not divide their dim are dropped (e.g. 8 KV heads over
      a 16-way model axis → replicate);
    * an axis may appear only once — later dims lose conflicts (e.g. MoE
      (expert, embed, mlp): when the expert dim takes "model" the mlp dim
      falls back to replicated, and when expert isn't divisible the mlp
      dim inherits "model" — EP↔TP-in-expert fallback for free).
    """
    out = []
    used: set[str] = set()
    for dim, axes in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axes is None:
            out.append(None)
            continue
        axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
        kept: list[str] = []
        size = dim
        for a in axes_t:
            if a not in mesh.shape or a in used:
                continue
            n = mesh.shape[a]
            if size % n == 0:
                kept.append(a)
                used.add(a)
                size //= n
            # else: drop → replicate along this mesh axis
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    return P(*out)


def logical_spec(shape: Sequence[int], logical_axes: Sequence[str | None],
                 plan: ShardingPlan | None = None) -> P:
    """Resolve logical axis names to a (sanitized) PartitionSpec."""
    plan = plan or current_plan()
    if plan is None:
        return P()
    assert len(shape) == len(logical_axes), (shape, logical_axes)
    raw = P(*(plan.mesh_axes_for(name) for name in logical_axes))
    return sanitize_spec(shape, raw, plan.mesh)


def logical_sharding(shape: Sequence[int], logical_axes: Sequence[str | None],
                     plan: ShardingPlan | None = None) -> NamedSharding | None:
    plan = plan or current_plan()
    if plan is None:
        return None
    return NamedSharding(plan.mesh, logical_spec(shape, logical_axes, plan))


def shard(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """Apply a logical sharding constraint (no-op outside a plan)."""
    plan = current_plan()
    if plan is None:
        return x
    spec = logical_spec(np.shape(x), logical_axes, plan)
    return jax.lax.with_sharding_constraint(x, NamedSharding(plan.mesh, spec))


def batch_shard_map(fn: Callable, x, *rest):
    """``fn(x, *rest)``, run once per batch shard of the current plan.

    ``x`` and every output split along dim 0 over the ``"batch"`` rule's
    mesh axes; ``rest`` replicates.  For per-example computations XLA
    cannot partition itself — a Mosaic kernel must sit inside a
    `shard_map`.  Outside a plan, or where the batch rule spans one
    device, this is ``fn(x, *rest)``.
    """
    plan = current_plan()
    if plan is None:
        return fn(x, *rest)
    spec = logical_spec(np.shape(x), ("batch",) + (None,) * (np.ndim(x) - 1),
                        plan)
    axes = spec[0] if len(spec) else None
    if axes is None:
        return fn(x, *rest)
    in_specs = (P(axes),) + (P(),) * len(rest)
    return jax.shard_map(fn, mesh=plan.mesh, in_specs=in_specs,
                         out_specs=P(axes), check_vma=False)(x, *rest)
