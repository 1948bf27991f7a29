"""Sharding hints: step builders publish mesh-axis names through
contextvars so mesh-agnostic model code can drop with_sharding_constraint
hints (kept separate from repro.sharding to avoid import cycles with the
model modules)."""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

_HEAD_AXIS: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "repro_head_axis", default=None)


@contextlib.contextmanager
def axis_hints():
    """Within this context :func:`constrain_heads` shards attention heads
    over the 'model' mesh axis."""
    tok = _HEAD_AXIS.set("model")
    try:
        yield
    finally:
        _HEAD_AXIS.reset(tok)


def per_chunk_shard(fn, x):
    """``fn(x)`` for a row-wise ``fn`` of a compression-domain tensor
    (num_chunks, ...) that XLA cannot partition, such as a Pallas
    kernel. Under a mesh with axes not yet manual, ``fn`` runs in a
    ``shard_map`` that makes every axis manual (a Mosaic kernel lowers
    only there), each device taking its share of the chunk rows split
    over those axes; rows that do not divide evenly stay whole on every
    device."""
    mesh = jax.sharding.get_abstract_mesh()
    free = tuple(a for a in mesh.axis_names if a not in mesh.manual_axes)
    if not free:
        return fn(x)
    size = 1
    for a in free:
        size *= mesh.shape[a]
    spec = P(free if x.shape[0] % size == 0 else None,
             *([None] * (x.ndim - 1)))
    return jax.shard_map(fn, in_specs=spec, out_specs=spec,
                         axis_names=set(mesh.axis_names),
                         check_vma=False)(x)


def per_head_shard(fn, *xs):
    """``fn(*xs)`` for arrays (B, H, ...) whose examples and heads are
    independent, with ``fn`` one that XLA cannot partition, such as a
    Pallas kernel. Under a mesh with axes not yet manual, ``fn`` runs in
    a ``shard_map`` that makes every axis manual (a Mosaic kernel lowers
    only there): heads split over 'model', examples over the other free
    axes; a dimension that does not divide evenly stays whole."""
    mesh = jax.sharding.get_abstract_mesh()
    free = tuple(a for a in mesh.axis_names if a not in mesh.manual_axes)
    if not free:
        return fn(*xs)

    def split(axes, n):
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        return axes if axes and n % size == 0 else None

    B, H = xs[0].shape[:2]
    spec = P(split(tuple(a for a in free if a != "model"), B),
             split(tuple(a for a in free if a == "model"), H))
    return jax.shard_map(fn, in_specs=spec, out_specs=spec,
                         axis_names=set(mesh.axis_names),
                         check_vma=False)(*xs)


def per_group_shard(fn, shared, *grouped):
    """``fn(shared, *grouped)`` for arrays ``grouped`` whose leading axis
    holds independent token groups (the expert layer's dispatch groups).
    Under a mesh whose axes not yet manual, 'model' aside, split the
    groups evenly, ``fn`` runs in a ``shard_map`` over those axes, each
    device taking its own groups, so no group's rows leave their device;
    ``shared`` enters whole and 'model' stays with the partitioner."""
    mesh = jax.sharding.get_abstract_mesh()
    free = tuple(a for a in mesh.axis_names
                 if a not in mesh.manual_axes and a != "model")
    size = 1
    for a in free:
        size *= mesh.shape[a]
    if not free or grouped[0].shape[0] % size:
        return fn(shared, *grouped)
    spec = P(free)
    return jax.shard_map(fn, in_specs=(P(),) + (spec,) * len(grouped),
                         out_specs=spec, axis_names=set(free),
                         check_vma=False)(shared, *grouped)


def constrain_heads(x):
    """Hint: shard dim -2 (the heads dim of (B,S,H,hd)) over the model
    axis. No-op outside a step-builder context; GSPMD pads uneven heads."""
    axis = _HEAD_AXIS.get()
    if axis is None:
        return x
    try:
        spec = P(*([None] * (x.ndim - 2) + [axis, None]))
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x
