"""Mesh-aware ``with_sharding_constraint`` wrappers.

Model code calls these unconditionally; they only emit a constraint when

  * a mesh context is active (``with mesh:``),
  * the named mesh axis exists and has size > 1, and
  * the constrained dimension is divisible by the axis size,

so the exact same forward runs unmodified on a single CPU device, under
``jax.eval_shape``, and on the 512-chip production mesh. The decode cache
layout (batch -> "data", seq -> "model") lives in :func:`dp_model_plan`; see
DESIGN.md §3 for why it must match ``sharding.cache_specs``.
"""
from __future__ import annotations

from typing import Optional, Union

import jax
from jax._src import mesh as mesh_lib
from jax.sharding import Mesh, PartitionSpec as P

Axis = Union[str, tuple, None]


def _context_mesh() -> Optional[Mesh]:
    """The ambient mesh installed by ``with mesh:``, or None outside one."""
    m = mesh_lib.thread_resources.env.physical_mesh
    return None if m.empty else m


def _axis_size(mesh: Mesh, name: Axis) -> int:
    """Product of mesh-axis sizes for a (possibly tuple) assignment; 0 when
    any named axis is missing from the mesh."""
    names = name if isinstance(name, tuple) else (name,)
    size = 1
    shape = dict(mesh.shape)
    for n in names:
        if n not in shape:
            return 0
        size *= shape[n]
    return size


def _ok(mesh: Mesh, name: Axis, dim: int) -> bool:
    size = _axis_size(mesh, name)
    return size > 1 and dim % size == 0


def constrain_spec(x: jax.Array, plan: dict) -> jax.Array:
    """Constrain ``x`` per ``plan`` ({dim index -> mesh axis name | None}).

    Dims not in the plan (and plan entries that fail the divisibility /
    existence checks) stay unconstrained; a fully empty plan is a no-op.
    """
    mesh = _context_mesh()
    if mesh is None:
        return x
    entries: list[Axis] = [None] * x.ndim
    for d, name in plan.items():
        if name is None:
            continue
        d = d % x.ndim
        if _ok(mesh, name, x.shape[d]):
            entries[d] = name
    if all(e is None for e in entries):
        return x
    return jax.lax.with_sharding_constraint(x, P(*entries))


def constrain_axis(x: jax.Array, axis: int, name: str = "model") -> jax.Array:
    """Constrain one dimension of ``x`` to a mesh axis (default TP)."""
    return constrain_spec(x, {axis: name})


def batch_axis(mesh: Mesh, dim: int) -> Axis:
    """The data-parallel assignment for a global-batch dim: the first of
    ("pod","data") combined, "data", "pod" that divides it, else None. The
    single definition used both for in-model constraints (constrain_batch)
    and jit-boundary input shardings (sharding.input_sharding)."""
    for cand in (("pod", "data"), "data", "pod"):
        if _ok(mesh, cand, dim):
            return cand
    return None


def constrain_batch(x: jax.Array) -> jax.Array:
    """Constrain the leading (batch) dim over the data-parallel axes,
    combining ("pod", "data") on multi-pod meshes when divisibility allows."""
    mesh = _context_mesh()
    if mesh is None:
        return x
    ax = batch_axis(mesh, x.shape[0])
    return constrain_spec(x, {0: ax}) if ax is not None else x


def dp_model_plan(batch: int, seq: int) -> tuple[Axis, Axis]:
    """The sequence-parallel decode layout: (batch axis, seq axis).

    Batch goes to "data"; the cached sequence dim goes to "model" (each TP
    shard holds a slice of the KV cache and computes a local partial softmax).
    When batch can't use "data" (e.g. the long_500k cell with batch 1) the
    sequence falls back to "data" so the cache is still distributed.
    Returns (None, None) when no mesh is active.
    """
    mesh = _context_mesh()
    if mesh is None:
        return None, None
    batch_ax: Axis = "data" if _ok(mesh, "data", batch) else None
    if _ok(mesh, "model", seq):
        seq_ax: Axis = "model"
    elif batch_ax is None and _ok(mesh, "data", seq):
        seq_ax = "data"
    else:
        seq_ax = None
    return batch_ax, seq_ax
