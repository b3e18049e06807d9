"""GPipe-style microbatch pipelining over a mesh axis.

``pipeline_stack`` splits a stacked group of layers over the devices of one
mesh axis (each device owns ``n_groups / n_stages`` consecutive groups) and
streams microbatches through the stages with ``ppermute``. The schedule is
the classic GPipe diagonal: at step ``t`` stage ``s`` processes microbatch
``t - s``; the ``n_stages - 1`` bubble steps compute on garbage that is never
written to the output, which keeps the loop straight-line and fully
differentiable (the backward pass is the reverse diagonal, derived by AD).
"""
from __future__ import annotations

from typing import Callable, Hashable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_stack(block: Callable, ws: jax.Array, x: jax.Array, *,
                   mesh: Mesh, axis: Hashable, n_micro: int) -> jax.Array:
    """Run ``block(stage_weights, h)`` as a pipeline over ``mesh[axis]``.

    ws: (n_groups, ...) stacked per-group weights, consumed in order.
    x:  (batch, ...) activations; batch is split into ``n_micro`` microbatches.
    Equivalent to folding ``block`` over all groups sequentially.
    """
    n_stages = dict(mesh.shape)[axis]
    n_groups = ws.shape[0]
    if n_groups % n_stages:
        raise ValueError(f"{n_groups} groups not divisible by "
                         f"{n_stages} pipeline stages")
    batch = x.shape[0]
    if batch % n_micro:
        raise ValueError(f"batch {batch} not divisible by n_micro {n_micro}")
    per_stage = n_groups // n_stages
    mb = batch // n_micro
    ws_staged = ws.reshape((n_stages, per_stage) + ws.shape[1:])
    x_micro = x.reshape((n_micro, mb) + x.shape[1:])

    def run_stage(ws_local, xm):
        # ws_local: (1, per_stage, ...) — this device's stage weights.
        # xm: (n_micro, mb, ...) — replicated microbatches.
        stage = jax.lax.axis_index(axis)
        stage_ws = ws_local[0]
        last = n_stages - 1
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        buf = jnp.zeros_like(xm[0])
        outs = jnp.zeros_like(xm)
        for t in range(n_micro + n_stages - 1):
            inp = jnp.where(stage == 0, xm[min(t, n_micro - 1)], buf)
            out = block(stage_ws, inp)
            m = t - last
            if m >= 0:  # microbatch m leaves the last stage at step t
                outs = outs.at[m].set(
                    jnp.where(stage == last, out, outs[m]))
            buf = jax.lax.ppermute(out, axis, fwd)
        # only the last stage holds real outputs; psum replicates them
        return jax.lax.psum(
            jnp.where(stage == last, outs, jnp.zeros_like(outs)), axis)

    spec_ws = P(axis)
    out = jax.shard_map(run_stage, mesh=mesh, in_specs=(spec_ws, P()),
                        out_specs=P(), check_vma=False)(ws_staged, x_micro)
    return out.reshape(x.shape)
