"""PartitionSpec assignment for params, decode caches, and input batches.

All functions take the mesh as an argument and only read ``mesh.axis_names``
and ``mesh.shape`` (a name -> size mapping), so they work with abstract
mesh stand-ins in tests as well as real ``jax.sharding.Mesh`` objects.

Invariants (property-tested in tests/test_sharding_properties.py):
  * a mesh axis is used at most once per spec;
  * an assigned dimension is always divisible by the axis size;
  * KV-cache / recurrent-state *stack* dims (the vmapped per-group leading
    dim) are never sharded — sharding them would make the decode scan
    all-gather the entire global cache every step (§Perf iteration 4b);
  * norm/bias parameters are replicated.

Parameter rules follow Megatron column/row duality: projections that *expand*
(wq/wk/wv/w_gate/w_up/...) shard their output dim over "model"; projections
that *contract* back to d_model (wo/w_down/out_proj) shard their input dim,
so the pair needs exactly one all-reduce. FSDP additionally shards the
largest remaining dim over "data" (ZeRO-3).
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ParallelConfig
from repro.dist.constrain import _axis_size, _ok, batch_axis

# column-parallel (shard dim -1) / row-parallel (shard dim -2) parents
_ROW = {"wo", "w_down", "out_proj"}
_COL = {"wq", "wk", "wv", "w_gate", "w_up", "router", "in_proj", "wr",
        "wg", "decay_a", "decay_b", "lm_head"}
# dict keys that hold the actual weight array under a projection parent
# (w_planes_pos/neg: the bit-packed plane artifact for the 'packed' kernel
# backend — (..., P, K/8, N), sharded by the parent's col/row rule on N/K8)
_WEIGHT_KEYS = {"w", "w_q", "w_planes_pos", "w_planes_neg"}
# leaves that are always replicated (act_*: per-projection activation-
# quantizer scalars — levels, frozen calibration range, and the hoisted
# (s, z) the fused-prologue kernels read; plane_shift: the rung view's
# dropped-low-plane count, a per-module data scalar)
_REPLICATED_KEYS = {"b", "bias", "scale", "w_scale", "act_n", "act_nlvl",
                    "act_lo", "act_hi", "act_s", "act_z", "w_colsum",
                    "plane_shift"}


def _path_names(path) -> list[str]:
    out = []
    for k in path:
        name = getattr(k, "key", getattr(k, "name", None))
        if name is None:
            name = str(getattr(k, "idx", k))
        out.append(str(name))
    return out


def greedy_spec(dims: Sequence[int], mesh) -> P:
    """Assign mesh axes (in mesh order, so "data" lands on the batch dim
    first) to the first divisible unassigned dim each."""
    entries: list[Any] = [None] * len(dims)
    for ax in mesh.axis_names:
        size = _axis_size(mesh, ax)
        if size <= 1:
            continue
        for i, d in enumerate(dims):
            if entries[i] is None and d % size == 0:
                entries[i] = ax
                break
    return P(*entries)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def _fsdp_dim(shape, entries, stacked: bool) -> int | None:
    """Largest unassigned dim (preferring non-stack dims) for ZeRO sharding."""
    cands = [i for i in range(len(shape))
             if entries[i] is None and not (stacked and i == 0)]
    if not cands:
        return None
    return max(cands, key=lambda i: shape[i])


def param_specs(shapes: Any, mesh, par: ParallelConfig) -> Any:
    """PartitionSpec tree matching a param (ShapeDtypeStruct) tree."""

    def rule(path, leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        names = _path_names(path)
        leaf_key = names[-1]
        stacked = "groups" in names[:-1]
        entries: list[Any] = [None] * len(shape)
        if leaf_key in _REPLICATED_KEYS or any("norm" in n for n in names):
            return P(*entries)
        parent = None
        for n in reversed(names):
            if n in _COL or n in _ROW or n == "embed":
                parent = n
                break
        is_weight = (leaf_key in _WEIGHT_KEYS or leaf_key in _COL
                     or leaf_key in _ROW or leaf_key == "table")
        if parent is None and leaf_key != "table":
            return P(*entries)
        if not is_weight or len(shape) < 2:
            return P(*entries)
        if leaf_key == "table":           # embedding: shard the vocab dim
            if _ok(mesh, "model", shape[-2]):
                entries[-2] = "model"
        elif parent in _ROW:
            if _ok(mesh, "model", shape[-2]):
                entries[-2] = "model"
        else:                             # column-parallel default
            if _ok(mesh, "model", shape[-1]):
                entries[-1] = "model"
        if par.fsdp:
            i = _fsdp_dim(shape, entries, stacked)
            if i is not None and _ok(mesh, "data", shape[i]):
                entries[i] = "data"
        return P(*entries)

    return jax.tree_util.tree_map_with_path(rule, shapes)


# ---------------------------------------------------------------------------
# Decode caches / recurrent state
# ---------------------------------------------------------------------------

def cache_specs(tree: Any, mesh) -> Any:
    """Greedy specs for decode state trees. Mirrors ``constrain.dp_model_plan``:
    batch -> "data", first divisible later dim (the cached sequence) ->
    "model"; stack dims (under "groups"/"cross_kv") stay unsharded; scalars
    map to P()."""

    def rule(path, leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        names = _path_names(path)
        stacked = "groups" in names or "cross_kv" in names
        entries: list[Any] = [None] * len(shape)
        start = 1 if stacked else 0
        if start >= len(shape):
            return P(*entries)
        if _ok(mesh, "data", shape[start]):
            entries[start] = "data"
        model_at = None
        for i in range(start + 1, len(shape)):
            if _ok(mesh, "model", shape[i]):
                entries[i] = "model"
                model_at = i
                break
        if entries[start] is None and model_at is None:
            for i in range(start + 1, len(shape)):
                if _ok(mesh, "data", shape[i]):
                    entries[i] = "data"
                    break
        return P(*entries)

    return jax.tree_util.tree_map_with_path(rule, tree)


# ---------------------------------------------------------------------------
# Inputs
# ---------------------------------------------------------------------------

def input_sharding(mesh, arr_shape: Sequence[int]) -> P:
    """Batch-shard model inputs; same axis resolution as constrain_batch
    (one definition — ``constrain.batch_axis``), so what the jit boundary
    pins and what the model constrains can never drift apart."""
    if len(arr_shape) == 0:
        return P()
    entries: list[Any] = [None] * len(arr_shape)
    entries[0] = batch_axis(mesh, arr_shape[0])
    return P(*entries)


# ---------------------------------------------------------------------------
# Specs -> shardings
# ---------------------------------------------------------------------------

def to_named(specs: Any, mesh) -> Any:
    """Map a PartitionSpec tree to NamedShardings on ``mesh``."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Fleet: rung shards (host-level sharding of the ladder's rung views)
# ---------------------------------------------------------------------------

def rung_shard(ladder_bits: Sequence[int], n_hosts: int
               ) -> dict[int, tuple[int, ...]]:
    """Assign ladder rungs to decode hosts, round-robin.

    The device-level specs above shard ONE variant's leaves across a mesh;
    this is the HOST-level rule of the serving fleet
    (``repro.serve_engine.fleet``): each decode host materializes (and
    warms up) only its shard of the per-rung view cache, so fleet-wide
    variant memory is flat in ladder depth x hosts rather than their
    product. Deterministic and total: every rung lands on at least one
    host and every host serves at least one rung — with more hosts than
    rungs the extra hosts replicate the ladder cyclically (capacity), with
    more rungs than hosts a host serves several rungs.
    """
    bits = sorted({int(b) for b in ladder_bits})
    if not bits or n_hosts <= 0:
        raise ValueError(f"need >=1 rung and >=1 host, got {bits!r} x "
                         f"{n_hosts}")
    shards: dict[int, set] = {h: set() for h in range(n_hosts)}
    for i in range(max(n_hosts, len(bits))):
        shards[i % n_hosts].add(bits[i % len(bits)])
    return {h: tuple(sorted(s)) for h, s in shards.items()}
