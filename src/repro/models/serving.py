"""Serving-time weight quantization: materialize PANN's deployment artifact.

Every projection weight is replaced by its PANN integer codes (Eq. 12,
per-output-channel gamma) stored in int8 — b_R <= 5 bits in practice
(Table 14), so int8 holds them losslessly — with dequant-on-load in the
forward. This is the §Perf iteration-5 change: decode is memory-bound and
weight-read bytes drop 2x vs bf16 (4x vs f32); the Pallas bit-plane kernel
(repro.kernels.pann_matmul) realizes the full b_R-bit layout on TPU.

By default activations stay in the compute dtype (W-PANN/A16); the PTQ
accuracy story at matched power is measured separately in
benchmarks/table2_ptq.py. Passing ``act_bits`` additionally quantizes
activations at b~x in the forward (stored as a data leaf so serve-engine
rungs share one compilation) — the full (b~x, R) operating point.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core import pann as pann_core
from repro.core import policy as pol
from repro.core import quant as quant_core
from repro.core.unsigned import unsigned_split
from repro.dist import sharding as shardlib
from repro.kernels.pann_matmul_packed import pack_planes

# projection parents whose "w" is PANN-quantized for serving
_QUANT_PARENTS = {
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "in_proj",
    "out_proj", "wr", "wg", "decay_a", "decay_b", "lm_head",
}


def _is_quant_parent(node: dict, trail: tuple) -> bool:
    """Does this pytree node hold a projection weight to quantize?

    Conv-stem layers (``params["conv_stem"]["s0"]`` etc.) qualify by trail,
    not by leaf name: their ``w`` is the FLAT (kh·kw·Cin, Cout) matrix of
    kernels/pann_conv's layout contract, so everything below — per-Cout
    gamma, plane packing, colsum, rung views — treats it as a linear.
    """
    if "w" not in node or getattr(node["w"], "ndim", 0) < 2:
        return False
    name = trail[-1] if trail else ""
    return name in _QUANT_PARENTS or "conv_stem" in trail

# Plane count used for ladder variant caches: int8 codes are clipped to
# +-127 = 2^7 - 1, so 7 planes reconstruct EVERY rung's codes exactly AND
# give every rung identical plane-leaf avals — the one-compiled-decode-step
# invariant extends to the packed backend for free (values-only variants).
LADDER_PLANE_COUNT = 7


@dataclasses.dataclass(frozen=True)
class ServingQuantSpec:
    """Every serving-quantizer knob in ONE object — the single place new
    knobs land, threaded through the engine, export, and fleet instead of
    the historical kwarg sprawl on ``quantize_params_for_serving`` /
    ``build_variant_cache`` / ``build_weight_store``.

    ``policy`` / ``r`` + ``act_bits`` pick the operating point (a tree, or
    one global (R, b~x)); the remaining fields mean exactly what the
    same-named kwargs of ``quantize_params_for_serving`` document.
    ``cache_bits`` additionally accepts a {rung key: bits} mapping when the
    spec parameterizes a whole-ladder build. Pass as ``spec=`` to any of the
    three builders; an explicit spec supersedes the individual kwargs.
    """
    policy: Optional[pol.PolicyTree] = None
    r: Optional[float] = None
    act_bits: Optional[int] = None
    store_dtype: Any = jnp.int8
    pack_planes: bool = False
    plane_count: Optional[int] = None
    calib: Optional[Mapping[str, Any]] = None
    cache_bits: Any = None

    def for_rung(self, cache_bits: Optional[int]) -> "ServingQuantSpec":
        """The per-rung restriction a ladder builder hands the per-variant
        quantizer: same knobs, this rung's resolved cache width."""
        return dataclasses.replace(self, cache_bits=cache_bits)


def _planes_artifact(codes, plane_count: int) -> dict:
    """Bit-pack the unsigned split of int codes into the deployment layout
    consumed by the 'packed' kernel backend (kernels/pann_matmul_packed).

    codes: (..., K, N) ints. Returns uint8 leaves of shape
    (..., P, ceil(K/8), N): the plane axis sits BEHIND any scan-stacked
    layer/group dims so ``lax.scan`` still slices per-layer artifacts, and
    K is the packed axis (8 codes/byte — 2*P/8 bytes per weight for both
    signs).
    """
    pos, neg = unsigned_split(codes.astype(jnp.int32))
    out = {}
    for key, half in (("w_planes_pos", pos), ("w_planes_neg", neg)):
        planes = pann_core.bitplane_decompose(half, plane_count)
        out[key] = pack_planes(jnp.moveaxis(planes, 0, -3))
    return out


@functools.partial(jax.jit, static_argnames=("store_dtype", "pack"))
def _store_leaf(w, r_max, *, store_dtype, pack: bool) -> dict:
    """One projection's store leaves at budget ``r_max``: clipped int codes,
    gamma, and (``pack``) the plane stacks. Quantization is per output
    channel over the fan-in axis, so each (K, N) matrix of a scan-stacked
    leaf is independent: ``lax.map`` quantizes them one at a time, which
    bounds the build's temporaries (int32 planes are 28 B/weight) by one
    matrix instead of the whole stack — what lets a full-width model's
    store build next to its fp32 params on one chip."""
    def one(m):
        w_q, gamma = pann_core.pann_quantize(m, r_max, axis=0)
        codes = jnp.clip(w_q, -127, 127)
        out = {"w_q": codes.astype(store_dtype),
               "w_scale": gamma.astype(jnp.float32)}
        if pack:
            out.update(_planes_artifact(codes, LADDER_PLANE_COUNT))
        return out

    lead = w.shape[:-2]
    out = jax.lax.map(one, w.astype(jnp.float32).reshape((-1,) + w.shape[-2:]))
    return jax.tree_util.tree_map(lambda a: a.reshape(lead + a.shape[1:]),
                                  out)


@jax.jit
def _view_colsum(codes, shift):
    """Per-output-channel sum of the codes a ``shift``-plane view realizes
    (fused, so no int32 copy of the code stack is materialized)."""
    return jnp.sum(pann_core.masked_codes(codes, shift), axis=-2)


def _cache_artifact(stack, cache_role_bits, calib) -> dict:
    """Per-rung KV-cache leaves: level counts + (when the role was
    calibrated) hoisted quantizer scalars, stack-shaped so scan bodies can
    slice them. One copy shared by the legacy per-rung quantizer and the
    weight-store view builder."""
    out = {}
    for role, prefix in zip(pol.CACHE_PATHS, ("k", "v")):
        n_lvl = float(quant_core.cap_levels(cache_role_bits[role]))
        out[f"{prefix}_nlvl"] = jnp.full(stack, n_lvl, jnp.float32)
        rng = calib.get(role) if calib else None
        if rng is not None and float(rng[0]) <= float(rng[1]):
            lo = jnp.minimum(jnp.float32(rng[0]), 0.0)
            hi = jnp.maximum(jnp.float32(rng[1]), 0.0)
            s, z = quant_core.affine_scale_zp(lo, hi, jnp.float32(n_lvl))
            out[f"{prefix}_s"] = jnp.full(stack, s, jnp.float32)
            out[f"{prefix}_z"] = jnp.full(stack, z, jnp.float32)
    return out


def _act_leaves(stack, ab, trail, calib) -> dict:
    """Per-rung activation-quantizer leaves for one projection at b~x=ab:
    level counts always, frozen range + hoisted (s, z) when calibrated.
    Shared by the legacy quantizer and the view builder (identical op
    sequences keep hoisted and derived scalars bit-exact)."""
    out = {
        # match the weight's stack dims (e.g. the vmapped group axis) so
        # scanned decode bodies can slice per group
        "act_n": jnp.full(stack, float((1 << int(ab)) - 1), jnp.float32),
        # hoisted kernel-facing level count min(act_n, 127): the decode
        # step reads the leaf instead of re-deriving the half-range cap
        # per projection per token (dispatch._act_scalars)
        "act_nlvl": jnp.full(stack, float(quant_core.cap_levels(int(ab))),
                             jnp.float32),
    }
    if calib:
        rng = calib.get(pol.serving_path(trail))
        if rng is not None and float(rng[0]) <= float(rng[1]):
            out["act_lo"] = jnp.full(stack, float(rng[0]), jnp.float32)
            out["act_hi"] = jnp.full(stack, float(rng[1]), jnp.float32)
            # frozen ranges admit build-time (s, z): the SAME f32 op
            # sequence as the serve-time derivation (quant.act_range_bounds
            # with a seen range + affine_scale_zp), so hoisted and derived
            # artifacts stay bit-exact
            lo = jnp.minimum(jnp.float32(rng[0]), 0.0)
            hi = jnp.maximum(jnp.float32(rng[1]), 0.0)
            s, z = quant_core.affine_scale_zp(
                lo, hi, jnp.float32(quant_core.cap_levels(int(ab))))
            out["act_s"] = jnp.full(stack, s, jnp.float32)
            out["act_z"] = jnp.full(stack, z, jnp.float32)
    return out


def quantize_params_for_serving(params: Any, cfg: ModelConfig,
                                r: float | None = None,
                                act_bits: int | None = None,
                                policy: Optional[pol.PolicyTree] = None,
                                store_dtype=jnp.int8,
                                pack_planes: bool = False,
                                plane_count: Optional[int] = None,
                                calib: Optional[Mapping[str, Any]] = None,
                                cache_bits: Optional[int] = None,
                                spec: Optional[ServingQuantSpec] = None
                                ) -> Any:
    """Walk the param tree; replace {"w": W} under known projections with
    {"w_q": int codes, "w_scale": gamma}. MoE stacked experts and the
    embedding gather table stay in floating point (documented).

    ``act_bits`` (b~x) additionally stores ``act_n = 2^b~x - 1`` per
    projection so the forward quantizes activations at the operating point's
    bit width; it is a data leaf, not a shape/dtype change, so serve-engine
    rungs with different b~x still share one compiled decode step. Without
    ``act_bits`` the artifact is W-PANN-only (activations in compute dtype),
    the legacy single-point behavior.

    ``policy`` (a ``core.policy.PolicyTree``) quantizes each projection at
    ITS OWN (R, b~x): the key trail through the pytree is mapped to the
    canonical module path (``policy.serving_path``) and the looked-up
    ``ModuleQuant`` supplies that projection's point. Since only leaf
    VALUES change — never shapes, dtypes, or the tree structure — a
    layerwise variant shares the decode-step compilation with every uniform
    variant (the serve_engine invariant).

    ``pack_planes`` additionally materializes the bit-packed plane artifact
    (``w_planes_pos``/``w_planes_neg`` uint8 leaves) the 'packed' kernel
    backend reads — 2 * P / 8 bytes per weight for plane count P.
    ``plane_count`` pins P; None derives each module's value-exact b_R
    (minimal HBM, single-point artifacts), while ladder caches pass
    ``LADDER_PLANE_COUNT`` so every rung shares plane-leaf avals. Codes are
    clipped to the planes' +-(2^P - 1) envelope (a no-op at P = 7, the int8
    range) so ``w_q`` and the planes always describe the SAME weights —
    the backends' bit-exactness contract.

    ``calib`` (an EMA activation-range collection from power-aware QAT,
    ``core.calibrate`` / ``launch/export.py``) freezes each projection's
    activation range into ``act_lo``/``act_hi`` leaves: the forward then
    quantizes against the SAME static ranges training converged on instead
    of the per-batch dynamic range — the train→serve closing move. Roles
    the training run never observed (lo > hi) stay dynamic. Requires an
    activation bit width (``act_bits`` or a ``policy``) so ``act_n`` is
    materialized alongside.

    ``cache_bits`` — or a ``policy`` with EXPLICIT cache-role overrides
    (``policy.CACHE_PATHS``; prefix fallback from "attn" is deliberately
    NOT an opt-in) — attaches a ``kv_cache`` artifact dict under every
    self-attention parent: per-role ``k_nlvl``/``v_nlvl`` DATA leaves (the
    rung's cache level counts, stack-shaped like ``act_n`` so scan bodies
    slice them) plus, when ``calib`` saw the cache roles, frozen quantizer
    scalars ``k_s``/``k_z``/``v_s``/``v_z`` hoisted with the identical
    ``affine_scale_zp`` op sequence the decode step would run. ``xattn``
    parents are skipped: cross-attention K/V are precomputed fp encoder
    projections, not a decode-time cache.

    ``spec`` (a ``ServingQuantSpec``) names the same knobs as one object
    and supersedes the individual kwargs."""
    if spec is not None:
        policy, r, act_bits = spec.policy, spec.r, spec.act_bits
        store_dtype, pack_planes = spec.store_dtype, spec.pack_planes
        plane_count, calib = spec.plane_count, spec.calib
        cache_bits = spec.cache_bits
    if policy is None:
        r = r if r is not None else cfg.quant.r
    if calib:
        if act_bits is None and policy is None:
            raise ValueError(
                "freezing calibrated ranges needs an activation bit width: "
                "pass act_bits= or a policy= tree")
        calib = {k: np.asarray(v, np.float32) for k, v in calib.items()}

    cache_role_bits = None
    policy_cache = pol.tree_cache_bits(policy) if policy is not None else {}
    if policy_cache or cache_bits is not None:
        default_b = cache_bits if cache_bits is not None else max(
            policy_cache.values())
        cache_role_bits = {
            role: int(policy_cache.get(role, default_b))
            for role in pol.CACHE_PATHS}

    def cache_artifact(stack) -> dict:
        return _cache_artifact(stack, cache_role_bits, calib)

    def walk(node, trail=()):
        if isinstance(node, dict):
            name = trail[-1] if trail else ""
            if _is_quant_parent(node, trail):
                w = node["w"]
                if policy is not None:
                    mq = policy.lookup(pol.serving_path(trail))
                    r_mod, ab = mq.r, mq.b_x_tilde
                else:
                    r_mod, ab = r, act_bits
                w_q, gamma = pann_core.pann_quantize(
                    w.astype(jnp.float32), float(r_mod), axis=w.ndim - 2)
                codes = jnp.clip(w_q, -127, 127)
                if pack_planes:
                    p_cnt = plane_count if plane_count is not None else \
                        pann_core.weight_storage_bits(codes)
                    cap = (1 << min(int(p_cnt), 7)) - 1
                    codes = jnp.clip(codes, -cap, cap)
                out = {
                    "w_q": codes.astype(store_dtype),
                    "w_scale": gamma.astype(jnp.float32),
                    # per-output-channel code sum, precomputed so the kernel
                    # backends' zero-point row (dispatch: zcol = z * colsum)
                    # never re-reads the code tensor at decode time — for
                    # 'packed' that read would dwarf the plane bytes
                    "w_colsum": jnp.sum(codes.astype(jnp.int32), axis=-2),
                }
                if pack_planes:
                    out.update(_planes_artifact(codes, int(p_cnt)))
                if ab is not None:
                    out.update(_act_leaves(w.shape[:-2], ab, trail, calib))
                if "b" in node:
                    out["b"] = node["b"]
                return out
            out = {k: walk(v, trail + (k,)) for k, v in node.items()}
            if (cache_role_bits is not None
                    and name in ("attn", "shared_attn") and "wk" in node
                    and isinstance(node["wk"], dict) and "w" in node["wk"]):
                out["kv_cache"] = cache_artifact(node["wk"]["w"].shape[:-2])
            return out
        if isinstance(node, list):
            return [walk(v, trail) for v in node]
        if isinstance(node, tuple):
            return tuple(walk(v, trail) for v in node)
        return node

    return walk(params)


# ---------------------------------------------------------------------------
# Operating-point variant cache (serve_engine)
# ---------------------------------------------------------------------------

def variant_shardings(variant: Any, mesh, par: Optional[ParallelConfig] = None
                      ) -> Any:
    """NamedShardings for one quantized variant on ``mesh`` — the same
    Megatron column/row rules as training params (``w_q`` follows ``w``,
    ``w_scale`` is replicated; see repro.dist.sharding)."""
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), variant)
    specs = shardlib.param_specs(shapes, mesh, par or ParallelConfig())
    return shardlib.to_named(specs, mesh)


def build_variant_cache(params: Any, cfg: ModelConfig,
                        r_by_rung: Mapping[Any, Any],
                        mesh=None, par: Optional[ParallelConfig] = None,
                        store_dtype=jnp.int8,
                        pack_planes: bool = False,
                        plane_count: Optional[int] = None,
                        calib: Optional[Mapping[str, Any]] = None,
                        cache_bits: Any = None,
                        spec: Optional[ServingQuantSpec] = None) -> dict:
    """Materialize one int8 weight-code variant per operating point.

    ``r_by_rung`` maps a rung key (e.g. the unsigned-MAC bit budget) to the
    rung's PANN addition budget R, to ``(R, b~x)`` to also quantize
    activations at the rung's bit width, or to a ``core.policy.PolicyTree``
    for a layerwise rung (each projection at its own per-module (R, b~x)).
    All variants share one pytree structure and one set of avals (b~x is
    stored as data, not shape), so a single jitted decode step serves every
    rung — switching rungs is a pointer swap, never a retrace. With a
    ``mesh``, each variant is device_put with the training-param layout so
    the cache scales past one device instead of replicating N ladders.

    ``pack_planes`` adds the uint8 plane leaves for the 'packed' kernel
    backend; callers must pin ``plane_count`` (e.g. ``LADDER_PLANE_COUNT``)
    so every rung's plane leaves share avals — a value-exact per-rung count
    would retrace the decode step at every rung switch.

    ``calib`` freezes EMA-calibrated activation ranges into every rung (see
    ``quantize_params_for_serving``); since the range leaves are values,
    not avals, calibrated and uncalibrated rungs still share one compiled
    decode step — but every rung in ONE cache must agree on which roles are
    calibrated (same leaf set), which passing one collection guarantees.

    ``cache_bits`` quantizes the decode-time KV cache per rung: an int
    applies to every rung, a mapping (rung key -> bits) gives each rung its
    own cache width — still one compiled step, because the width rides in
    the ``k_nlvl``/``v_nlvl`` DATA leaves. All-or-none across rungs (a rung
    without cache leaves would change the pytree structure); PolicyTree
    rungs may instead carry explicit cache-role overrides.

    ``spec`` (a ``ServingQuantSpec``) supersedes the per-knob kwargs.
    """
    if spec is not None:
        store_dtype, pack_planes = spec.store_dtype, spec.pack_planes
        plane_count, calib = spec.plane_count, spec.calib
        cache_bits = spec.cache_bits
    if isinstance(cache_bits, Mapping):
        missing = set(r_by_rung) - set(cache_bits)
        if missing:
            raise ValueError(
                f"cache_bits mapping must cover every rung (missing "
                f"{sorted(missing)}): rungs with and without kv_cache "
                "leaves cannot share one pytree structure")
    if pack_planes and plane_count is None and len(r_by_rung) > 1:
        raise ValueError(
            "pack_planes over multiple rungs needs a pinned plane_count "
            "(e.g. serving.LADDER_PLANE_COUNT); per-rung value-exact plane "
            "counts give rungs different avals and break the one-compiled-"
            "decode-step invariant")
    base = ServingQuantSpec(store_dtype=store_dtype,
                            pack_planes=pack_planes,
                            plane_count=plane_count, calib=calib)
    cache = {}
    shardings = None
    for key, rung_spec in r_by_rung.items():
        cb = (cache_bits.get(key) if isinstance(cache_bits, Mapping)
              else cache_bits)
        rq = base.for_rung(None if cb is None else int(cb))
        if isinstance(rung_spec, pol.PolicyTree):
            rq = dataclasses.replace(rq, policy=rung_spec)
        else:
            r, act_bits = rung_spec if isinstance(rung_spec, tuple) \
                else (rung_spec, None)
            rq = dataclasses.replace(rq, r=float(r), act_bits=act_bits)
        v = quantize_params_for_serving(params, cfg, spec=rq)
        if mesh is not None:
            if shardings is None:     # variants share avals: compute once
                shardings = variant_shardings(v, mesh, par)
            v = jax.device_put(v, shardings)
        cache[key] = v
    return cache


# ---------------------------------------------------------------------------
# Max-R weight store + zero-copy rung views (DESIGN.md §11)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WeightStore:
    """One quantized artifact serving a whole ladder.

    ``store`` holds the big leaves quantized ONCE at each module's maximal
    budget (w_q codes, packed plane stacks, w_scale = gamma_R, biases,
    frozen calibration ranges, plus every fp passthrough leaf). ``views``
    maps each rung key to a decode-ready variant that REFERENCES the store's
    big leaves (same arrays, same device buffers) and adds only per-rung
    small leaves: ``plane_shift`` (the dropped-low-plane count the kernels
    predicate on), the view's ``w_colsum``, the rung's activation-quantizer
    scalars, and its ``kv_cache`` level counts. Weight HBM is therefore
    INDEPENDENT of ladder depth — a 5-rung ladder holds one code tensor per
    module, not five (benchmarks/table14_footprint.py gates this).

    Rung numerics under views are the truncation-consistent scheme: rung
    codes are the top planes of the max-R codes, so a rung realizes the
    SNAPPED budget r_max / 2^shift rather than its exactly-planned R
    (``core.pann.view_shift``; accuracy delta measured at equal power by
    benchmarks/artifact_parity.py)."""
    store: Any
    views: dict


def _resolve_point(spec, trail) -> tuple[float, Optional[int]]:
    """One rung spec -> (R, b~x) for the module at ``trail`` — the same
    three spellings ``build_variant_cache`` accepts (PolicyTree / (R, b~x) /
    bare R)."""
    if isinstance(spec, pol.PolicyTree):
        mq = spec.lookup(pol.serving_path(trail))
        return float(mq.r), int(mq.b_x_tilde)
    if isinstance(spec, tuple):
        r, ab = spec
        return float(r), (None if ab is None else int(ab))
    return float(spec), None


def _rung_cache_role_bits(spec, cb: Optional[int]) -> Optional[dict]:
    """Per-role cache bits of one rung: explicit PolicyTree overrides win,
    ``cb`` fills the rest; None when the rung keeps the fp cache."""
    policy_cache = pol.tree_cache_bits(spec) \
        if isinstance(spec, pol.PolicyTree) else {}
    if not policy_cache and cb is None:
        return None
    default_b = cb if cb is not None else max(policy_cache.values())
    return {role: int(policy_cache.get(role, default_b))
            for role in pol.CACHE_PATHS}


def build_weight_store(params: Any, cfg: ModelConfig,
                       r_by_rung: Mapping[Any, Any],
                       mesh=None, par: Optional[ParallelConfig] = None,
                       store_dtype=jnp.int8,
                       pack_planes: bool = False,
                       calib: Optional[Mapping[str, Any]] = None,
                       cache_bits: Any = None,
                       spec: Optional[ServingQuantSpec] = None
                       ) -> WeightStore:
    """Quantize once at the per-module max budget; realize every rung of
    ``r_by_rung`` as a view over that single store (see ``WeightStore``).

    Accepts the same rung-spec / ``calib`` / ``cache_bits`` spellings as
    ``build_variant_cache`` and produces views with the legacy variants'
    pytree structure plus one extra data leaf per projection
    (``plane_shift``) — all views share avals, so the one-compiled-decode-
    step invariant holds across mixed weight-rung x cache-rung ladders.
    Plane leaves (``pack_planes``) are always built at ``LADDER_PLANE_COUNT``
    so the full truncation envelope is stored.

    With a ``mesh`` the store is device_put ONCE under the training param
    rules; views then alias the store's device buffers and only their small
    per-rung leaves are placed separately — the flat-HBM property survives
    sharding.

    ``spec`` (a ``ServingQuantSpec``) supersedes the per-knob kwargs.
    """
    if spec is not None:
        store_dtype, pack_planes = spec.store_dtype, spec.pack_planes
        calib, cache_bits = spec.calib, spec.cache_bits
    if isinstance(cache_bits, Mapping):
        missing = set(r_by_rung) - set(cache_bits)
        if missing:
            raise ValueError(
                f"cache_bits mapping must cover every rung (missing "
                f"{sorted(missing)}): rungs with and without kv_cache "
                "leaves cannot share one pytree structure")
    if calib:
        calib = {k: np.asarray(v, np.float32) for k, v in calib.items()}
    keys = list(r_by_rung)
    if not keys:
        raise ValueError("r_by_rung must name at least one rung")
    rung_cache: dict = {}
    for key in keys:
        cb = (cache_bits.get(key) if isinstance(cache_bits, Mapping)
              else cache_bits)
        rung_cache[key] = _rung_cache_role_bits(
            r_by_rung[key], None if cb is None else int(cb))
    cached = [k for k in keys if rung_cache[k] is not None]
    if cached and len(cached) != len(keys):
        raise ValueError(
            "kv_cache leaves must be all-or-none across rungs: rungs "
            f"{sorted(set(keys) - set(cached))!r} have no cache bits while "
            f"{sorted(cached)!r} do")

    def walk(node, trail=()):
        """Returns (store_node, {rung key: view_node}); passthrough leaves
        are the SAME object in the store and every view."""
        if isinstance(node, dict):
            name = trail[-1] if trail else ""
            if _is_quant_parent(node, trail):
                w = node["w"]
                points = {k: _resolve_point(r_by_rung[k], trail)
                          for k in keys}
                r_max = max(r for r, _ in points.values())
                shared = _store_leaf(w, jnp.float32(r_max),
                                     store_dtype=store_dtype,
                                     pack=pack_planes)
                codes = shared["w_q"]
                if "b" in node:
                    shared["b"] = node["b"]
                stack = w.shape[:-2]
                views = {}
                for k in keys:
                    r_mod, ab = points[k]
                    sh = pann_core.view_shift(r_max, r_mod,
                                              LADDER_PLANE_COUNT - 1)
                    v = dict(shared)
                    v["plane_shift"] = jnp.full(stack, float(sh),
                                                jnp.float32)
                    # the view's zero-point row: colsum of the codes the
                    # plane-skipping kernels REALIZE, not the stored ones
                    v["w_colsum"] = _view_colsum(codes, jnp.int32(sh))
                    if ab is not None:
                        v.update(_act_leaves(stack, ab, trail, calib))
                    views[k] = v
                return shared, views
            pairs = {k2: walk(v, trail + (k2,)) for k2, v in node.items()}
            store_n = {k2: p[0] for k2, p in pairs.items()}
            view_n = {k: {k2: p[1][k] for k2, p in pairs.items()}
                      for k in keys}
            if (cached and name in ("attn", "shared_attn") and "wk" in node
                    and isinstance(node["wk"], dict) and "w" in node["wk"]):
                stack = node["wk"]["w"].shape[:-2]
                for k in keys:
                    view_n[k]["kv_cache"] = _cache_artifact(
                        stack, rung_cache[k], calib)
            return store_n, view_n
        if isinstance(node, list):
            pairs = [walk(v, trail) for v in node]
            return ([p[0] for p in pairs],
                    {k: [p[1][k] for p in pairs] for k in keys})
        if isinstance(node, tuple):
            pairs = [walk(v, trail) for v in node]
            return (tuple(p[0] for p in pairs),
                    {k: tuple(p[1][k] for p in pairs) for k in keys})
        return node, {k: node for k in keys}

    store, view_trees = walk(params)
    if mesh is not None:
        store_dev = jax.device_put(store,
                                   variant_shardings(store, mesh, par))
        relink = {id(h): d for h, d in
                  zip(jax.tree_util.tree_leaves(store),
                      jax.tree_util.tree_leaves(store_dev))}

        def put(x, s):
            hit = relink.get(id(x))
            return hit if hit is not None else jax.device_put(x, s)

        shardings = None
        out_views = {}
        for k, vt in view_trees.items():
            if shardings is None:     # views share avals: compute once
                shardings = variant_shardings(vt, mesh, par)
            out_views[k] = jax.tree_util.tree_map(put, vt, shardings)
        return WeightStore(store=store_dev, views=out_views)
    return WeightStore(store=store, views=view_trees)


def device_put_weight_store(ws: WeightStore, mesh=None,
                            par: Optional[ParallelConfig] = None
                            ) -> WeightStore:
    """Place a host-memory weight store (e.g. ``serve_engine.artifact.
    load_artifact``'s mmap-backed numpy views) on device, PRESERVING the
    store/view aliasing: every store leaf is uploaded exactly once, view
    leaves that alias the store resolve to the SAME device buffer, and only
    the small per-rung leaves are placed separately — so serving straight
    from an artifact keeps weight HBM flat in ladder depth, exactly like a
    store built in-process (``build_weight_store``). With a ``mesh`` the
    training-param sharding rules apply, as there."""
    if mesh is not None:
        store_dev = jax.device_put(ws.store,
                                   variant_shardings(ws.store, mesh, par))
    else:
        store_dev = jax.device_put(ws.store)
    relink = {id(h): d for h, d in
              zip(jax.tree_util.tree_leaves(ws.store),
                  jax.tree_util.tree_leaves(store_dev))}

    shardings = None
    out_views = {}
    for k, vt in ws.views.items():
        if mesh is not None and shardings is None:  # views share avals
            shardings = variant_shardings(vt, mesh, par)

        def put(x, s=None):
            hit = relink.get(id(x))
            if hit is not None:
                return hit
            return jax.device_put(x) if s is None else jax.device_put(x, s)

        if mesh is not None:
            out_views[k] = jax.tree_util.tree_map(put, vt, shardings)
        else:
            out_views[k] = jax.tree_util.tree_map(put, vt)
    return WeightStore(store=store_dev, views=out_views)


def materialize_view(view: Any) -> Any:
    """Copy one rung view out into a standalone legacy-format variant:
    ``w_q`` becomes the masked codes the plane-skipping kernels realize
    (``core.pann.masked_codes``), plane leaves are re-packed from them, and
    the ``plane_shift`` leaf is dropped. Same gamma_R scale, same bias grid,
    same integer dataflow — the decode outputs are bit-identical to running
    the view itself, which tests/test_artifact.py asserts per module and
    per backend."""
    def walk(node):
        if isinstance(node, dict):
            if "w_q" in node and "plane_shift" in node:
                sh = jnp.asarray(node["plane_shift"],
                                 jnp.int32).reshape(-1)[0]
                masked = pann_core.masked_codes(node["w_q"], sh)
                out = {k: v for k, v in node.items() if k != "plane_shift"}
                out["w_q"] = masked.astype(node["w_q"].dtype)
                out["w_colsum"] = jnp.sum(masked, axis=-2)
                if "w_planes_pos" in node:
                    out.update(
                        _planes_artifact(masked, LADDER_PLANE_COUNT))
                return out
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        if isinstance(node, tuple):
            return tuple(walk(v) for v in node)
        return node

    return walk(view)
