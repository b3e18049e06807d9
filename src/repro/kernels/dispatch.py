"""Pluggable backends for the serving matmul — the single choke point that
turns the PANN deployment artifact ({"w_q", "w_scale", ...}; built by
``models/serving.build_weight_store``) into projection outputs, and the one
module that knows what each backend needs.

Backends (selected per engine via ``ModelConfig.kernel_backend`` and threaded
through ``models.layers.apply_linear``; None reads as 'ref'):

  ``ref``     plain-jnp integer dataflow — runs on any platform; the oracle.
  ``fused``   Pallas bit-plane matmul (``kernels/pann_matmul``, mode='fused'):
              bit-planes are rebuilt from the int8 codes at trace time and
              fed to one int8 MXU pass per tile.
  ``packed``  Pallas packed-plane matmul (``kernels/pann_matmul_packed``):
              reads the bit-packed ``w_planes_pos``/``w_planes_neg`` artifact
              leaves (8 codes/byte along K — 2*P/8 bytes/weight HBM for plane
              count P, 1.75 at the ladder's P = 7), rebuilding the int8
              operand in VMEM with word-wide bit arithmetic.

The Pallas backends use the FUSED-PROLOGUE kernels (``pann_matmul_act`` /
``pann_matmul_packed_act``): fp32 activations go straight into the kernel,
which affine-encodes them tile-locally in VMEM — the int8 code tensor never
round-trips through HBM (ROADMAP item 3; ``kernel_bench`` accounts the
eliminated bytes). Only the (s, z) SCALARS are computed outside (a global
range reduction can't be tile-local), by the one ``core.quant`` derivation
all backends share; for export-frozen calibration they are precomputed
artifact leaves (``act_s``/``act_z``, hoisted by ``models/serving``).
Block shapes come from ``kernels.autotune`` — measured-best per
(M, K, N, planes) from a persistent per-device cache, VMEM-model heuristic
otherwise; the lookup is deterministic at trace time so warmed engines
never retrace.

Every backend realizes the SAME integer dataflow, so their fp32 outputs are
bit-identical (asserted in tests/test_kernel_dispatch.py, gated in CI by
``benchmarks/kernel_bench.py --check``):

  1. activations are affine-quantized to unsigned codes
     ``q = clip(round(x/s) + z, 0, n)`` with ``n = min(act_n, 127)`` — the
     zero point z absorbs signed transformer activations (DESIGN.md §4) and
     n is capped at the kernels' half-range int8 code space (App. A.4);
     the ref backend applies ``quant.affine_encode`` in XLA, the Pallas
     backends apply the same formula in-kernel on the same sealed (s, z);
  2. ``y_int = q @ w_q - z * colsum(w_q) + round(b / (s*gamma))`` is
     computed exactly in int32 (MXU pass or jnp; the kernels fuse the
     combined zero-point/bias row ``zcol`` into the accumulator) — the
     per-output-channel correction keeps the MACs genuinely unsigned
     (Observation 1 / Eq. 5-6), and the bias lands on the output grid the
     way integer inference engines add it;
  3. ``y = y_int * s * gamma`` — two fp32 multiplies, identical
     association everywhere, and nothing downstream for XLA to
     fma-contract differently per backend.

Fallback policy (``resolve_backend``): 'fused'/'packed' degrade to 'ref' off
TPU, where the Pallas kernels would only be emulated. Appending ``:force`` (e.g.
"packed:force") runs the Pallas kernel anyway — interpret mode off-TPU;
slow, test/CI only, bit-identical by construction. Pad-to-block handling
lives HERE, not in callers: inputs are padded to tile multiples with zero
codes / zero planes (exact no-ops) and the result is sliced back.
"""
from __future__ import annotations

import contextlib
import contextvars
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.pann import bitplane_decompose, masked_codes
from repro.kernels import autotune
from repro.kernels import ops
from repro.kernels import pann_attention as _pa
from repro.kernels import pann_conv as _pc
from repro.kernels import pann_matmul as _pm
from repro.kernels import pann_matmul_packed as _pk
from repro.kernels import ref as _ref
from repro.kernels import rwkv_wkv as _wkv

Array = jax.Array

BACKENDS = ("ref", "fused", "packed")

# int8 serving codes are clipped to +-127 = 2^7 - 1, so 7 planes always
# reconstruct them exactly — the envelope used when no packed artifact
# pins the module's plane count.
INT8_PLANES = 7

# n = 2^7 - 1: the kernels' int8 lanes hold unsigned codes in [0, 127]
# (the paper's App.-A.4 half-range convention), so b~x >= 8 operating
# points run their activations at this ceiling inside the kernels.
HALF_RANGE_LEVELS = 127.0


def parse_backend(spec: str) -> tuple[str, bool]:
    """'fused' -> ('fused', False); 'packed:force' -> ('packed', True)."""
    name, _, opt = spec.partition(":")
    if name not in BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}; "
                         f"have {BACKENDS}")
    if opt not in ("", "force"):
        raise ValueError(f"unknown backend option {opt!r} in {spec!r}; "
                         "only ':force' (run Pallas in interpret mode "
                         "off-TPU) is recognized")
    return name, opt == "force"


def effective_backend(spec: str) -> str:
    """What ``spec`` runs on this host — 'ref' for a Pallas backend off TPU
    unless forced, '<name>:interpret' when forced there — so a summary
    shows the fallback instead of the name that was asked for."""
    name, force = parse_backend(spec)
    if name == "ref" or ops.on_tpu():
        return name
    return f"{name}:interpret" if force else "ref"


def reads_planes(spec: str) -> bool:
    """Does backend ``spec`` read the packed plane leaves? Only 'packed'
    does, so only its artifacts are built with ``pack_planes``."""
    return parse_backend(spec)[0] == "packed"


def resolve_backend(spec: str, p: dict) -> tuple[str, bool]:
    """(effective backend, interpret flag) for artifact ``p`` on this host.

    Non-TPU hosts without ':force' resolve to 'ref' (ragged shapes are not
    misfits — padding below absorbs them). A 'packed' request against a
    variant built without plane leaves is a build error, not a misfit —
    raised, never silently degraded.
    """
    name, force = parse_backend(spec)
    if name == "ref":
        return "ref", False
    if reads_planes(spec) and "w_planes_pos" not in p:
        raise ValueError(
            "backend 'packed' needs the w_planes_pos/w_planes_neg artifact "
            "leaves; build the store with "
            "build_weight_store(..., pack_planes=True)")
    if not ops.on_tpu() and not force:
        return "ref", False
    return name, not ops.on_tpu()


class StackedPlanes(NamedTuple):
    """A plane leaf that names its stack and its layer: ``stack`` is the
    (L, P, K/8, N) uint8 store leaf of L stacked layers, ``index`` the
    layer (an int or an int32 scalar). ``models.decode_step`` hands these
    to the projections in place of sliced (P, K/8, N) leaves, and the
    packed kernel reads the layer where it lies in the store."""
    stack: Array
    index: Array | int


# the plane leaves of a packed artifact
PLANE_KEYS = ("w_planes_pos", "w_planes_neg")

# the tallies of the open ``plane_traffic`` contexts
_PLANE_TALLIES: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "plane_tallies", default=())


@contextlib.contextmanager
def plane_traffic():
    """Tally the packed planes that the packed calls traced while the
    context is open read: ``in_place`` the bytes the kernel reads from the
    store leaf itself, ``copied`` those it reads through a sliced or
    padded copy (K or N off its block). One layer's pos and neg planes
    count once per call; counted from shapes at trace time, nothing is
    added to the traced program."""
    tally = {"in_place": 0, "copied": 0}
    token = _PLANE_TALLIES.set(_PLANE_TALLIES.get() + (tally,))
    try:
        yield tally
    finally:
        _PLANE_TALLIES.reset(token)


def _plane_layer_shape(leaf) -> tuple[int, ...]:
    """(P, K/8, N) of one layer of a plane leaf, stacked or not."""
    if isinstance(leaf, StackedPlanes):
        return leaf.stack.shape[1:]
    return leaf.shape


def _pick_bk(bk: int, mult: int) -> int:
    """Largest multiple of ``mult`` <= bk (floor at ``mult``)."""
    return max(mult, bk - bk % mult)


def _matmul_ref(q8: Array, w_q: Array, s, gamma: Array, zcol: Array
                ) -> Array:
    """jnp oracle of the kernels' finalize: exact int32 matmul, exact int32
    zero-point subtraction, then the identical fp32 multiply chain
    (y * s * gamma, in that association)."""
    y_int = jax.lax.dot_general(q8, w_q, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
    return (y_int - zcol).astype(jnp.float32) * s * gamma


def _qparams(s, z, n_lvl, shift=None) -> Array:
    """(1, 4) f32 SMEM block [s, z, n_lvl, plane_shift] for the
    fused-prologue kernels. ``shift`` is the count of LOW bit-planes the
    kernel skips at runtime (a rung view over a max-R plane store); None
    means 0 — all planes live."""
    if shift is None:
        shift = jnp.float32(0.0)
    return jnp.stack([jnp.asarray(s, jnp.float32).reshape(()),
                      jnp.asarray(z, jnp.float32).reshape(()),
                      jnp.asarray(n_lvl, jnp.float32).reshape(()),
                      jnp.asarray(shift, jnp.float32).reshape(())]
                     ).reshape(1, 4)


def _matmul_fused(xf: Array, w_q: Array, s, z, n_lvl, gamma: Array,
                  zcol: Array, n_planes: int, interpret: bool,
                  shift=None,
                  params: autotune.KernelParams | None = None) -> Array:
    """Fused-prologue bit-plane kernel on planes rebuilt from the int8
    codes: fp32 activations in, affine-encoded in VMEM (codes never touch
    HBM). Padded fp32 rows/cols encode to the code z, which multiplies the
    zero-padded plane region — an exact no-op, then sliced away. With a
    view ``shift``, the codes are the max-R store's and the kernel skips
    the dead low planes at runtime."""
    pos = bitplane_decompose(jnp.maximum(w_q, 0), n_planes)
    neg = bitplane_decompose(jnp.maximum(-w_q.astype(jnp.int32), 0),
                             n_planes)
    m, k = xf.shape
    n = w_q.shape[-1]
    if params is None:
        params = autotune.params_for(m, k, n, n_planes, "fused")
    bm, bn, bk = params.blocks
    xp = ops._pad_to(ops._pad_to(xf, bm, 0), bk, 1)
    pp = ops._pad_to(ops._pad_to(pos, bk, 1), bn, 2)
    pn = ops._pad_to(ops._pad_to(neg, bk, 1), bn, 2)
    gp = ops._pad_to(gamma, bn, 0)
    zp = ops._pad_to(zcol, bn, 0)
    y = _pm.pann_matmul_act(xp, pp, pn, _qparams(s, z, n_lvl, shift), gp,
                            zp, mode="fused", bm=bm, bn=bn, bk=bk,
                            depth=params.depth, grid_order=params.order,
                            interpret=interpret)
    return y[:m, :n]


def _matmul_packed(xf: Array, pp, pn, s, z, n_lvl, gamma: Array,
                   zcol: Array, interpret: bool, shift=None,
                   params: autotune.KernelParams | None = None) -> Array:
    """Fused-prologue packed-plane kernel on the uint8 artifact leaves:
    (P, K/8, N) arrays, or ``StackedPlanes`` that name a layer of an
    (L, P, K/8, N) store leaf. The kernel rebuilds the weight rows in a K
    order permuted within each 256-row group
    (``pann_matmul_packed.permute_k``); x's columns are permuted to match
    where they are padded.

    Where K and N are whole multiples of the blocks, the kernel reads the
    planes where they lie, a stacked leaf at its layer; elsewhere the
    layer is sliced out and padded (a copy of its planes every call)."""
    m, k = xf.shape
    n_planes, k8, n = _plane_layer_shape(pp)
    k_full = k8 * 8                  # pack_planes padded K up to 8
    if params is None:
        params = autotune.params_for(m, k_full, n, n_planes, "packed")
    bm, bn, bk = params.blocks
    bk = _pick_bk(bk, _pk.K_GROUP)   # whole 256-row reconstruct groups
    bn = -(-bn // 128) * 128         # whole 128-lane plane chunks
    xp = ops._pad_to(ops._pad_to(xf, bm, 0), k_full, 1)
    xp = _pk.permute_k(ops._pad_to(xp, bk, 1))
    k_pad = xp.shape[1]
    in_place = k_pad == k_full and n % bn == 0
    for tally in _PLANE_TALLIES.get():
        tally["in_place" if in_place else "copied"] += 2 * n_planes * k8 * n
    layer = None
    if isinstance(pp, StackedPlanes):
        if in_place:
            layer, pp, pn = pp.index, pp.stack, pn.stack
        else:
            pp, pn = pp.stack[pp.index], pn.stack[pn.index]
    # a padded copy of the planes is made only where N or K is not a
    # multiple of its block (the heuristic's blocks avoid that wherever N
    # is a multiple of 128: the TPU compiler refuses a shorter copy)
    ppp = ops._pad_to(ops._pad_to(pp, k_pad // 8, -2), bn, -1)
    pnp = ops._pad_to(ops._pad_to(pn, k_pad // 8, -2), bn, -1)
    gp = ops._pad_to(gamma, bn, 0)
    zp = ops._pad_to(zcol, bn, 0)
    y = _pk.pann_matmul_packed_act(xp, ppp, pnp,
                                   _qparams(s, z, n_lvl, shift),
                                   gp, zp, layer, bm=bm, bn=bn, bk=bk,
                                   depth=params.depth,
                                   grid_order=params.order,
                                   interpret=interpret)
    return y[:m, :n]


def _act_scalars(xf: Array, p: dict) -> tuple[Array, Array, Array]:
    """The per-projection activation-quantizer scalars (s, z, n_lvl).

    PREFERS the artifact leaves hoisted by ``models/serving``:
    ``act_nlvl`` (= min(act_n, 127), saving a min per projection per decode
    step) and — for export-frozen calibration — ``act_s``/``act_z``, which
    turn the whole derivation into two leaf reads. Both hoists are computed
    at build time with the IDENTICAL ``core.quant`` op sequence used here,
    so hoisted and derived artifacts are bit-exact. Fallbacks keep
    pre-hoist artifacts (and hand-built test leaves) serving unchanged.

    include_zero (inside ``act_range_bounds``) bounds z to [0, n]: without
    it, activations that do not span zero produce |z| far outside int32 and
    the zcol correction wraps.
    """
    nlvl = p.get("act_nlvl")
    if nlvl is not None:
        n_lvl = jnp.asarray(nlvl, jnp.float32).reshape(())
    else:
        act_n = p.get("act_n")
        if act_n is None:
            n_lvl = jnp.float32(HALF_RANGE_LEVELS)
        else:
            n_lvl = jnp.minimum(jnp.asarray(act_n, jnp.float32).reshape(()),
                                HALF_RANGE_LEVELS)
    act_s = p.get("act_s")
    if act_s is not None:
        # frozen calibration with build-time-hoisted scalars
        return (jnp.asarray(act_s, jnp.float32).reshape(()),
                jnp.asarray(p["act_z"], jnp.float32).reshape(()),
                n_lvl)
    act_lo = p.get("act_lo")
    # named so a profiler trace can price the per-tensor range reduction
    with jax.named_scope("act_range"):
        if act_lo is not None:
            # export-frozen EMA calibration without the hoist (older
            # artifacts): same zero-extended frozen-range convention as the
            # QAT forward — one range convention everywhere
            lo, hi = quant.act_range_bounds(
                xf, jnp.asarray(act_lo, jnp.float32).reshape(()),
                jnp.asarray(p["act_hi"], jnp.float32).reshape(()))
        else:
            lo, hi = quant.act_range_bounds(xf, include_zero=True)
        s, z = quant.affine_scale_zp(lo, hi, n_lvl)
    return s, z, n_lvl


def _shift_leaf(p: dict):
    """The module's ``plane_shift`` view leaf as a traced f32 scalar.

    A rung VIEW over a max-R plane store (models/serving build_rung_views)
    marks its dead low planes with this DATA leaf; the kernels skip them at
    runtime, so every rung shares one compilation. Artifacts without the
    leaf get None -> shift 0 -> the pre-view dataflow.
    """
    shift = p.get("plane_shift")
    if shift is None:
        return None
    return jnp.asarray(shift, jnp.float32).reshape(())


def _gamma_zcol(p: dict, s, z, shift) -> tuple[Array, Array]:
    """(gamma, zcol): the per-output-channel dequant scale and the EXACT
    int32 zero-point/bias row — s(q - z) @ (gamma*w) = s*gamma*(q @ w_q
    - z*colsum(w_q)). Subtracting inside the integer accumulator (kernels
    take zcol; the jnp oracles mirror it) keeps the epilogue free of fp
    adds, which XLA would contract into backend-dependent fmas — the
    backends' bit-exactness depends on this.

    The artifact carries colsum precomputed (models/serving.py) so the
    packed backend never has to stream the full int8 code tensor just for
    this reduction; recomputing is the fallback for hand-built leaves.
    """
    w_q = p["w_q"]
    gamma = p["w_scale"].astype(jnp.float32).reshape(-1)
    colsum = p.get("w_colsum")
    if colsum is None:
        wc = (masked_codes(w_q, shift) if shift is not None
              else w_q.astype(jnp.int32))
        colsum = jnp.sum(wc, axis=-2)
    zcol = z.astype(jnp.int32) * colsum
    if "b" in p:
        # bias joins the accumulator too, quantized onto the output grid
        # s*gamma — the standard integer-inference bias treatment
        # (gemmlowp/TFLite) and the only formulation whose rounding XLA
        # cannot re-associate differently per backend (an fp "+ b" after
        # the dequant multiplies gets fma-contracted next to a jnp dot but
        # not next to a pallas call). Clipped so zcol - b_q stays well
        # inside int32 whatever the scales are.
        b_q = jnp.clip(jnp.round(p["b"].astype(jnp.float32) / (s * gamma)),
                       -2.0 ** 30, 2.0 ** 30).astype(jnp.int32)
        zcol = zcol - b_q
    return gamma, zcol


def _dispatch_rows(xf: Array, p: dict, s, z, n_lvl, gamma: Array,
                   zcol: Array, shift, name: str, interpret: bool) -> Array:
    """The backend branch on SEALED scalars: fp32 patch/token rows in,
    (M, N) fp32 out — shared verbatim by ``serving_linear`` and
    ``serving_conv``, which is what makes the conv projection inherit the
    matmuls' cross-backend bit-exactness rather than re-prove it.

    The result leaves through an exit barrier, the twin of the callers'
    entry barrier: without it, XLA on TPU fuses the jnp oracle's dot into
    its consumers — e.g. the residual add and the next RMSNorm's sum of
    squares, as one output fusion — and sums in another order than the
    loop fusion that reads a Pallas call's result. The programs around
    the backends then differ by an ulp, and greedy tokens drift apart."""
    w_q = p["w_q"]
    if name == "fused":
        n_planes = (_plane_layer_shape(p["w_planes_pos"])[0]
                    if "w_planes_pos" in p else INT8_PLANES)
        y = _matmul_fused(xf, w_q, s, z, n_lvl, gamma, zcol, n_planes,
                          interpret, shift=shift)
    elif name == "packed":
        y = _matmul_packed(xf, p["w_planes_pos"], p["w_planes_neg"],
                           s, z, n_lvl, gamma, zcol, interpret, shift=shift)
    else:
        y = _dispatch_ref(xf, w_q, s, z, n_lvl, gamma, zcol, shift)
    return jax.lax.optimization_barrier(y)


def _dispatch_ref(xf: Array, w_q: Array, s, z, n_lvl, gamma: Array,
                  zcol: Array, shift) -> Array:
    """The jnp oracle branch of ``_dispatch_rows``."""
    # the jnp oracle materializes the codes (quant.affine_encode — the
    # formula the kernels inline) and seals them so XLA cannot re-fuse
    # the encode into the dot differently than the kernels would
    q8 = jax.lax.optimization_barrier(
        quant.affine_encode(xf, s, z, n_lvl).astype(jnp.int8))
    # view shift: mask the dead low planes out of the codes — the jnp
    # mirror of the kernels' plane skip (masked * gamma_R is exactly
    # the truncated-code weight at the rung step gamma_R * 2^shift)
    w_ref_q = (masked_codes(w_q, shift).astype(jnp.int8)
               if shift is not None else w_q)
    return _matmul_ref(q8, w_ref_q, s, gamma, zcol)


def serving_linear(x: Array, p: dict, backend: str | None) -> Array:
    """The serving projection: y = affine-quant(x) @ deq(w_q) [+ b] through
    the selected backend (None reads as 'ref'). ``p`` is one module's
    serving artifact (2-D w_q — callers slice stacked leaves before we ever
    see them; the plane leaves may instead name their layer of the stack,
    ``StackedPlanes``).

    Output dtype follows x; the fp32 result is bit-identical across
    backends (module docstring). ``act_n`` (2^b~x - 1, a data leaf so
    ladder rungs share one compilation) sets the activation levels; absent,
    activations quantize at the 8-bit operating point's half-range.
    """
    name, interpret = resolve_backend(backend or "ref", p)
    w_q = p["w_q"]
    assert w_q.ndim == 2, (
        f"serving_linear wants a per-layer (K, N) weight slice, got "
        f"{w_q.shape} — callers must slice stacked leaves first")
    lead, k = x.shape[:-1], x.shape[-1]
    n_out = w_q.shape[-1]

    # entry barrier: seal the backend-specific subgraph off from upstream
    # fusion/layout decisions, so the surrounding (graph-identical) program
    # compiles the same way whichever backend sits between the barriers —
    # the bit-exactness contract must survive jit, not just eager mode
    xf = jax.lax.optimization_barrier(x.reshape(-1, k).astype(jnp.float32))
    s, z, n_lvl = _act_scalars(xf, p)
    shift = _shift_leaf(p)
    # seal the quantizer scalars: left open, XLA folds their derivation
    # into the backend-specific consumer cluster (e.g. strength-reducing
    # the x/s divide differently next to a dot than next to a pallas call)
    # and the codes stop matching across backends. The Pallas backends
    # consume these SAME sealed scalars — the in-kernel encode and the ref
    # encode below run the identical affine map on identical inputs.
    s, z, n_lvl = jax.lax.optimization_barrier((s, z, n_lvl))
    gamma, zcol = _gamma_zcol(p, s, z, shift)
    y = _dispatch_rows(xf, p, s, z, n_lvl, gamma, zcol, shift,
                       name, interpret)
    return y.reshape(*lead, n_out).astype(x.dtype)


def serving_conv(x: Array, p: dict, spec, backend: str | None) -> Array:
    """The serving CONV projection: im2col over the serving matmuls.

    ``x``: (B, H, W, Cin) fp input; ``p``: the layer's serving artifact with
    the kernel FLAT as (kh*kw*Cin, Cout) w_q (kernels/pann_conv layout
    contract — same leaves, plane packing, and rung views as any linear);
    ``spec``: the static geometry (any object with kh/kw/sh/sw/ph/pw ints,
    e.g. ``configs.base.ConvSpec``). Returns (B, Ho, Wo, Cout) in x.dtype.

    One deliberate divergence from ``serving_linear``: the activation
    scalars are derived from the PADDED INPUT tensor, not the patch rows.
    Strided geometry may leave pixels out of every patch, so patch-derived
    ranges could differ between geometries over the same input; deriving
    from the input keeps the quantizer a function of the tensor alone, and
    ``serving_conv_oracle`` consumes the identical sealed scalars so the
    bit-exactness contract is unaffected. Padding happens in fp BEFORE the
    encode: with include_zero ranges the border encodes to exactly z and
    the zcol correction makes it an exact no-op (pann_conv docstring).
    ``backend`` None reads as 'ref', as in ``serving_linear``.
    """
    name, interpret = resolve_backend(backend or "ref", p)
    w_q = p["w_q"]
    assert w_q.ndim == 2 and x.ndim == 4, (w_q.shape, x.shape)
    b = x.shape[0]
    n_out = w_q.shape[-1]
    # entry barrier on the padded fp input — the conv analogue of sealing
    # the (-1, K) rows: everything backend-specific hangs off this value
    xpad = jax.lax.optimization_barrier(
        _pc.pad_nhwc(x.astype(jnp.float32), spec.ph, spec.pw))
    s, z, n_lvl = _act_scalars(xpad.reshape(-1, xpad.shape[-1]), p)
    shift = _shift_leaf(p)
    s, z, n_lvl = jax.lax.optimization_barrier((s, z, n_lvl))
    gamma, zcol = _gamma_zcol(p, s, z, shift)
    patches = _pc.extract_patches(xpad, spec.kh, spec.kw, spec.sh, spec.sw)
    ho, wo = patches.shape[1], patches.shape[2]
    xf = patches.reshape(-1, patches.shape[-1])
    y = _dispatch_rows(xf, p, s, z, n_lvl, gamma, zcol, shift,
                       name, interpret)
    return y.reshape(b, ho, wo, n_out).astype(x.dtype)


def serving_conv_oracle(x: Array, p: dict, spec) -> Array:
    """jnp int32 convolution oracle for ``serving_conv``: the same sealed
    scalars and zcol row, but the integer accumulation runs through
    ``lax.conv_general_dilated`` instead of im2col + matmul. Integer sums
    are associative, so every backend of ``serving_conv`` must match this
    bit-for-bit in fp32 (asserted in tests/test_encoder_serving.py) — the
    conv counterpart of ``_matmul_ref``."""
    w_q = p["w_q"]
    xpad = jax.lax.optimization_barrier(
        _pc.pad_nhwc(x.astype(jnp.float32), spec.ph, spec.pw))
    s, z, n_lvl = _act_scalars(xpad.reshape(-1, xpad.shape[-1]), p)
    shift = _shift_leaf(p)
    s, z, n_lvl = jax.lax.optimization_barrier((s, z, n_lvl))
    gamma, zcol = _gamma_zcol(p, s, z, shift)
    q = jax.lax.optimization_barrier(
        quant.affine_encode(xpad, s, z, n_lvl).astype(jnp.int8))
    w_int = (masked_codes(w_q, shift) if shift is not None
             else w_q.astype(jnp.int32))
    y_int = _pc.conv_int32(q, w_int, spec.kh, spec.kw, spec.sh, spec.sw)
    y = (y_int - zcol).astype(jnp.float32) * s * gamma
    return y.astype(x.dtype)


def cache_planes_active(n_lvl) -> Array:
    """Live LOW bit-planes of a cache code space with ``n_lvl`` levels:
    codes <= n_lvl < 2^b zero every plane >= b = log2(n_lvl + 1). Traced —
    the level count is a DATA leaf so ladder rungs share one compilation."""
    n = jnp.asarray(n_lvl, jnp.float32).reshape(())
    return jnp.ceil(jnp.log2(n + 1.0) - 1e-6)


def decode_attention(q: Array, kv, backend, *, num_kv_heads: int,
                     window=None, softcap: float = 0.0,
                     k_nlvl=None, v_nlvl=None) -> Array:
    """Decode attention over a quantized KV cache — the attention analogue
    of ``serving_linear``, one dispatch point for every backend.

    ``q``: (B, H, hd) fp queries of the current token (RoPE applied).
    ``kv``: a quantized cache, duck-typed — any object with ``k_planes`` /
    ``v_planes`` (B, P, S, K * hd//8) uint8 in the attention kernel's
    layout (``kernels.ref.cache_planes``), which the kernel reads as it
    lies,
    ``k_s``/``k_z``/``v_s``/``v_z`` (B, S) f32 and scalar ``length``
    (``models.attention.QuantKVCache``; no models import here, same reason
    serving_linear takes a plain dict).

    Queries are affine-quantized per-tensor at the kernels' half-range
    ceiling (q is transient — the cache codes are the power knob, DESIGN.md
    §10), with the same sealed-scalar discipline as ``serving_linear`` so
    'ref' and a ':force'd Pallas run consume identical codes. Backend
    fallback mirrors ``resolve_backend``: 'fused'/'packed' both name the
    one bit-plane attention kernel and degrade to the jnp oracle off-TPU
    unless forced.

    ``k_nlvl``/``v_nlvl`` (traced scalars; the cache's level-count leaves)
    let the kernel skip the DMA + unpack of the dead HIGH planes — codes
    <= n_lvl leave planes >= log2(n_lvl+1) all-zero, so skipping them is
    bit-exact and the oracle needs no counterpart. None = all planes live.
    """
    name, force = parse_backend(backend or "ref")
    use_kernel = name != "ref" and (ops.on_tpu() or force)
    b, h, hd = q.shape
    g = h // num_kv_heads
    # entry barrier + sealed quantizer scalars: the serving_linear contract
    qf = jax.lax.optimization_barrier(
        q.astype(jnp.float32).reshape(b, num_kv_heads, g, hd))
    lo, hi = quant.act_range_bounds(qf, include_zero=True)
    s_q, z_q = quant.affine_scale_zp(lo, hi, HALF_RANGE_LEVELS)
    q_scale = s_q * jnp.float32(hd) ** -0.5   # fold the 1/sqrt(hd) in once
    s_q, z_q, q_scale = jax.lax.optimization_barrier((s_q, z_q, q_scale))
    qq = jax.lax.optimization_barrier(
        quant.affine_encode(qf, s_q, z_q, HALF_RANGE_LEVELS)
        .astype(jnp.int32))
    args = (qq, z_q, q_scale, kv.k_planes, kv.k_s, kv.k_z,
            kv.v_planes, kv.v_s, kv.v_z, kv.length)
    if use_kernel:
        k_pact = (cache_planes_active(k_nlvl) if k_nlvl is not None
                  else None)
        v_pact = (cache_planes_active(v_nlvl) if v_nlvl is not None
                  else None)
        out = _pa.decode_attention(*args, k_pact, v_pact, window=window,
                                   softcap=softcap,
                                   interpret=not ops.on_tpu())
    else:
        out = _ref.decode_attention_ref(*args, window=window,
                                        softcap=softcap)
    # exit barrier: the _dispatch_rows contract
    return jax.lax.optimization_barrier(out).reshape(b, h, hd)


def _is_quant_cache(node) -> bool:
    return hasattr(node, "k_planes") and hasattr(node, "k_s")


def kv_caches(state) -> list[tuple[int, int]]:
    """(attention calls, cached positions) of each quantized KV cache in a
    decode ``state``, from shapes alone: a cache leaf stacked over layers
    or call sites serves one call per stacked slice."""
    return [(int(np.prod(n.k_s.shape[:-2])), n.k_s.shape[-1])
            for n in jax.tree_util.tree_leaves(state, is_leaf=_is_quant_cache)
            if _is_quant_cache(n)]


def kv_block_traffic(caches, pos: int) -> tuple[int, int] | None:
    """(read, held): the 128-position blocks that one decode step's
    attention calls over ``caches`` (:func:`kv_caches`) read at cache
    position ``pos`` (``pann_attention.live_blocks``, the kernel's own
    rule), and the blocks those caches hold. None without a cache."""
    if not caches:
        return None
    read = sum(n * _pa.live_blocks(int(pos), s) for n, s in caches)
    held = sum(n * _pa.live_blocks(s - 1, s) for n, s in caches)
    return read, held


def wkv_decode(r: Array, k: Array, v: Array, w: Array, u: Array,
               state: Array, backend) -> tuple[Array, Array]:
    """One RWKV-6 wkv decode step, the recurrence's dispatch point.

    r, k, v, w: (B, H, hd) — receptance, key, value and the decay in
    (0, 1); u: (H, hd), the bonus; ``state``: (B, hd, H * hd) f32 in the
    value-major layout of ``kernels.rwkv_wkv``. Returns (o (B, H, hd) f32,
    the new state). 'fused'/'packed' run the Pallas kernel on TPU (or when
    ':force'd, in interpret mode) and 'ref' the jnp oracle, as in
    ``decode_attention``; both compute in f32 at full precision."""
    name, force = parse_backend(backend or "ref")
    f32 = jnp.float32
    args = tuple(a.astype(f32) for a in (r, k, v, w, u, state))
    if name != "ref" and (ops.on_tpu() or force):
        return _wkv.wkv_decode(*args, interpret=not ops.on_tpu())
    return _ref.wkv_decode_ref(*args)


# ---------------------------------------------------------------------------
# Offline block autotuning (ServeEngine(autotune=True) / launch --autotune)
# ---------------------------------------------------------------------------

def _time_call(fn, iters: int = 3) -> float:
    fn()                               # compile + warm
    t0 = time.perf_counter()
    r = None
    for _ in range(iters):
        r = fn()
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters


def tune_projection(m: int, p: dict, backend: str,
                    planes_active: int | None = None) -> None:
    """Measure-and-cache the best kernel parameters (blocks + DMA depth +
    grid order) for one projection artifact at decode row count ``m``.
    Strictly offline: call before ``warmup`` — ``serving_linear`` then
    picks the cached parameters up at trace time (``autotune.params_for``).
    Off-TPU the heuristic is recorded untimed (interpret-mode timings are
    emulator noise; see ``kernels.autotune``).

    ``planes_active`` keys a single-point tuning run where the live plane
    count is STATIC (a fixed deployment at one rung). The serving ladder
    leaves it None: one compiled kernel serves every rung (the shift is
    data), so its lookups key on the full plane count.
    """
    name, _ = parse_backend(backend)
    if name == "ref":
        return
    w_q = p["w_q"]
    assert w_q.ndim == 2, w_q.shape
    k, n = w_q.shape
    n_planes = (p["w_planes_pos"].shape[-3] if "w_planes_pos" in p
                else INT8_PLANES)
    key = jax.random.PRNGKey(0)
    xf = jax.random.normal(key, (m, k), jnp.float32)
    s, z, n_lvl = _act_scalars(xf, p)
    shift = p.get("plane_shift")
    if shift is not None:
        shift = jnp.asarray(shift, jnp.float32).reshape(())
    colsum = p.get("w_colsum")
    if colsum is None:
        wc = (masked_codes(w_q, shift) if shift is not None
              else w_q.astype(jnp.int32))
        colsum = jnp.sum(wc, axis=-2)
    zcol = z.astype(jnp.int32) * colsum
    gamma = p["w_scale"].astype(jnp.float32).reshape(-1)
    k_eff = p["w_planes_pos"].shape[-2] * 8 if name == "packed" else k

    def runner(params):
        if name == "packed":
            fn = lambda: _matmul_packed(
                xf, p["w_planes_pos"], p["w_planes_neg"], s, z, n_lvl,
                gamma, zcol, interpret=not ops.on_tpu(), shift=shift,
                params=params)
        else:
            fn = lambda: _matmul_fused(
                xf, w_q, s, z, n_lvl, gamma, zcol, n_planes,
                interpret=not ops.on_tpu(), shift=shift, params=params)
        return _time_call(fn)

    autotune.tune(m, k_eff, n, n_planes, name, runner,
                  active=planes_active)
