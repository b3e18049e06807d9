"""Shared layers: norms, embeddings, and QuantLinear — the single choke point
through which every projection in every architecture runs, realizing the
paper's quantization modes (none / RUQ / RUQ-unsigned / PANN).

Activation handling for transformers (a generalization the paper doesn't
need for its ReLU CNNs): activations into projections are signed, so we use
*asymmetric* (zero-point) quantization: x ~ s (x_q - z) with unsigned codes
x_q. Then W x = s (W x_q) - s z (sum_k W[k, :]) — the correction term is a
per-output constant folded into the bias, so the MACs stay unsigned and the
Sec.-4 accumulator saving is preserved. See DESIGN.md §4.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import pann as pann_core
from repro.core import quant
from repro.kernels import dispatch
from repro.kernels import pann_conv as _pc

Array = jax.Array


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale)).astype(x.dtype)


def layernorm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def apply_norm(x: Array, params: dict, kind: str) -> Array:
    if kind == "layernorm":
        return layernorm(x, params["scale"], params["bias"])
    return rmsnorm(x, params["scale"])


def init_norm(d: int, kind: str) -> dict:
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.zeros((d,), jnp.float32)}


def softcap(x: Array, cap: float) -> Array:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    return cap * jnp.tanh(x / cap) if cap > 0 else x


# ---------------------------------------------------------------------------
# Asymmetric (zero-point) activation quantization
# ---------------------------------------------------------------------------

def affine_act_quant(x: Array, bits: int):
    """x ~= s * (q - z), q unsigned in [0, 2^b - 1]. Returns (q, s, z).

    The numerics live in ``core.quant.affine_quant_levels`` — one copy
    shared with the integer serving backends (``kernels.dispatch``)."""
    return quant.affine_quant_levels(x, (1 << bits) - 1)


def affine_fake_quant(x: Array, bits: int) -> Array:
    q, s, z = affine_act_quant(x, bits)
    xq = s * (q - z)
    return x + jax.lax.stop_gradient(xq - x)


def affine_fake_quant_ranged(x: Array, bits: int, rng: Array) -> Array:
    """``affine_fake_quant`` against a calibrated [lo, hi] range (STE).

    ``rng`` is a (2,)-shaped [lo, hi] entry from the EMA calibration
    collection (``core.calibrate``); the unseen sentinel (lo > hi) falls
    back to the dynamic per-tensor range, bit-exact with
    ``affine_fake_quant``."""
    xf = x.astype(jnp.float32)
    q, s, z = quant.affine_from_range(xf, float((1 << bits) - 1),
                                      rng[0], rng[1])
    xq = s * (q - z)
    return xf + jax.lax.stop_gradient(xq - xf)


# ---------------------------------------------------------------------------
# Activation-range calibration tap (QAT observers; core/calibrate.py)
# ---------------------------------------------------------------------------

class CalibTap:
    """Trace-scoped activation observer.

    While installed (``calib_tap``), every ``qlinear`` call that knows its
    module path (a) records the per-tensor min/max of its input under that
    path into ``observed``, and (b) quantizes activations against the
    calibrated EMA range in ``ranges`` (falling back to the dynamic range
    while a role is unseen).  The tap is installed *inside* each scan body
    (``models.model``) so the observed tracers stay inside their scan scope
    and are merged out through the carry — never leaked across traces.
    """

    __slots__ = ("ranges", "observed")

    def __init__(self, ranges):
        self.ranges = ranges or {}
        self.observed: dict[str, Array] = {}

    def observe(self, path: str, x: Array) -> None:
        xf = x.astype(jnp.float32)
        rec = jnp.stack([jnp.min(xf), jnp.max(xf)])
        prev = self.observed.get(path)
        if prev is not None:
            rec = jnp.stack([jnp.minimum(prev[0], rec[0]),
                             jnp.maximum(prev[1], rec[1])])
        self.observed[path] = rec

    def range_for(self, path: str) -> Optional[Array]:
        rng = self.ranges.get(path)
        if rng is None:
            return None
        return jax.lax.stop_gradient(rng)


_TAPS: list[CalibTap] = []


@contextlib.contextmanager
def calib_tap(ranges):
    """Install an activation observer for the enclosed trace scope."""
    tap = CalibTap(ranges)
    _TAPS.append(tap)
    try:
        yield tap
    finally:
        _TAPS.pop()


@contextlib.contextmanager
def calib_suspend():
    """Mask the active tap for a nested trace scope.

    Projections that run inside an INNER ``lax.scan`` (the MoE expert loop)
    must not observe into the outer tap: their min/max tracers belong to
    the inner trace and leak (UnexpectedTracerError) when the outer scope
    merges them. Suspended projections keep dynamic per-tensor ranges; the
    roles stay *unseen* in the collection, so export leaves them dynamic
    too — the train→serve agreement is preserved, just without frozen
    ranges for those roles."""
    _TAPS.append(None)
    try:
        yield
    finally:
        _TAPS.pop()


def _active_tap() -> Optional[CalibTap]:
    return _TAPS[-1] if _TAPS else None


def _act_fake_quant(x: Array, bits: int, path: Optional[str]) -> Array:
    """The activation side of ``qlinear``: dynamic per-tensor fake-quant,
    upgraded to observed + EMA-calibrated quantization when a tap is
    installed and the call site identified itself with a module path."""
    xf = x.astype(jnp.float32)
    tap = _active_tap()
    if tap is not None and path is not None:
        tap.observe(path, xf)
        rng = tap.range_for(path)
        if rng is not None:
            return affine_fake_quant_ranged(xf, bits, rng)
    return affine_fake_quant(xf, bits)


# ---------------------------------------------------------------------------
# QuantLinear
# ---------------------------------------------------------------------------

def module_quant(cfg, path: str):
    """Resolve the quant spec of the module at ``path`` ("attn.wq",
    "mlp.w_down", ...; vocabulary in core/policy.py).

    Without a policy tree this returns the global ``cfg.quant`` — the exact
    pre-policy object down the exact pre-policy code path, so uniform
    configs are bit-identical to the pre-refactor behavior. With one, each
    projection gets its own ``ModuleQuant`` (whose QuantConfig-compatible
    aliases feed the same ``qlinear`` branches).
    """
    if cfg.policy is None:
        return cfg.quant
    return cfg.policy.lookup(path)


def qlinear(x: Array, w: Array, b: Optional[Array], qc,
            path: Optional[str] = None) -> Array:
    """y = quant(x) @ quant(w) + b under the configured scheme.
    ``qc`` is a ``QuantConfig`` or a per-module ``core.policy.ModuleQuant``
    (attribute-compatible).  ``path`` is the module's canonical policy path
    ("attn.wq", ...): when given and a calibration tap is installed
    (``calib_tap``), the activation range is observed and the EMA-calibrated
    range drives the quantizer — without a tap the path is inert and the
    numerics are bit-exact with the pre-calibration behavior.

    Shapes: x (..., d_in), w (d_in, d_out). All schemes are implemented as
    (differentiable) fake-quant so the same code path serves PTQ evaluation
    and QAT (STE); the integer-exact deployment path lives in repro.kernels.

    'ruq_unsigned' is numerically identical to 'ruq' (Eq. 5-6 is exact) — the
    difference is pure power accounting — so it shares the ruq compute path;
    the split itself is exercised by repro.core.unsigned and the kernels.
    """
    mode = qc.mode
    dtype = x.dtype
    if mode == "none":
        y = x @ w
    elif mode in ("ruq", "ruq_unsigned"):
        wq = quant.fake_quant(w.astype(jnp.float32), qc.weight_bits,
                              signed=True, axis=0).astype(dtype)
        xq = _act_fake_quant(x, qc.act_bits, path).astype(dtype)
        y = xq @ wq
    elif mode == "pann":
        # the STE branch lives in core.pann so PANN's training semantics sit
        # beside its deployment semantics; per-module (b~x, R) comes from qc
        tap = _active_tap()
        rng = None
        if tap is not None and path is not None:
            tap.observe(path, x)
            rng = tap.range_for(path)
        y = pann_core.pann_qat_matmul(x, w, qc, act_range=rng)
    else:
        raise ValueError(f"unknown quant mode {mode!r}")
    if b is not None:
        y = y + b
    return y


def project(x: Array, p: dict, cfg, path: str) -> Array:
    """The one-call projection idiom: resolve the module's policy
    (``module_quant``), route through ``apply_linear`` with the configured
    kernel backend, and identify the module to the calibration tap. Every
    model projection goes through here so the per-module (b̃x, R) operating
    point and its observed activation range always travel together."""
    return apply_linear(x, p, module_quant(cfg, path),
                        backend=cfg.kernel_backend, path=path)


def init_linear(key, d_in: int, d_out: int, bias: bool = False,
                scale: float | None = None) -> dict:
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": jax.random.normal(key, (d_in, d_out), jnp.float32) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def apply_linear(x: Array, p: dict, qc, backend: Optional[str] = None,
                 path: Optional[str] = None) -> Array:
    """The projection entry point. A serving artifact ("w_q" present) routes
    through the selected kernel backend (``kernels.dispatch``: 'ref' |
    'fused' | 'packed', None reading as 'ref' — call sites thread
    ``cfg.kernel_backend``); training params route through ``qlinear``.
    ``path`` identifies the module for activation-range calibration
    (``calib_tap``); inert unless a tap is installed.
    """
    if "w_q" in p:
        return dispatch.serving_linear(x, p, backend)
    b = p.get("b")
    b = None if b is None else b.astype(x.dtype)
    return qlinear(x, p["w"].astype(x.dtype), b, qc, path=path)


# ---------------------------------------------------------------------------
# Conv stem (modality frontend)
# ---------------------------------------------------------------------------

def init_conv(key, spec) -> dict:
    """One conv-stem layer. The kernel is stored FLAT as a
    (kh*kw*c_in, c_out) matrix (kernels/pann_conv layout contract: feature
    order (di, dj, c) ⇔ HWIO reshape), so the quantizers, the serving
    artifact, and the rung-view machinery all see an ordinary linear with
    fan-in kh*kw*c_in. Conv stems conventionally carry a bias; the serving
    path folds it into the exact int32 zcol correction (kernels.dispatch)."""
    scale = spec.fan_in ** -0.5
    return {"w": jax.random.normal(key, (spec.fan_in, spec.c_out),
                                   jnp.float32) * scale,
            "b": jnp.zeros((spec.c_out,), jnp.float32)}


def apply_conv(x: Array, p: dict, cfg, spec, path: str) -> Array:
    """Conv projection through the same choke point as every linear.

    x: (B, H, W, C) raw frontend input. Serving artifacts ("w_q") route
    through ``dispatch.serving_conv`` — im2col over the integer matmuls,
    bit-identical to the int32 conv oracle. The training path lowers to the
    *same* im2col (pad -> patches -> matmul) and reuses ``apply_linear``:
    QAT fake-quant and calibration taps apply to conv exactly as to linears.
    """
    if "w_q" in p:
        return dispatch.serving_conv(x, p, spec, cfg.kernel_backend)
    xpad = _pc.pad_nhwc(x.astype(jnp.float32), spec.ph, spec.pw)
    patches = _pc.extract_patches(xpad, spec.kh, spec.kw, spec.sh, spec.sw)
    b, ho, wo, _ = patches.shape
    flat = patches.reshape(b * ho * wo, -1).astype(x.dtype)
    y = apply_linear(flat, p, module_quant(cfg, path), path=path)
    return y.reshape(b, ho, wo, spec.c_out).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int) -> dict:
    return {"table": jax.random.normal(key, (vocab, d), jnp.float32) * 0.02}


def embed(tokens: Array, p: dict, dtype) -> Array:
    return p["table"].astype(dtype)[tokens]


def unembed(x: Array, p: dict, qc) -> Array:
    """LM head (weight-activation matmul -> quantized like any projection)."""
    return qlinear(x, jnp.transpose(p["table"]).astype(x.dtype), None, qc,
                   path="lm_head")
