"""Pure-jnp oracles for every Pallas kernel (ground truth for tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def pann_matmul_ref(x_q: Array, planes_pos: Array, planes_neg: Array,
                    s_x: Array, gamma: Array) -> Array:
    """Oracle for kernels.pann_matmul: reconstruct signed integer weights from
    bit-planes, integer matmul, dequantize."""
    p = planes_pos.shape[0]
    weights = (2 ** jnp.arange(p, dtype=jnp.int32)).reshape(p, 1, 1)
    w_q = jnp.sum(weights * (planes_pos.astype(jnp.int32)
                             - planes_neg.astype(jnp.int32)), axis=0)
    y = jnp.matmul(x_q.astype(jnp.int32), w_q,
                   preferred_element_type=jnp.int32)
    return y.astype(jnp.float32) * s_x * gamma.reshape(1, -1)


def quantize_act_ref(x: Array, bits: int = 8) -> tuple[Array, Array]:
    """Oracle for kernels.quantize_act (per-row half-range unsigned codes)."""
    qmax = (1 << (bits - 1)) - 1
    xp = jnp.maximum(x.astype(jnp.float32), 0.0)
    amax = jnp.max(xp, axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / qmax
    q = jnp.clip(jnp.round(xp / scale), 0, qmax).astype(jnp.int8)
    return q, scale


def unsigned_matmul_ref(x_q: Array, w_q: Array, s_x: Array, s_w: Array
                        ) -> Array:
    """Oracle for kernels.unsigned_matmul: plain signed integer matmul."""
    y = jnp.matmul(x_q.astype(jnp.int32), w_q.astype(jnp.int32),
                   preferred_element_type=jnp.int32)
    return y.astype(jnp.float32) * s_x * s_w.reshape(1, -1)


# ---------------------------------------------------------------------------
# Quantized KV-cache codec + decode-attention oracle (docs/kv_cache.md)
# ---------------------------------------------------------------------------

# The cache layout pins this many bit-planes whatever the rung's cache bits
# are: rungs that use fewer bits write zero high planes, so one jitted
# decode step serves every cache rung (the LADDER_PLANE_COUNT analogue for
# the cache; unsigned affine codes are clipped to n <= 127 = 2^7 - 1).
CACHE_PLANES = 7

# Probabilities are re-quantized to this fixed-point scale for the exact
# int32 PV pass: sum_s p = 1, so sum_s round(p * 2^14) ~ 2^14 and
# pq @ vq <= 127 * 2^14 — int32-safe for ANY sequence length.
PROB_SCALE = float(1 << 14)

# The softmax numerators exp(sc - max) in (0, 1] are put on this fixed grid
# before they are summed, so the normalizer is an exact int32 sum: a float
# sum's rounding depends on its reduction order, which XLA and Mosaic choose
# differently (and Mosaic sums the lane-padded row). int32-safe while the
# cache holds at most MAX_CACHE_LEN positions.
EXP_SCALE = float(1 << 15)
MAX_CACHE_LEN = (1 << 31) // (1 << 15) - 1

_CACHE_NEG_INF = -1e30   # matches models.attention.NEG_INF


def pack_cache_codes(codes: Array, n_planes: int = CACHE_PLANES) -> Array:
    """Pack unsigned integer codes (..., d) in [0, 2^n_planes) into
    bit-planes of 8 bits/byte along the LAST axis: (n_planes, ..., d//8)
    uint8. Plane p holds bit p of every code; byte j of a plane holds
    positions 8j..8j+7, element 8j+i at bit i. Requires d % 8 == 0
    (head dims are; asserted). Distinct from ``core.pann.pack_planes``,
    which packs the weight planes along axis -2 for the matmul kernels."""
    d = codes.shape[-1]
    assert d % 8 == 0, f"cache codec packs along head_dim; {d} % 8 != 0"
    c = codes.astype(jnp.int32)
    shifts = jnp.arange(n_planes, dtype=jnp.int32).reshape(
        (n_planes,) + (1,) * c.ndim)
    planes = (c[None] >> shifts) & 1                      # (P, ..., d)
    bits = planes.reshape(planes.shape[:-1] + (d // 8, 8))
    weights = (1 << jnp.arange(8, dtype=jnp.int32))
    return jnp.sum(bits * weights, axis=-1).astype(jnp.uint8)


def unpack_cache_codes(packed: Array) -> Array:
    """Inverse of :func:`pack_cache_codes`: (P, ..., d//8) uint8 ->
    (..., d) int32."""
    p = packed.shape[0]
    bits = (packed[..., None].astype(jnp.int32)
            >> jnp.arange(8, dtype=jnp.int32)) & 1        # (P, ..., d8, 8)
    bits = bits.reshape(packed.shape[:-1] + (packed.shape[-1] * 8,))
    weights = (1 << jnp.arange(p, dtype=jnp.int32)).reshape(
        (p,) + (1,) * (packed.ndim - 1))
    return jnp.sum(bits * weights, axis=0)


def decode_attention_ref(qq: Array, q_z: Array, q_scale: Array,
                         k_planes: Array, k_s: Array, k_z: Array,
                         v_planes: Array, v_s: Array, v_z: Array,
                         pos: Array, *, window=None, softcap: float = 0.0,
                         prob_scale: float = PROB_SCALE) -> Array:
    """Oracle for kernels.pann_attention.decode_attention: one-token GQA
    decode attention read DIRECTLY off the packed bit-plane KV cache.

    Shapes: qq (B, K, G, hd) int32 affine q codes (zero point ``q_z``,
    scalar int32); ``q_scale`` = s_q * hd**-0.5, scalar fp32; k_planes /
    v_planes (B, P, S, K, hd//8) uint8; k_s/k_z/v_s/v_z (B, S) fp32
    per-position quantizer rows (z integer-valued); pos () or (B,) int32.

    The integer passes are EXACT (both zero points corrected inside int32;
    probabilities re-quantized at ``prob_scale``); the fp32 epilogue is the
    op sequence the Pallas kernel replicates VERBATIM, so ref and kernel
    are bit-identical in fp32 (tests/test_kv_cache_quant.py).
    """
    b, kh, g, hd = qq.shape
    s = k_planes.shape[2]
    kq = unpack_cache_codes(jnp.moveaxis(k_planes, 1, 0))   # (B, S, K, hd)
    vq = unpack_cache_codes(jnp.moveaxis(v_planes, 1, 0))
    qq = qq.astype(jnp.int32)
    qz = jnp.asarray(q_z, jnp.int32)
    kz = jnp.round(k_z).astype(jnp.int32)                   # (B, S)
    vz = jnp.round(v_z).astype(jnp.int32)
    # exact int32 QK^T with BOTH zero points corrected in the accumulator:
    # (qq - z_q) . (kq - z_k) = qq.kq - z_q*colsum(kq) - z_k*rowsum(qq)
    #                           + z_q*z_k*hd
    dots = jnp.einsum("bkgh,bskh->bkgs", qq, kq,
                      preferred_element_type=jnp.int32)
    colsum_k = jnp.sum(kq, axis=-1)                         # (B, S, K)
    rowsum_q = jnp.sum(qq, axis=-1)                         # (B, K, G)
    kz_b = kz[:, None, None, :]                             # (B, 1, 1, S)
    i32 = (dots
           - qz * jnp.moveaxis(colsum_k, 1, -1)[:, :, None, :]
           - kz_b * rowsum_q[..., None]
           + qz * kz_b * hd)
    # fp32 epilogue — fixed association, replicated in the kernel
    sc = (i32.astype(jnp.float32) * jnp.asarray(q_scale, jnp.float32)
          ) * k_s[:, None, None, :]
    if softcap > 0:
        sc = softcap * jnp.tanh(sc / softcap)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    k_pos = jnp.arange(s, dtype=jnp.int32)
    valid = k_pos[None, :] <= pos_b[:, None]                # (B, S)
    if window is not None:
        valid &= (pos_b[:, None] - k_pos[None, :]) < window
    sc = jnp.where(valid[:, None, None, :], sc, _CACHE_NEG_INF)
    m = jnp.max(sc, axis=-1, keepdims=True)
    eq = jnp.round(jnp.exp(sc - m) * EXP_SCALE).astype(jnp.int32)
    p = eq.astype(jnp.float32) / jnp.sum(
        eq, axis=-1, keepdims=True).astype(jnp.float32)
    # exact int32 PV: probs are rescaled into V's largest per-batch scale,
    # re-quantized at prob_scale, and the V zero point is subtracted inside
    # the accumulator (same zcol convention as serving_linear)
    sv_ref = jnp.maximum(jnp.max(jnp.where(valid, v_s, 0.0), axis=-1),
                         1e-12)                             # (B,)
    ratio = v_s / sv_ref[:, None]                           # (B, S)
    pq = jnp.round(p * ratio[:, None, None, :] * prob_scale
                   ).astype(jnp.int32)                      # (B, K, G, S)
    pv = jnp.einsum("bkgs,bskh->bkgh", pq, vq,
                    preferred_element_type=jnp.int32)
    corr = jnp.einsum("bkgs,bs->bkg", pq, vz,
                      preferred_element_type=jnp.int32)
    scale = sv_ref / prob_scale                             # (B,)
    return ((pv - corr[..., None]).astype(jnp.float32)
            * scale[:, None, None, None])


# ---------------------------------------------------------------------------
# RWKV-6 wkv decode step (kernels/rwkv_wkv.py)
# ---------------------------------------------------------------------------

def wkv_sum(t: Array) -> Array:
    """Sum over the last axis (a power of two) by halving: element i adds
    element i + n/2, then i + n/4, ... — the order of the wkv kernel."""
    while t.shape[-1] > 1:
        half = t.shape[-1] // 2
        t = t[..., :half] + t[..., half:]
    return t[..., 0]


def wkv_decode_ref(r: Array, k: Array, v: Array, w: Array, u: Array,
                   state: Array) -> tuple[Array, Array]:
    """Oracle for kernels.rwkv_wkv.wkv_decode, to the bit: r, k, v, w (B,
    H, hd), u (H, hd), value-major state (B, hd, H * hd) holding S[h, i, j]
    at [j, h * hd + i]. Returns (o (B, H, hd), new state)."""
    b, h, hd = r.shape
    s = state.astype(jnp.float32).reshape(b, hd, h, hd)       # [b, j, h, i]
    kv = k[:, None] * jnp.swapaxes(v, 1, 2)[..., None]        # k[i] v[j]
    t = r[:, None] * (s + u[None, None] * kv)
    o = jnp.swapaxes(wkv_sum(t), 1, 2)                        # (B, H, hd)
    s_new = w[:, None] * s + kv
    return o, s_new.reshape(b, hd, h * hd)
