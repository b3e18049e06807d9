"""Attention: GQA/MHA with RoPE, sliding-window and local/global variants,
logit soft-capping, cross-attention, KV caches, and a flash-style chunked
implementation (online softmax over KV blocks) so 32k-token prefill never
materializes an (S, S) score matrix.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import quant
from repro.dist import constrain as C
from repro.kernels import dispatch as KD
from repro.kernels import ref as KREF
from repro.models import layers as L

Array = jax.Array

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, T, H, hd); positions: (B, T) or (T,)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    if angles.ndim == 2:                                # (T, hd/2) -> batch dim
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Parameter init / projections
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, cross: bool = False) -> dict:
    hd = cfg.resolved_head_dim
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "wq": L.init_linear(ks[0], d, cfg.num_heads * hd, bias=cfg.qkv_bias),
        "wk": L.init_linear(ks[1], d, cfg.num_kv_heads * hd, bias=cfg.qkv_bias),
        "wv": L.init_linear(ks[2], d, cfg.num_kv_heads * hd, bias=cfg.qkv_bias),
        "wo": L.init_linear(ks[3], cfg.num_heads * hd, d),
    }


class KVCache(NamedTuple):
    k: Array          # (B, S_max, K, hd)
    v: Array          # (B, S_max, K, hd)
    length: Array     # () int32 — tokens currently cached


class QuantKVCache(NamedTuple):
    """Power-aware KV cache: K/V as packed bit-plane affine codes.

    Codes are unsigned affine (``core.quant.affine_encode``), bit-plane
    decomposed and packed 8/byte along head_dim, in the attention kernel's
    own layout (``kernels.ref.cache_planes``: one position per row, S the
    ``max_len``), so the kernel reads the cache where it lies. The plane
    axis sits behind the batch/scan dims and is pinned at
    ``kernels.ref.CACHE_PLANES`` whatever the rung's cache bits, so every
    cache rung shares ONE pytree structure and one jitted decode step (the
    ladder invariant). Quantizer (s, z) are per position — dynamic ranges
    vary per token; frozen (calibrated) ranges broadcast one scalar — with
    z integer-valued f32 (docs/kv_cache.md).
    """
    k_planes: Array   # (B, P, S, K * hd//8) uint8
    v_planes: Array   # (B, P, S, K * hd//8) uint8
    k_s: Array        # (B, S) f32 per-position K scales
    k_z: Array        # (B, S) f32 per-position K zero points (integer)
    v_s: Array        # (B, S) f32
    v_z: Array        # (B, S) f32
    length: Array     # () int32 — tokens currently cached


def _project_qkv(x: Array, kv_src: Array, p: dict, cfg: ModelConfig):
    hd = cfg.resolved_head_dim
    b, t, _ = x.shape
    s = kv_src.shape[1]
    q = L.project(x, p["wq"], cfg, "attn.wq") \
        .reshape(b, t, cfg.num_heads, hd)
    k = L.project(kv_src, p["wk"], cfg, "attn.wk") \
        .reshape(b, s, cfg.num_kv_heads, hd)
    v = L.project(kv_src, p["wv"], cfg, "attn.wv") \
        .reshape(b, s, cfg.num_kv_heads, hd)
    return q, k, v


# ---------------------------------------------------------------------------
# Flash-style chunked attention (training / prefill)
# ---------------------------------------------------------------------------

def _chunked_attention(q: Array, k: Array, v: Array, *,
                       causal: bool, window: Optional[int],
                       softcap_val: float, q_offset: int = 0,
                       q_chunk: int = 512, kv_chunk: int = 1024,
                       unroll: bool = False) -> Array:
    """Online-softmax attention over KV chunks.

    q: (B, T, K, G, hd) — queries grouped per KV head.
    k, v: (B, S, K, hd).
    Never materializes more than (T, kv_chunk) scores per pass.
    ``unroll`` (cost-probe mode): straight-line code so cost_analysis counts
    every chunk; block sizes grow so probe HLO stays small (FLOPs are
    identical — masking doesn't change block compute).
    """
    b, t, kh, g, hd = q.shape
    s = k.shape[1]
    scale = hd ** -0.5
    q = q * scale
    if unroll:
        q_chunk, kv_chunk = 4096, 8192
    kv_chunk = min(kv_chunk, s)
    q_chunk = min(q_chunk, t)
    if s % kv_chunk:    # short cross-attn sources (e.g. 1600 image tokens)
        kv_chunk = s
    if t % q_chunk:
        q_chunk = t
    n_kv = s // kv_chunk

    q_pos_base = jnp.arange(t) + q_offset

    def one_q_chunk(qc_idx):
        qi = q_chunk * qc_idx
        qch = jax.lax.dynamic_slice_in_dim(q, qi, q_chunk, axis=1)
        q_pos = jax.lax.dynamic_slice_in_dim(q_pos_base, qi, q_chunk)

        def body(carry, kv_idx):
            m_prev, l_prev, acc = carry
            ki = kv_chunk * kv_idx
            kch = jax.lax.dynamic_slice_in_dim(k, ki, kv_chunk, axis=1)
            vch = jax.lax.dynamic_slice_in_dim(v, ki, kv_chunk, axis=1)
            k_pos = jnp.arange(kv_chunk) + ki
            # scores: (B, Tq, K, G, Skv)
            scores = jnp.einsum("btkgh,bskh->btkgs", qch, kch,
                                preferred_element_type=jnp.float32)
            if softcap_val > 0:
                scores = L.softcap(scores, softcap_val)
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            scores = jnp.where(mask[None, :, None, None, :], scores, NEG_INF)
            m_cur = jnp.max(scores, axis=-1)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(scores - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("btkgs,bskh->btkgh", p.astype(v.dtype), vch,
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, q_chunk, kh, g), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, q_chunk, kh, g), jnp.float32)
        a0 = jnp.zeros((b, q_chunk, kh, g, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(n_kv),
                                      unroll=n_kv if unroll else 1)
        return acc / jnp.maximum(l[..., None], 1e-30)

    if t == q_chunk:
        out = one_q_chunk(0)
    elif unroll:
        outs = jnp.stack([one_q_chunk(i) for i in range(t // q_chunk)])
        out = jnp.moveaxis(outs, 0, 1).reshape(b, t, kh, g, hd)
    else:
        outs = jax.lax.map(one_q_chunk, jnp.arange(t // q_chunk))
        out = jnp.moveaxis(outs, 0, 1).reshape(b, t, kh, g, hd)
    return out


def attend(x: Array, p: dict, cfg: ModelConfig, *,
           kv_src: Optional[Array] = None,
           positions: Optional[Array] = None,
           causal: bool = True,
           window: Optional[int] = None,
           use_rope: bool = True) -> Array:
    """Full (training / prefill) attention. x: (B, T, d).

    GQA is realized by repeating K/V to the full head count and constraining
    the head dim to the TP ("model") axis — sharding propagation does NOT
    survive the grouped 5D einsum (GSPMD replicates the score computation
    across TP, a measured 16x flop bloat; see EXPERIMENTS.md §Perf).
    """
    b, t, _ = x.shape
    kv_in = x if kv_src is None else kv_src
    q, k, v = _project_qkv(x, kv_in, p, cfg)
    if positions is None:
        positions = jnp.arange(t)
    if use_rope and kv_src is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if kv_src is None:
        # observe the CACHE roles (post-RoPE K, V — exactly what decode
        # writes) so serving can freeze the cache quantizer ranges from
        # the same EMA calibration as the projection inputs
        tap = L._active_tap()
        if tap is not None:
            tap.observe("attn.k_cache", k)
            tap.observe("attn.v_cache", v)
    g = cfg.num_heads // cfg.num_kv_heads
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    q = C.constrain_axis(q, 2)
    k = C.constrain_axis(k, 2)
    v = C.constrain_axis(v, 2)
    qg = q.reshape(b, t, cfg.num_heads, 1, cfg.resolved_head_dim)
    out = _chunked_attention(qg, k, v, causal=causal and kv_src is None,
                             window=window, softcap_val=cfg.attn_softcap,
                             unroll=cfg.unroll_loops)
    out = out.astype(x.dtype).reshape(b, t, -1)
    return L.project(out, p["wo"], cfg, "attn.wo")


# ---------------------------------------------------------------------------
# Decode (single token, KV cache)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    hd = cfg.resolved_head_dim
    if cfg.cache_bits:
        # packed bit-plane cache (cfg-only knob, so params-free decode
        # state init keeps working); all-zero planes/scales are inert —
        # unwritten positions are masked off by ``length`` anyway
        assert hd % 8 == 0, (
            f"quantized KV cache packs 8 codes/byte along head_dim; "
            f"head_dim={hd} is not a multiple of 8")
        shape = (batch, KREF.CACHE_PLANES, max_len,
                 cfg.num_kv_heads * hd // 8)
        row = jnp.zeros((batch, max_len), jnp.float32)
        return QuantKVCache(k_planes=jnp.zeros(shape, jnp.uint8),
                            v_planes=jnp.zeros(shape, jnp.uint8),
                            k_s=row, k_z=row, v_s=row, v_z=row,
                            length=jnp.zeros((), jnp.int32))
    if cfg.kv_cache_dtype:
        dtype = jnp.dtype(cfg.kv_cache_dtype)
    shape = (batch, max_len, cfg.num_kv_heads, hd)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   length=jnp.zeros((), jnp.int32))


def decode_attend(x: Array, cache: KVCache, p: dict, cfg: ModelConfig, *,
                  window: Optional[int] = None,
                  use_rope: bool = True) -> tuple[Array, KVCache]:
    """One-token decode step. x: (B, 1, d). Returns (out, updated cache).

    Sequence-parallel decode (§Perf iteration 4): the cache layout is
    (batch -> dp, seq -> model | dp) and every intermediate is constrained
    to it, so attention over the cached keys is a LOCAL partial softmax per
    shard plus a tiny reduction — instead of the all-gather of the whole
    cache that GSPMD otherwise inserts (measured 6.9e10 B/device/step).
    """
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    pos = cache.length
    s_max = (cache.k_s if isinstance(cache, QuantKVCache)
             else cache.k).shape[1]
    batch_ax, seq_ax = C.dp_model_plan(b, s_max)
    q, k_new, v_new = _project_qkv(x, x, p, cfg)
    if use_rope:
        posv = jnp.full((b, 1), pos, jnp.int32)
        q = apply_rope(q, posv, cfg.rope_theta)
        k_new = apply_rope(k_new, posv, cfg.rope_theta)
    if isinstance(cache, QuantKVCache):
        return _decode_attend_quant(x, cache, p, cfg, q, k_new, v_new,
                                    window=window, batch_ax=batch_ax)
    # masked (select) cache update: a dynamic_update_slice at a traced
    # position on the sharded seq dim triggers GSPMD's "involuntary full
    # rematerialization" — an all-gather of the WHOLE cache every step
    # (measured 7.1e10 B/device; §Perf iteration 4). The elementwise select
    # is shard-local and fuses into an in-place update on donated buffers.
    cache_plan = {0: batch_ax, 1: seq_ax}
    write = (jnp.arange(s_max) == pos)[None, :, None, None]
    k = C.constrain_spec(
        jnp.where(write, k_new.astype(cache.k.dtype), cache.k), cache_plan)
    v = C.constrain_spec(
        jnp.where(write, v_new.astype(cache.v.dtype), cache.v), cache_plan)
    g = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(b, 1, cfg.num_kv_heads, g, hd) * hd ** -0.5
    qg = C.constrain_spec(qg, {0: batch_ax})
    scores = jnp.einsum("btkgh,bskh->btkgs", qg, k.astype(qg.dtype),
                        preferred_element_type=jnp.float32)
    scores = C.constrain_spec(scores, {0: batch_ax, 4: seq_ax})
    if cfg.attn_softcap > 0:
        scores = L.softcap(scores, cfg.attn_softcap)
    k_pos = jnp.arange(s_max)
    valid = k_pos <= pos
    if window is not None:
        valid &= (pos - k_pos) < window
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)  # sharded-S softmax: GSPMD
    probs = C.constrain_spec(probs, {0: batch_ax, 4: seq_ax})
    out = jnp.einsum("btkgs,bskh->btkgh", probs.astype(x.dtype),
                     v.astype(x.dtype), preferred_element_type=jnp.float32)
    out = C.constrain_spec(out.astype(x.dtype).reshape(b, 1, -1),
                           {0: batch_ax})
    y = L.project(out, p["wo"], cfg, "attn.wo")
    return y, KVCache(k=k, v=v, length=pos + 1)


def _cache_rows(new: Array, s_leaf, z_leaf, n_lvl) -> tuple[Array, Array]:
    """The per-batch cache quantizer (s, z) of one new K or V token
    (B, 1, K, hd). Frozen calibration (artifact leaves hoisted by
    ``models/serving`` with the IDENTICAL ``affine_scale_zp`` op sequence)
    broadcasts one scalar; otherwise the dynamic per-batch extremes,
    zero-extended — the ``act_range_bounds(include_zero=True)`` convention
    that bounds z to [0, n] (the kernels' int32-safety requirement)."""
    b = new.shape[0]
    if s_leaf is not None:
        s = jnp.broadcast_to(jnp.asarray(s_leaf, jnp.float32).reshape(()),
                             (b,))
        z = jnp.broadcast_to(jnp.asarray(z_leaf, jnp.float32).reshape(()),
                             (b,))
        return s, z
    xf = new.astype(jnp.float32)
    lo = jnp.minimum(jnp.min(xf, axis=(1, 2, 3)), 0.0)
    hi = jnp.maximum(jnp.max(xf, axis=(1, 2, 3)), 0.0)
    return quant.affine_scale_zp(lo, hi, n_lvl)


def _cache_write(planes: Array, s_row: Array, z_row: Array, new: Array,
                 s: Array, z: Array, n_lvl, pos: Array):
    """Encode one token and select-write its packed planes + quantizer row
    at ``pos`` (masked select, not dynamic_update_slice — same GSPMD
    rationale as the fp cache write above)."""
    s_max = s_row.shape[1]
    codes = quant.affine_encode(new.astype(jnp.float32),
                                s[:, None, None, None],
                                z[:, None, None, None], n_lvl)
    tok = KREF.cache_planes(codes.astype(jnp.int32))       # (B, P, 1, K*d8)
    sel = jnp.arange(s_max) == pos
    planes = jnp.where(sel[:, None], tok, planes)
    s_row = jnp.where(sel[None, :], s[:, None], s_row)
    z_row = jnp.where(sel[None, :], z[:, None], z_row)
    return planes, s_row, z_row


def _decode_attend_quant(x: Array, cache: QuantKVCache, p: dict,
                         cfg: ModelConfig, q: Array, k_new: Array,
                         v_new: Array, *, window: Optional[int],
                         batch_ax) -> tuple[Array, QuantKVCache]:
    """The quantized-cache decode step: encode-write this token's K/V at
    the rung's cache bits, then attend THROUGH the packed planes via
    ``kernels.dispatch.decode_attention`` (Pallas bit-plane kernel on TPU,
    its bit-identical jnp oracle elsewhere).

    Cache bits arrive as DATA leaves (``p["kv_cache"]["k_nlvl"]``/
    ``v_nlvl``, built per rung by ``models/serving``) so mixed cache-rung
    ladders share one compilation; raw params fall back to the static
    ``cfg.cache_bits``. Frozen ranges ride as hoisted ``k_s``/``k_z``/
    ``v_s``/``v_z`` scalar leaves next to them.
    """
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    pos = cache.length
    kc = p.get("kv_cache", {}) if hasattr(p, "get") else {}

    def nlvl(leaf):
        if leaf is not None:
            return jnp.asarray(leaf, jnp.float32).reshape(())
        bits = int(cfg.cache_bits or 8)
        return jnp.float32(quant.cap_levels(bits))

    k_nlvl = nlvl(kc.get("k_nlvl"))
    v_nlvl = nlvl(kc.get("v_nlvl"))
    ks, kz = _cache_rows(k_new, kc.get("k_s"), kc.get("k_z"), k_nlvl)
    vs, vz = _cache_rows(v_new, kc.get("v_s"), kc.get("v_z"), v_nlvl)
    kp, ks_row, kz_row = _cache_write(cache.k_planes, cache.k_s, cache.k_z,
                                      k_new, ks, kz, k_nlvl, pos)
    vp, vs_row, vz_row = _cache_write(cache.v_planes, cache.v_s, cache.v_z,
                                      v_new, vs, vz, v_nlvl, pos)
    kp = C.constrain_spec(kp, {0: batch_ax})
    vp = C.constrain_spec(vp, {0: batch_ax})
    view = QuantKVCache(k_planes=kp, v_planes=vp, k_s=ks_row, k_z=kz_row,
                        v_s=vs_row, v_z=vz_row, length=pos)
    out = KD.decode_attention(q.reshape(b, cfg.num_heads, hd), view,
                              cfg.kernel_backend or "ref",
                              num_kv_heads=cfg.num_kv_heads, window=window,
                              softcap=cfg.attn_softcap,
                              k_nlvl=k_nlvl, v_nlvl=v_nlvl)
    out = C.constrain_spec(out.astype(x.dtype).reshape(b, 1, -1),
                           {0: batch_ax})
    y = L.project(out, p["wo"], cfg, "attn.wo")
    return y, view._replace(length=pos + 1)


def cross_attend_cached(x: Array, enc_kv: tuple[Array, Array], p: dict,
                        cfg: ModelConfig) -> Array:
    """Cross-attention against precomputed encoder/image K,V (decode path)."""
    b, t, _ = x.shape
    hd = cfg.resolved_head_dim
    q = L.project(x, p["wq"], cfg, "attn.wq").reshape(
        b, t, cfg.num_heads, hd)
    k, v = enc_kv
    g = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(b, t, cfg.num_kv_heads, g, hd) * hd ** -0.5
    scores = jnp.einsum("btkgh,bskh->btkgs", qg, k,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("btkgs,bskh->btkgh", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out.astype(x.dtype).reshape(b, t, -1)
    return L.project(out, p["wo"], cfg, "attn.wo")


def project_cross_kv(enc: Array, p: dict, cfg: ModelConfig
                     ) -> tuple[Array, Array]:
    """Project encoder outputs to (K, V) once; reused every decode step."""
    b, s, _ = enc.shape
    hd = cfg.resolved_head_dim
    k = L.project(enc, p["wk"], cfg, "attn.wk").reshape(
        b, s, cfg.num_kv_heads, hd)
    v = L.project(enc, p["wv"], cfg, "attn.wv").reshape(
        b, s, cfg.num_kv_heads, hd)
    return k, v
