"""Plain reference of what the served path computes, written from the
configuration and the published equations alone. It imports nothing of
the program and takes nothing the program made: it draws the same fp32
weights from the seed (bench/weights.py) and quantizes them itself.

What the configuration states, and this file computes:

* every linear layer (each parameter dict with a "w") is PANN-quantized
  (arXiv:2202.02783, Eq. 12): per output channel, gamma = ||w||_1 / (R_max d)
  over the fan-in, codes = clip(round(w / gamma), -127, 127). A rung at
  budget R serves the top planes of those codes: sign(c) (|c| >> s) << s
  with s = round(log2(R_max / R)), dequantized with the same gamma;
* the input of each linear layer is affine-quantized per call, over all
  rows of the batch: lo = min(x, 0), hi = max(x, 0), s = (hi - lo) / n,
  z = round(-lo / s), q = clip(round(x / s) + z, 0, n) with n = 2^b~x - 1;
  the product is the exact integer sum (q - z) . codes, times s * gamma;
* the attention cache holds K and V as affine codes at the rung's cache
  bits, scaled per batch row and position; the query is affine-quantized
  at 127 levels; scores are exact integers, the softmax numerators sit on
  a 2^15 grid and the probabilities on a 2^14 grid before the integer PV
  sum (the power-aware cache's stated arithmetic);
* norms, the Mamba2 and RWKV6 recurrences, RoPE and the embedding stay in
  the configuration's float precision.

``dtype=jnp.bfloat16`` computes every float of the same equations in
bfloat16: that is the control, which a sound comparison must reject.

Rows are decoded in lockstep, one token per call, exactly as the served
batch was: the activation quantizer's range spans the whole batch, so a
row's logits depend on the rows beside it.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import head_dim

EXP_GRID = float(1 << 15)     # softmax numerators
PROB_GRID = float(1 << 14)    # probabilities in the PV sum
Q_LEVELS = 127.0              # query codes
NEG = -1e30


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

@jax.jit
def _quantize_leaf(w, r_max):
    """(codes int8, gamma f32 (..., 1, N)) of one (stacked) weight, one
    (K, N) matrix at a time."""
    d = w.shape[-2]

    def one(m):
        l1 = jnp.sum(jnp.abs(m), axis=0, keepdims=True)
        gamma = jnp.maximum(l1, 1e-12) / (r_max * d)
        codes = jnp.clip(jnp.round(m / gamma), -127, 127).astype(jnp.int8)
        return codes, gamma

    lead = w.shape[:-2]
    codes, gamma = jax.lax.map(one, w.reshape((-1,) + w.shape[-2:]))
    return (codes.reshape(lead + codes.shape[1:]),
            gamma.reshape(lead + gamma.shape[1:]))


def _quantize_tree(params, r_max: float, dtype):
    """Every linear's codes at the largest budget; other leaves cast."""
    def walk(node):
        if isinstance(node, dict):
            if "w" in node:
                w = node["w"].astype(dtype).astype(jnp.float32)
                codes, gamma = _quantize_leaf(w, jnp.float32(r_max))
                return {"codes": codes, "gamma": gamma}
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node.astype(dtype)
    return walk(params)


@jax.jit
def _rung_view(tree, shift):
    """Codes of one rung: the top planes of the max-budget codes."""
    def walk(node):
        if isinstance(node, dict):
            if "codes" in node:
                c = node["codes"].astype(jnp.int32)
                m = ((jnp.maximum(c, 0) >> shift)
                     - (jnp.maximum(-c, 0) >> shift)) << shift
                return {"codes": m.astype(jnp.int8), "gamma": node["gamma"],
                        "colsum": jnp.sum(m, axis=-2)}
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node
    return walk(tree)


def rung_shift(r_max: float, r: float) -> int:
    return int(min(max(round(math.log2(r_max / r)), 0), 6))


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------

def _affine(x, n):
    lo = jnp.minimum(jnp.min(x), 0.0)
    hi = jnp.maximum(jnp.max(x), 0.0)
    s = jnp.maximum((hi - lo) / n, 1e-12)
    z = jnp.round(-lo / s)
    return s, z


# The activation quantizer's range spans a whole tensor, so a difference of
# one ulp anywhere can move a code and, through the next ranges, every later
# value: the reference must round as the served program does. The compiler
# chooses the order of a reduction (a norm's sum of squares, a range) by how
# it fuses the ops around it; the barriers below keep the fusion boundaries
# the served program has around each quantized product, so that the float
# work between them compiles, and rounds, alike. They change no value.
_seal = jax.lax.optimization_barrier


def linear(x, p, n_lvl, ft):
    """x (B, K) -> (B, N): affine-quantized input times the rung's codes."""
    x = _seal(x.astype(ft))
    s, z = _affine(x, n_lvl.astype(ft))
    s, z = _seal((s, z))
    q = _seal(jnp.clip(jnp.round(x / s) + z, 0, n_lvl.astype(ft))
              .astype(jnp.int8))
    acc = jax.lax.dot_general(q, p["codes"], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    acc = acc - z.astype(jnp.int32) * p["colsum"].reshape(1, -1)
    return _seal(acc.astype(ft) * s * p["gamma"].reshape(1, -1).astype(ft))


def rmsnorm(x, scale, ft):
    x = x.astype(ft)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * (1.0 + scale.astype(ft))


def layernorm(x, scale, bias, ft):
    x = x.astype(ft)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * scale + bias


def take(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


# ---------------------------------------------------------------------------
# Attention over the bit-plane KV cache (for families that have it)
# ---------------------------------------------------------------------------

def _rope(x, pos, theta, ft):
    """x (B, heads, hd), rotate-half RoPE at scalar position ``pos``."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang).astype(ft), jnp.sin(ang).astype(ft)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _cache_codes(x, n, ft):
    """Per-row affine codes of one token's K or V (B, K, hd)."""
    lo = jnp.minimum(jnp.min(x, axis=(1, 2)), 0.0)
    hi = jnp.maximum(jnp.max(x, axis=(1, 2)), 0.0)
    s = jnp.maximum((hi - lo) / n, 1e-12)
    z = jnp.round(-lo / s)
    q = jnp.clip(jnp.round(x / s[:, None, None]) + z[:, None, None], 0, n)
    return q.astype(jnp.int8), s, z


def attention(x, p, kv, pos, spec, act_n, cache_n, ft):
    b = x.shape[0]
    heads, kvh = spec["num_heads"], spec["num_kv_heads"]
    hd = head_dim(spec)
    g = heads // kvh
    q = linear(x, p["wq"], act_n, ft).reshape(b, heads, hd)
    k = linear(x, p["wk"], act_n, ft).reshape(b, kvh, hd)
    v = linear(x, p["wv"], act_n, ft).reshape(b, kvh, hd)
    q = _rope(q, pos, spec["rope_theta"], ft)
    k = _rope(k, pos, spec["rope_theta"], ft)
    kc, ks_new, kz_new = _cache_codes(k, cache_n.astype(ft), ft)
    vc, vs_new, vz_new = _cache_codes(v, cache_n.astype(ft), ft)
    kq, ks, kz, vq, vs, vz = kv
    at = jnp.arange(kq.shape[1]) == pos
    kq = jnp.where(at[None, :, None, None], kc[:, None], kq)
    vq = jnp.where(at[None, :, None, None], vc[:, None], vq)
    ks = jnp.where(at[None], ks_new[:, None].astype(ks.dtype), ks)
    kz = jnp.where(at[None], kz_new[:, None].astype(kz.dtype), kz)
    vs = jnp.where(at[None], vs_new[:, None].astype(vs.dtype), vs)
    vz = jnp.where(at[None], vz_new[:, None].astype(vz.dtype), vz)
    # the query: one affine code space over the whole batch
    qg = _seal(q.reshape(b, kvh, g, hd))
    s_q, z_q = _affine(qg, jnp.asarray(Q_LEVELS, ft))
    q_scale = s_q * jnp.asarray(hd, ft) ** -0.5
    s_q, z_q, q_scale = _seal((s_q, z_q, q_scale))
    qq = _seal(jnp.clip(jnp.round(qg / s_q) + z_q, 0, Q_LEVELS)
               .astype(jnp.int32))
    qi = qq - z_q.astype(jnp.int32)
    ki = kq.astype(jnp.int32) - jnp.round(kz).astype(jnp.int32)[:, :, None, None]
    vi = vq.astype(jnp.int32) - jnp.round(vz).astype(jnp.int32)[:, :, None, None]
    dots = jnp.einsum("bkgh,bskh->bkgs", qi, ki,
                      preferred_element_type=jnp.int32)
    sc = (dots.astype(ft) * q_scale) * ks.astype(ft)[:, None, None, :]
    valid = jnp.arange(kq.shape[1]) <= pos
    sc = jnp.where(valid[None, None, None, :], sc, NEG)
    m = jnp.max(sc, axis=-1, keepdims=True)
    e = jnp.round(jnp.exp(sc - m) * EXP_GRID).astype(jnp.int32)
    prob = e.astype(ft) / jnp.sum(e, axis=-1, keepdims=True).astype(ft)
    vs_f = vs.astype(ft)
    sv = jnp.maximum(jnp.max(jnp.where(valid[None], vs_f, 0.0), axis=-1),
                     1e-12)
    pq = jnp.round(prob * (vs_f / sv[:, None])[:, None, None, :] * PROB_GRID
                   ).astype(jnp.int32)
    pv = jnp.einsum("bkgs,bskh->bkgh", pq, vi,
                    preferred_element_type=jnp.int32)
    out = _seal(pv.astype(ft) * (sv / PROB_GRID)[:, None, None, None])
    y = linear(out.reshape(b, heads * hd), p["wo"], act_n, ft)
    return y, (kq, ks, kz, vq, vs, vz)


# ---------------------------------------------------------------------------
# Teacher-forced decoding of one served batch
# ---------------------------------------------------------------------------

class Reference:
    """The reference model of one configuration at one float precision."""

    def __init__(self, spec: dict, params: Any, dtype=jnp.float32):
        self.spec = spec
        self.ft = jnp.dtype(dtype)
        pts = spec["operating_points"]
        self.r_max = max(float(p["r"]) for p in pts.values())
        self.tree = _quantize_tree(params, self.r_max, self.ft)
        from bench import families
        fam = families.load(spec)
        step_fn, self._state_fn = fam.step, fam.state
        sizes = {k: v for k, v in spec.items()
                 if isinstance(v, (int, float, str)) and not isinstance(v, bool)}
        self._sizes = sizes
        ft = self.ft
        vocab = int(spec["vocab_size"])

        def step(w, state, tok, pos, served, act_n, cache_n):
            logits, state = step_fn(w, state, tok, pos, sizes, act_n,
                                    cache_n, ft)
            lg = logits[:, :vocab].astype(jnp.float32)
            best = jnp.max(lg, axis=-1)
            gap = best - jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
            return state, gap, jnp.argmax(lg, axis=-1).astype(jnp.int32)

        self._step = jax.jit(step)

    def rung(self, bits: int):
        """(weights, act levels, cache levels) of the rung ``bits``."""
        p = self.spec["operating_points"][str(bits)]
        w = _rung_view(self.tree, jnp.int32(rung_shift(self.r_max,
                                                       float(p["r"]))))
        act_n = jnp.float32(min((1 << int(p["b_x_tilde"])) - 1, 127))
        cb = p.get("cache_bits")
        cache_n = jnp.float32(min((1 << int(cb)) - 1, 127) if cb else 127)
        return w, act_n, cache_n

    def run(self, rung, tokens, max_len: int, scored=None):
        """Teacher-force ``tokens`` (B, T) through the rung, one position at
        a time. Returns, for positions 1..T-1, the gap below the best logit
        of each token of ``scored`` (B, T-1; default the next input token)
        and the reference's argmax (B, T-1)."""
        w, act_n, cache_n = rung
        # host arrays: a column per step is a transfer, not a compile
        tokens = np.asarray(tokens, np.int32)
        b, t = tokens.shape
        scored = tokens[:, 1:] if scored is None else np.asarray(scored,
                                                                 np.int32)
        state = self._state_fn(self._sizes, b, max_len, self.ft)
        gaps, tops = [], []
        for i in range(t - 1):
            state, gap, top = self._step(
                w, state, np.ascontiguousarray(tokens[:, i]), np.int32(i),
                np.ascontiguousarray(scored[:, i]), act_n, cache_n)
            gaps.append(gap)
            tops.append(top)
        return jnp.stack(gaps, 1), jnp.stack(tops, 1)
