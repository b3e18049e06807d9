"""rwkv6_finch: the published RWKV-6 "Finch" block (arXiv:2404.05892 §4;
BlinkDL/RWKV-LM, RWKV-v5/src/model.py, RWKV_Tmix_x060 and RWKV_CMix_x060),
written from its equations.

For the layer's normalized input x and the previous token's x₋:

* ddlerp: xx = x₋ - x, m = tanh((x + xx μx) A) B with A (d -> 5 r) and B
  five (r -> d) matrices applied as five calls, x□ = x + xx (μ□ + m□) for
  □ in (w, k, v, r, g);
* r, k, v = W x□, g = silu(Wg xg), w = exp(-exp(λ + tanh(xw Aw) Bw));
* per head of 64: o = r (S + diag(u) kᵀv), S' = diag(w) S + kᵀv;
* x += Wo(GroupNorm_H(o, eps 64e-5) g);
* channel mix: x += σ(W'r x'r) ⊙ W'v relu(W'k x'k)², x'k and x'r static
  lerps with x₋;
* ln0, a LayerNorm of the embedding, before layer 0.

Every matrix is a PANN linear (bench/reference.linear); the recurrence,
the norms and the lerps stay in the float precision asked for. The sum
over i of the recurrence's output is taken by halving (element i adds
element i + 32, then i + 16, ... 1): a served model's activation quantizers
turn an ulp of difference into another code, so the reference sums in the
one order the served kernel states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import layernorm, linear, take
from bench.weights import ln, lin

HEAD = 64
MIX_RANK = 32          # RWKV_Tmix_x060 at n_embd 2048
DECAY_RANK = 64
GN_EPS = 64e-5
ORDER = ("w", "k", "v", "r", "g")


def params(ks, spec):
    d, n = spec["d_model"], spec["num_layers"]
    lead = (n,)
    heads = d // HEAD
    u = jax.random.uniform
    tm = {"maa_x": u(ks(), lead + (d,), jnp.float32),
          "maa_wkvrg": u(ks(), lead + (5, d), jnp.float32),
          "maa_w1": lin(ks, d, 5 * MIX_RANK, lead),
          "maa_w2": lin(ks, MIX_RANK, d, lead + (5,)),
          "wr": lin(ks, d, d, lead), "wk": lin(ks, d, d, lead),
          "wv": lin(ks, d, d, lead), "wg": lin(ks, d, d, lead),
          "decay_a": lin(ks, d, DECAY_RANK, lead),
          "decay_b": lin(ks, DECAY_RANK, d, lead),
          "decay_base": -4.0 + 0.5 * jax.random.normal(ks(), lead + (d,),
                                                       jnp.float32),
          "bonus": 0.1 * jax.random.normal(ks(), lead + (heads, HEAD),
                                           jnp.float32),
          "ln_x": {"scale": 1.0 + 0.1 * jax.random.normal(
                       ks(), lead + (heads, HEAD), jnp.float32),
                   "bias": 0.1 * jax.random.normal(
                       ks(), lead + (heads, HEAD), jnp.float32)},
          "wo": lin(ks, d, d, lead)}
    cm = {"maa_k": u(ks(), lead + (d,), jnp.float32),
          "maa_r": u(ks(), lead + (d,), jnp.float32),
          "wk": lin(ks, d, spec["d_ff"], lead),
          "wv": lin(ks, spec["d_ff"], d, lead),
          "wr": lin(ks, d, d, lead)}
    layer = {"norm1": ln(ks, d, lead), "tm": tm, "norm2": ln(ks, d, lead),
             "cm": cm}
    return {"decoder": {"groups": {"layers": [layer]}},
            "final_norm": ln(ks, d), "ln0": ln(ks, d)}


def projections(spec):
    """[(K, N, calls per decode step)] of the family's quantized linears."""
    d, ff, nl = spec["d_model"], spec["d_ff"], spec["num_layers"]
    return [(d, d, 6 * nl), (d, 5 * MIX_RANK, nl), (MIX_RANK, d, 5 * nl),
            (d, DECAY_RANK, nl), (DECAY_RANK, d, nl), (d, ff, nl),
            (ff, d, nl)]


def attention_layers(spec):
    return 0


def wkv_bytes(spec, rows):
    """Bytes one wkv decode call must move: the f32 state read and
    written, r, k, v and w in, o out, and u."""
    d = spec["d_model"]
    state = rows * d * HEAD * 4
    return 2 * state + 4 * rows * d * 4 + rows * d * 4 + d * 4


def _group_norm(o, p, ft):
    mu = jnp.mean(o, axis=-1, keepdims=True)
    var = jnp.var(o, axis=-1, keepdims=True)
    y = (o - mu) * jax.lax.rsqrt(var + GN_EPS)
    return y * p["scale"].astype(ft) + p["bias"].astype(ft)


def _time_mix(h, prev, s, tm, act_n, ft, heads):
    xx = prev - h
    m = jnp.tanh(linear(h + xx * tm["maa_x"], tm["maa_w1"], act_n, ft))
    m = m.reshape(-1, 5, MIX_RANK)
    x = {}
    for i, name in enumerate(ORDER):
        m_i = linear(m[:, i], take(tm["maa_w2"], i), act_n, ft)
        x[name] = h + xx * (tm["maa_wkvrg"][i] + m_i)
    r = linear(x["r"], tm["wr"], act_n, ft).reshape(-1, heads, HEAD)
    k = linear(x["k"], tm["wk"], act_n, ft).reshape(-1, heads, HEAD)
    v = linear(x["v"], tm["wv"], act_n, ft).reshape(-1, heads, HEAD)
    gate = jax.nn.silu(linear(x["g"], tm["wg"], act_n, ft))
    low = jnp.tanh(linear(x["w"], tm["decay_a"], act_n, ft))
    dd = linear(low, tm["decay_b"], act_n, ft) + tm["decay_base"]
    decay = jnp.exp(-jnp.exp(dd)).reshape(-1, heads, HEAD)
    kv = k[..., :, None] * v[..., None, :]                # (B,H,hd,hd)
    t = r[..., :, None] * (s + tm["bonus"][None, :, :, None] * kv)
    while t.shape[-2] > 1:                   # the sum over i, by halving
        half = t.shape[-2] // 2
        t = t[..., :half, :] + t[..., half:, :]
    out = t[..., 0, :]
    s_new = decay[..., :, None] * s + kv
    out = _group_norm(out, tm["ln_x"], ft).reshape(h.shape) * gate
    return linear(out, tm["wo"], act_n, ft), s_new


def _channel_mix(h, prev, cm, act_n, ft):
    xx = prev - h
    xk = h + xx * cm["maa_k"]
    xr = h + xx * cm["maa_r"]
    kk = jnp.square(jax.nn.relu(linear(xk, cm["wk"], act_n, ft)))
    kv = linear(kk, cm["wv"], act_n, ft)
    return jax.nn.sigmoid(linear(xr, cm["wr"], act_n, ft)) * kv


def step(w, state, tok, pos, spec, act_n, cache_n, ft):
    del pos, cache_n
    wkv, sh_tm, sh_cm = state
    heads = spec["d_model"] // HEAD
    lay = w["decoder"]["groups"]["layers"][0]
    x = w["embed"]["table"][tok].astype(ft)
    x = layernorm(x, w["ln0"]["scale"], w["ln0"]["bias"], ft)
    n_wkv, n_tm, n_cm = [], [], []
    for i in range(spec["num_layers"]):
        lp = take(lay, i)
        h = layernorm(x, lp["norm1"]["scale"], lp["norm1"]["bias"], ft)
        y, s_new = _time_mix(h, sh_tm[i], wkv[i], lp["tm"], act_n, ft, heads)
        x = x + y
        n_wkv.append(s_new)
        n_tm.append(h)
        h = layernorm(x, lp["norm2"]["scale"], lp["norm2"]["bias"], ft)
        x = x + _channel_mix(h, sh_cm[i], lp["cm"], act_n, ft)
        n_cm.append(h)
    x = layernorm(x, w["final_norm"]["scale"], w["final_norm"]["bias"], ft)
    logits = linear(x, w["lm_head"], act_n, ft)
    return logits, (jnp.stack(n_wkv), jnp.stack(n_tm), jnp.stack(n_cm))


def state(spec, batch, max_len, ft):
    del max_len
    d, nl = spec["d_model"], spec["num_layers"]
    return (jnp.zeros((nl, batch, d // HEAD, HEAD, HEAD), ft),
            jnp.zeros((nl, batch, d), ft), jnp.zeros((nl, batch, d), ft))
