"""RWKV-6 "Finch" block: attention-free time mixing with data-dependent
decay and data-dependent token shift (arXiv:2404.05892 §4; BlinkDL/RWKV-LM
``RWKV_Tmix_x060`` / ``RWKV_CMix_x060``), plus the squared-ReLU channel mix.

Time mixing keeps a per-head (hd x hd) wkv state — O(1) memory per token.
Training and prefill by scan run the recurrence as a ``lax.scan`` over the
tokens in jnp; one-token decode (``decode_time_mix``) runs it through
``kernels.dispatch.wkv_decode`` (the Pallas kernel ``kernels/rwkv_wkv`` on
TPU), on the kernel's value-major state layout.

The published block, for the layer's normalized input x and the previous
token's x₋ (which the state keeps):

* token shift ("ddlerp"): ``xx = x₋ - x``, ``m = tanh((x + xx μx) A) B``
  with the mix LoRA A (``maa_w1``, d -> 5 r) and B (``maa_w2``, five r -> d
  matrices, applied as five calls), and ``x□ = x + xx (μ□ + m□)`` for □ in
  (w, k, v, r, g);
* ``r, k, v = W x□``, ``g = silu(Wg xg)``, the decay
  ``w = exp(-exp(λ + tanh(xw Aw) Bw))`` through the rank-64 decay LoRA;
* the recurrence above, then ``Wo(GroupNorm_H(o) g)`` — a norm per head,
  eps 64e-5;
* channel mix ``σ(Wr x'r) ⊙ Wv relu(Wk x'k)²`` with static lerps x'k, x'r.

Every matrix — the five projections, both LoRAs and the channel-mix
matrices — is a PANN linear through ``layers.project`` (DESIGN.md §4); the
lerp vectors, λ, u and the norms stay in float.

Each published piece follows the weights it is given, so an older static
form still runs: a time mix whose params hold ``mu`` (5, d) mixes with a
static lerp in (r, k, v, g, w) order, an ``ln_x`` scale of shape (d,) is a
LayerNorm over the whole width, a channel mix without ``wr`` has no
receptance gate, and ``mu`` (2, d) there is its static lerp. The embedding
LayerNorm ``ln0`` lives in ``models/model.py``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist import constrain as C
from repro.kernels import dispatch
from repro.models import layers as L

Array = jax.Array

HEAD_DIM = 64
# LoRA ranks of RWKV_Tmix_x060 at every width but n_embd 4096 (which
# doubles both): the token-shift mix and the decay
MIX_RANK = 32
DECAY_RANK = 64
GROUP_NORM_EPS = 64e-5          # 1e-5 * head_size_divisor (8) squared
MIX_ORDER = ("w", "k", "v", "r", "g")   # rows of maa_wkvrg and maa_w2


class RWKVState(NamedTuple):
    wkv: Array      # (B, hd, H * hd) value-major (kernels/rwkv_wkv.py)
    shift_tm: Array  # (B, d) previous token (time mix)
    shift_cm: Array  # (B, d) previous token (channel mix)
    length: Array


def _heads(cfg: ModelConfig) -> int:
    assert cfg.d_model % HEAD_DIM == 0
    return cfg.d_model // HEAD_DIM


def init_rwkv_time_mix(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    h = _heads(cfg)
    ks = jax.random.split(key, 12)
    return {
        "maa_x": jax.random.uniform(ks[0], (d,), jnp.float32),
        "maa_wkvrg": jax.random.uniform(ks[1], (5, d), jnp.float32),
        "maa_w1": L.init_linear(ks[2], d, 5 * MIX_RANK),
        "maa_w2": {"w": MIX_RANK ** -0.5 * jax.random.normal(
            ks[3], (5, MIX_RANK, d), jnp.float32)},
        "wr": L.init_linear(ks[4], d, d),
        "wk": L.init_linear(ks[5], d, d),
        "wv": L.init_linear(ks[6], d, d),
        "wg": L.init_linear(ks[7], d, d),
        # data-dependent decay: w = exp(-exp(base + tanh(xw A) B))
        "decay_a": L.init_linear(ks[8], d, DECAY_RANK),
        "decay_b": L.init_linear(ks[9], DECAY_RANK, d),
        "decay_base": jnp.full((d,), -4.0, jnp.float32),
        # per-head "u" term
        "bonus": 0.1 * jax.random.normal(ks[11], (h, HEAD_DIM), jnp.float32),
        "ln_x": {"scale": jnp.ones((h, HEAD_DIM), jnp.float32),
                 "bias": jnp.zeros((h, HEAD_DIM), jnp.float32)},
        "wo": L.init_linear(ks[10], d, d),
    }


def init_rwkv_channel_mix(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    return {
        "maa_k": jax.random.uniform(ks[0], (d,), jnp.float32),
        "maa_r": jax.random.uniform(ks[1], (d,), jnp.float32),
        "wk": L.init_linear(ks[2], d, cfg.d_ff),
        "wv": L.init_linear(ks[3], cfg.d_ff, d),
        "wr": L.init_linear(ks[4], d, d),
    }


def _token_shift(x: Array, prev: Array) -> Array:
    """Shifted sequence: [prev, x_0, ..., x_{T-2}]. x: (B, T, d)."""
    return jnp.concatenate([prev[:, None, :], x[:, :-1, :]], axis=1)


def _ddlerp(x: Array, xs: Array, p: dict, cfg: ModelConfig) -> dict:
    """The data-dependent token-shift mix: {□: x□} for □ in MIX_ORDER.
    The five rank-r -> d matrices of maa_w2 are five calls, each with its
    own activation range."""
    xx = xs - x
    m = jnp.tanh(L.project(x + xx * p["maa_x"].astype(x.dtype),
                           p["maa_w1"], cfg, "rwkv.tm.maa_w1"))
    m = m.reshape(m.shape[:-1] + (5, MIX_RANK))
    mu = p["maa_wkvrg"].astype(x.dtype)
    out = {}
    for i, name in enumerate(MIX_ORDER):
        w2 = jax.tree_util.tree_map(lambda a, i=i: a[i], p["maa_w2"])
        out[name] = x + xx * (mu[i] + L.project(m[..., i, :], w2, cfg,
                                                "rwkv.tm.maa_w2"))
    return out


def _static_mix(x: Array, xs: Array, p: dict) -> dict:
    """The static token-shift lerp of the older form, rows in (r, k, v, g,
    w) order."""
    mu = p["mu"].astype(x.dtype)
    return {name: x * mu[i] + xs * (1 - mu[i])
            for i, name in enumerate("rkvgw")}


def _shift_mix(x: Array, xs: Array, p: dict, cfg: ModelConfig) -> dict:
    with jax.named_scope("rwkv_shift"):
        if "mu" in p:
            return _static_mix(x, xs, p)
        return _ddlerp(x, xs, p, cfg)


def _group_norm(o: Array, p: dict) -> Array:
    """ln_x over each head's hd channels. o: (..., H, hd)."""
    of = o.astype(jnp.float32)
    mu = jnp.mean(of, axis=-1, keepdims=True)
    var = jnp.var(of, axis=-1, keepdims=True)
    y = (of - mu) * jax.lax.rsqrt(var + GROUP_NORM_EPS)
    return (y * p["scale"] + p["bias"]).astype(o.dtype)


def _ln_x(o: Array, p: dict) -> Array:
    """o: (..., H, hd) -> (..., d): per-head GroupNorm where the scale is
    (H, hd), a LayerNorm over the whole width where it is (d,)."""
    if p["scale"].ndim == 2:
        o = _group_norm(o, p)
        return o.reshape(o.shape[:-2] + (-1,))
    return L.apply_norm(o.reshape(o.shape[:-2] + (-1,)), p, "layernorm")


def _projections(mix: dict, p: dict, cfg: ModelConfig, h: int):
    """(r, k, v, g, w) from the token mixes; r, k, v, w as (..., H, hd),
    w the decay in (0, 1) in f32."""
    def heads(a):
        return a.reshape(a.shape[:-1] + (h, HEAD_DIM))

    def proj(a, name):
        return L.project(a, p[name], cfg, f"rwkv.tm.{name}")

    r, k, v = (heads(proj(mix[n], "w" + n)) for n in "rkv")
    g = jax.nn.silu(proj(mix["g"], "wg"))
    dd = proj(jnp.tanh(proj(mix["w"], "decay_a")), "decay_b") \
        + p["decay_base"]
    w = heads(jnp.exp(-jnp.exp(dd.astype(jnp.float32))))
    return r, k, v, g, w


def state_to_heads(wkv: Array) -> Array:
    """Value-major (B, hd, H * hd) -> (B, H, hd_i, hd_j)."""
    b, hd, n = wkv.shape
    return jnp.transpose(wkv.reshape(b, hd, n // hd, hd), (0, 2, 3, 1))


def state_from_heads(s: Array) -> Array:
    """(B, H, hd_i, hd_j) -> value-major (B, hd, H * hd)."""
    b, h, hd, _ = s.shape
    return jnp.transpose(s, (0, 3, 1, 2)).reshape(b, hd, h * hd)


def _time_mix_inner(r, k, v, w, u, state):
    """Sequential wkv recurrence over the tokens (vectorized over B, H).

    r,k,v: (B, T, H, hd); w: (B, T, H, hd) decays in (0,1); u: (H, hd).
    state: (B, H, hd, hd). Returns (out (B,T,H,hd), new state).
    """
    def step(s, inp):
        rt, kt, vt, wt = inp                      # (B,H,hd) each
        kv = kt[..., :, None] * vt[..., None, :]  # (B,H,hd,hd)
        out = jnp.einsum("bhi,bhij->bhj", rt, s + u[None, :, :, None] * kv)
        s_new = wt[..., :, None] * s + kv
        return s_new, out

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))
    state, outs = jax.lax.scan(step, state, seq)
    return jnp.moveaxis(outs, 0, 1), state


def apply_time_mix(x: Array, p: dict, cfg: ModelConfig,
                   state: RWKVState | None = None
                   ) -> tuple[Array, Array, Array]:
    """x: (B, T, d) -> (y, final wkv state (value-major), last token).
    Training and prefill by scan."""
    b, t, d = x.shape
    h = _heads(cfg)
    prev = jnp.zeros((b, d), x.dtype) if state is None else \
        state.shift_tm.astype(x.dtype)
    mix = _shift_mix(x, _token_shift(x, prev), p, cfg)
    r, k, v, g, w = _projections(mix, p, cfg, h)
    r, k, v = (C.constrain_axis(a, 2) for a in (r, k, v))
    s0 = jnp.zeros((b, h, HEAD_DIM, HEAD_DIM), jnp.float32) if state is None \
        else state_to_heads(state.wkv)
    out, s_fin = _time_mix_inner(r.astype(jnp.float32), k.astype(jnp.float32),
                                 v.astype(jnp.float32), w,
                                 p["bonus"], s0)
    out = _ln_x(out.astype(x.dtype), p["ln_x"]) * g
    y = L.project(out, p["wo"], cfg, "rwkv.tm.wo")
    return y, state_from_heads(s_fin), x[:, -1, :]


def decode_time_mix(x: Array, p: dict, cfg: ModelConfig, state: RWKVState
                    ) -> tuple[Array, Array]:
    """One token. x: (B, d) -> (y (B, d), new wkv state)."""
    h = _heads(cfg)
    mix = _shift_mix(x, state.shift_tm.astype(x.dtype), p, cfg)
    r, k, v, g, w = _projections(mix, p, cfg, h)
    with jax.named_scope("rwkv_wkv"):
        out, wkv = dispatch.wkv_decode(r, k, v, w, p["bonus"], state.wkv,
                                       cfg.kernel_backend)
    out = _ln_x(out.astype(x.dtype), p["ln_x"]) * g
    return L.project(out, p["wo"], cfg, "rwkv.tm.wo"), wkv


def _receptance(xr: Array, p: dict, cfg: ModelConfig) -> Array:
    """The channel mix's receptance gate σ(Wr x'r)."""
    return jax.nn.sigmoid(L.project(xr, p["wr"], cfg, "rwkv.cm.wr"))


def channel_mix(x: Array, xs: Array, p: dict, cfg: ModelConfig) -> Array:
    """The channel mix of x given its shifted sequence xs (same shape)."""
    if "mu" in p:
        mu = p["mu"].astype(x.dtype)
        xk = x * mu[0] + xs * (1 - mu[0])
    else:
        xx = xs - x
        xk = x + xx * p["maa_k"].astype(x.dtype)
        xr = x + xx * p["maa_r"].astype(x.dtype)
    k = jnp.square(jax.nn.relu(L.project(xk, p["wk"], cfg, "rwkv.cm.wk")))
    kv = L.project(k, p["wv"], cfg, "rwkv.cm.wv")
    if "wr" in p:
        return _receptance(xr, p, cfg) * kv
    return kv


def apply_channel_mix(x: Array, p: dict, cfg: ModelConfig,
                      prev: Array | None = None) -> tuple[Array, Array]:
    b, t, d = x.shape
    pv = jnp.zeros((b, d), x.dtype) if prev is None else prev.astype(x.dtype)
    return channel_mix(x, _token_shift(x, pv), p, cfg), x[:, -1, :]


def init_rwkv_state(cfg: ModelConfig, batch: int, dtype) -> RWKVState:
    h = _heads(cfg)
    return RWKVState(
        wkv=jnp.zeros((batch, HEAD_DIM, h * HEAD_DIM), jnp.float32),
        shift_tm=jnp.zeros((batch, cfg.d_model), dtype),
        shift_cm=jnp.zeros((batch, cfg.d_model), dtype),
        length=jnp.zeros((), jnp.int32))
