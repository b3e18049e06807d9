"""Mamba2 (SSD) block — chunked state-space duality formulation in pure JAX.

Follows the minimal SSD reference (Dao & Gu 2024): within-chunk quadratic
attention-like term + cross-chunk recurrent state passing, O(T) overall.
Decode keeps an explicit (H, P, N) state per sequence — O(1) per token, which
is what makes the long_500k cell runnable for hybrid/SSM architectures.

PANN applies to the in/out projections (weight x activation matmuls); the
selective-scan itself is state x input arithmetic with no static weight and
is left in floating point (DESIGN.md §4).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist import constrain as C
from repro.models import layers as L

Array = jax.Array


class SSMState(NamedTuple):
    state: Array       # (B, H, P, N) recurrent state
    conv: Array        # (B, W-1, conv_dim) causal-conv tail
    length: Array      # () int32


def _dims(cfg: ModelConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    return d_inner, n_heads, cfg.ssm_head_dim, cfg.ssm_state


def init_ssm(key, cfg: ModelConfig) -> dict:
    d_inner, h, p_dim, n = _dims(cfg)
    d = cfg.d_model
    conv_dim = d_inner + 2 * n * 1  # x + B + C streams (single group)
    ks = jax.random.split(key, 5)
    return {
        # in_proj emits [z (gate), x, B, C, dt]
        "in_proj": L.init_linear(ks[0], d, 2 * d_inner + 2 * n + h),
        "conv_w": jax.random.normal(ks[1], (cfg.ssm_conv_width, conv_dim),
                                    jnp.float32) * 0.2,
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, h).astype(jnp.float32)),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm": L.init_norm(d_inner, "rmsnorm"),
        "out_proj": L.init_linear(ks[4], d_inner, d),
    }


def _split_proj(zxbcdt: Array, cfg: ModelConfig):
    d_inner, h, p_dim, n = _dims(cfg)
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * n], axis=-1)
    x, b_ssm, c_ssm = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)
    return z, x, b_ssm, c_ssm, dt


def _causal_conv(x: Array, w: Array, b: Array, tail: Array | None = None):
    """Depthwise causal conv along time. x: (B, T, C); w: (W, C)."""
    width = w.shape[0]
    if tail is None:
        pad = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    else:
        pad = tail.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :].astype(x.dtype)
              for i in range(width))
    new_tail = xp[:, -(width - 1):, :] if width > 1 else None
    return jax.nn.silu(out + b.astype(x.dtype)), new_tail


def _ssd_chunked(x: Array, dt: Array, a_log: Array, b_ssm: Array,
                 c_ssm: Array, chunk: int = 64):
    """SSD scan. x: (B, T, H, P); dt: (B, T, H); b,c: (B, T, N).

    Returns y (B, T, H, P) and the final state (B, H, P, N).

    chunk=64: the within-chunk decay tensor is (B, C, L, L, H) — at chunk
    256 that is tokens x 256 x H elements (~68 TB at the train_4k cell,
    §Perf iteration 6); at 64 it fits comfortably under remat. A Pallas SSD
    kernel holding the block in VMEM would allow larger chunks on TPU.
    """
    bsz, t, h, p_dim = x.shape
    n = b_ssm.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0
    n_chunks = t // chunk
    a = -jnp.exp(a_log.astype(jnp.float32))                 # (H,) negative
    dt = jax.nn.softplus(dt.astype(jnp.float32))            # (B, T, H)
    da = dt * a[None, None, :]                              # (B, T, H) log-decay

    xr = x.reshape(bsz, n_chunks, chunk, h, p_dim)
    dtr = dt.reshape(bsz, n_chunks, chunk, h)
    dar = da.reshape(bsz, n_chunks, chunk, h)
    br = b_ssm.reshape(bsz, n_chunks, chunk, n)
    cr = c_ssm.reshape(bsz, n_chunks, chunk, n)

    cum = jnp.cumsum(dar, axis=2)                           # (B, C, L, H)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]     # (B,C,Lq,Lk,H)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    # mask BEFORE exp: masked entries have seg > 0 and would overflow,
    # poisoning gradients through the where (0 * inf = NaN)
    seg = jnp.where(causal[None, None, :, :, None], seg, -1e30)
    decay = jnp.exp(seg)

    # within-chunk (quadratic in chunk length only)
    scores = jnp.einsum("bcln,bcmn->bclm", cr, br) \
        [..., None] * decay                                  # (B,C,L,M,H)
    y_diag = jnp.einsum("bclmh,bcmh,bcmhp->bclhp", scores, dtr, xr)

    # per-chunk input -> state contribution
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)          # (B,C,L,H)
    states = jnp.einsum("bcln,bclh,bclhp->bchpn",
                        br, dtr * decay_to_end, xr)

    # cross-chunk recurrence over chunk index
    chunk_decay = jnp.exp(cum[:, :, -1, :])                  # (B, C, H)

    def scan_fn(carry, inp):
        s_new, dec = inp                                     # (B,H,P,N),(B,H)
        carry_out = carry * dec[:, :, None, None] + s_new
        return carry_out, carry                              # emit state *before* chunk

    init = jnp.zeros((bsz, h, p_dim, n), jnp.float32)
    final_state, prev_states = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)            # (B,C,H,P,N)

    # contribution of carried-in state to each position
    decay_from_start = jnp.exp(cum)                          # (B,C,L,H)
    y_off = jnp.einsum("bcln,bclh,bchpn->bclhp",
                       cr, decay_from_start, prev_states)

    y = (y_diag + y_off).reshape(bsz, t, h, p_dim)
    return y, final_state


def apply_ssm(x: Array, p: dict, cfg: ModelConfig) -> Array:
    """Training/prefill forward. x: (B, T, d) -> (B, T, d)."""
    d_inner, h, p_dim, n = _dims(cfg)
    zxbcdt = L.project(x, p["in_proj"], cfg, "ssm.in_proj")
    z, xs, b_ssm, c_ssm, dt = _split_proj(zxbcdt, cfg)
    conv_in = jnp.concatenate([xs, b_ssm, c_ssm], axis=-1)
    conv_out, _ = _causal_conv(conv_in, p["conv_w"], p["conv_b"])
    xs, b_ssm, c_ssm = jnp.split(conv_out, [d_inner, d_inner + n], axis=-1)
    xh = C.constrain_axis(xs.reshape(*xs.shape[:-1], h, p_dim), 2)
    y, _ = _ssd_chunked(xh, dt, p["a_log"], b_ssm, c_ssm)
    y = y + p["d_skip"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(*x.shape[:-1], d_inner).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = L.apply_norm(y, p["norm"], "rmsnorm")
    return L.project(y, p["out_proj"], cfg, "ssm.out_proj")


def init_ssm_state(cfg: ModelConfig, batch: int, dtype) -> SSMState:
    d_inner, h, p_dim, n = _dims(cfg)
    conv_dim = d_inner + 2 * n
    return SSMState(
        state=jnp.zeros((batch, h, p_dim, n), jnp.float32),
        conv=jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_dim), dtype),
        length=jnp.zeros((), jnp.int32))


def decode_ssm(x: Array, st: SSMState, p: dict, cfg: ModelConfig
               ) -> tuple[Array, SSMState]:
    """Single-token recurrent step. x: (B, 1, d)."""
    d_inner, h, p_dim, n = _dims(cfg)
    zxbcdt = L.project(x, p["in_proj"], cfg, "ssm.in_proj")
    z, xs, b_ssm, c_ssm, dt = _split_proj(zxbcdt, cfg)
    conv_in = jnp.concatenate([xs, b_ssm, c_ssm], axis=-1)   # (B, 1, C)
    conv_out, _ = _causal_conv(conv_in, p["conv_w"], p["conv_b"],
                               tail=st.conv)
    new_tail = jnp.concatenate([st.conv, conv_in.astype(st.conv.dtype)],
                               axis=1)[:, 1:, :]
    xs, b_ssm, c_ssm = jnp.split(conv_out, [d_inner, d_inner + n], axis=-1)
    xh = xs.reshape(xs.shape[0], h, p_dim).astype(jnp.float32)
    dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32))      # (B, H)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    # the recurrent update reads and writes the whole f32 state: named so
    # a profiler trace can price it (on TPU the name stays on the y read;
    # the state's write fuses under the layer scan's stacking and loses it)
    with jax.named_scope("ssm_state"):
        dec = jnp.exp(dtv * a[None, :])                      # (B, H)
        bv = b_ssm[:, 0].astype(jnp.float32)                 # (B, N)
        cv = c_ssm[:, 0].astype(jnp.float32)
        upd = jnp.einsum("bh,bhp,bn->bhpn", dtv, xh, bv)
        state = st.state * dec[:, :, None, None] + upd
        y = jnp.einsum("bhpn,bn->bhp", state, cv)
    y = y + p["d_skip"][None, :, None] * xh
    y = y.reshape(x.shape[0], 1, d_inner).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = L.apply_norm(y, p["norm"], "rmsnorm")
    out = L.project(y, p["out_proj"], cfg, "ssm.out_proj")
    return out, SSMState(state=state, conv=new_tail, length=st.length + 1)
