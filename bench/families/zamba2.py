"""zamba2: Mamba2 layers, and one attention + MLP block whose weights are
shared by every ``attn_period``-th position (arXiv:2411.15242), in the
form the configuration file states: the block reads the residual stream,
its attention has ``head_dim``-wide heads and its MLP is ``activation``
(gelu, or geglu with a gate)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.flops import head_dim
from bench.reference import attention, linear, rmsnorm, take
from bench.weights import lin, rms


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _ssm(ks, spec, lead=()):
    d = spec["d_model"]
    d_inner = spec["ssm_expand"] * d
    h = d_inner // spec["ssm_head_dim"]
    n = spec["ssm_state"]
    conv_dim = d_inner + 2 * n
    a_log = jnp.log(jnp.linspace(1.0, 16.0, h).astype(jnp.float32))
    return {
        "in_proj": lin(ks, d, 2 * d_inner + 2 * n + h, lead),
        "conv_w": 0.2 * jax.random.normal(
            ks(), lead + (spec["ssm_conv_width"], conv_dim), jnp.float32),
        "conv_b": 0.1 * jax.random.normal(ks(), lead + (conv_dim,),
                                          jnp.float32),
        "a_log": jnp.broadcast_to(a_log, lead + (h,)),
        "dt_bias": jnp.zeros(lead + (h,), jnp.float32),
        "d_skip": 1.0 + 0.1 * jax.random.normal(ks(), lead + (h,),
                                                jnp.float32),
        "norm": rms(ks, d_inner, lead),
        "out_proj": lin(ks, d_inner, d, lead),
    }


def params(ks, spec):
    d, period = spec["d_model"], spec["attn_period"]
    n_groups, n_tail = divmod(spec["num_layers"], period)
    hd = head_dim(spec)
    group = [{"norm1": rms(ks, d, (n_groups,)),
              "ssm": _ssm(ks, spec, (n_groups,))} for _ in range(period)]
    tail = [{"norm1": rms(ks, d), "ssm": _ssm(ks, spec)}
            for _ in range(n_tail)]
    mlp = {"w_up": lin(ks, d, spec["d_ff"]),
           "w_down": lin(ks, spec["d_ff"], d)}
    if spec["activation"] == "geglu":
        mlp["w_gate"] = lin(ks, d, spec["d_ff"])
    shared = {
        "norm1": rms(ks, d),
        "attn": {"wq": lin(ks, d, spec["num_heads"] * hd),
                 "wk": lin(ks, d, spec["num_kv_heads"] * hd),
                 "wv": lin(ks, d, spec["num_kv_heads"] * hd),
                 "wo": lin(ks, spec["num_heads"] * hd, d)},
        "norm2": rms(ks, d),
        "mlp": mlp,
    }
    dec = {"groups": {"layers": group}}
    if n_tail:
        dec["tail"] = tail
    return {"decoder": dec, "shared_attn": shared, "final_norm": rms(ks, d)}


# ---------------------------------------------------------------------------
# Work (bench/flops.py)
# ---------------------------------------------------------------------------

def projections(spec):
    """[(K, N, calls per decode step)] of the family's quantized linears."""
    d, ff, nl = spec["d_model"], spec["d_ff"], spec["num_layers"]
    d_in = spec["ssm_expand"] * d
    n, hp = spec["ssm_state"], spec["ssm_head_dim"]
    na = attention_layers(spec)
    qd = spec["num_heads"] * head_dim(spec)
    kvd = spec["num_kv_heads"] * head_dim(spec)
    mlp = 3 if spec["activation"] == "geglu" else 2
    return ([(d, 2 * d_in + 2 * n + d_in // hp, nl), (d_in, d, nl),
             (d, qd, na), (d, kvd, 2 * na), (qd, d, na)]
            + [(d, ff, (mlp - 1) * na), (ff, d, na)])


def attention_layers(spec):
    return spec["num_layers"] // spec["attn_period"]


# ---------------------------------------------------------------------------
# Reference step
# ---------------------------------------------------------------------------

def _mamba(x, p, st, spec, act_n, ft):
    """One Mamba2 token step. x (B, d); st = (ssm (B,H,P,N), tail (B,W-1,C))."""
    d_inner = spec["ssm_expand"] * spec["d_model"]
    hp, n = spec["ssm_head_dim"], spec["ssm_state"]
    h = d_inner // hp
    ssm, tail = st
    zxbcdt = linear(x, p["in_proj"], act_n, ft)
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * n]
    dt = zxbcdt[:, 2 * d_inner + 2 * n:]
    win = jnp.concatenate([tail, xbc[:, None, :]], axis=1)     # (B, W, C)
    conv = sum(win[:, i] * p["conv_w"][i] for i in range(win.shape[1]))
    conv = jax.nn.silu(conv + p["conv_b"])
    xs = conv[:, :d_inner].reshape(-1, h, hp)
    bv = conv[:, d_inner:d_inner + n]
    cv = conv[:, d_inner + n:]
    dtv = jax.nn.softplus(dt)
    dec = jnp.exp(dtv * -jnp.exp(p["a_log"]))                  # (B, H)
    ssm = (ssm * dec[:, :, None, None]
           + jnp.einsum("bh,bhp,bn->bhpn", dtv, xs, bv))
    y = jnp.einsum("bhpn,bn->bhp", ssm, cv) + p["d_skip"][:, None] * xs
    y = y.reshape(-1, d_inner) * jax.nn.silu(z)
    y = rmsnorm(y, p["norm"]["scale"], ft)
    return linear(y, p["out_proj"], act_n, ft), (ssm, win[:, 1:])


def _mlp(h, p, spec, act_n, ft):
    up = linear(h, p["w_up"], act_n, ft)
    if spec["activation"] == "geglu":
        h = jax.nn.gelu(linear(h, p["w_gate"], act_n, ft)) * up
    else:
        h = jax.nn.gelu(up)
    return linear(h, p["w_down"], act_n, ft)


def step(w, state, tok, pos, spec, act_n, cache_n, ft):
    period = spec["attn_period"]
    n_groups = spec["num_layers"] // period
    ssm, tails, kvs = state
    x = w["embed"]["table"][tok].astype(ft)
    new_ssm, new_tails, new_kv = [], [], []
    shared = w["shared_attn"]
    for i in range(spec["num_layers"]):
        gi, j = divmod(i, period)
        if gi < n_groups:
            lp = take(w["decoder"]["groups"]["layers"][j], gi)
        else:
            lp = w["decoder"]["tail"][j]
        h = rmsnorm(x, lp["norm1"]["scale"], ft)
        y, (s_i, t_i) = _mamba(h, lp["ssm"], (ssm[i], tails[i]), spec,
                               act_n, ft)
        new_ssm.append(s_i)
        new_tails.append(t_i)
        x = x + y
        if gi < n_groups and j == period - 1:
            h = rmsnorm(x, shared["norm1"]["scale"], ft)
            y, kv = attention(h, shared["attn"], take(kvs, gi), pos, spec,
                              act_n, cache_n, ft)
            new_kv.append(kv)
            x = x + y
            h = rmsnorm(x, shared["norm2"]["scale"], ft)
            x = x + _mlp(h, shared["mlp"], spec, act_n, ft)
    x = rmsnorm(x, w["final_norm"]["scale"], ft)
    logits = linear(x, w["lm_head"], act_n, ft)
    kv_stack = tuple(jnp.stack(parts) for parts in zip(*new_kv))
    return logits, (jnp.stack(new_ssm), jnp.stack(new_tails), kv_stack)


def state(spec, batch, max_len, ft):
    d_inner = spec["ssm_expand"] * spec["d_model"]
    hp, n = spec["ssm_head_dim"], spec["ssm_state"]
    nl = spec["num_layers"]
    na = nl // spec["attn_period"]
    codes = jnp.zeros((na, batch, max_len, spec["num_kv_heads"],
                       head_dim(spec)), jnp.int8)
    row = jnp.zeros((na, batch, max_len), ft)
    return (jnp.zeros((nl, batch, d_inner // hp, hp, n), ft),
            jnp.zeros((nl, batch, spec["ssm_conv_width"] - 1,
                       d_inner + 2 * n), ft),
            (codes, row, row, codes, row, row))
