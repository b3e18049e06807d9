"""rwkv6: time mix with a data-dependent decay through a low-rank
projection, and a squared-ReLU channel mix (arXiv:2404.05892), in the form
the configuration file states: heads of 64, static token-shift mixing."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import layernorm, linear, take
from bench.weights import ln, lin

HEAD = 64
DECAY_RANK = 64


def params(ks, spec):
    d, n = spec["d_model"], spec["num_layers"]
    lead = (n,)
    heads = d // HEAD
    tm = {"mu": jax.random.uniform(ks(), lead + (5, d), jnp.float32),
          "wr": lin(ks, d, d, lead), "wk": lin(ks, d, d, lead),
          "wv": lin(ks, d, d, lead), "wg": lin(ks, d, d, lead),
          "decay_a": lin(ks, d, DECAY_RANK, lead),
          "decay_b": lin(ks, DECAY_RANK, d, lead),
          "decay_base": -4.0 + 0.5 * jax.random.normal(ks(), lead + (d,),
                                                       jnp.float32),
          "bonus": 0.1 * jax.random.normal(ks(), lead + (heads, HEAD),
                                           jnp.float32),
          "ln_x": ln(ks, d, lead), "wo": lin(ks, d, d, lead)}
    cm = {"mu": jax.random.uniform(ks(), lead + (2, d), jnp.float32),
          "wk": lin(ks, d, spec["d_ff"], lead),
          "wv": lin(ks, spec["d_ff"], d, lead)}
    layer = {"norm1": ln(ks, d, lead), "tm": tm, "norm2": ln(ks, d, lead),
             "cm": cm}
    return {"decoder": {"groups": {"layers": [layer]}},
            "final_norm": ln(ks, d)}


def projections(spec):
    """[(K, N, calls per decode step)] of the family's quantized linears."""
    d, ff, nl = spec["d_model"], spec["d_ff"], spec["num_layers"]
    return [(d, d, 5 * nl), (d, DECAY_RANK, nl), (DECAY_RANK, d, nl),
            (d, ff, nl), (ff, d, nl)]


def attention_layers(spec):
    return 0


def step(w, state, tok, pos, spec, act_n, cache_n, ft):
    del pos, cache_n
    wkv, sh_tm, sh_cm = state
    d = spec["d_model"]
    heads = d // HEAD
    lay = w["decoder"]["groups"]["layers"][0]
    x = w["embed"]["table"][tok].astype(ft)
    n_wkv, n_tm, n_cm = [], [], []
    for i in range(spec["num_layers"]):
        lp = take(lay, i)
        tm, cm = lp["tm"], lp["cm"]
        h = layernorm(x, lp["norm1"]["scale"], lp["norm1"]["bias"], ft)
        prev = sh_tm[i]
        mix = [h * tm["mu"][k] + prev * (1 - tm["mu"][k]) for k in range(5)]
        r = linear(mix[0], tm["wr"], act_n, ft).reshape(-1, heads, HEAD)
        k = linear(mix[1], tm["wk"], act_n, ft).reshape(-1, heads, HEAD)
        v = linear(mix[2], tm["wv"], act_n, ft).reshape(-1, heads, HEAD)
        gate = jax.nn.silu(linear(mix[3], tm["wg"], act_n, ft))
        low = jnp.tanh(linear(mix[4], tm["decay_a"], act_n, ft))
        dd = linear(low, tm["decay_b"], act_n, ft) + tm["decay_base"]
        decay = jnp.exp(-jnp.exp(dd)).reshape(-1, heads, HEAD)
        kv = k[..., :, None] * v[..., None, :]                # (B,H,hd,hd)
        s = wkv[i]
        out = jnp.einsum("bhi,bhij->bhj", r,
                         s + tm["bonus"][None, :, :, None] * kv)
        n_wkv.append(decay[..., :, None] * s + kv)
        out = layernorm(out.reshape(-1, d), tm["ln_x"]["scale"],
                        tm["ln_x"]["bias"], ft) * gate
        x = x + linear(out, tm["wo"], act_n, ft)
        n_tm.append(h)
        h = layernorm(x, lp["norm2"]["scale"], lp["norm2"]["bias"], ft)
        xk = h * cm["mu"][0] + sh_cm[i] * (1 - cm["mu"][0])
        kk = jnp.square(jax.nn.relu(linear(xk, cm["wk"], act_n, ft)))
        x = x + linear(kk, cm["wv"], act_n, ft)
        n_cm.append(h)
    x = layernorm(x, w["final_norm"]["scale"], w["final_norm"]["bias"], ft)
    logits = linear(x, w["lm_head"], act_n, ft)
    return logits, (jnp.stack(n_wkv), jnp.stack(n_tm), jnp.stack(n_cm))


def state(spec, batch, max_len, ft):
    del max_len
    d, nl = spec["d_model"], spec["num_layers"]
    return (jnp.zeros((nl, batch, d // HEAD, HEAD, HEAD), ft),
            jnp.zeros((nl, batch, d), ft), jnp.zeros((nl, batch, d), ft))
