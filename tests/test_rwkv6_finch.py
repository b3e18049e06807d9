"""RWKV-6 "Finch" as published, at a small size on the CPU, against a plain
reference written here from the equations (arXiv:2404.05892 §4; RWKV-LM
RWKV_Tmix_x060 / RWKV_CMix_x060) on seeded random weights.

* Teacher-forced decode through ``ServeEngine`` (``ref`` backend, one
  rung) against the reference's full-sequence float64 forward of the same
  served weights (the rung's PANN codes times gamma) with each projection's
  input on the served activation grid (per position, over the batch rows:
  one decode step's tensor). The two differ by f32 against float64
  rounding, about 1e-6 of the logits' spread; an activation code flipped
  at a rounding tie would move a logit by well under 1e-2 of it, the
  limit. Leaving out any one published piece — the ddlerp LoRA, the
  per-head ln_x, the channel mix's receptance gate, ln0 — moves the logits
  by more than twice the spread, so each mutation case fails it.
* The same reference on float weights against the program's float decode,
  and the chunked-scan path (``forward``, T > 1) against step-by-step
  decode: the same f32 arithmetic in another order, 1e-4.
* The older static-form params (static lerp, whole-width ln_x, no gate,
  no ln0) still run, and their two paths agree.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import costs
from repro.models import model as MD
from repro.models import rwkv as R
from repro.serve_engine.engine import ServeEngine

B, T = 3, 10
BITS = 8                     # the rung served (b~x 5, R 12.3 at d 64)
LIMIT = 1e-2                 # of the reference logits' spread (docstring)


def _cfg():
    return configs.reduced(configs.get_config("rwkv6-1.6b"))


def _params(seed=0):
    cfg = _cfg()
    p = MD.init_params(jax.random.PRNGKey(seed), cfg)
    # norms away from the identity, so ln0 and ln_x have work to do
    ks = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 16))

    def jitter(node):
        return {"scale": node["scale"] + 0.2 * jax.random.normal(
                    next(ks), node["scale"].shape),
                "bias": node["bias"] + 0.2 * jax.random.normal(
                    next(ks), node["bias"].shape)}

    lay = p["decoder"]["groups"]["layers"][0]
    lay["tm"]["ln_x"] = jitter(lay["tm"]["ln_x"])
    p["ln0"] = jitter(p["ln0"])
    return cfg, p


def _tokens(cfg, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (B, T), 0,
                                         cfg.vocab_size), np.int32)


# ---------------------------------------------------------------------------
# The plain reference (float64 numpy), from the equations
# ---------------------------------------------------------------------------

def _ln(x, p, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return (x - mu) / np.sqrt(var + eps) * p["scale"] + p["bias"]


def _shift(x):
    return np.concatenate([np.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _linear(x, node):
    """x (B, T, K) @ one matrix. Float weights ({"w"}) multiply as they
    are. A served artifact ({"w_q", "w_scale", ...}) multiplies its codes
    times gamma, and x is first put on the served activation grid: per
    position, over the batch rows (one decode step's tensor), the
    zero-extended range in n = act_nlvl levels, in f32 as served."""
    if "w" in node:
        return x @ node["w"]
    assert not np.any(node["plane_shift"]), "one rung: no planes skipped"
    n = np.float32(node["act_nlvl"])
    xf = x.astype(np.float32)
    lo = np.minimum(xf.min(axis=(0, 2), keepdims=True), np.float32(0))
    hi = np.maximum(xf.max(axis=(0, 2), keepdims=True), np.float32(0))
    s = np.maximum((hi - lo) / n, np.float32(1e-12))
    z = np.round(-lo / s)
    q = np.clip(np.round(xf / s) + z, 0, n)
    xq = (q.astype(np.float64) - z) * s
    return xq @ (node["w_q"].astype(np.float64) * node["w_scale"])


def reference_forward(tree, cfg, tokens):
    """(B, T, V) logits of the published block over the whole sequence,
    from float params or from a served rung's artifact."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), tree)
    hd, d = 64, cfg.d_model
    h_n = d // hd
    x = _ln(p["embed"]["table"][tokens], p["ln0"])
    stack = p["decoder"]["groups"]["layers"][0]
    for layer in range(cfg.num_layers):
        lp = jax.tree_util.tree_map(lambda a: a[layer], stack)
        tm, cm = lp["tm"], lp["cm"]
        h = _ln(x, lp["norm1"])
        xx = _shift(h) - h
        m = np.tanh(_linear(h + xx * tm["maa_x"], tm["maa_w1"]))
        m = m.reshape(B, T, 5, -1)
        mix = {n: h + xx * (tm["maa_wkvrg"][i] + _linear(
                   m[:, :, i], jax.tree_util.tree_map(lambda a: a[i],
                                                      tm["maa_w2"])))
               for i, n in enumerate("wkvrg")}
        r, k, v = (_linear(mix[n], tm["w" + n]) for n in "rkv")
        g = _silu(_linear(mix["g"], tm["wg"]))
        dd = tm["decay_base"] + _linear(
            np.tanh(_linear(mix["w"], tm["decay_a"])), tm["decay_b"])
        w = np.exp(-np.exp(dd))
        r, k, v, w = (a.reshape(B, T, h_n, hd) for a in (r, k, v, w))
        s = np.zeros((B, h_n, hd, hd))
        outs = []
        for t in range(T):
            kv = k[:, t, :, :, None] * v[:, t, :, None, :]
            outs.append(np.einsum("bhi,bhij->bhj", r[:, t],
                                  s + tm["bonus"][None, :, :, None] * kv))
            s = w[:, t, :, :, None] * s + kv
        o = np.stack(outs, 1)                            # (B, T, H, hd)
        mu = o.mean(-1, keepdims=True)
        var = o.var(-1, keepdims=True)
        o = (o - mu) / np.sqrt(var + 64e-5) * tm["ln_x"]["scale"] \
            + tm["ln_x"]["bias"]
        x = x + _linear(o.reshape(B, T, d) * g, tm["wo"])
        h = _ln(x, lp["norm2"])
        xx = _shift(h) - h
        xk, xr = h + xx * cm["maa_k"], h + xx * cm["maa_r"]
        kk = np.square(np.maximum(_linear(xk, cm["wk"]), 0.0))
        x = x + _sigmoid(_linear(xr, cm["wr"])) * _linear(kk, cm["wv"])
    x = _ln(x, p["final_norm"])
    return _linear(x, p["lm_head"])[..., :cfg.vocab_size]


def engine_logits(params, cfg, tokens):
    """Teacher-forced decode through the engine's compiled step and its
    top rung: ((B, T, V) logits, one position a step; the rung's view)."""
    eng = ServeEngine(cfg, params=params, ladder_bits=(BITS,), max_batch=B,
                      max_len=T, backend="ref")
    state = eng._init_state(BITS)
    out = []
    for t in range(T):
        logits, state = eng._step(eng.variants[BITS], state,
                                  jnp.asarray(tokens[:, t:t + 1]))
        out.append(np.asarray(logits[:, 0, :cfg.vocab_size], np.float64))
    return np.stack(out, 1), eng.variants[BITS]


def gap(params, cfg, tokens):
    """Widest logit difference over the reference's logit spread."""
    got, view = engine_logits(params, cfg, tokens)
    want = reference_forward(view, cfg, tokens)
    return float(np.abs(got - want).max() / want.std())


def test_engine_decode_matches_the_published_block():
    cfg, params = _params()
    assert gap(params, cfg, _tokens(cfg)) < LIMIT


def test_float_decode_matches_the_published_block():
    """On float weights, without quantization: the program's step-by-step
    decode is the plain reference's forward to f32 rounding."""
    cfg, params = _params()
    tokens = _tokens(cfg)
    _, steps, _ = _scan_vs_decode(params, _float_cfg(cfg), tokens)
    want = reference_forward(params, cfg, tokens)
    np.testing.assert_allclose(steps[..., :cfg.vocab_size], want,
                               rtol=1e-4, atol=1e-4 * np.abs(want).max())


def _no_ddlerp(x, xs, p, cfg):
    xx = xs - x
    mu = p["maa_wkvrg"].astype(x.dtype)
    return {n: x + xx * mu[i] for i, n in enumerate(R.MIX_ORDER)}


def _whole_width_ln_x(o, p):
    flat = {k: v.reshape(v.shape[:-2] + (-1,)) for k, v in p.items()}
    return R.L.apply_norm(o.reshape(o.shape[:-2] + (-1,)), flat, "layernorm")


@pytest.mark.parametrize("module,name,fake", [
    (R, "_ddlerp", _no_ddlerp),
    (R, "_ln_x", _whole_width_ln_x),
    (R, "_receptance", lambda xr, p, cfg: 1.0),
    (MD, "_ln0", lambda x, params: x),
], ids=["ddlerp", "per_head_ln_x", "receptance_gate", "ln0"])
def test_each_published_piece_is_needed(monkeypatch, module, name, fake):
    cfg, params = _params()
    monkeypatch.setattr(module, name, fake)
    assert gap(params, cfg, _tokens(cfg)) > LIMIT


# ---------------------------------------------------------------------------
# Chunked scan against step-by-step decode; the static form
# ---------------------------------------------------------------------------

def _float_cfg(cfg):
    return dataclasses.replace(
        cfg, quant=dataclasses.replace(cfg.quant, mode="none"))


def _scan_vs_decode(params, cfg, tokens):
    full = np.asarray(MD.forward(params, cfg, jnp.asarray(tokens),
                                 remat=False).logits)
    state = MD.init_decode_state(params, cfg, B, T)
    step = jax.jit(lambda s, t: MD.decode_step(params, cfg, s, t))
    out = []
    for t in range(T):
        logits, state = step(state, jnp.asarray(tokens[:, t:t + 1]))
        out.append(np.asarray(logits[:, 0]))
    return full, np.stack(out, 1), state


def test_chunked_scan_matches_decode():
    cfg, params = _params()
    cfg = _float_cfg(cfg)
    tokens = _tokens(cfg)
    full, steps, state = _scan_vs_decode(params, cfg, tokens)
    np.testing.assert_allclose(steps, full, rtol=1e-4,
                               atol=1e-4 * np.abs(full).max())
    # and the decode state is the scan's final state, in the same layout
    h = MD.L.apply_norm(MD._embed(params, cfg, jnp.asarray(tokens),
                                  jnp.float32),
                        jax.tree_util.tree_map(
                            lambda a: a[0],
                            params["decoder"]["groups"]["layers"][0]
                            ["norm1"]), cfg.norm)
    lay0 = jax.tree_util.tree_map(
        lambda a: a[0], params["decoder"]["groups"]["layers"][0]["tm"])
    _, wkv, _ = R.apply_time_mix(h, lay0, cfg)
    np.testing.assert_allclose(np.asarray(state.caches["groups"][0].wkv[0]),
                               np.asarray(wkv), rtol=1e-4,
                               atol=1e-4 * float(jnp.abs(wkv).max()))


def _static_form(params, cfg):
    """The older form: static lerps, whole-width ln_x, no receptance gate,
    no ln0."""
    d = cfg.d_model
    lay = params["decoder"]["groups"]["layers"][0]
    n = lay["tm"]["maa_x"].shape[0]
    tm = {k: v for k, v in lay["tm"].items()
          if k not in ("maa_x", "maa_wkvrg", "maa_w1", "maa_w2")}
    tm["mu"] = jnp.full((n, 5, d), 0.5, jnp.float32)
    tm["ln_x"] = {"scale": jnp.ones((n, d)), "bias": jnp.zeros((n, d))}
    cm = {k: lay["cm"][k] for k in ("wk", "wv")}
    cm["mu"] = jnp.full((n, 2, d), 0.5, jnp.float32)
    out = {k: v for k, v in params.items() if k != "ln0"}
    out["decoder"] = {"groups": {"layers": [dict(lay, tm=tm, cm=cm)]}}
    return out


def test_static_form_params_still_run():
    cfg, params = _params()
    cfg = _float_cfg(cfg)
    static = _static_form(params, cfg)
    tokens = _tokens(cfg)
    full, steps, _ = _scan_vs_decode(static, cfg, tokens)
    assert np.isfinite(full).all()
    np.testing.assert_allclose(steps, full, rtol=1e-4,
                               atol=1e-4 * np.abs(full).max())
    # and they are another model than the published form's
    published, _, _ = _scan_vs_decode(params, cfg, tokens)
    assert np.abs(published - full).max() > 0.1 * np.abs(full).std()


def test_engine_counts_the_recurrent_state():
    cfg, params = _params()
    eng = ServeEngine(cfg, params=params, ladder_bits=(4,), max_batch=B,
                      max_len=T, backend="ref")
    d, hd = cfg.d_model, 64
    per_layer = B * d * hd * 4 + 2 * B * d * 4 + 4   # wkv, shifts, length
    assert eng.describe()["recurrent_state_bytes"] == \
        cfg.num_layers * per_layer


def test_param_count_is_the_published_block():
    """Every matrix of RWKV_Tmix_x060 / RWKV_CMix_x060 at the released
    checkpoint's shapes (RWKV/v6-Finch-1B6-HF): 24 x 55.4 M + the two
    65536 x 2048 tables."""
    cfg = configs.get_config("rwkv6-1.6b")
    d, ff = 2048, 7168
    layer = 5 * d * d + d * 160 + 5 * 32 * d + d * 64 + 64 * d \
        + 2 * d * ff + d * d
    assert costs.param_count(cfg) == 24 * layer + 2 * 65536 * d
    paths = {m.path for m in costs.module_cost_profile(cfg)}
    assert {"rwkv.tm.maa_w1", "rwkv.tm.maa_w2", "rwkv.cm.wr"} <= paths
