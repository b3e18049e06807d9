"""Kernel-dispatch backends (repro.kernels.dispatch): ref / fused / packed
bit-exactness per module role and rung, the off-TPU fallback policy, and the
one-compiled-decode-step-per-backend invariant through the serve engine."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import QuantConfig
from repro.core import policy as pol
from repro.kernels import dispatch
from repro.models import layers as L
from repro.models import model as MD
from repro.models import serving
from repro.serve_engine import Request, ServeEngine

RNG = np.random.default_rng(0)
PALLAS = ("fused:force", "packed:force")   # interpret mode on CPU


def _cfg(arch="llama3-8b"):
    cfg = configs.reduced(configs.get_config(arch))
    return dataclasses.replace(cfg, quant=QuantConfig(mode="none"))


def _leaf(k, n, r=3.0, act_bits=6, bias=False):
    """One projection's serving artifact via the real quantizer walk."""
    node = {"w": jnp.asarray(RNG.standard_normal((k, n)), jnp.float32)}
    if bias:
        node["b"] = jnp.asarray(RNG.standard_normal((n,)), jnp.float32)
    qp = serving.quantize_params_for_serving(
        {"wq": node}, _cfg(), r=r, act_bits=act_bits, pack_planes=True)
    return qp["wq"]


@pytest.mark.parametrize("k,n,act_bits,bias", [
    (64, 48, 6, False),    # n not a tile multiple
    (72, 64, 8, True),     # b~x = 8 runs at the int8 half-range ceiling
    (60, 40, 3, False),    # K % 8 != 0: pack_planes pads K
    (129, 257, None, True),  # no act_n leaf; everything ragged
])
def test_backends_bit_identical(k, n, act_bits, bias):
    leaf = _leaf(k, n, act_bits=act_bits, bias=bias)
    x = jnp.asarray(RNG.standard_normal((3, 5, k)), jnp.float32)
    y_ref = jax.jit(lambda x, p: dispatch.serving_linear(x, p, "ref"))(
        x, leaf)
    for spec in PALLAS:
        y = jax.jit(lambda x, p: dispatch.serving_linear(x, p, spec))(
            x, leaf)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref),
                                      err_msg=spec)


def _quantized_modules(qp):
    """{role: per-layer artifact dict} over the whole quantized param tree."""
    found = {}

    def walk(node, trail=()):
        if isinstance(node, dict):
            if "w_q" in node:
                sd = node["w_q"].ndim - 2      # scan-stacked leading dims
                found.setdefault(
                    pol.serving_path(trail),
                    {kk: v[(0,) * sd] if sd else v for kk, v in node.items()})
                return
            for kk, v in node.items():
                walk(v, trail + (kk,))
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v, trail)

    walk(qp)
    return found


@pytest.mark.parametrize("arch,expect", [
    ("llama3-8b", {"attn.wq", "attn.wo", "mlp.w_gate", "mlp.w_down",
                   "lm_head"}),
    ("zamba2-1.2b", {"ssm.in_proj", "ssm.out_proj", "attn.wq", "mlp.w_up"}),
    ("rwkv6-1.6b", {"rwkv.tm.wr", "rwkv.tm.wk", "rwkv.tm.decay_a",
                    "rwkv.tm.decay_b", "rwkv.tm.maa_w1", "rwkv.tm.maa_w2",
                    "rwkv.tm.wo", "rwkv.cm.wk", "rwkv.cm.wv",
                    "rwkv.cm.wr"}),
])
def test_every_module_role_bit_identical(arch, expect):
    cfg = _cfg(arch)
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    qp = serving.quantize_params_for_serving(
        params, cfg, r=3.0, act_bits=6, pack_planes=True)
    modules = _quantized_modules(qp)
    assert expect <= set(modules), sorted(modules)
    for role, leaf in sorted(modules.items()):
        k = leaf["w_q"].shape[0]
        x = jnp.asarray(RNG.standard_normal((2, k)), jnp.float32)
        y_ref = dispatch.serving_linear(x, leaf, "ref")
        for spec in PALLAS:
            y = dispatch.serving_linear(x, leaf, spec)
            np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref),
                                          err_msg=f"{arch}:{role}:{spec}")


def test_dispatch_tracks_float_dequant():
    """Backend-vs-backend equality can't catch a shared sign/zcol/bias bug;
    the integer dataflow must also approximate the float dequant product."""
    leaf = _leaf(128, 96, r=8.0, act_bits=8, bias=True)
    x = jnp.asarray(RNG.standard_normal((4, 128)), jnp.float32)
    y = np.asarray(dispatch.serving_linear(x, leaf, "ref"))
    w = leaf["w_q"].astype(jnp.float32) * leaf["w_scale"]
    y_fp = np.asarray(x @ w + leaf["b"])
    denom = np.abs(y_fp).mean() + 1e-9
    assert np.abs(y - y_fp).mean() / denom < 0.05


def test_zero_point_bounded_for_nonspanning_activations():
    """Regression: activations that do not span zero (post-ReLU-ish values
    near 100) must NOT overflow the zero point — the calibration range is
    extended to include 0, bounding z to [0, n]. Before the fix zcol
    wrapped int32 and the ref backend returned garbage/zeros."""
    leaf = _leaf(64, 32, r=8.0, act_bits=8, bias=False)
    x = jnp.asarray(100.0 + 1e-6 * RNG.standard_normal((4, 64)), jnp.float32)
    y = np.asarray(dispatch.serving_linear(x, leaf, "ref"))
    w = leaf["w_q"].astype(jnp.float32) * leaf["w_scale"]
    y_fp = np.asarray(x @ w)
    denom = np.abs(y_fp).mean() + 1e-9
    assert np.abs(y - y_fp).mean() / denom < 0.05
    for spec in PALLAS:   # and the backends still agree bitwise
        np.testing.assert_array_equal(
            np.asarray(dispatch.serving_linear(x, leaf, spec)), y)


def test_colsum_leaf_matches_recomputation():
    """w_colsum is precomputed in the artifact; a hand-built leaf without
    it must fall back to recomputing and produce identical outputs."""
    leaf = _leaf(48, 24, act_bits=6)
    assert leaf["w_colsum"].dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(leaf["w_colsum"]),
        np.asarray(jnp.sum(leaf["w_q"].astype(jnp.int32), axis=0)))
    stripped = {kk: v for kk, v in leaf.items() if kk != "w_colsum"}
    x = jnp.asarray(RNG.standard_normal((3, 48)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(dispatch.serving_linear(x, leaf, "ref")),
        np.asarray(dispatch.serving_linear(x, stripped, "ref")))


def test_fallback_off_tpu_is_ref():
    leaf = _leaf(64, 32)
    assert dispatch.resolve_backend("fused", leaf) == ("ref", False)
    assert dispatch.resolve_backend("fused:force", leaf) == ("fused", True)
    # the fallback is reported, not hidden behind the requested name
    assert dispatch.effective_backend("packed") == "ref"
    assert dispatch.effective_backend("packed:force") == "packed:interpret"
    assert dispatch.effective_backend("ref") == "ref"
    x = jnp.asarray(RNG.standard_normal((4, 64)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(dispatch.serving_linear(x, leaf, "fused")),
        np.asarray(dispatch.serving_linear(x, leaf, "ref")))


def test_parse_backend_rejects_typos():
    with pytest.raises(ValueError, match="unknown kernel backend"):
        dispatch.parse_backend("fast")
    with pytest.raises(ValueError, match="unknown backend option"):
        dispatch.parse_backend("fused:interpret")


def test_packed_without_planes_is_a_build_error():
    node = {"w": jnp.asarray(RNG.standard_normal((32, 16)), jnp.float32)}
    leaf = serving.quantize_params_for_serving({"wq": node}, _cfg(),
                                               r=2.0)["wq"]
    x = jnp.asarray(RNG.standard_normal((2, 32)), jnp.float32)
    with pytest.raises(ValueError, match="pack_planes=True"):
        dispatch.serving_linear(x, leaf, "packed:force")


def test_variant_cache_pins_plane_count_across_rungs():
    """Every rung view of a packed store holds its planes at
    LADDER_PLANE_COUNT with one shape per module, so rungs share avals."""
    cfg = _cfg()
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    ws = serving.build_weight_store(params, cfg, {2: 1.1, 4: 3.7},
                                    pack_planes=True)
    shapes = {}
    for rung, view in ws.views.items():
        for path, leaf in jax.tree_util.tree_leaves_with_path(view):
            key = getattr(path[-1], "key", "")
            if key in dispatch.PLANE_KEYS:
                # the plane axis sits behind the scan-stacked dims
                assert leaf.shape[-3] == serving.LADDER_PLANE_COUNT, (
                    rung, jax.tree_util.keystr(path), leaf.shape)
                shapes.setdefault(jax.tree_util.keystr(path),
                                  set()).add(leaf.shape)
    assert shapes and all(len(v) == 1 for v in shapes.values()), shapes


def test_legacy_backend_none_is_unchanged():
    """An artifact under kernel_backend=None runs the 'ref' integer path:
    apply_linear equals dispatch.serving_linear(..., 'ref') bit for bit,
    with a bias and without act_bits."""
    leaf = _leaf(48, 32, act_bits=None, bias=True)
    assert "act_n" not in leaf and "b" in leaf
    x = jnp.asarray(RNG.standard_normal((3, 48)), jnp.float32)
    y = L.apply_linear(x, leaf, None, backend=None)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(dispatch.serving_linear(x, leaf, "ref")))


@pytest.mark.parametrize("allocation", ["uniform", "layerwise"])
def test_ladder_bitwise_across_backends_no_recompile(allocation):
    """The acceptance gate: every rung of a uniform AND a layerwise ladder
    decodes bit-identically (fp32 logits) through all three backends, each
    with exactly one compiled decode step surviving mixed-rung traffic."""
    cfg = _cfg()
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    logits, engines = {}, {}
    for spec in ("ref",) + PALLAS:
        eng = ServeEngine(cfg, params, ladder_bits=(2, 4), max_batch=2,
                          max_len=6, allocation=allocation, backend=spec)
        eng.warmup()
        state = eng._init_state(2)
        tok = jnp.zeros((2, 1), jnp.int32)
        per_rung = []
        for bits in (2, 4, 2):       # revisit rung 2: pointer-swap switching
            lg, state = eng._step(eng.variants[bits], state, tok)
            per_rung.append(np.asarray(lg))
        logits[spec] = np.stack(per_rung)
        engines[spec] = eng
    for spec in PALLAS:
        np.testing.assert_array_equal(logits[spec], logits["ref"],
                                      err_msg=f"{allocation}:{spec}")
    reqs = [Request(uid=i,
                    prompt=np.asarray([1, 2], np.int32),
                    max_new_tokens=2, power_budget_bits=[2, 4][i % 2])
            for i in range(4)]
    for spec, eng in engines.items():
        toks = [r.tokens for r in eng.generate(reqs)]
        eng.assert_no_recompile()
        assert eng.describe()["backend"] == spec
        assert eng.describe()["effective_backend"] == \
            dispatch.effective_backend(spec)
        # interpret mode lowers Pallas to plain HLO: no TPU custom call
        assert eng.pallas_calls_in_step() == 0
        eng.assert_no_recompile()
        if spec != "ref":
            ref_toks = [r.tokens for r in engines["ref"].generate(reqs)]
            assert toks == ref_toks, spec


def test_kernel_bench_check_baseline_logic():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import kernel_bench
    base = {"invariants": {
        "shape": {"m": 1}, "hbm_bytes_per_weight": {"int8_codes": 1.0},
        "parity": {"a": {"exact": True, "max_abs_diff": 0.0}}}}
    good = {"invariants": {
        "shape": {"m": 1}, "hbm_bytes_per_weight": {"int8_codes": 1.0},
        "parity": {"a": {"exact": True, "max_abs_diff": 0.0}}}}
    import json
    import tempfile
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(base, f)
        path = f.name
    assert kernel_bench.check_baseline(good, path) == []
    bad = json.loads(json.dumps(good))
    bad["invariants"]["parity"]["a"] = {"exact": False,
                                       "max_abs_diff": 0.25}
    assert any("parity broken" in m
               for m in kernel_bench.check_baseline(bad, path))
    drift = json.loads(json.dumps(good))
    drift["invariants"]["shape"] = {"m": 2}
    assert any("drifted" in m
               for m in kernel_bench.check_baseline(drift, path))
    os.unlink(path)
