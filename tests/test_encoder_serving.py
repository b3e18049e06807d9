"""Encoder & conv serving (PR 10): the conv projection's bit-exactness
contract, the conv cost/policy plumbing, and the batch-oriented
``EncodeEngine`` — on reduced ``llama-3.2-vision-90b`` (vision conv stem)
and ``seamless-m4t-medium`` (speech conv stem + bidirectional encoder).

The conv claims mirror the linear ones (tests/test_kernel_dispatch.py):

  * ``dispatch.serving_conv`` is bit-identical (fp32 ``assert_array_equal``)
    to the jnp int32 conv oracle (``serving_conv_oracle``) across all three
    backends — ref, fused Pallas, packed planes;
  * across RUNG VIEWS of one weight store the same identity holds per view
    (the view's masked codes feed both sides);
  * ``costs`` accounts conv MACs exactly (kh·kw·Cin·Cout·Ho·Wo) and
    ``allocate_layerwise`` prices the ``conv.s{i}`` roles under one budget.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import QuantConfig
from repro.core import costs
from repro.core import policy as pol
from repro.data import pipeline
from repro.kernels import dispatch, pann_conv
from repro.models import model as MD
from repro.models import serving
from repro.serve_engine import EncodeEngine, EncodeRequest

BACKENDS = ("ref", "fused:force", "packed:force")
ARCHS = ("llama-3.2-vision-90b", "seamless-m4t-medium")


def _reduced(arch):
    cfg = configs.reduced(configs.get_config(arch))
    return dataclasses.replace(cfg, quant=QuantConfig(mode="none"))


@pytest.fixture(scope="module", params=ARCHS)
def setup(request):
    cfg = _reduced(request.param)
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    raw = pipeline.frontend_raw_stub(cfg, 2, step=0)
    return cfg, params, jnp.asarray(raw)


# ---------------------------------------------------------------------------
# im2col plumbing
# ---------------------------------------------------------------------------

def test_extract_patches_matches_flat_weight_layout():
    """The layout contract: patch features in (di, dj, c) order match
    ``w_flat.reshape(kh, kw, c_in, c_out)`` read as HWIO — so the patch
    matmul IS the conv, verified in float against lax.conv."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 9, 7, 5)), jnp.float32)
    w_flat = jnp.asarray(rng.standard_normal((3 * 2 * 5, 4)), jnp.float32)
    patches = pann_conv.extract_patches(x, 3, 2, 2, 1)
    y_mat = patches.reshape(-1, patches.shape[-1]) @ w_flat
    y_conv = jax.lax.conv_general_dilated(
        x, w_flat.reshape(3, 2, 5, 4), window_strides=(2, 1),
        padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(np.asarray(y_mat).reshape(y_conv.shape),
                               np.asarray(y_conv), rtol=1e-5, atol=1e-5)


def test_conv_out_size_rejects_empty_output():
    with pytest.raises(ValueError):
        pann_conv.conv_out_size(2, 5, 1, 0)


# ---------------------------------------------------------------------------
# Bit-exactness: serving_conv vs int32 conv oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_serving_conv_bit_identical_to_oracle(setup, backend):
    cfg, params, raw = setup
    sp = serving.quantize_params_for_serving(
        params, cfg, r=4.0, act_bits=6,
        pack_planes=dispatch.reads_planes(backend))
    for i, spec in enumerate(cfg.conv_stem):
        p = sp["conv_stem"][f"s{i}"]
        x = raw if i == 0 else jnp.zeros(
            (2,) + cfg.conv_stem[i - 1].out_hw(*cfg.frontend_hw)
            + (spec.c_in,), jnp.float32)
        y = dispatch.serving_conv(x, p, spec, backend)
        oracle = dispatch.serving_conv_oracle(x, p, spec)
        assert y.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(y), np.asarray(oracle))


@pytest.mark.parametrize("backend", BACKENDS)
def test_serving_conv_exact_across_rung_views(setup, backend):
    """One store, every rung a view: the conv projection stays bit-exact
    vs the oracle THROUGH the view's plane_shift masking, per rung."""
    cfg, params, raw = setup
    ws = serving.build_weight_store(
        params, cfg, {2: (2.0, 6), 6: (16.0, 6)}, pack_planes=True)
    spec = cfg.conv_stem[0]
    outs = {}
    for rung, view in ws.views.items():
        p = view["conv_stem"]["s0"]
        assert "plane_shift" in p
        y = dispatch.serving_conv(raw, p, spec, backend)
        oracle = dispatch.serving_conv_oracle(raw, p, spec)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(oracle))
        outs[rung] = np.asarray(y)
    # the narrow rung genuinely differs (planes were dropped) — the
    # cross-view equality above is not vacuous
    assert not np.array_equal(outs[2], outs[6])


def test_zero_padding_is_exact_not_approximate(setup):
    """Padding soundness: scalars come from the PADDED input (include_zero
    ranges), so padding zeros encode exactly to the zero point and the
    border contributes exactly b_q - zcol. Checked by comparing against
    manual fp padding + the same conv on a pad-free spec."""
    cfg, params, raw = setup
    spec = cfg.conv_stem[0]
    if spec.ph == 0 and spec.pw == 0:
        pytest.skip("first stem layer of this arch is unpadded")
    sp = serving.quantize_params_for_serving(params, cfg, r=4.0,
                                             act_bits=6)
    p = sp["conv_stem"]["s0"]
    y = dispatch.serving_conv(raw, p, spec, "ref")
    xpad = pann_conv.pad_nhwc(raw, spec.ph, spec.pw)
    spec0 = dataclasses.replace(spec, ph=0, pw=0)
    y_manual = dispatch.serving_conv(xpad, p, spec0, "ref")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_manual))


# ---------------------------------------------------------------------------
# Costs & allocator: conv roles under one budget
# ---------------------------------------------------------------------------

def test_conv_macs_exact_account(setup):
    cfg, _, _ = setup
    rows = costs.conv_stem_item_costs(cfg)
    assert len(rows) == len(cfg.conv_stem)
    h, w = cfg.frontend_hw
    for row, spec in zip(rows, cfg.conv_stem):
        ho, wo = spec.out_hw(h, w)
        assert row.macs == spec.kh * spec.kw * spec.c_in * spec.c_out \
            * ho * wo
        assert row.fan_in == spec.fan_in
        h, w = ho, wo


def test_profile_roots_include_conv_and_sum_matches(setup):
    cfg, _, _ = setup
    profile = costs.module_cost_profile(cfg)
    roots = {m.path.split(".")[0] for m in profile}
    assert "conv" in roots
    total = sum(m.macs for m in profile)
    assert total == pytest.approx(costs.macs_per_token(cfg).weight_macs,
                                  rel=1e-9)


def test_allocator_spends_budget_across_conv_roles(setup):
    """``allocate_layerwise`` on the per-item encoder profile must assign
    every conv role its own operating point AND keep the total power at
    the uniform budget — conv bits genuinely trade against the rest."""
    from repro.core import planner
    cfg, _, _ = setup
    profile = costs.encoder_cost_profile(cfg)
    conv_paths = [m.path for m in profile if m.path.startswith("conv.")]
    assert conv_paths
    lw = planner.allocate_layerwise(planner.budget_from_bits(4), profile)
    for path in conv_paths:
        mq = lw.tree.lookup(path)
        assert mq.mode == "pann" and mq.r > 0
    total, breakdown = pol.tree_power_per_token(profile, lw.tree,
                                                act_macs=0.0)
    for path in conv_paths:
        assert breakdown[path] > 0


def test_serving_path_maps_conv_trail():
    assert pol.serving_path(("conv_stem", "s0")) == "conv.s0"
    assert pol.serving_path(("conv_stem", "s1")) == "conv.s1"


# ---------------------------------------------------------------------------
# EncodeEngine: encoder serving end to end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("allocation", ("uniform", "layerwise"))
def test_encode_engine_serves_ladder_without_recompile(setup, allocation):
    cfg, params, raw = setup
    eng = EncodeEngine(cfg, params, ladder_bits=(2, 4, 6), max_batch=2,
                       backend="ref", allocation=allocation)
    eng.warmup()
    assert eng.compilations_after_warmup == 1
    reqs = [EncodeRequest(uid=i, item=np.asarray(raw[i % 2]),
                          power_budget_bits=b)
            for i, b in enumerate((2, 4, 6, 6))]
    out = eng.encode(reqs)
    eng.assert_no_recompile()
    assert [r.uid for r in out] == [0, 1, 2, 3]
    assert [r.rung_bits for r in out] == [2, 4, 6, 6]
    t = costs.encoder_tokens(cfg)
    for r in out:
        assert r.encoded.shape == (t, cfg.d_model)
        assert r.metadata["est_bitflips_per_token"] > 0
    # higher budget -> at least as much power per item
    flips = [r.metadata["est_bitflips_per_token"] for r in out[:3]]
    assert flips[0] < flips[1] < flips[2]


def test_encode_engine_ledger_itemizes_conv_roles(setup):
    cfg, params, raw = setup
    eng = EncodeEngine(cfg, params, ladder_bits=(2, 6), max_batch=1,
                       backend="ref", allocation="layerwise")
    out = eng.encode([EncodeRequest(uid=0, item=np.asarray(raw[0]),
                                    power_budget_bits=6)])
    breakdown = out[0].metadata["per_module_gbitflips_per_token"]
    conv_roles = {k for k in breakdown if k.startswith("conv.")}
    assert conv_roles == {f"conv.s{i}" for i in range(len(cfg.conv_stem))}
    assert all(breakdown[k] > 0 for k in conv_roles)


def test_encode_engine_outputs_differ_across_rungs(setup):
    """The dial is real: a 2-bit encode differs from a 6-bit encode of the
    same item, and each equals a direct MD.encode through that rung's
    variant (the engine adds batching, not numerics)."""
    cfg, params, raw = setup
    eng = EncodeEngine(cfg, params, ladder_bits=(2, 6), max_batch=2,
                       backend="ref")
    item = np.asarray(raw[0])
    out = eng.encode([EncodeRequest(uid=0, item=item, power_budget_bits=2),
                      EncodeRequest(uid=1, item=item, power_budget_bits=6)])
    assert not np.array_equal(out[0].encoded, out[1].encoded)
    cfg_b = dataclasses.replace(eng.cfg)
    for resp in out:
        direct = MD.encode(eng.variants[resp.rung_bits], cfg_b,
                           jnp.asarray(np.stack([item, item])))
        np.testing.assert_array_equal(resp.encoded, np.asarray(direct[0]))


def test_encode_engine_rejects_wrong_item_shape(setup):
    cfg, params, _ = setup
    eng = EncodeEngine(cfg, params, ladder_bits=(4,), max_batch=1,
                       backend="ref")
    bad = np.zeros((3, 3, 3), np.float32)
    with pytest.raises(ValueError, match="item shape"):
        eng.encode([EncodeRequest(uid=0, item=bad)])


def test_encode_engine_rejects_lm_only_config():
    cfg = configs.reduced(configs.get_config("llama3-8b"))
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="no encode path"):
        EncodeEngine(cfg, params, ladder_bits=(4,))


def test_encoder_forward_matches_training_float_path(setup):
    """4-D raw input through ``forward`` routes through the stem and
    agrees with explicitly stemmed 3-D input — train/serve stay one
    code path."""
    cfg, params, raw = setup
    toks = jnp.zeros((2, 4), jnp.int32)
    kw4 = {"image_embeds": raw} if cfg.family == "vlm" \
        else {"enc_inputs": raw}
    emb = MD.apply_conv_stem(params, cfg, raw)
    kw3 = {"image_embeds": emb} if cfg.family == "vlm" \
        else {"enc_inputs": emb}
    out4 = MD.forward(params, cfg, toks, **kw4)
    out3 = MD.forward(params, cfg, toks, **kw3)
    np.testing.assert_array_equal(np.asarray(out4.logits),
                                  np.asarray(out3.logits))
