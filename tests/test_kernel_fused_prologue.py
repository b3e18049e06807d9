"""Fused act-quant prologue + block autotuner (kernels/pann_matmul act
entry points, kernels/autotune, the hoisted act_s/act_z artifact leaves):
bit-exactness vs the ref oracle across dynamic and export-frozen calibrated
ranges, odd shapes through the padding path, cache semantics, and the
no-recompile invariant with the autotuner active."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import QuantConfig
from repro.core import policy as pol
from repro.kernels import autotune, dispatch, ops, ref
from repro.models import serving
from repro.serve_engine import Request, ServeEngine

RNG = np.random.default_rng(7)
PALLAS = ("fused:force", "packed:force")


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    """Redirect the persistent autotune cache to a throwaway file."""
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune.clear_memory_cache()
    yield path
    autotune.clear_memory_cache()


def _cfg():
    cfg = configs.reduced(configs.get_config("llama3-8b"))
    return dataclasses.replace(cfg, quant=QuantConfig(mode="none"))


def _leaf(k, n, act_bits=6, calib_range=None):
    node = {"w": jnp.asarray(RNG.standard_normal((k, n)), jnp.float32)}
    calib = None
    if calib_range is not None:
        calib = {pol.serving_path(("wq",)): calib_range}
    qp = serving.quantize_params_for_serving(
        {"wq": node}, _cfg(), r=3.0, act_bits=act_bits, pack_planes=True,
        calib=calib)
    return qp["wq"]


# ---------------------------------------------------------------------------
# parity: dynamic AND export-frozen calibrated ranges
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("calib_range", [None, (-1.5, 2.25), (0.5, 4.0)])
def test_fused_prologue_bit_identical(calib_range):
    """Pallas backends (fp activations in, codes encoded in VMEM) must match
    the ref oracle bit-for-bit, for the dynamic per-batch range and for
    frozen calibration — including a non-zero-spanning range whose zero
    extension bounds z."""
    leaf = _leaf(72, 56, act_bits=8, calib_range=calib_range)
    x = jnp.asarray(RNG.standard_normal((2, 3, 72)), jnp.float32)
    y_ref = jax.jit(lambda x, p: dispatch.serving_linear(x, p, "ref"))(
        x, leaf)
    for spec in PALLAS:
        y = jax.jit(lambda x, p: dispatch.serving_linear(x, p, spec))(
            x, leaf)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref),
                                      err_msg=f"{spec}:{calib_range}")


def test_hoisted_calibration_scalars_bit_exact():
    """The build-time-hoisted act_s/act_z leaves and the serve-time
    derivation from act_lo/act_hi are the same f32 op sequence — stripping
    the hoist must not change a single bit on any backend."""
    leaf = _leaf(64, 48, act_bits=8, calib_range=(-2.0, 3.0))
    assert "act_s" in leaf and "act_z" in leaf
    stripped = {k: v for k, v in leaf.items() if k not in ("act_s", "act_z")}
    x = jnp.asarray(RNG.standard_normal((4, 64)), jnp.float32)
    for spec in ("ref",) + PALLAS:
        np.testing.assert_array_equal(
            np.asarray(dispatch.serving_linear(x, leaf, spec)),
            np.asarray(dispatch.serving_linear(x, stripped, spec)),
            err_msg=spec)


def test_unseen_calibration_stays_dynamic():
    """lo > hi marks a role the training run never observed: the artifact
    carries no frozen leaves and the backends fall back to the dynamic
    range, still bit-identically."""
    leaf = _leaf(64, 32, act_bits=6, calib_range=(1.0, -1.0))
    assert "act_lo" not in leaf and "act_s" not in leaf
    x = jnp.asarray(RNG.standard_normal((3, 64)), jnp.float32)
    y_ref = dispatch.serving_linear(x, leaf, "ref")
    for spec in PALLAS:
        np.testing.assert_array_equal(
            np.asarray(dispatch.serving_linear(x, leaf, spec)),
            np.asarray(y_ref), err_msg=spec)


# ---------------------------------------------------------------------------
# odd shapes through the pad-to-block path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [
    (5, 60, 40),      # K % 8 != 0 (pack_planes pads), ragged M and N
    (1, 129, 257),    # decode row count 1, everything prime-ish
    (7, 72, 48),      # M not a multiple of any MXU-aligned bm
])
def test_odd_shapes_bit_identical(m, k, n):
    leaf = _leaf(k, n, act_bits=6)
    x = jnp.asarray(RNG.standard_normal((m, k)), jnp.float32)
    y_ref = dispatch.serving_linear(x, leaf, "ref")
    for spec in PALLAS:
        np.testing.assert_array_equal(
            np.asarray(dispatch.serving_linear(x, leaf, spec)),
            np.asarray(y_ref), err_msg=spec)


def test_cached_blocks_force_ragged_m_padding(tmp_cache):
    """Plant cache entries whose bm does NOT divide M, so serving_linear
    runs the fused-prologue kernels through the pad-rows path (padded fp32
    zeros encode to the code z against zero plane rows — an exact no-op)."""
    k, n, m = 64, 48, 6
    leaf = _leaf(k, n, act_bits=8)
    n_planes = leaf["w_planes_pos"].shape[-3]
    k_full = leaf["w_planes_pos"].shape[-2] * 8
    autotune.record(m, k, n, n_planes, "fused", (4, 48, 64))
    autotune.record(m, k_full, n, n_planes, "packed", (4, 48, 64))
    assert autotune.blocks_for(m, k, n, n_planes, "fused") == (4, 48, 64)
    x = jnp.asarray(RNG.standard_normal((m, k)), jnp.float32)
    y_ref = dispatch.serving_linear(x, leaf, "ref")
    for spec in PALLAS:
        np.testing.assert_array_equal(
            np.asarray(dispatch.serving_linear(x, leaf, spec)),
            np.asarray(y_ref), err_msg=spec)


def test_quantize_act_ragged_and_platform_default():
    """Ragged M pads up and slices back (bit-identical to the oracle), and
    interpret=None resolves by platform instead of the old unconditional
    interpret=True."""
    x = jnp.abs(jnp.asarray(RNG.standard_normal((13, 40)), jnp.float32))
    q, s = ops.quantize_act(x, bits=8)          # interpret resolved inside
    q_ref, s_ref = ref.quantize_act_ref(x, 8)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))


# ---------------------------------------------------------------------------
# autotune cache semantics
# ---------------------------------------------------------------------------

def test_heuristic_respects_vmem_budget():
    for packed in (False, True):
        for (m, n, k) in [(4, 4096, 8192), (256, 512, 512), (1, 64, 48)]:
            bm, bn, bk = autotune.heuristic_blocks(m, n, k, packed=packed)
            assert autotune.vmem_bytes(bm, bn, bk, k, packed) \
                <= 8 * 2 ** 20, (m, n, k, packed)
            # whole (32, 128) int8 tiles of the codes panel: Mosaic refuses
            # a panel slice at a lane offset off the 128-lane tiling
            assert bm % autotune.ROW_TILE == 0 and bk % autotune.LANE_TILE == 0


def test_candidate_grid_fits_budget_and_contains_heuristic():
    cands = autotune.candidate_blocks(64, 256, 1024, 7)
    assert autotune.heuristic_blocks(64, 256, 1024, 7) in cands
    for bm, bn, bk in cands:
        assert autotune.vmem_bytes(bm, bn, bk, 1024, False) <= 8 * 2 ** 20


def test_record_persists_and_survives_process_cache_drop(tmp_cache):
    assert autotune.blocks_for(8, 64, 32, 7, "fused") == \
        autotune.heuristic_blocks(8, 32, 64, 7)
    autotune.record(8, 64, 32, 7, "fused", (8, 32, 64))
    autotune.clear_memory_cache()               # force a disk re-read
    assert autotune.blocks_for(8, 64, 32, 7, "fused") == (8, 32, 64)
    on_disk = json.loads(tmp_cache.read_text())
    assert on_disk["version"] == autotune.CACHE_VERSION
    key = autotune.cache_key(8, 64, 32, 7, "fused")
    assert on_disk["blocks"][key] == {"blocks": [8, 32, 64], "depth": 2,
                                      "order": "mnk"}


def test_corrupt_or_foreign_cache_is_ignored(tmp_cache):
    tmp_cache.write_text("{ not json")
    assert autotune.blocks_for(8, 64, 32, 7, "fused") == \
        autotune.heuristic_blocks(8, 32, 64, 7)
    autotune.clear_memory_cache()
    tmp_cache.write_text(json.dumps(
        {"version": 999, "blocks": {autotune.cache_key(
            8, 64, 32, 7, "fused"): [1, 1, 1]}}))
    assert autotune.blocks_for(8, 64, 32, 7, "fused") == \
        autotune.heuristic_blocks(8, 32, 64, 7)


def test_tune_off_tpu_records_heuristic_and_short_circuits(tmp_cache):
    calls = []
    best = autotune.tune(4, 128, 64, 7, "fused",
                         runner=lambda b: calls.append(b) or 1.0)
    assert best == autotune.heuristic_params(4, 64, 128, 7)
    assert best.blocks == autotune.heuristic_blocks(4, 64, 128, 7)
    assert calls == []          # off-TPU: never timed, emulator noise
    # cached entry short-circuits without consulting the runner either
    assert autotune.tune(4, 128, 64, 7, "fused",
                         runner=lambda b: 1 / 0) == best


def test_tune_projection_fills_cache_for_real_artifacts(tmp_cache):
    leaf = _leaf(64, 48, act_bits=8)
    n_planes = leaf["w_planes_pos"].shape[-3]
    k_full = leaf["w_planes_pos"].shape[-2] * 8
    dispatch.tune_projection(4, leaf, "packed:force")
    assert autotune.cache_key(4, k_full, 48, n_planes, "packed") in \
        json.loads(tmp_cache.read_text())["blocks"]
    dispatch.tune_projection(4, leaf, "ref")    # ref: nothing to tune
    assert len(json.loads(tmp_cache.read_text())["blocks"]) == 1


# ---------------------------------------------------------------------------
# the engine invariant with the autotuner active
# ---------------------------------------------------------------------------

def test_engine_autotune_no_recompile(tmp_cache):
    """ServeEngine(autotune=True) tunes strictly before warmup; blocks_for
    is pure at trace time, so mixed-rung traffic still decodes through ONE
    compiled step — and the tuner actually populated the cache."""
    cfg = _cfg()
    from repro.models import model as MD
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, ladder_bits=(2, 4), max_batch=2,
                      max_len=6, backend="packed:force", autotune=True)
    eng.warmup()
    assert tmp_cache.exists()
    assert len(json.loads(tmp_cache.read_text())["blocks"]) > 0
    reqs = [Request(uid=i, prompt=np.asarray([1, 2], np.int32),
                    max_new_tokens=2, power_budget_bits=[2, 4][i % 2])
            for i in range(4)]
    eng.generate(reqs)
    eng.assert_no_recompile()
