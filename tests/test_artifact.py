"""The mmap-able serving artifact (DESIGN.md §11, docs/artifact.md):

  * zero-copy rung VIEWS over one max-budget weight store
    (``models.serving.build_weight_store`` / ``materialize_view``) —
    per-module, per-backend bit-equality between serving a view and
    serving its materialized copy, and through a full decode step;
  * the truncation-consistent scheme itself: a rung's effective codes are
    exactly the top planes of the max-R codes (property-based, vendored
    hypothesis stub);
  * the on-disk schema (``serve_engine.artifact``): manifest + blob
    round-trip bit-identically through one ``np.memmap`` with no
    Python-side copy, and corruption / version skew is REJECTED, never
    half-loaded.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs
from repro.configs.base import QuantConfig
from repro.core import pann as pann_core
from repro.core import planner
from repro.kernels import dispatch
from repro.models import model as MD
from repro.models import serving
from repro.serve_engine import (ArtifactError, ServeEngine, load_artifact,
                                write_artifact)
from repro.serve_engine import artifact as art_mod

BACKENDS = ("ref", "fused:force", "packed:force")


@pytest.fixture(scope="module")
def setup():
    cfg = configs.reduced(configs.get_config("llama3-8b"))
    cfg = dataclasses.replace(cfg, quant=QuantConfig(mode="none"))
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def specs(setup):
    cfg, _ = setup
    return {b: (p.r, p.b_x_tilde) for b, p in
            ((b, planner.plan_with_theory(planner.budget_from_bits(b),
                                          float(cfg.d_model)))
             for b in (2, 4, 6))}


@pytest.fixture(scope="module")
def ws(setup, specs):
    cfg, params = setup
    return serving.build_weight_store(
        params, cfg, specs, pack_planes=True,
        cache_bits={b: 4 for b in specs})


def _leaves(tree):
    return jax.tree_util.tree_leaves_with_path(tree)


# ---------------------------------------------------------------------------
# Views: zero-copy sharing + bit-equality vs materialization
# ---------------------------------------------------------------------------

def test_views_reference_store_leaves_by_identity(ws):
    """The zero-copy claim at the object level: every big leaf in a view
    IS the store's leaf — same array, same device buffer."""
    big = {"w_q", "w_planes_pos", "w_planes_neg", "w_scale", "b"}
    store_ids = {id(leaf) for _, leaf in _leaves(ws.store)}
    shared = 0
    for view in ws.views.values():
        for path, leaf in _leaves(view):
            if getattr(path[-1], "key", "") in big:
                assert id(leaf) in store_ids, path
                shared += 1
    assert shared > 0


def test_views_share_one_pytree_structure(ws):
    assert len({jax.tree_util.tree_structure(v)
                for v in ws.views.values()}) == 1


def test_narrow_rung_actually_shifts(ws):
    """The cross-rung tests below are vacuous unless at least one rung
    drops planes."""
    shifts = {rung: {float(np.asarray(leaf).reshape(-1)[0])
                     for path, leaf in _leaves(view)
                     if getattr(path[-1], "key", "") == "plane_shift"}
              for rung, view in ws.views.items()}
    assert max(max(s) for s in shifts.values() if s) > 0
    assert shifts[max(shifts)] == {0.0}     # top rung served exactly


@pytest.mark.parametrize("backend", BACKENDS)
def test_view_matches_materialized_per_module(setup, backend):
    """serving_linear over a plane-shifted VIEW == the same rung
    MATERIALIZED (codes re-quantized to the truncated values, planes
    re-packed, no plane_shift leaf) — per backend, bit-identical fp32."""
    cfg, _ = setup
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((64, 96)), jnp.float32)
    store = serving.build_weight_store({"wq": {"w": w}}, cfg,
                                       {2: (2.0, 8), 6: (16.0, 8)},
                                       pack_planes=True)
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    for rung, view in store.views.items():
        shift = float(np.asarray(view["wq"]["plane_shift"]).reshape(-1)[0])
        if rung == min(store.views):
            assert shift > 0            # the narrow rung must drop planes
        mat = serving.materialize_view(view)
        assert "plane_shift" not in mat["wq"]
        y_view = dispatch.serving_linear(x, view["wq"], backend)
        y_mat = dispatch.serving_linear(x, mat["wq"], backend)
        np.testing.assert_array_equal(np.asarray(y_view), np.asarray(y_mat))


@pytest.mark.parametrize("backend", ("ref", "packed:force"))
def test_full_decode_step_view_vs_materialized(setup, ws, backend):
    """The whole reduced llama3 decode step — every projection plus the
    4-bit quantized KV cache — is bit-identical serving a rung view vs
    that view materialized."""
    cfg, _ = setup
    cfg_q = dataclasses.replace(cfg, kernel_backend=backend, cache_bits=4)
    tok = jnp.zeros((1, 1), jnp.int32)
    for rung in (min(ws.views), max(ws.views)):
        view = ws.views[rung]
        mat = serving.materialize_view(view)
        lv, _ = MD.decode_step(
            view, cfg_q, MD.init_decode_state(view, cfg_q, 1, 4), tok)
        lm, _ = MD.decode_step(
            mat, cfg_q, MD.init_decode_state(mat, cfg_q, 1, 4), tok)
        np.testing.assert_array_equal(np.asarray(lv), np.asarray(lm))


# ---------------------------------------------------------------------------
# Truncation consistency (property-based)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 6), st.integers(0, 10_000))
def test_rung_codes_are_top_planes_of_max_codes(shift, seed):
    """The scheme's defining identity: the integer weights a shift-s view
    realizes (``masked_codes``) equal the reconstruction from ONLY the top
    planes (p >= s) of the max-R plane stacks, per sign."""
    rng = np.random.default_rng(seed)
    codes = jnp.asarray(rng.integers(-127, 128, (16, 8)), jnp.int32)
    planes_p = pann_core.bitplane_decompose(jnp.maximum(codes, 0), 7)
    planes_n = pann_core.bitplane_decompose(jnp.maximum(-codes, 0), 7)
    top = sum(((planes_p[p].astype(jnp.int32)
                - planes_n[p].astype(jnp.int32)) << p)
              for p in range(shift, 7))
    np.testing.assert_array_equal(
        np.asarray(pann_core.masked_codes(codes, shift)), np.asarray(top))


@settings(max_examples=20, deadline=None)
@given(st.floats(0.2, 120.0), st.floats(0.2, 120.0))
def test_view_shift_snaps_within_sqrt2(r_max, r):
    r = min(r, r_max)                   # rungs never exceed the store
    sh = pann_core.view_shift(r_max, r)
    assert 0 <= sh <= 6
    snapped = pann_core.snapped_r(r_max, sh)
    if sh < 6:                          # inside the clip, nearest-pow2 bound
        assert snapped / r < 2 ** 0.5 + 1e-9
    assert pann_core.view_shift(r_max, r_max) == 0
    assert pann_core.snapped_r(r_max, 0) == r_max


def test_view_shift_rejects_nonpositive_budgets():
    with pytest.raises(ValueError):
        pann_core.view_shift(0.0, 1.0)
    with pytest.raises(ValueError):
        pann_core.view_shift(4.0, -1.0)


# ---------------------------------------------------------------------------
# On-disk schema: round trip, zero-copy mmap, rejection
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def written(ws, tmp_path_factory):
    d = tmp_path_factory.mktemp("artifact")
    write_artifact(str(d), ws, meta={"note": "test"})
    return str(d)


def test_round_trip_bit_identical(ws, written):
    loaded = load_artifact(written)
    for orig, back in ((ws.store, loaded.store),
                       *((ws.views[r], loaded.views[r]) for r in ws.views)):
        fo, fb = _leaves(orig), _leaves(back)
        assert len(fo) == len(fb)
        for (po, lo), (pb, lb) in zip(fo, fb):
            assert po == pb
            assert np.asarray(lo).dtype == np.asarray(lb).dtype
            assert np.asarray(lo).shape == np.asarray(lb).shape
            np.testing.assert_array_equal(np.asarray(lo), np.asarray(lb))


def test_loaded_leaves_are_views_over_one_mmap(written):
    loaded = load_artifact(written)
    bases = set()
    for _, leaf in _leaves((loaded.store, loaded.views)):
        base = leaf
        while getattr(base, "base", None) is not None:
            base = base.base
        bases.add(id(base))
    assert len(bases) == 1              # every leaf windows ONE buffer
    # and ref leaves resolve to the SAME object as the store's, not a copy
    for view in loaded.views.values():
        for path, leaf in _leaves(view):
            if getattr(path[-1], "key", "") == "w_q":
                store_node = loaded.store
                for p in path[:-1]:
                    store_node = store_node[getattr(p, "key", getattr(
                        p, "idx", None))]
                assert leaf is store_node["w_q"]


def test_meta_round_trip(written):
    assert art_mod.read_meta(written)["note"] == "test"


def _copy_artifact(src, dst):
    os.makedirs(dst, exist_ok=True)
    for name in (art_mod.MANIFEST, art_mod.BLOB):
        with open(os.path.join(src, name), "rb") as f:
            data = f.read()
        with open(os.path.join(dst, name), "wb") as f:
            f.write(data)
    return dst


def test_rejects_version_skew(written, tmp_path):
    d = _copy_artifact(written, str(tmp_path / "skew"))
    mpath = os.path.join(d, art_mod.MANIFEST)
    with open(mpath) as f:
        man = json.load(f)
    man["version"] = art_mod.ARTIFACT_VERSION + 1
    with open(mpath, "w") as f:
        json.dump(man, f)
    with pytest.raises(ArtifactError, match="version"):
        load_artifact(d)


def test_rejects_wrong_magic(written, tmp_path):
    d = _copy_artifact(written, str(tmp_path / "magic"))
    mpath = os.path.join(d, art_mod.MANIFEST)
    with open(mpath) as f:
        man = json.load(f)
    man["magic"] = "not-a-weight-store"
    with open(mpath, "w") as f:
        json.dump(man, f)
    with pytest.raises(ArtifactError, match="magic"):
        load_artifact(d)


def test_rejects_truncated_blob(written, tmp_path):
    d = _copy_artifact(written, str(tmp_path / "trunc"))
    bpath = os.path.join(d, art_mod.BLOB)
    with open(bpath, "rb") as f:
        data = f.read()
    with open(bpath, "wb") as f:
        f.write(data[:len(data) // 2])
    with pytest.raises(ArtifactError):
        load_artifact(d)


def test_rejects_missing_manifest(tmp_path):
    with pytest.raises(ArtifactError):
        load_artifact(str(tmp_path))


# ---------------------------------------------------------------------------
# Engine integration: one store behind the ladder
# ---------------------------------------------------------------------------

def test_engine_views_only_legacy_retired(setup):
    """The engine's rung views alias ONE store's big leaves: each code and
    plane tensor is held once, whatever the ladder depth."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, ladder_bits=(2, 6), max_batch=1,
                      max_len=12)
    store_ids = {id(leaf) for _, leaf in _leaves(eng.weight_store)}
    big = {"w_q", "w_scale"} | set(dispatch.PLANE_KEYS)
    n = 0
    for view in eng.variants.values():
        for path, leaf in _leaves(view):
            if getattr(path[-1], "key", "") in big:
                assert id(leaf) in store_ids, jax.tree_util.keystr(path)
                n += 1
    assert n > 0 and len(eng.variants) == 2


def test_engine_serves_loaded_artifact_bit_identically(setup, ws, written):
    """ROADMAP item 5 end-to-end: ``ServeEngine(weight_store=
    load_artifact(dir))`` serves WITHOUT re-quantizing, and its decode
    stream is bit-identical to an engine built over the in-memory store."""
    cfg, params = setup
    cfg_q = dataclasses.replace(cfg, quant=QuantConfig(mode="none"))
    prompt = np.arange(5, dtype=np.int32)
    schedule = [(2, 3), (6, 3)]

    def run(store):
        eng = ServeEngine(cfg_q, weight_store=store, ladder_bits=(2, 6),
                          max_batch=1, max_len=16, cache_bits=4)
        eng.warmup()
        out = eng.decode_stream(prompt, schedule)
        eng.assert_no_recompile()
        return out

    mem = run(ws)
    loaded = run(load_artifact(written))
    assert mem["tokens"] == loaded["tokens"]
    assert mem["segments"] == loaded["segments"]


def test_engine_views_no_recompile_mixed_weight_cache_ladder(setup):
    """The §11 acceptance claim: with views, a mixed weight-rung x
    cache-rung ladder still decodes through ONE compiled step, and the
    views really do share the store's code arrays on device."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, ladder_bits=(2, 4, 6), max_batch=2,
                      max_len=20, cache_bits="auto")
    eng.warmup()
    assert eng.compilations_after_warmup == 1
    assert len(set(eng._cache_bits_by_rung.values())) > 1
    from repro.serve_engine import Request
    reqs = [Request(uid=i, prompt=np.arange(6, dtype=np.int32),
                    max_new_tokens=3, power_budget_bits=b)
            for i, b in enumerate((2, 4, 6))]
    eng.generate(reqs)
    eng.assert_no_recompile()
    ids = [{id(leaf) for path, leaf in _leaves(v)
            if getattr(path[-1], "key", "") == "w_q"}
           for v in eng.variants.values()]
    assert ids[0] == ids[1] == ids[2]   # one code tensor per module, shared
