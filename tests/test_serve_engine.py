"""repro.serve_engine: ladder planning, rung views, per-request rung
selection, mid-stream rung switching, and the no-recompilation claim."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import QuantConfig
from repro.core import planner
from repro.models import model as MD
from repro.models.serving import quantize_params_for_serving
from repro.serve_engine import (Request, Scheduler, ServeEngine, build_ladder,
                                select_rung)

LADDER_BITS = (2, 4, 6)


@pytest.fixture(scope="module")
def setup():
    cfg = configs.reduced(configs.get_config("llama3-8b"))
    cfg = dataclasses.replace(cfg, quant=QuantConfig(mode="none"))
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def engine(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, ladder_bits=LADDER_BITS, max_batch=2,
                      max_len=28)
    eng.warmup()
    return eng


def _prompt(seed=0, n=8, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


# ---------------------------------------------------------------------------
# Ladder planning
# ---------------------------------------------------------------------------

def test_ladder_planning_deterministic():
    a = build_ladder(LADDER_BITS, d=64.0)
    b = build_ladder(list(reversed(LADDER_BITS)), d=64.0)
    assert a == b                      # pure function of (bits, d), any order
    plans = planner.plan_ladder(LADDER_BITS, d=64.0)
    assert [p.power_budget for p in plans] == sorted(p.power_budget
                                                     for p in plans)
    for op, plan in zip(a, plans):
        assert (op.b_x_tilde, op.r) == (plan.b_x_tilde, plan.r)


def test_ladder_matches_equal_power_budget():
    for op in build_ladder(LADDER_BITS, d=64.0):
        assert op.power == planner.budget_from_bits(op.bits)
        # the planned point sits on the rung's equal-power curve (Fig. 3)
        curve = dict(planner.equal_power_curve(op.bits))
        assert op.b_x_tilde in curve
        assert curve[op.b_x_tilde] == pytest.approx(op.r)


# ---------------------------------------------------------------------------
# Variant cache
# ---------------------------------------------------------------------------

def _without_plane_shift(tree):
    return [(p, leaf) for p, leaf in jax.tree_util.tree_leaves_with_path(tree)
            if getattr(p[-1], "key", "") != "plane_shift"]


def test_variant_cache_bit_exact(setup):
    """The engine's top-rung view (the max budget: no plane dropped) is
    the single-point artifact at that point, leaf for leaf."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, ladder_bits=LADDER_BITS, max_batch=2,
                      max_len=28, backend="ref")
    assert sorted(eng.variants) == sorted(op.bits for op in eng.ladder)
    top = max(eng.ladder, key=lambda op: op.r)
    direct = quantize_params_for_serving(params, cfg, r=top.r,
                                         act_bits=top.b_x_tilde)
    flat_v = _without_plane_shift(eng.variants[top.bits])
    flat_d = jax.tree_util.tree_leaves_with_path(direct)
    assert len(flat_v) == len(flat_d)
    for (pv, lv), (pd, ld) in zip(flat_v, flat_d):
        assert pv == pd
        assert lv.dtype == ld.dtype
        np.testing.assert_array_equal(np.asarray(lv), np.asarray(ld))


def test_variants_share_pytree_structure(engine):
    treedefs = {jax.tree_util.tree_structure(v)
                for v in engine.variants.values()}
    assert len(treedefs) == 1          # why one jit compilation covers all


def test_variants_carry_per_rung_act_bits(engine):
    """b~x is data in the variant, so rungs differ in BOTH (b~x, R)."""
    def act_ns(tree):
        vals = set()
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            if getattr(path[-1], "key", "") == "act_n":
                vals.update(np.asarray(leaf).reshape(-1).tolist())
        return vals

    for op in engine.ladder:
        ns = act_ns(engine.variants[op.bits])
        assert ns == {float((1 << op.b_x_tilde) - 1)}


# ---------------------------------------------------------------------------
# Per-request rung selection
# ---------------------------------------------------------------------------

def test_select_rung_power_budget():
    ladder = build_ladder(LADDER_BITS, d=64.0)
    assert select_rung(ladder, power_budget_bits=6).bits == 6
    assert select_rung(ladder, power_budget_bits=5).bits == 4   # best <= 5
    assert select_rung(ladder, power_budget_bits=2).bits == 2
    assert select_rung(ladder, power_budget_bits=1).bits == 2   # clamped up
    assert select_rung(ladder).bits == 6                        # default: top


def test_select_rung_accuracy_floor():
    ladder = build_ladder(LADDER_BITS, d=64.0)
    scores = {op.bits: op.score for op in ladder}
    assert scores[2] < scores[4] < scores[6]    # -MSE rises with power
    # cheapest rung meeting the floor
    assert select_rung(ladder, min_score=scores[2]).bits == 2
    assert select_rung(ladder, min_score=scores[4]).bits == 4
    # unattainable floor -> best available
    assert select_rung(ladder, min_score=scores[6] + 1.0).bits == 6


def test_select_rung_honors_both_constraints():
    ladder = build_ladder(LADDER_BITS, d=64.0)
    scores = {op.bits: op.score for op in ladder}
    # cheapest rung meeting the floor within the budget
    sel = select_rung(ladder, power_budget_bits=6, min_score=scores[4])
    assert sel.bits == 4
    # floor unreachable within the budget -> refuse, never silently violate
    with pytest.raises(ValueError, match="power budget"):
        select_rung(ladder, power_budget_bits=2, min_score=scores[6])


def test_scheduler_routes_and_batches():
    ladder = build_ladder(LADDER_BITS, d=64.0)
    sched = Scheduler(ladder, max_batch=2)
    for i, bits in enumerate([4, 4, 2, 4]):
        sched.submit(Request(uid=i, prompt=_prompt(i),
                             power_budget_bits=bits))
    waves = []
    while sched.pending():
        waves.append(sched.next_wave())
    got = [(w.rung.bits, [r.uid for r in w.requests]) for w in waves]
    # max_batch=2 splits the three 4-bit requests; the 2-bit one interleaves
    assert (4, [0, 1]) in got and (2, [2]) in got and (4, [3]) in got


def test_generate_selects_rung_per_request(engine):
    budgets = [2, 6, 4, 2]
    reqs = [Request(uid=i, prompt=_prompt(1), max_new_tokens=4,
                    power_budget_bits=b) for i, b in enumerate(budgets)]
    resps = engine.generate(reqs)
    assert [r.uid for r in resps] == [0, 1, 2, 3]
    assert [r.rung_bits for r in resps] == budgets
    for r in resps:
        assert len(r.tokens) == 4
        assert r.metadata["b_x_tilde"] == engine.rungs[r.rung_bits].b_x_tilde
    # energy metadata orders with the rung's power
    per_tok = {r.rung_bits: r.metadata["est_bitflips_per_token"]
               for r in resps}
    assert per_tok[2] < per_tok[4] < per_tok[6]


# ---------------------------------------------------------------------------
# Rung switching without re-quantization / recompilation
# ---------------------------------------------------------------------------

def test_no_recompile_across_rungs(engine):
    assert engine.compilations_after_warmup == 1
    reqs = [Request(uid=i, prompt=_prompt(2), max_new_tokens=4,
                    power_budget_bits=b) for i, b in enumerate(LADDER_BITS)]
    engine.generate(reqs)
    engine.assert_no_recompile()
    assert engine.rung_switches > 0


def test_generate_rejects_oversized_requests_upfront(engine):
    ok = Request(uid=0, prompt=_prompt(4), max_new_tokens=4,
                 power_budget_bits=2)
    too_big = Request(uid=1, prompt=_prompt(4), max_new_tokens=1000,
                      power_budget_bits=2)
    with pytest.raises(ValueError, match="max_len"):
        engine.generate([ok, too_big])
    assert engine.scheduler.pending() == 0    # nothing was half-admitted
    assert len(engine.generate([ok])) == 1    # engine still serves


def test_generate_rejects_infeasible_constraints_upfront(engine):
    ok = Request(uid=0, prompt=_prompt(4), max_new_tokens=4,
                 power_budget_bits=2)
    infeasible = Request(uid=1, prompt=_prompt(4), max_new_tokens=4,
                         power_budget_bits=2, min_score=1e9)
    with pytest.raises(ValueError, match="power budget"):
        engine.generate([ok, infeasible])
    # the ok request must not be stranded in the queue and served (and
    # billed) inside a later, unrelated generate() call
    assert engine.scheduler.pending() == 0
    later = engine.generate([Request(uid=7, prompt=_prompt(4),
                                     max_new_tokens=4,
                                     power_budget_bits=2)])
    assert [r.uid for r in later] == [7]


def test_encdec_frontend_quantized_at_serving_rung():
    """For encdec, init_decode_state runs the encoder + cross-K/V projections
    through the variant — so different rungs must produce different states."""
    import jax.numpy as jnp
    from repro.data.pipeline import frontend_stub
    cfg = configs.reduced(configs.get_config("seamless-m4t-medium"))
    cfg = dataclasses.replace(cfg, quant=QuantConfig(mode="none"))
    params = MD.init_params(jax.random.PRNGKey(0), cfg)

    def fe_fn(batch):
        return {"enc_inputs": jnp.asarray(frontend_stub(cfg, batch, 0, 0))}

    eng = ServeEngine(cfg, params, ladder_bits=(2, 6), max_batch=2,
                      max_len=16, frontend_kwargs_fn=fe_fn)
    lo = eng._init_state(2)
    hi = eng._init_state(6)
    diff = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32)).max()),
        lo.cross_kv, hi.cross_kv)
    assert max(jax.tree_util.tree_leaves(diff)) > 0.0


def test_midstream_switch_matches_fresh_server(setup, engine):
    cfg, params = setup
    prompt = _prompt(3, n=8)
    out = engine.decode_stream(prompt, [(2, 4), (6, 4)])
    assert len(out["tokens"]) == 8
    seg1, seg2 = out["segments"]
    assert (seg1["rung_bits"], seg2["rung_bits"]) == (2, 6)

    # a FRESH server at the target rung, given the same prefix, must produce
    # the identical continuation
    fresh = ServeEngine(cfg, params, ladder_bits=LADDER_BITS, max_batch=2,
                        max_len=28)
    fresh.warmup()
    prefix = np.concatenate([prompt, np.asarray(seg1["tokens"], np.int32)])
    fresh_out = fresh.decode_stream(prefix, [(6, 4)])
    assert fresh_out["tokens"] == seg2["tokens"]
    engine.assert_no_recompile()


def test_no_recompile_mixed_weight_and_cache_rungs(setup):
    """ONE jitted decode step serves a mixed weight-rung x cache-rung
    ladder: cache_bits='auto' gives every rung its own cache width
    (k_nlvl/v_nlvl DATA leaves), and the packed-plane cache layout is
    pinned at 7 planes — so serving traffic across all rungs must not add
    a single compilation past warmup."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, ladder_bits=LADDER_BITS, max_batch=2,
                      max_len=28, cache_bits="auto")
    eng.warmup()
    assert eng.compilations_after_warmup == 1
    # the rungs really do carry DIFFERENT cache widths (mixed ladder)
    assert len(set(eng._cache_bits_by_rung.values())) > 1
    reqs = [Request(uid=i, prompt=_prompt(7), max_new_tokens=4,
                    power_budget_bits=b) for i, b in enumerate(LADDER_BITS)]
    resps = eng.generate(reqs)
    eng.assert_no_recompile()
    assert eng.rung_switches > 0
    for r in resps:
        cb = r.metadata["cache_bits"]
        assert set(cb) == {"attn.k_cache", "attn.v_cache"}
        # the response itemizes the cache's own bit-flip spend
        assert r.metadata["per_module_gbitflips_per_token"][
            "attn.k_cache"] > 0


def test_midstream_switch_with_quantized_cache_matches_fresh_server(setup):
    """The rung-switch replay contract survives cache quantization: a
    switch re-encodes the prefix's cache codes from scratch at the target
    rung's width, so the continuation is bit-identical to a fresh server
    at that rung — quantized cache and all."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, ladder_bits=LADDER_BITS, max_batch=2,
                      max_len=28, cache_bits="auto")
    eng.warmup()
    prompt = _prompt(8, n=8)
    out = eng.decode_stream(prompt, [(2, 4), (6, 4)])
    seg1, seg2 = out["segments"]

    fresh = ServeEngine(cfg, params, ladder_bits=LADDER_BITS, max_batch=2,
                        max_len=28, cache_bits="auto")
    fresh.warmup()
    prefix = np.concatenate([prompt, np.asarray(seg1["tokens"], np.int32)])
    fresh_out = fresh.decode_stream(prefix, [(6, 4)])
    assert fresh_out["tokens"] == seg2["tokens"]
    eng.assert_no_recompile()


def test_decode_stream_zero_length_segment(engine):
    prompt = _prompt(5, n=8)
    out = engine.decode_stream(prompt, [(2, 0), (6, 3)])
    assert len(out["tokens"]) == 3            # zero segments emit no tokens
    assert out["segments"][0]["tokens"] == []
    assert len(out["segments"][1]["tokens"]) == 3


def test_decode_stream_rejects_unknown_rung_upfront(engine):
    before = dict(engine.steps_by_rung)
    with pytest.raises(KeyError, match="no rung"):
        engine.decode_stream(_prompt(6, n=8), [(2, 4), (5, 4)])
    # validation happens before any decode work, so no steps were burned
    assert engine.steps_by_rung == before


# ---------------------------------------------------------------------------
# Family and mesh coverage
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["rwkv6-1.6b", "zamba2-1.2b"])
def test_ladder_serving_recurrent_families(arch):
    """The act_n path must survive rwkv/mamba decode bodies, not just
    attention projections."""
    cfg = configs.reduced(configs.get_config(arch))
    cfg = dataclasses.replace(cfg, quant=QuantConfig(mode="none"))
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, ladder_bits=(2, 6), max_batch=2,
                      max_len=12)
    eng.warmup()
    reqs = [Request(uid=i, prompt=_prompt(6), max_new_tokens=4,
                    power_budget_bits=b) for i, b in enumerate((2, 6))]
    resps = eng.generate(reqs)
    assert [r.rung_bits for r in resps] == [2, 6]
    assert all(len(r.tokens) == 4 for r in resps)
    eng.assert_no_recompile()


def test_variant_cache_mesh_sharded():
    """DESIGN.md §6's 'sharded like training params' claim, on a real
    (2, 4) mesh in an 8-device subprocess (multidev pattern)."""
    from test_dist_multidev import run_py
    r = run_py("""
        import dataclasses, json
        import jax, jax.numpy as jnp
        import numpy as np
        from repro import configs
        from repro.configs.base import QuantConfig
        from repro.launch.mesh import make_mesh
        from repro.models import model as MD
        from repro.models.serving import (build_weight_store,
                                          quantize_params_for_serving)

        cfg = configs.reduced(configs.get_config("llama3-8b"))
        cfg = dataclasses.replace(cfg, quant=QuantConfig(mode="none"))
        params = MD.init_params(jax.random.PRNGKey(0), cfg)
        mesh = make_mesh((2, 4), ("data", "model"))
        ws = build_weight_store(params, cfg, {2: (2.83, 3)}, mesh=mesh)
        direct = quantize_params_for_serving(params, cfg, r=2.83, act_bits=3)

        n_wq = n_wq_sharded = 0
        exact = True
        repl_ok = True
        view = [(p, x) for p, x in
                jax.tree_util.tree_leaves_with_path(ws.views[2])
                if getattr(p[-1], "key", "") != "plane_shift"]
        flat_d = jax.tree_util.tree_leaves_with_path(direct)
        exact &= len(view) == len(flat_d)
        for (path, leaf), (path_d, ref) in zip(view, flat_d):
            key = getattr(path[-1], "key", "")
            exact &= path == path_d
            exact &= bool(np.array_equal(np.asarray(leaf), np.asarray(ref)))
            if key == "w_q":
                n_wq += 1
                n_wq_sharded += int(any(leaf.sharding.spec))
            if key in ("w_scale", "act_n"):
                repl_ok &= not any(leaf.sharding.spec)
        print(json.dumps({"n_wq": n_wq, "n_wq_sharded": n_wq_sharded,
                          "exact": exact, "repl_ok": repl_ok}))
    """)
    assert r["n_wq"] > 0 and r["n_wq_sharded"] == r["n_wq"]
    assert r["exact"]          # sharding never changes the codes
    assert r["repl_ok"]        # scales and act_n replicated
