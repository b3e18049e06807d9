"""Per-module QuantPolicy trees and the layer-wise power-budget allocator.

Covers the PR's acceptance criteria:
  * allocator invariants (property-tested): total power <= budget, within
    1% of the matched uniform plan, theory score never worse than uniform;
  * `uniform_policy(qc)` forwards are bit-exact with the pre-policy path;
  * the layerwise serving ladder runs end to end through ONE jitted decode
    step with per-rung power parity and score dominance over uniform.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs
from repro.configs.base import QuantConfig
from repro.core import costs, planner
from repro.core import policy as pol
from repro.core import power as pw
from repro.models import model as MD
from repro.models.serving import quantize_params_for_serving
from repro.serve_engine import Request, ServeEngine


# ---------------------------------------------------------------------------
# PolicyTree semantics
# ---------------------------------------------------------------------------

def test_policy_tree_lookup_prefix_and_default():
    base = pol.ModuleQuant(mode="pann", r=2.0, b_x_tilde=4)
    fine = pol.ModuleQuant(mode="pann", r=8.0, b_x_tilde=6)
    coarse = pol.ModuleQuant(mode="pann", r=1.0, b_x_tilde=3)
    tree = pol.policy_tree(base, {"attn.wq": fine, "mlp": coarse})
    assert tree.lookup("attn.wq") is fine          # exact
    assert tree.lookup("mlp.w_down") is coarse     # dotted prefix
    assert tree.lookup("mlp.w_up") is coarse
    assert tree.lookup("attn.wk") is base          # no match -> default
    assert tree.lookup("lm_head") is base


def test_module_quant_aliases_match_quant_config():
    qc = QuantConfig(mode="ruq", weight_bits=5, act_bits=6, r=3.0,
                     act_bits_tilde=7, acc_bits=24)
    mq = pol.as_module_quant(qc)
    assert (mq.weight_bits, mq.act_bits, mq.act_bits_tilde) == (5, 6, 7)
    assert (mq.b_w, mq.b_x, mq.b_x_tilde) == (5, 6, 7)
    assert mq.acc_bits == 24 and mq.r == 3.0 and mq.mode == "ruq"


def test_serving_path_mapping():
    assert pol.serving_path(("decoder", "groups", "layers", "attn",
                             "wq")) == "attn.wq"
    assert pol.serving_path(("xattn", "wk")) == "attn.wk"
    assert pol.serving_path(("shared_attn", "mlp", "w_up")) == "mlp.w_up"
    assert pol.serving_path(("tail", "tm", "decay_b")) == "rwkv.tm.decay_b"
    assert pol.serving_path(("cm", "wv")) == "rwkv.cm.wv"
    assert pol.serving_path(("ssm", "in_proj")) == "ssm.in_proj"
    assert pol.serving_path(("lm_head",)) == "lm_head"


# ---------------------------------------------------------------------------
# uniform_policy(qc) is bit-exact with the pre-policy path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["llama3-8b", "mixtral-8x7b", "rwkv6-1.6b",
                                  "zamba2-1.2b"])
@pytest.mark.parametrize("mode", ["ruq", "pann"])
def test_uniform_policy_bit_exact(arch, mode):
    qc = QuantConfig(mode=mode, weight_bits=8, act_bits=8, r=4.0,
                     act_bits_tilde=8)
    cfg = dataclasses.replace(configs.reduced(configs.get_config(arch)),
                              quant=qc)
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 8)), jnp.int32)
    plain = MD.forward(params, cfg, tokens, remat=False).logits
    lifted = MD.forward(params, dataclasses.replace(
        cfg, policy=pol.uniform_policy(qc)), tokens, remat=False).logits
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(lifted))


# ---------------------------------------------------------------------------
# Allocator invariants (property test, hypothesis / vendored stub)
# ---------------------------------------------------------------------------

_PROFILES = {arch: costs.module_cost_profile(configs.get_config(arch))
             for arch in ("llama3-8b", "mixtral-8x7b", "rwkv6-1.6b",
                          "zamba2-1.2b", "seamless-m4t-medium")}


@settings(max_examples=40, deadline=None)
@given(st.floats(min_value=pw.p_mac_unsigned(2),
                 max_value=pw.p_mac_unsigned(8)),
       st.sampled_from(sorted(_PROFILES)))
def test_allocator_invariants(power_budget, arch):
    """For ANY budget and architecture: the layerwise plan's total network
    power never exceeds the budget, lands within 1% of the matched uniform
    plan's total (they are equal to float precision by the R-fill), and its
    theory score never trails the uniform tree's."""
    profile = _PROFILES[arch]
    lw = planner.allocate_layerwise(power_budget, profile)
    budget_total = power_budget * lw.total_macs
    assert lw.total_power <= budget_total * (1 + 1e-9)
    assert abs(lw.total_power - budget_total) <= 0.01 * budget_total
    assert lw.score >= lw.uniform_score - 1e-12
    # the recomputed scores agree with the plan's record
    assert pol.tree_theory_score(profile, lw.tree) == \
        pytest.approx(lw.score)
    assert pol.tree_theory_score(profile, lw.uniform_tree) == \
        pytest.approx(lw.uniform_score)


def test_allocator_beats_uniform_on_heterogeneous_fanins():
    """Real architectures have heterogeneous fan-ins, so the greedy spend
    should deliver a STRICT score improvement (not just the guarantee)."""
    for arch in ("llama3-8b", "rwkv6-1.6b", "zamba2-1.2b"):
        lw = planner.allocate_layerwise(planner.budget_from_bits(4),
                                        _PROFILES[arch])
        assert lw.score > lw.uniform_score, arch


def test_allocator_raises_below_floor_and_on_empty_profile():
    with pytest.raises(ValueError, match="too small|below the cheapest"):
        planner.allocate_layerwise(1.0, _PROFILES["llama3-8b"])
    with pytest.raises(ValueError, match="empty"):
        planner.allocate_layerwise(24.0, ())


def test_allocator_eval_backend_mirrors_plan_with_eval():
    """eval_fn(tree) scores both candidate trees; a judge that prefers the
    uniform tree must make the allocator return it (same contract as
    Algorithm 1's eval backend: measurements outrank theory)."""
    profile = _PROFILES["llama3-8b"]
    uni = planner.allocate_layerwise(24.0, profile).uniform_tree

    def prefers_uniform(tree):
        return 1.0 if tree == uni else 0.0

    lw = planner.allocate_layerwise(24.0, profile,
                                    eval_fn=prefers_uniform)
    assert lw.tree == uni and lw.score == 1.0


def test_plan_ladder_layerwise_axis():
    profile = _PROFILES["llama3-8b"]
    plans = planner.plan_ladder((2, 4, 6), allocation="layerwise",
                                profile=profile)
    assert [p.power_budget for p in plans] == \
        [planner.budget_from_bits(b) for b in (2, 4, 6)]
    assert all(isinstance(p, planner.LayerwisePlan) for p in plans)
    with pytest.raises(ValueError, match="profile"):
        planner.plan_ladder((2, 4), allocation="layerwise")
    with pytest.raises(ValueError, match="allocation"):
        planner.plan_ladder((2, 4), allocation="magic")
    # the per-(b~x, R) eval backend cannot score a tree: rejected loudly,
    # never silently dropped (build_ladder relies on this too)
    with pytest.raises(ValueError, match="allocate_layerwise"):
        planner.plan_ladder((2, 4), eval_fn=lambda b, r: 1.0,
                            allocation="layerwise", profile=profile)


def test_launch_serve_rejects_allocation_without_ladder():
    from repro.launch import serve as serve_launch
    with pytest.raises(SystemExit, match="power_ladder"):
        serve_launch.main(["--arch", "llama3-8b", "--reduced",
                           "--allocation", "layerwise", "--gen", "2",
                           "--prompt_len", "2", "--batch", "1"])


# ---------------------------------------------------------------------------
# Eq. 20 accumulator widths flow into the profile and the trees
# ---------------------------------------------------------------------------

def test_module_costs_use_eq20_acc_bits():
    """core/costs.py sizes accumulators by Eq. 20 per layer — not the
    global 32-bit default — wherever the fan-in permits."""
    profile = costs.module_cost_profile(configs.get_config("llama3-8b"))
    for m in profile:
        want = min(pw.DEFAULT_ACC_BITS,
                   pw.required_acc_bits(8, 8, m.fan_in))
        assert m.acc_bits(8, 8) == want
        # llama3 fan-ins (4096 / 14336) all permit narrower-than-32
        assert m.acc_bits(8, 8) < pw.DEFAULT_ACC_BITS
    # huge synthetic fan-in caps at the hardware default
    wide = costs.ModuleCost(path="x", macs=1.0, fan_in=1 << 40)
    assert wide.acc_bits(16, 16) == pw.DEFAULT_ACC_BITS


def test_allocator_trees_carry_eq20_acc_bits():
    profile = _PROFILES["rwkv6-1.6b"]
    lw = planner.allocate_layerwise(planner.budget_from_bits(4), profile)
    for m in profile:
        mq = lw.tree.lookup(m.path)
        want = min(pw.DEFAULT_ACC_BITS,
                   pw.required_acc_bits(mq.b_x_tilde, mq.b_w, m.fan_in))
        assert mq.acc_bits == want
    # the 64-fan-in decay_b head needs a much narrower accumulator than
    # the 7168-fan-in channel-mix down-projection
    narrow = lw.tree.lookup("rwkv.tm.decay_b").acc_bits
    wide = lw.tree.lookup("rwkv.cm.wv").acc_bits
    assert narrow < wide


# ---------------------------------------------------------------------------
# Layerwise serving ladder, end to end
# ---------------------------------------------------------------------------

LADDER_BITS = (2, 4, 6)


@pytest.fixture(scope="module")
def lw_engine():
    cfg = configs.reduced(configs.get_config("llama3-8b"))
    cfg = dataclasses.replace(cfg, quant=QuantConfig(mode="none"))
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, ladder_bits=LADDER_BITS, max_batch=2,
                      max_len=24, allocation="layerwise")
    eng.warmup()
    return eng


def test_layerwise_rungs_match_uniform_power_and_dominate_score(lw_engine):
    """Each layerwise rung spends the same total bit-flip budget as its
    uniform twin (within 1%) and never scores below it."""
    profile = lw_engine.profile
    total_macs = sum(m.macs for m in profile)
    for op in lw_engine.ladder:
        assert op.allocation == "layerwise" and op.tree is not None
        lw_total, _ = pol.tree_power_per_token(profile, op.tree)
        uni_total = op.power * total_macs
        assert abs(lw_total - uni_total) <= 0.01 * uni_total
        assert pol.tree_theory_score(profile, op.tree) >= \
            pol.tree_theory_score(profile, op.lw.uniform_tree) - 1e-12


def test_layerwise_ladder_one_compilation(lw_engine):
    """All layerwise rungs share ONE compiled decode step, and serving
    mixed-budget traffic across them never retraces."""
    assert lw_engine.compilations_after_warmup == 1
    prompt = np.random.default_rng(0).integers(0, 512, 8).astype(np.int32)
    reqs = [Request(uid=i, prompt=prompt, max_new_tokens=4,
                    power_budget_bits=b) for i, b in enumerate(LADDER_BITS)]
    resps = lw_engine.generate(reqs)
    lw_engine.assert_no_recompile()
    assert [r.rung_bits for r in resps] == list(LADDER_BITS)
    for r in resps:
        assert r.metadata["allocation"] == "layerwise"
        share = r.metadata["per_module_share"]
        assert share and sum(share.values()) == pytest.approx(1.0, abs=0.01)
        # headline number equals the itemized breakdown for layerwise rungs
        per_mod = r.metadata["per_module_gbitflips_per_token"]
        assert sum(per_mod.values()) * 1e9 == \
            pytest.approx(r.metadata["est_bitflips_per_token"], rel=1e-6)
    # energy still orders with the rung
    per_tok = {r.rung_bits: r.metadata["est_bitflips_per_token"]
               for r in resps}
    assert per_tok[2] < per_tok[4] < per_tok[6]


def test_layerwise_variant_structure_matches_uniform(lw_engine):
    """A layerwise variant has the SAME pytree structure and avals as a
    uniform one — why one jit compilation covers both allocations — while
    its act_n leaves actually differ per module."""
    cfg = lw_engine.cfg
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    op = lw_engine.ladder[-1]
    v_lw = quantize_params_for_serving(params, cfg, policy=op.tree)
    v_uni = quantize_params_for_serving(params, cfg, r=op.r,
                                        act_bits=op.b_x_tilde)
    assert jax.tree_util.tree_structure(v_lw) == \
        jax.tree_util.tree_structure(v_uni)
    for a, b in zip(jax.tree_util.tree_leaves(v_lw),
                    jax.tree_util.tree_leaves(v_uni)):
        assert a.shape == b.shape and a.dtype == b.dtype

    # the tree genuinely differentiates modules at this rung (distinct R)
    assert len({round(mq.r, 4) for _, mq in op.tree.items()}) > 1


def _act_ns(tree):
    vals = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if getattr(path[-1], "key", "") == "act_n":
            vals.update(np.asarray(leaf).reshape(-1).tolist())
    return vals


def test_layerwise_variant_mixes_act_bits_per_module():
    """Where the allocator assigns different b~x per module (zamba2's
    heterogeneous fan-ins even when reduced), the serving artifact carries
    per-module act_n values — as DATA, so the one-jit invariant holds."""
    cfg = configs.reduced(configs.get_config("zamba2-1.2b"))
    cfg = dataclasses.replace(cfg, quant=QuantConfig(mode="none"))
    profile = costs.module_cost_profile(cfg)
    lw = planner.allocate_layerwise(planner.budget_from_bits(2), profile)
    assert len({mq.b_x_tilde for _, mq in lw.tree.items()}) > 1
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    v_lw = quantize_params_for_serving(params, cfg, policy=lw.tree)
    v_uni = quantize_params_for_serving(params, cfg, r=2.0, act_bits=4)
    assert len(_act_ns(v_lw)) > 1
    assert len(_act_ns(v_uni)) == 1
    assert jax.tree_util.tree_structure(v_lw) == \
        jax.tree_util.tree_structure(v_uni)


@pytest.mark.parametrize("arch", ["rwkv6-1.6b", "zamba2-1.2b"])
def test_layerwise_serving_recurrent_families(arch):
    cfg = configs.reduced(configs.get_config(arch))
    cfg = dataclasses.replace(cfg, quant=QuantConfig(mode="none"))
    params = MD.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, ladder_bits=(2, 6), max_batch=2,
                      max_len=12, allocation="layerwise")
    eng.warmup()
    prompt = np.random.default_rng(1).integers(0, 512, 6).astype(np.int32)
    resps = eng.generate([Request(uid=i, prompt=prompt, max_new_tokens=4,
                                  power_budget_bits=b)
                          for i, b in enumerate((2, 6))])
    assert [r.rung_bits for r in resps] == [2, 6]
    eng.assert_no_recompile()


def test_launch_serve_layerwise_cli():
    """The acceptance-criterion entry point: --power_ladder --allocation
    layerwise serves every rung in one process (assert_no_recompile runs
    inside serve_ladder)."""
    from repro.launch import serve as serve_launch
    out = serve_launch.main([
        "--arch", "llama3-8b", "--reduced", "--power_ladder", "2,4",
        "--allocation", "layerwise", "--budgets", "2,4", "--batch", "2",
        "--prompt_len", "4", "--gen", "4"])
    assert out["engine"]["allocation"] == "layerwise"
    assert out["engine"]["compilations_after_warmup"] == 1
    assert {r["rung_bits"] for r in out["requests"]} == {2, 4}
    for r in out["requests"]:
        assert r["allocation"] == "layerwise"
        assert r["per_module_share"]
