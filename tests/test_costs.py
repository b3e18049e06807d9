"""core/costs.py: analytic parameter counts vs actual initialized trees."""
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core import costs
from repro.models import model as MD


def _actual_params(cfg) -> int:
    params = jax.eval_shape(
        lambda k: MD.init_params(k, cfg), jax.random.PRNGKey(0))
    return sum(int(jnp.prod(jnp.asarray(l.shape)))
               for l in jax.tree_util.tree_leaves(params))


@pytest.mark.parametrize("arch", ["llama3-8b", "mixtral-8x7b", "gemma2-9b",
                                  "zamba2-1.2b", "rwkv6-1.6b",
                                  "seamless-m4t-medium",
                                  "llama-3.2-vision-90b"])
def test_param_count_matches_init(arch):
    cfg = configs.reduced(configs.get_config(arch))
    analytic = costs.param_count(cfg)
    actual = _actual_params(cfg)
    # analytic ignores norms / tiny vectors; must be within 5%
    assert analytic == pytest.approx(actual, rel=0.05), (analytic, actual)


def test_known_full_sizes():
    """Full configs land near their nameplate sizes."""
    cases = {
        "llama3-8b": (8.0e9, 0.1),
        "mixtral-8x7b": (46.7e9, 0.1),
        "dbrx-132b": (132e9, 0.12),
        "llama-3.2-vision-90b": (90e9, 0.25),  # includes cross-attn layers
        "rwkv6-1.6b": (1.6e9, 0.01),    # the published block's matrices
        "zamba2-1.2b": (1.2e9, 0.35),
    }
    for arch, (want, tol) in cases.items():
        got = costs.param_count(configs.get_config(arch))
        assert got == pytest.approx(want, rel=tol), (arch, got)


def test_active_params_moe():
    dbrx = configs.get_config("dbrx-132b")
    total = costs.param_count(dbrx)
    active = costs.param_count(dbrx, active_only=True)
    assert active < 0.4 * total
    # dbrx-base quotes 36B active
    assert active == pytest.approx(36e9, rel=0.15)


def test_model_flops_conventions():
    cfg = configs.get_config("llama3-8b")
    train = costs.model_flops(cfg, configs.SHAPES_BY_NAME["train_4k"])
    prefill = costs.model_flops(cfg, configs.SHAPES_BY_NAME["prefill_32k"])
    decode = costs.model_flops(cfg, configs.SHAPES_BY_NAME["decode_32k"])
    n = costs.param_count(cfg)
    assert train == pytest.approx(6 * n * 4096 * 256)
    assert prefill == pytest.approx(2 * n * 32768 * 32)
    assert decode == pytest.approx(2 * n * 128)


@pytest.mark.parametrize("arch", ["llama3-8b", "mixtral-8x7b", "gemma2-9b",
                                  "zamba2-1.2b", "rwkv6-1.6b",
                                  "seamless-m4t-medium",
                                  "llama-3.2-vision-90b", "dbrx-132b"])
def test_module_cost_profile_sums_to_weight_macs(arch):
    """The per-module profile is the same account as macs_per_token's
    weight side, just itemized — totals must agree to float precision."""
    cfg = configs.get_config(arch)
    profile = costs.module_cost_profile(cfg)
    total = sum(m.macs for m in profile)
    assert total == pytest.approx(
        costs.macs_per_token(cfg).weight_macs, rel=1e-9)
    assert all(m.macs > 0 and m.fan_in >= 1 for m in profile)
    # paths stay within the canonical vocabulary (core/policy.py)
    roots = {m.path.split(".")[0] for m in profile}
    assert roots <= {"attn", "mlp", "moe", "ssm", "rwkv", "lm_head", "conv"}


def test_macs_split_weight_vs_act():
    cfg = configs.get_config("llama3-8b")
    m = costs.macs_per_token(cfg, context_len=4096)
    assert m.weight_macs > 0 and m.act_macs > 0
    # attention act-MACs at 4k ctx: 2 * H * hd * ctx * L
    want = 2.0 * 32 * 128 * 4096 * 32
    assert m.act_macs == pytest.approx(want)
    # rwkv is attention-free -> no act MACs counted
    r = costs.macs_per_token(configs.get_config("rwkv6-1.6b"), 4096)
    assert r.act_macs == 0
