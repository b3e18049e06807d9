"""MoE: router properties + dense-scan vs capacity-dispatch equivalence."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs
from repro.models import mlp as M


def _moe_cfg():
    cfg = configs.reduced(configs.get_config("mixtral-8x7b"))
    return cfg


@given(st.integers(0, 1000), st.integers(1, 4))
@settings(max_examples=20, deadline=None)
def test_router_topk_properties(seed, k):
    rng = np.random.default_rng(seed)
    e = 8
    logits = jnp.asarray(rng.standard_normal((3, 5, e)), jnp.float32)
    gates, mask = M.router_topk(logits, k)
    # exactly k experts selected per token; gates sum to 1 over selected
    assert int(mask.sum(-1).min()) == k and int(mask.sum(-1).max()) == k
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
    assert float(gates.min()) >= 0
    # the selected experts are the k largest logits
    sel_logits = jnp.where(mask, logits, -jnp.inf)
    thresh = jnp.min(jnp.where(mask, logits, jnp.inf), axis=-1)
    assert bool((jnp.where(~mask, logits, -jnp.inf)
                 <= thresh[..., None] + 1e-6).all())


def test_moe_scan_forward_uses_gates():
    cfg = _moe_cfg()
    p = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 8, cfg.d_model)), jnp.float32)
    y, aux = M.apply_moe(x, p, cfg)
    assert y.shape == x.shape
    assert float(aux) > 0.5  # load-balance loss near E * 1/E * 1 = 1


def test_moe_aux_loss_balanced_router_is_topk():
    """With uniform router probabilities the Switch-style aux loss
    E * sum_e f_e p_e equals top_k (f sums to k, p uniform)."""
    cfg = _moe_cfg()
    p = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((2, 64, cfg.d_model))
    p = {**p, "router": {"w": jnp.zeros_like(p["router"]["w"])}}
    _, aux = M.apply_moe(x, p, cfg)
    assert float(aux) == pytest.approx(cfg.moe.top_k, rel=0.05)


def test_capacity_dispatch_matches_scan_multidev():
    """The §Perf capacity path must match the dense scan wherever no token
    is dropped (generous capacity_factor)."""
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..",
                                      "src")}
    code = textwrap.dedent("""
        import dataclasses, json
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro import configs
        from repro.configs.base import MoEConfig
        from repro.models import mlp as M
        from repro.dist.moe_ep import apply_moe_capacity

        cfg = configs.reduced(configs.get_config("mixtral-8x7b"))
        cfg = dataclasses.replace(
            cfg, moe=MoEConfig(num_experts=4, top_k=2, capacity_factor=4.0))
        p = M.init_moe(jax.random.PRNGKey(0), cfg)
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            (8, 16, cfg.d_model)), jnp.float32)
        y_scan, aux_scan = M.apply_moe(x, p, cfg)
        mesh = make_mesh((4, 2), ("data", "model"))
        with mesh:
            y_cap, aux_cap = jax.jit(
                lambda x_, p_: apply_moe_capacity(x_, p_, cfg, mesh))(x, p)
        err = float(jnp.abs(y_scan - y_cap).max()
                    / (jnp.abs(y_scan).max() + 1e-9))
        # gradients flow through the dispatch path
        g = jax.grad(lambda p_: jnp.sum(
            apply_moe_capacity(x, p_, cfg, mesh)[0] ** 2))(p)
        gn = float(sum(jnp.abs(l).sum()
                       for l in jax.tree_util.tree_leaves(g)))
        print(json.dumps({"err": err, "aux_scan": float(aux_scan),
                          "aux_cap": float(aux_cap), "grad_norm": gn}))
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["err"] < 1e-4, r
    assert r["aux_cap"] == pytest.approx(r["aux_scan"], rel=0.05)
    assert r["grad_norm"] > 0
