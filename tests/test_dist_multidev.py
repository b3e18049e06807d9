"""Multi-device distribution tests — run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the flag must never be
set in THIS process: smoke tests see 1 device, per the assignment)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ENV = {**os.environ,
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}


def run_py(body: str) -> dict:
    """Run a snippet in a fresh 8-device process; it must print one JSON."""
    code = textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", code], env=ENV,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compressed_allreduce_error_feedback():
    r = run_py("""
        import jax, jax.numpy as jnp, json
        from repro.launch.mesh import make_mesh
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.dist.collectives import compressed_psum_mean

        mesh = make_mesh((8,), ("data",))
        g_global = jnp.asarray(np.random.default_rng(0)
                               .standard_normal((8, 64)), jnp.float32)

        def step(g, err):
            return compressed_psum_mean(g, err, "data")

        f = jax.shard_map(step, mesh=mesh,
                          in_specs=(P("data", None), P("data", None)),
                          out_specs=(P(None, None), P("data", None)),
                          check_vma=False)
        err = jnp.zeros((8, 64))
        out, errd = f({"w": g_global}, {"w": err})
        mean, err = out["w"], errd["w"]
        true_mean = g_global.mean(0)
        rel = float(jnp.abs(mean[0] - true_mean).max()
                    / jnp.abs(true_mean).max())
        # int8 wire: one-step error bounded; feedback carries the residual
        total_err = float(jnp.abs(err).sum())
        # second round with error feedback on the SAME grads must reduce
        # the accumulated bias
        out2, _ = f({"w": g_global}, {"w": err})
        mean2 = out2["w"]
        bias1 = float(jnp.abs(mean[0] - true_mean).mean())
        # error feedback telescopes: the running average of compressed means
        # converges on the true mean even though each round is quantized
        avg_bias = float(jnp.abs((mean[0] + mean2[0]) / 2 - true_mean).mean())
        print(json.dumps({"rel": rel, "bias1": bias1, "avg_bias": avg_bias,
                          "err_nonzero": total_err > 0}))
    """)
    assert r["rel"] < 0.05
    assert r["err_nonzero"]
    assert r["avg_bias"] <= r["bias1"]  # feedback cancels quantization bias


def test_gpipe_matches_sequential():
    r = run_py("""
        import jax, jax.numpy as jnp, json, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.dist.pipeline import pipeline_stack

        mesh = make_mesh((4,), ("pod",))
        rng = np.random.default_rng(0)
        n_groups, d = 8, 16
        ws = jnp.asarray(rng.standard_normal((n_groups, d, d)) * 0.2,
                         jnp.float32)
        x = jnp.asarray(rng.standard_normal((8, 4, d)), jnp.float32)

        def block(stage_ws, h):
            def body(c, w):
                return jnp.tanh(c @ w), None
            out, _ = jax.lax.scan(body, h, stage_ws)
            return out

        def seq(ws, h):
            def body(c, w):
                return jnp.tanh(c @ w), None
            out, _ = jax.lax.scan(body, h, ws)
            return out

        with mesh:
            y_pipe = jax.jit(lambda w, h: pipeline_stack(
                block, w, h, mesh=mesh, axis="pod", n_micro=4))(ws, x)
        y_seq = seq(ws, x)
        err = float(jnp.abs(y_pipe - y_seq).max())
        print(json.dumps({"err": err}))
    """)
    assert r["err"] < 1e-5


def test_gpipe_is_differentiable():
    r = run_py("""
        import jax, jax.numpy as jnp, json, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.dist.pipeline import pipeline_stack

        mesh = make_mesh((2,), ("pod",))
        rng = np.random.default_rng(1)
        ws = jnp.asarray(rng.standard_normal((4, 8, 8)) * 0.3, jnp.float32)
        x = jnp.asarray(rng.standard_normal((4, 2, 8)), jnp.float32)

        def block(stage_ws, h):
            def body(c, w):
                return jnp.tanh(c @ w), None
            out, _ = jax.lax.scan(body, h, stage_ws)
            return out

        def loss_pipe(w):
            with mesh:
                y = pipeline_stack(block, w, x, mesh=mesh, axis="pod",
                                   n_micro=2)
            return jnp.sum(y ** 2)

        def loss_seq(w):
            def body(c, ww):
                return jnp.tanh(c @ ww), None
            out, _ = jax.lax.scan(body, x, w)
            return jnp.sum(out ** 2)

        g1 = jax.jit(jax.grad(loss_pipe))(ws)
        g2 = jax.grad(loss_seq)(ws)
        err = float(jnp.abs(g1 - g2).max() / (jnp.abs(g2).max() + 1e-9))
        print(json.dumps({"err": err}))
    """)
    assert r["err"] < 1e-4


def test_sharded_train_step_matches_single_device():
    """The pjit'd train step on a 4x2 mesh must produce the same loss
    trajectory as single-device execution (same seed, same data)."""
    body_tpl = """
        import jax, jax.numpy as jnp, json
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from functools import partial
        from repro import configs
        from repro.configs.base import TrainConfig, ParallelConfig
        from repro.data.pipeline import SyntheticLM
        from repro.dist import sharding as SH
        from repro.launch import steps as ST
        from repro.optim.optimizers import AdamWState

        MESH = %s
        cfg = configs.reduced(configs.get_config("llama3-8b"))
        tcfg = TrainConfig(lr=1e-3, warmup_steps=2, total_steps=10)
        par = ParallelConfig(remat="none")
        data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16,
                           global_batch=8, seed=0)
        mesh = make_mesh(MESH, ("data", "model"))
        with mesh:
            state = ST.make_train_state(jax.random.PRNGKey(0), cfg, tcfg)
            pspecs = SH.param_specs(jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                state.params), mesh, par)
            sspec = ST.TrainState(params=pspecs,
                                  opt=AdamWState(mu=pspecs, nu=pspecs,
                                                 count=P()), step=P())
            ssh = SH.to_named(sspec, mesh)
            state = jax.tree_util.tree_map(jax.device_put, state, ssh)
            fn = jax.jit(partial(ST.train_step, cfg=cfg, tcfg=tcfg, par=par),
                         in_shardings=(ssh, None), out_shardings=(ssh, None))
            losses = []
            for step in range(4):
                batch = {k: jnp.asarray(v)
                         for k, v in data.global_batch_arrays(step).items()}
                state, m = fn(state, batch)
                losses.append(float(m["loss"]))
        print(json.dumps({"losses": losses}))
    """
    multi = run_py(body_tpl % "(4, 2)")
    single = run_py(body_tpl % "(1, 1)")
    # equality across meshes is the correctness property here; convergence
    # over hundreds of steps is covered by the end-to-end system test
    for a, b in zip(multi["losses"], single["losses"]):
        assert a == pytest.approx(b, rel=2e-3), (multi, single)


def test_elastic_remesh_restore():
    """Save under a (4,2) mesh, restore under (2,4) and (8,1) — elastic
    rescaling across checkpoint boundaries."""
    r = run_py("""
        import jax, jax.numpy as jnp, json, tempfile, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt import checkpoint as ck

        d = tempfile.mkdtemp()
        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        mesh_a = make_mesh((4, 2), ("data", "model"))
        sh_a = {"w": NamedSharding(mesh_a, P("data", "model"))}
        tree_a = jax.tree_util.tree_map(jax.device_put, tree, sh_a)
        ck.save(d, 1, tree_a)

        results = []
        for shape in [(2, 4), (8, 1)]:
            mesh_b = make_mesh(shape, ("data", "model"))
            sh_b = {"w": NamedSharding(mesh_b, P("data", "model"))}
            out = ck.restore(d, 1, tree, sh_b)
            results.append(bool((np.asarray(out["w"]) ==
                                 np.asarray(tree["w"])).all()))
            results.append(out["w"].sharding.mesh.shape["data"] == shape[0])
        print(json.dumps({"ok": all(results)}))
    """)
    assert r["ok"]
