"""Data pipeline, optimizers, checkpointing, fault tolerance (single-device)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import checkpoint as ck
from repro.configs.base import TrainConfig
from repro.data.pipeline import SyntheticLM, frontend_stub
from repro.dist.fault import StepMonitor, Supervisor
from repro.optim import optimizers as OPT


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_replay():
    d = SyntheticLM(vocab_size=64, seq_len=32, global_batch=8, seed=3)
    a = d.global_batch_arrays(step=7)
    b = d.global_batch_arrays(step=7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = d.global_batch_arrays(step=8)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_data_shards_partition_global_batch():
    d = SyntheticLM(vocab_size=64, seq_len=16, global_batch=8, seed=0)
    shards = [d.host_local_batch(step=1, shard=i, num_shards=4)
              for i in range(4)]
    assert all(s["tokens"].shape == (2, 16) for s in shards)
    # different shards draw different streams
    assert not np.array_equal(shards[0]["tokens"], shards[1]["tokens"])


def test_data_labels_are_shifted_tokens():
    d = SyntheticLM(vocab_size=64, seq_len=16, global_batch=2, seed=1)
    b = d.global_batch_arrays(0)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
    assert (b["labels"][:, -1] == -1).all()  # masked final position


def test_data_is_learnable_structure():
    """The bigram-cycle structure must be present (next = 5*cur+1 mod V)."""
    d = SyntheticLM(vocab_size=64, seq_len=256, global_batch=4, seed=0,
                    structure=0.9)
    b = d.global_batch_arrays(0)["tokens"]
    follows = (b[:, 1:] == (5 * b[:, :-1] + 1) % 64).mean()
    assert follows > 0.7


def test_frontend_stub_shapes():
    from repro import configs
    cfg = configs.reduced(configs.get_config("seamless-m4t-medium"))
    fe = frontend_stub(cfg, batch=3, step=0)
    assert fe.shape == (3, cfg.encoder_seq_len, cfg.d_model)
    cfg = configs.reduced(configs.get_config("llama-3.2-vision-90b"))
    fe = frontend_stub(cfg, batch=2, step=0)
    assert fe.shape == (2, cfg.num_image_tokens, cfg.d_model)
    assert frontend_stub(configs.reduced(configs.get_config("llama3-8b")),
                         2, 0) is None


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

def _quad_params():
    return {"a": jnp.asarray([2.0, -3.0]), "b": jnp.asarray([[1.0, 2.0]])}


@pytest.mark.parametrize("name", ["adamw", "sgdm"])
def test_optimizer_descends_quadratic(name):
    tcfg = TrainConfig(lr=0.05, weight_decay=0.0, warmup_steps=1,
                       total_steps=200, grad_clip=10.0)
    opt = OPT.make_optimizer(name, tcfg)
    params = _quad_params()
    state = opt.init(params)
    loss_fn = lambda p: sum(jnp.sum(x ** 2) for x in jax.tree_util.tree_leaves(p))
    l0 = float(loss_fn(params))
    for _ in range(100):
        grads = jax.grad(loss_fn)(params)
        params, state, _ = opt.update(grads, state, params)
    assert float(loss_fn(params)) < 0.1 * l0


def test_grad_clip_bounds_norm():
    g = {"w": jnp.full((4,), 100.0)}
    clipped, norm = OPT.clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(200.0)
    n2 = jnp.sqrt(sum(jnp.sum(x ** 2)
                      for x in jax.tree_util.tree_leaves(clipped)))
    assert float(n2) == pytest.approx(1.0, rel=1e-5)


def test_schedule_warmup_and_decay():
    tcfg = TrainConfig(lr=1e-3, warmup_steps=10, total_steps=100)
    sched = OPT.cosine_warmup_schedule(tcfg)
    assert float(sched(jnp.asarray(0))) == pytest.approx(0.0)
    assert float(sched(jnp.asarray(10))) == pytest.approx(1e-3, rel=1e-5)
    assert float(sched(jnp.asarray(100))) < 2e-4


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"x": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "nested": {"y": jnp.ones((4,), jnp.int32)},
            "lst": [jnp.zeros((2,)), jnp.full((1,), 7.0)]}
    ck.save(str(tmp_path), 5, tree, meta={"note": "t"})
    assert ck.latest_step(str(tmp_path)) == 5
    out = ck.restore(str(tmp_path), 5, tree)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ck.read_meta(str(tmp_path), 5)["note"] == "t"


def test_checkpoint_keep_k(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    for s in [1, 2, 3, 4, 5]:
        ck.save(str(tmp_path), s, tree, keep=2)
    assert ck.all_steps(str(tmp_path)) == [4, 5]


def test_checkpoint_partial_write_ignored(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    ck.save(str(tmp_path), 1, tree)
    # simulate a torn write: directory without COMMITTED
    os.makedirs(tmp_path / "step_00000002")
    assert ck.latest_step(str(tmp_path)) == 1


def test_checkpoint_shape_mismatch_raises(tmp_path):
    ck.save(str(tmp_path), 1, {"x": jnp.zeros((2,))})
    with pytest.raises(ValueError):
        ck.restore(str(tmp_path), 1, {"x": jnp.zeros((3,))})


# ---------------------------------------------------------------------------
# Fault tolerance
# ---------------------------------------------------------------------------

def test_straggler_detector_flags_slow_step():
    mon = StepMonitor(warmup=2, threshold=2.0)
    for i in range(10):
        mon.record(i, 0.1)
    assert mon.record(10, 0.5) is True
    assert mon.summary()["stragglers"] == 1


def test_supervisor_restarts_after_injected_crash(tmp_path):
    """The run crashes at step 7; the supervisor restores from the step-5
    checkpoint and completes — no step is lost or repeated in the result."""
    crashed = {"done": False}

    def init_fn():
        return {"value": jnp.zeros(()), "steps_seen": []}

    def resume_fn(step):
        st = ck.restore(str(tmp_path), step, {"value": jnp.zeros(())})
        return {"value": st["value"], "steps_seen": []}

    def step_fn(state, step):
        if step == 7 and not crashed["done"]:
            crashed["done"] = True
            raise RuntimeError("injected node failure")
        return {"value": state["value"] + 1.0,
                "steps_seen": state["steps_seen"] + [step]}

    def save_fn(state, step):
        ck.save(str(tmp_path), step, {"value": state["value"]})

    sup = Supervisor(str(tmp_path), ckpt_every=5)
    final = sup.run(total_steps=10, init_fn=init_fn, resume_fn=resume_fn,
                    step_fn=step_fn, save_fn=save_fn)
    assert sup.restarts == 1
    assert float(final["value"]) == 10.0       # 5 from ckpt + steps 5..9
    assert final["steps_seen"] == [5, 6, 7, 8, 9]
    assert ck.latest_step(str(tmp_path)) == 10


# ---------------------------------------------------------------------------
# Launch plumbing: meshes and the compile cache
# ---------------------------------------------------------------------------

def test_meshes_have_auto_axes_and_refuse_a_ragged_model_axis():
    from jax.sharding import AxisType
    from repro.launch.mesh import make_local_mesh, make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    assert make_local_mesh(1).shape == {"data": 1, "model": 1}
    with pytest.raises(ValueError, match="does not divide"):
        make_local_mesh(2)


def test_compile_cache_dir_is_the_env_var_or_fixed_in_the_checkout(
        monkeypatch):
    from repro import CHECKOUT_DIR
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.enable_compile_cache() is None    # CPU: off
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.setattr(compile_cache.jax, "default_backend", lambda: "tpu")
    try:
        assert compile_cache.enable_compile_cache() == os.path.join(
            CHECKOUT_DIR, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            compile_cache.DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
