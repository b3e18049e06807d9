"""The serving kernels compile for TPU v5e at zamba2-1.2b widths.

Each test compiles one kernel, through the dispatch code that pads and
blocks its operands, for a described ``v5e:2x2`` topology: the TPU compiler
runs here without a chip and refuses what the chip would refuse (int8
vector arithmetic Mosaic cannot lower, blocks off the (8, 128) tiling, VMEM
overruns) — faults the interpret-mode parity tests cannot see. Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import every
test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.kernels import pann_attention as pa

PLANES = 7              # dispatch.INT8_PLANES: the ladder's plane count
ROWS = 4                # decode batch: one row per request


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct maker on one described chip. The persistent
    compile cache is off meanwhile: an executable for a described chip is
    written to it but can never be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _scalars(spec):
    f32 = spec((), jnp.float32)
    return f32, f32, f32, f32           # s, z, n_lvl, plane_shift


# zamba2-1.2b: d_model 2048, shared-block MLP d_ff 8192 (up and down);
# and widths under one lane tile (a reduced config), which dispatch pads
WIDTHS = [(2048, 8192), (8192, 2048), (64, 48)]


@pytest.mark.parametrize("k,n", WIDTHS)
def test_packed_matmul_compiles(spec, k, n):
    s, z, n_lvl, shift = _scalars(spec)
    planes = spec((PLANES, k // 8, n), jnp.uint8)

    def f(x, pp, pn, s, z, n_lvl, gamma, zcol, shift):
        return dispatch._matmul_packed(x, pp, pn, s, z, n_lvl, gamma, zcol,
                                       interpret=False, shift=shift)

    text = _compiled_text(f, spec((ROWS, k), jnp.float32), planes, planes,
                          s, z, n_lvl, spec((n,), jnp.float32),
                          spec((n,), jnp.int32), shift)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,n", WIDTHS)
def test_fused_matmul_compiles(spec, k, n):
    s, z, n_lvl, shift = _scalars(spec)

    def f(x, w_q, s, z, n_lvl, gamma, zcol, shift):
        return dispatch._matmul_fused(x, w_q, s, z, n_lvl, gamma, zcol,
                                      PLANES, interpret=False, shift=shift)

    text = _compiled_text(f, spec((ROWS, k), jnp.float32),
                          spec((k, n), jnp.int8), s, z, n_lvl,
                          spec((n,), jnp.float32), spec((n,), jnp.int32),
                          shift)
    assert "tpu_custom_call" in text


def test_decode_attention_compiles(spec):
    # the shared attention block: 32 heads (kv 32), head_dim 64, a cache
    # of prompt 32 + 16 generated positions
    b, kh, g, hd, s = ROWS, 32, 1, 64, 48
    f32 = spec((), jnp.float32)
    planes = spec((b, PLANES, s, kh, hd // 8), jnp.uint8)
    row = spec((b, s), jnp.float32)

    def f(*args):
        return pa.decode_attention(*args, interpret=False)

    text = _compiled_text(f, spec((b, kh, g, hd), jnp.int32), f32, f32,
                          planes, row, row, planes, row, row,
                          spec((), jnp.int32), f32, f32)
    assert "tpu_custom_call" in text


def test_ref_projection_leaves_through_its_barrier(spec):
    """The jnp oracle's dot is not fused into what consumes it. Fused with
    a residual add and the next RMSNorm's sum of squares (XLA's choice for
    a bare dot on TPU), the sum runs in another order than next to a
    Pallas call, and the backends' outputs drift an ulp apart on the chip;
    ``dispatch._dispatch_rows``'s exit barrier prevents it."""
    k = n = 2048
    p = {"w_q": spec((k, n), jnp.int8), "w_scale": spec((1, n), jnp.float32),
         "w_colsum": spec((n,), jnp.int32),
         "act_nlvl": spec((), jnp.float32),
         "plane_shift": spec((), jnp.float32)}

    def f(x, resid, p):
        h = resid + dispatch.serving_linear(x, p, "ref")
        return h, jnp.sum(h * h, axis=-1)

    text = _compiled_text(f, spec((ROWS, k), jnp.float32),
                          spec((ROWS, n), jnp.float32), p)
    comps = text.split("\n\n")
    assert any(" convolution(" in c for c in comps)
    assert not [c.split("\n")[0] for c in comps
                if " convolution(" in c and " reduce(" in c]
