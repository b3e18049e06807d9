"""The RWKV-6 wkv decode kernel (kernels/rwkv_wkv.py) in interpret mode
against its jnp oracle, and the oracle against the recurrence's equations.

Kernel and oracle state the same f32 operations in the same order (the sum
over the head's 64 keys by halving), so under jit they agree to the bit.
Both are compiled, as the decode step compiles them: run op by op, the
oracle's ``w*s + k*v`` rounds twice where a compiled fusion may round once.
Against the equations in float64 the tolerance is f32 rounding: 1e-5 of
the largest value."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels import ref as kref
from repro.models import rwkv as R

H, HD = 32, 64


def _inputs(rows, decay, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r, k, v = (jax.random.normal(ks[i], (rows, H, HD), jnp.float32)
               for i in range(3))
    # decays close to 0 (exp(-exp(3)) ~ 2e-9) or to 1 (exp(-exp(-6)))
    lam = {"near0": 3.0, "near1": -6.0}[decay]
    dd = lam + 0.5 * jax.random.normal(ks[3], (rows, H, HD), jnp.float32)
    w = jnp.exp(-jnp.exp(dd))
    u = jax.random.normal(ks[4], (H, HD), jnp.float32)
    state = 3.0 * jax.random.normal(ks[5], (rows, HD, H * HD), jnp.float32)
    return r, k, v, w, u, state


@pytest.mark.parametrize("decay", ["near0", "near1"])
@pytest.mark.parametrize("rows", [1, 3, 16])
def test_kernel_matches_oracle(rows, decay):
    args = _inputs(rows, decay, seed=rows)
    run = jax.jit(dispatch.wkv_decode, static_argnums=6)
    o_ref, s_ref = map(np.asarray, run(*args, "ref"))
    o, s = map(np.asarray, run(*args, "packed:force"))
    assert o.shape == (rows, H, HD) and s.shape == (rows, HD, H * HD)
    np.testing.assert_array_equal(o, o_ref)
    np.testing.assert_array_equal(s, s_ref)


def test_oracle_is_the_recurrence():
    """The oracle's value-major layout holds S[h, i, j] at [j, h*hd + i]:
    o = r (S + diag(u) k^T v), S' = diag(w) S + k^T v, per head, in
    float64 from the equations."""
    r, k, v, w, u, state = (np.asarray(a, np.float64)
                            for a in _inputs(3, "near1", seed=7))
    s = np.asarray(R.state_to_heads(jnp.asarray(state)), np.float64)
    kv = k[..., :, None] * v[..., None, :]
    o_eq = np.einsum("bhi,bhij->bhj", r, s + u[None, :, :, None] * kv)
    s_eq = w[..., :, None] * s + kv
    o, s_new = kref.wkv_decode_ref(*(jnp.asarray(a, jnp.float32) for a in
                                     (r, k, v, w, u, state)))
    np.testing.assert_allclose(np.asarray(o), o_eq, rtol=1e-5,
                               atol=1e-5 * np.abs(o_eq).max())
    np.testing.assert_allclose(
        np.asarray(R.state_to_heads(s_new)), s_eq, rtol=1e-5,
        atol=1e-5 * np.abs(s_eq).max())
    np.testing.assert_array_equal(
        np.asarray(R.state_from_heads(R.state_to_heads(jnp.asarray(
            state, jnp.float32)))), np.asarray(state, np.float32))
