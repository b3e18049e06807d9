"""Random fp32 weights from a seed, in the parameter layout the serving
engine takes, made on the device in one jitted call.

The same function feeds the program (which quantizes them into its weight
store) and the plain reference (which quantizes them itself), so neither
takes anything the other made. Sizes come from the configuration file.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import families


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one beyond 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def padded_vocab(spec: dict) -> int:
    return ((int(spec["vocab_size"]) + 255) // 256) * 256


class _Keys:
    def __init__(self, key):
        self.key = key

    def __call__(self):
        self.key, k = jax.random.split(self.key)
        return k


def lin(ks, d_in, d_out, lead=(), scale=None):
    scale = d_in ** -0.5 if scale is None else scale
    return {"w": jax.random.normal(ks(), lead + (d_in, d_out),
                                   jnp.float32) * scale}


def rms(ks, d, lead=()):
    return {"scale": 0.1 * jax.random.normal(ks(), lead + (d,), jnp.float32)}


def ln(ks, d, lead=()):
    return {"scale": 1.0 + 0.1 * jax.random.normal(ks(), lead + (d,),
                                                   jnp.float32),
            "bias": 0.1 * jax.random.normal(ks(), lead + (d,), jnp.float32)}


def _make(key, spec):
    ks = _Keys(key)
    v, d = padded_vocab(spec), spec["d_model"]
    body = families.load(spec).params(ks, spec)
    body["embed"] = {"table": 0.02 * jax.random.normal(ks(), (v, d),
                                                       jnp.float32)}
    body["lm_head"] = lin(ks, d, v, scale=0.02)
    return body


def make_params(spec: dict, seed: int):
    """The fp32 parameter pytree for ``spec`` (a configuration file's
    sizes), drawn from ``seed`` in one jitted call on the default device."""
    families.load(spec)
    sizes = {k: spec[k] for k in SIZE_KEYS if k in spec}
    fn = jax.jit(partial(_make, spec=_Frozen(sizes)))
    return fn(key_for(seed))


SIZE_KEYS = ("reference", "num_layers", "d_model", "num_heads",
             "num_kv_heads", "head_dim", "d_ff", "activation", "vocab_size",
             "ssm_state", "ssm_head_dim", "ssm_expand", "ssm_conv_width",
             "attn_period")


class _Frozen(dict):
    """A hashable dict, so the sizes can ride as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
