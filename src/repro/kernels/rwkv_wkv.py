"""Pallas TPU kernel: one RWKV-6 wkv decode step (arXiv:2404.05892 §4).

Per batch row and head, with r, k, v, w (decay) and u (bonus) vectors of
the head size hd and the (hd, hd) state S:

    o[j]     = sum_i r[i] (S[i, j] + u[i] k[i] v[j])
    S'[i, j] = w[i] S[i, j] + k[i] v[j]

State layout ("value-major"): one row's state is an (hd, H * hd) f32 matrix
holding S[h, i, j] at [j, h * hd + i]. The minor dim is H * hd wide, so the
array is lane-dense in HBM; the (B, H, hd, hd) form would have a 64-wide
minor dim, which the TPU's (8, 128) tiling pads to 128 lanes, doubling the
bytes a step moves. In this layout r, k, w and u are rows (broadcast down
the sublanes) and v is expanded per head across its hd lanes by a 0/1
selection matmul.

The arithmetic is f32 and in a fixed order, the oracle's
(``kernels.ref.wkv_decode_ref``) to the bit: ``t = r (S + u (k v))``, the
sum over i by halving (lane i adds lane i + hd/2, then i + hd/4, ... 1),
``S' = w S + k v``. The two selection matmuls (v's expansion, and o's pick
of each head's first lane) have one nonzero product per output and run at
``HIGHEST`` precision, which makes them exact. A served model's activation
quantizers turn an ulp of difference into a different code, so a kernel
that summed in the MXU's order could not be checked against a reference.
The state is read once and written once: the output aliases its buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NAME = "rwkv6_wkv_decode"      # the kernel's name in the compiled program
_HI = jax.lax.Precision.HIGHEST


def _rows_per_block(b: int) -> int:
    for rows in (4, 2, 1):
        if b % rows == 0:
            return rows
    return 1


def _kernel(r_ref, k_ref, w_ref, u_ref, vt_ref, s_ref, o_ref, so_ref, *,
            heads: int, hd: int, rows: int):
    n = heads * hd
    shift = hd.bit_length() - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (heads, n), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (heads, n), 0)
    # head_of[h, l] = 1 where lane l = h * hd + i belongs to head h;
    # first_of[h, l] = 1 at the head's first lane only
    head_of = ((lane >> shift) == head).astype(jnp.float32)
    first_of = (lane == (head << shift)).astype(jnp.float32)
    u = u_ref[...]                                            # (1, n)
    for b in range(rows):
        # vexp[j, h * hd + i] = v[h, j]
        vexp = jnp.dot(vt_ref[b], head_of, precision=_HI,
                       preferred_element_type=jnp.float32)    # (hd, n)
        s = s_ref[b]                                          # (hd, n)
        kv = k_ref[b] * vexp
        t = r_ref[b] * (s + u * kv)
        # the sum over i, halving: lane i takes lane i + d, d = hd/2 .. 1
        d = hd // 2
        while d:
            t = t + pltpu.roll(t, n - d, 1)
            d //= 2
        # o[h, j] = t[j, h * hd]
        o_ref[b] = jax.lax.dot_general(
            first_of, t, (((1,), (1,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32)               # (H, hd)
        so_ref[b] = w_ref[b] * s + kv


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv_decode(r: Array, k: Array, v: Array, w: Array, u: Array,
               state: Array, *, interpret: bool = False
               ) -> tuple[Array, Array]:
    """(o (B, H, hd), new state (B, hd, H * hd)) of one decode step.

    r, k, v, w: (B, H, hd) f32; u: (H, hd); state: value-major (module
    docstring). Oracle: ``kernels.ref.wkv_decode_ref``."""
    b, heads, hd = r.shape
    n = heads * hd
    assert hd & (hd - 1) == 0, hd
    assert state.shape == (b, hd, n), (state.shape, r.shape)
    rows = _rows_per_block(b)
    f32 = jnp.float32
    flat = lambda a: a.astype(f32).reshape(b, 1, n)          # noqa: E731
    vt = jnp.swapaxes(v.astype(f32), 1, 2)                    # (B, hd, H)
    vec = pl.BlockSpec((rows, 1, n), lambda i: (i, 0, 0))
    mat = pl.BlockSpec((rows, hd, n), lambda i: (i, 0, 0))
    kernel = functools.partial(_kernel, heads=heads, hd=hd, rows=rows)
    o, s_new = pl.pallas_call(
        kernel,
        grid=(b // rows,),
        in_specs=[vec, vec, vec,
                  pl.BlockSpec((1, n), lambda i: (0, 0)),
                  pl.BlockSpec((rows, hd, heads), lambda i: (i, 0, 0)),
                  mat],
        out_specs=[pl.BlockSpec((rows, heads, hd), lambda i: (i, 0, 0)),
                   mat],
        out_shape=[jax.ShapeDtypeStruct((b, heads, hd), f32),
                   jax.ShapeDtypeStruct((b, hd, n), f32)],
        input_output_aliases={5: 1},
        interpret=interpret,
        name=NAME,
    )(flat(r), flat(k), flat(w), u.astype(f32).reshape(1, n), vt,
      state.astype(f32))
    return o, s_new
