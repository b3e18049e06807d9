"""Pallas TPU kernel: one-token GQA decode attention read DIRECTLY off the
packed bit-plane KV cache (docs/kv_cache.md; DESIGN.md §10).

The cache stores K/V as unsigned affine codes, bit-plane-decomposed and
packed 8 bits/byte along head_dim (``kernels.ref.pack_cache_codes`` — NOT
the weight-plane ``pack_planes``, which packs along K). One grid cell per
(batch, kv_head); each cell streams its plane panels through
double-buffered manual DMAs, accumulates the unpacked codes into an int32
panel, runs the exact int32 QK^T with BOTH zero points corrected inside the
accumulator (the serving_linear ``zcol`` convention, applied twice), the
fp32 softmax epilogue in the oracle's exact op sequence (its numerators on
a fixed 2^15 grid, so the normalizer is an exact int32 sum that no
reduction order can round differently), then re-quantizes the
probabilities to a fixed 2^14 grid for an exact int32 PV pass —
``sum_s p = 1`` bounds ``pq @ vq`` by ``127 * 2^14``, int32-safe for ANY
sequence length. Bit-identical (fp32) to ``kernels.ref.decode_attention_ref``
(tests/test_kv_cache_quant.py).

TPU layout: inside the kernel the planes are head-dim-major — (hd/8, S)
panels, positions on lanes — so a packed panel unpacks exactly like the
weight kernels' (rows of bytes -> 8 rows of bits each), which is the only
unpack Mosaic lowers. The wrapper transposes the (B, P, S, K, hd/8) cache
to (B, P, K, hd/8, S) for this. Mosaic has no int8 vector arithmetic and no
int32 matmul, so codes are accumulated in int32 and cast to int8 only at
the MXU operands, and the PV pass splits the <= 2^14 probability codes into
three int8-range limbs (bits 0-6, 7-13, 14) whose int32 products recombine
exactly.

Plane skipping: cache codes are <= n_lvl < 2^b, so only the LOW
``planes_active`` planes can be nonzero (the opposite prefix from the
weight kernels, which skip low planes under a view shift). The per-role
active counts ride in as SMEM DATA scalars — derived from the cache's
``k_nlvl``/``v_nlvl`` leaves — so a 2-bit cache rung DMAs and shift-adds 2
planes, not 7, while every rung shares one compiled kernel. Skipped planes
are all-zero in the cache by construction, so the jnp oracle needs no
planes_active argument and the parity suite is unchanged.

Whole-S blocks: decode reads every cached position once per token, so the
panel (7 planes x S x hd/8 bytes) must fit VMEM — ~57 KB at S=4096,
hd=128. No K-grid accumulation loop is needed at these sizes; a
sequence-blocked online-softmax variant is the follow-up if contexts
outgrow VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import (CACHE_PLANES, EXP_SCALE, MAX_CACHE_LEN,
                               PROB_SCALE)

Array = jax.Array

NEG_INF = -1e30     # matches models.attention.NEG_INF / ref._CACHE_NEG_INF


def _unpack_plane(pk: Array) -> Array:
    """(d8, S) uint8 — ONE packed plane — -> (hd, S) int32 {0,1} bits.
    Byte j, bit i -> row 8j+i: the per-plane slice of the exact inverse of
    ``ref.pack_cache_codes``, transposed."""
    d8, s = pk.shape
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 8, 1), 1)
    bits = (pk.astype(jnp.int32)[:, None, :] >> shifts) & 1  # (d8, 8, S)
    return bits.reshape(d8 * 8, s)


def _dot(a: Array, b: Array, contract: tuple[int, int]) -> Array:
    """Exact int32 product of int8-range int32 operands on the MXU."""
    return jax.lax.dot_general(
        a.astype(jnp.int8), b.astype(jnp.int8),
        (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.int32)


def _decode_attention_kernel(qp_ref, pos_ref, q_ref, kp_hbm, ks_ref, kz_ref,
                             vp_hbm, vs_ref, vz_ref, o_ref, kcode, vcode,
                             kbuf, vbuf, ksem, vsem, *, n_planes: int,
                             hd: int, window, softcap: float,
                             exp_scale: float, prob_scale: float):
    """Grid = (B, K): one cell per (batch, kv_head). Codes are (hd, S)."""
    bi, ki = pl.program_id(0), pl.program_id(1)
    qz = qp_ref[0, 0].astype(jnp.int32)
    q_scale = qp_ref[0, 1]                      # s_q * hd**-0.5, sealed
    k_pact = jnp.round(qp_ref[0, 2]).astype(jnp.int32)
    v_pact = jnp.round(qp_ref[0, 3]).astype(jnp.int32)
    pos = pos_ref[0, 0]
    s = kcode.shape[1]

    def plane_dma(buf, hbm, sem, slot, p):
        return pltpu.make_async_copy(hbm.at[bi, p, ki],
                                     buf.at[slot], sem.at[slot])

    # plane 0 is live for ANY level count >= 1; higher planes are started
    # and waited under matching predicates so the semaphores stay balanced
    plane_dma(kbuf, kp_hbm, ksem, 0, 0).start()
    plane_dma(vbuf, vp_hbm, vsem, 0, 0).start()

    # accumulate codes = sum_p 2^p * plane_p over the LIVE prefix only;
    # the dead high planes are all-zero in the cache, so the sum equals the
    # full 7-plane unpack bit-for-bit
    kcode[...] = jnp.zeros_like(kcode)
    for p in range(n_planes):
        @pl.when(p < k_pact)
        def _accum_k(p=p, slot=p % 2):
            if p + 1 < n_planes:
                @pl.when(p + 1 < k_pact)
                def _prefetch():
                    plane_dma(kbuf, kp_hbm, ksem, 1 - (p % 2), p + 1).start()
            plane_dma(kbuf, kp_hbm, ksem, slot, p).wait()
            kcode[...] += jnp.int32(1 << p) * _unpack_plane(kbuf[slot])

    qq = q_ref[...]                             # (G, hd) int32 affine codes
    kq = kcode[...]                             # (hd, S) int32

    # exact int32 QK^T: (qq - z_q) . (kq - z_k) expanded inside the
    # accumulator — codes <= 127 and hd <= 256 keep every term int32-safe
    dots = _dot(qq, kq, (1, 0))                 # (G, S)
    colsum_k = jnp.sum(kq, axis=0, keepdims=True)           # (1, S)
    rowsum_q = jnp.sum(qq, axis=1, keepdims=True)           # (G, 1)
    kz = jnp.round(kz_ref[...]).astype(jnp.int32)           # (1, S)
    i32 = dots - qz * colsum_k - kz * rowsum_q + qz * kz * hd

    # fp32 epilogue — the oracle's exact op sequence (ref.py): change both
    # or neither, the parity suite holds them bit-identical
    sc = (i32.astype(jnp.float32) * q_scale) * ks_ref[...]
    if softcap > 0:
        sc = softcap * jnp.tanh(sc / softcap)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    valid = k_pos <= pos
    if window is not None:
        valid &= (pos - k_pos) < window
    sc = jnp.where(valid, sc, NEG_INF)
    m = jnp.max(sc, axis=-1, keepdims=True)
    eq = jnp.round(jnp.exp(sc - m) * exp_scale).astype(jnp.int32)
    p_ = eq.astype(jnp.float32) / jnp.sum(
        eq, axis=-1, keepdims=True).astype(jnp.float32)

    # stream + accumulate the V planes (their DMAs overlapped the QK^T work)
    vcode[...] = jnp.zeros_like(vcode)
    for p in range(n_planes):
        @pl.when(p < v_pact)
        def _accum_v(p=p, slot=p % 2):
            if p + 1 < n_planes:
                @pl.when(p + 1 < v_pact)
                def _prefetch():
                    plane_dma(vbuf, vp_hbm, vsem, 1 - (p % 2), p + 1).start()
            plane_dma(vbuf, vp_hbm, vsem, slot, p).wait()
            vcode[...] += jnp.int32(1 << p) * _unpack_plane(vbuf[slot])

    # exact int32 PV: rescale every position into the largest valid V scale,
    # re-quantize the probabilities, subtract the V zero point in-accumulator
    vq = vcode[...]                                          # (hd, S) int32
    vs = vs_ref[...]                                         # (1, S)
    sv_ref = jnp.maximum(jnp.max(jnp.where(valid, vs, 0.0)), 1e-12)
    ratio = vs / sv_ref
    pq = jnp.round(p_ * ratio * prob_scale).astype(jnp.int32)   # (G, S)
    pv = (_dot(pq & 127, vq, (1, 1))
          + 128 * _dot((pq >> 7) & 127, vq, (1, 1))
          + 16384 * _dot(pq >> 14, vq, (1, 1)))              # (G, hd)
    vz = jnp.round(vz_ref[...]).astype(jnp.int32)            # (1, S)
    corr = jnp.sum(pq * vz, axis=-1, keepdims=True)          # (G, 1)
    scale = sv_ref / prob_scale
    o_ref[...] = (pv - corr).astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("window", "softcap",
                                             "interpret"))
def decode_attention(qq: Array, q_z: Array, q_scale: Array,
                     k_planes: Array, k_s: Array, k_z: Array,
                     v_planes: Array, v_s: Array, v_z: Array,
                     pos: Array, k_pact: Array | None = None,
                     v_pact: Array | None = None, *, window=None,
                     softcap: float = 0.0, interpret: bool = True) -> Array:
    """out[b, k, g, :] = softmax-attention of query group (b, k, g) over the
    packed bit-plane KV cache. Argument shapes match
    ``kernels.ref.decode_attention_ref`` exactly (its docstring is the
    spec), except ``pos`` must be a scalar — the engine's caches share one
    ``length`` across the batch — and ``k_pact``/``v_pact`` (traced scalar
    counts of LIVE low planes, from the cache level counts; None = all)
    have no oracle counterpart because the skipped planes are all-zero.
    """
    b, kh, g, hd = qq.shape
    _, n_planes, s, kh2, d8 = k_planes.shape
    assert kh == kh2 and d8 * 8 == hd, (qq.shape, k_planes.shape)
    assert v_planes.shape == k_planes.shape
    assert n_planes <= CACHE_PLANES, n_planes
    assert s <= MAX_CACHE_LEN, s
    if k_pact is None:
        k_pact = jnp.float32(n_planes)
    if v_pact is None:
        v_pact = jnp.float32(n_planes)
    qp = jnp.stack([jnp.asarray(q_z, jnp.float32).reshape(()),
                    jnp.asarray(q_scale, jnp.float32).reshape(()),
                    jnp.clip(jnp.asarray(k_pact, jnp.float32).reshape(()),
                             1.0, float(n_planes)),
                    jnp.clip(jnp.asarray(v_pact, jnp.float32).reshape(()),
                             1.0, float(n_planes))]).reshape(1, 4)
    pos2 = jnp.asarray(pos, jnp.int32).reshape(1, 1)

    kernel = functools.partial(_decode_attention_kernel, n_planes=n_planes,
                               hd=hd, window=window, softcap=softcap,
                               exp_scale=EXP_SCALE, prob_scale=PROB_SCALE)
    # The (B, S) scale/zero rows ride as (B, 1, S): a block's last two dims
    # must be tile multiples or whole, and (1, S) is whole on that view.
    # Positions ride on lanes, so S is padded to whole lane tiles; padded
    # positions lie past ``pos`` and are masked like unwritten ones.
    pad = (-s) % 128
    s += pad
    row_spec = pl.BlockSpec((None, 1, s), lambda bi, ki: (bi, 0, 0))
    rows = lambda a: jnp.pad(a, ((0, 0), (0, pad))).reshape(b, 1, s)  # noqa: E731
    head_major = lambda a: jnp.pad(                             # noqa: E731
        jnp.transpose(a, (0, 1, 3, 4, 2)), ((0, 0),) * 4 + ((0, pad),))
    qo_spec = pl.BlockSpec((None, None, g, hd),
                           lambda bi, ki: (bi, ki, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, kh),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # [q_z, q_scale, pacts]
            pl.BlockSpec(memory_space=pltpu.SMEM),   # pos
            qo_spec,
            pl.BlockSpec(memory_space=pl.ANY),    # K planes (manual DMA)
            row_spec, row_spec,                      # K s/z
            pl.BlockSpec(memory_space=pl.ANY),    # V planes (manual DMA)
            row_spec, row_spec,                      # V s/z
        ],
        out_specs=qo_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((hd, s), jnp.int32),          # accumulated K codes
            pltpu.VMEM((hd, s), jnp.int32),          # accumulated V codes
            pltpu.VMEM((2, d8, s), jnp.uint8),       # K plane slots
            pltpu.VMEM((2, d8, s), jnp.uint8),       # V plane slots
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(qp, pos2, qq.astype(jnp.int32), head_major(k_planes), rows(k_s),
      rows(k_z), head_major(v_planes), rows(v_s), rows(v_z))
