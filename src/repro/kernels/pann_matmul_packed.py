"""Pallas TPU kernel: PANN bit-plane matmul with PACKED plane storage.

The deployment-optimal layout: the binary planes of the unsigned-split PANN
codes are packed 8 bits per byte along K, so weight HBM bytes are
2 * P * K * N / 8 (P = b_R plane count) — e.g. b_R=3 costs 0.75 byte/weight
for BOTH signs vs 2 bytes for bf16 (2.7x) and 1 byte for int8 codes.
Planes are unpacked in VMEM with shifts (VPU) and fed to the same int8 MXU
pass as kernels/pann_matmul.

Layout: packed[p, k8, n] holds bit (k8*8 + j) of plane p in bit j.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def pack_planes(planes: Array) -> Array:
    """(..., K, N) {0,1} int8 -> (..., K/8, N) uint8 (K padded to 8).

    Packing runs along axis -2 (the reduction dim); any leading dims —
    the plane axis, and for serving artifacts the scan-stacked layer/group
    dims, which must stay leading so ``lax.scan`` slices them — pass
    through untouched. packed[..., k8, n] holds bit (k8*8 + j) in bit j.
    """
    *lead, k, n = planes.shape
    pad = (-k) % 8
    if pad:
        planes = jnp.pad(planes,
                         [(0, 0)] * len(lead) + [(0, pad), (0, 0)])
        k += pad
    bits = planes.reshape(*lead, k // 8, 8, n).astype(jnp.uint8)
    weights = (1 << jnp.arange(8, dtype=jnp.uint8)).reshape(8, 1)
    return jnp.sum(bits * weights, axis=-2).astype(jnp.uint8)


def unpack_planes(packed: Array, k: int) -> Array:
    """Inverse of pack_planes (reference / in-kernel helper)."""
    *lead, k8, n = packed.shape
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape(8, 1)
    bits = (packed[..., :, None, :] >> shifts) & jnp.uint8(1)
    return bits.reshape(*lead, k8 * 8, n)[..., :k, :].astype(jnp.int8)


def _unpack_tile(tile: Array) -> Array:
    """(bk//8, bn) uint8 packed tile -> (bk, bn) int32 {0,1} bits.

    Mosaic has no int8/uint8 vector arithmetic, so the shifts run in int32
    and the result is cast to int8 only at the MXU operand."""
    k8, bn = tile.shape
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 8, 1), 1)
    bits = (tile.astype(jnp.int32)[:, None, :] >> shifts) & 1
    return bits.reshape(k8 * 8, bn)


def _kernel(x_ref, pos_ref, neg_ref, sx_ref, gamma_ref, zcol_ref, o_ref,
            acc_ref, *, n_planes: int, k_steps: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                  # (bm, bk) int8
    bk = x.shape[1]

    w = jnp.zeros((bk, o_ref.shape[1]), jnp.int32)
    for p in range(n_planes):
        w = w + (1 << p) * (_unpack_tile(pos_ref[p]) - _unpack_tile(neg_ref[p]))
    acc_ref[...] += jax.lax.dot_general(
        x, w.astype(jnp.int8), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(kk == k_steps - 1)
    def _done():
        o_ref[...] = ((acc_ref[...] - zcol_ref[...]).astype(jnp.float32)
                      * sx_ref[...] * gamma_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def pann_matmul_packed(x_q: Array, packed_pos: Array, packed_neg: Array,
                       s_x: Array, gamma: Array, zcol: Array | None = None,
                       *, bm: int = 128, bn: int = 128, bk: int = 128,
                       interpret: bool = True) -> Array:
    """y = ((x_q @ (W+ - W-)) - zcol) * s_x * gamma with bit-packed planes.

    x_q (M, K) int8; packed_pos/neg (P, K/8, N) uint8; K % bk == 0, bk % 8.
    zcol (N,) int32: zero-point row (z * colsum(w_q); None = 0), subtracted
    in the exact int32 accumulator before the fused dequant.
    """
    m, k = x_q.shape
    p, k8, n = packed_pos.shape
    assert k8 * 8 == k and bk % 8 == 0
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    if zcol is None:
        zcol = jnp.zeros((n,), jnp.int32)
    k_steps = k // bk
    kernel = functools.partial(_kernel, n_planes=p, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((p, bk // 8, bn), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((p, bk // 8, bn), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_q, packed_pos, packed_neg, s_x, gamma.reshape(1, -1),
      zcol.reshape(1, -1))


# ---------------------------------------------------------------------------
# Fused act-quant prologue + double-buffered packed-plane DMAs
# ---------------------------------------------------------------------------

def _act_kernel(qp_ref, x_hbm, pos_hbm, neg_hbm, gamma_ref, zcol_ref, o_ref,
                xbuf, codes, pos_buf, neg_buf, w_ref, acc_ref, xsem, pos_sem,
                neg_sem, *, n_planes: int, k_steps: int, bk: int, depth: int,
                i_axis: int, j_axis: int, encode_every_step: bool):
    """Packed twin of ``pann_matmul._pann_matmul_act_kernel`` (see its
    docstring for the dataflow): fp32 x is DMA'd + affine-encoded into a
    persistent VMEM codes panel on its first visit, and the (bk/8, bn)
    uint8 plane tiles stream through ``depth`` VMEM slots with the copy of
    plane p+depth-1 started before plane p's wait, overlapping transfer
    with the VPU unpack/shift-add. Planes below the runtime plane_shift
    scalar (qparams[0, 3]) are dead: no DMA, no unpack, no shift-add."""
    i, j = pl.program_id(i_axis), pl.program_id(j_axis)
    kk = pl.program_id(2)
    s = qp_ref[0, 0]
    z = qp_ref[0, 1]
    n_clip = qp_ref[0, 2]
    if qp_ref.shape == (1, 4):
        shift = jnp.round(qp_ref[0, 3]).astype(jnp.int32)
    else:
        shift = jnp.int32(0)
    bm = xbuf.shape[0]
    bn = o_ref.shape[1]

    def _encode_panel():
        cp = pltpu.make_async_copy(
            x_hbm.at[pl.ds(i * bm, bm), pl.ds(kk * bk, bk)], xbuf, xsem)
        cp.start()
        cp.wait()
        # VERBATIM core.quant.affine_encode — change both or neither
        codes[:, pl.ds(kk * bk, bk)] = jnp.clip(
            jnp.round(xbuf[...] / s) + z, 0.0, n_clip).astype(jnp.int8)

    if encode_every_step:
        _encode_panel()
    else:
        pl.when(j == 0)(_encode_panel)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = codes[:, pl.ds(kk * bk, bk)]            # (bm, bk) int8 codes

    def plane_dma(buf, hbm, sem, slot, p):
        return pltpu.make_async_copy(
            hbm.at[p, pl.ds(kk * (bk // 8), bk // 8), pl.ds(j * bn, bn)],
            buf.at[slot], sem.at[slot])

    # predicated pipeline fill from the first LIVE plane (see pann_matmul)
    for p0 in range(n_planes):
        @pl.when(shift == p0)
        def _fill(p0=p0):
            for d in range(depth - 1):
                if p0 + d < n_planes:
                    plane_dma(pos_buf, pos_hbm, pos_sem,
                              (p0 + d) % depth, p0 + d).start()
                    plane_dma(neg_buf, neg_hbm, neg_sem,
                              (p0 + d) % depth, p0 + d).start()

    w_ref[...] = jnp.zeros_like(w_ref)
    for p in range(n_planes):
        @pl.when(p >= shift)
        def _accum_plane(p=p, slot=p % depth):
            nxt = p + depth - 1
            if nxt < n_planes:
                plane_dma(pos_buf, pos_hbm, pos_sem, nxt % depth,
                          nxt).start()
                plane_dma(neg_buf, neg_hbm, neg_sem, nxt % depth,
                          nxt).start()
            plane_dma(pos_buf, pos_hbm, pos_sem, slot, p).wait()
            plane_dma(neg_buf, neg_hbm, neg_sem, slot, p).wait()
            w_ref[...] += (1 << p) * (_unpack_tile(pos_buf[slot])
                                      - _unpack_tile(neg_buf[slot]))
    acc_ref[...] += jax.lax.dot_general(
        x, w_ref[...].astype(jnp.int8), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(kk == k_steps - 1)
    def _done():
        o_ref[...] = ((acc_ref[...] - zcol_ref[...]).astype(jnp.float32)
                      * s * gamma_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "depth",
                                             "grid_order", "interpret"))
def pann_matmul_packed_act(x: Array, packed_pos: Array, packed_neg: Array,
                           qparams: Array, gamma: Array,
                           zcol: Array | None = None, *, bm: int = 128,
                           bn: int = 128, bk: int = 128, depth: int = 2,
                           grid_order: str = "mnk",
                           interpret: bool = True) -> Array:
    """Fused-prologue packed-plane matmul: quantize-in-kernel on the
    2*P/8-bytes-per-weight deployment artifact.

    x (M, K) f32; packed_pos/neg (P, K/8, N) uint8; K % bk == 0, bk % 8 == 0.
    qparams (1, 4) f32 SMEM scalars [s, z, n_lvl, plane_shift]
    (``quant.affine_scale_zp`` outside the kernel — the shared
    cross-backend derivation; plane_shift = LOW planes to skip at runtime,
    see ``pann_matmul.pann_matmul_act``; (1, 3) accepted = shift 0).
    zcol (N,) int32: zero-point/bias row, subtracted in the exact int32
    accumulator. ``depth``/``grid_order`` as in ``pann_matmul_act``.
    """
    m, k = x.shape
    p, k8, n = packed_pos.shape
    assert k8 * 8 == k and bk % 8 == 0
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    assert qparams.shape in ((1, 3), (1, 4)), qparams.shape
    assert depth >= 2, depth
    assert grid_order in ("mnk", "nmk"), grid_order
    if zcol is None:
        zcol = jnp.zeros((n,), jnp.int32)
    k_steps = k // bk
    m_steps, n_steps = m // bm, n // bn
    if grid_order == "mnk":
        grid = (m_steps, n_steps, k_steps)
        i_axis, j_axis = 0, 1
        nidx = lambda a, b, kk: (0, b)      # noqa: E731
        oidx = lambda a, b, kk: (a, b)      # noqa: E731
    else:
        grid = (n_steps, m_steps, k_steps)
        i_axis, j_axis = 1, 0
        nidx = lambda a, b, kk: (0, a)      # noqa: E731
        oidx = lambda a, b, kk: (b, a)      # noqa: E731
    encode_every_step = (grid_order == "nmk" and m_steps > 1)
    kernel = functools.partial(_act_kernel, n_planes=p, k_steps=k_steps,
                               bk=bk, depth=depth, i_axis=i_axis,
                               j_axis=j_axis,
                               encode_every_step=encode_every_step)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # qparams
            pl.BlockSpec(memory_space=pl.ANY),        # x (manual DMA)
            pl.BlockSpec(memory_space=pl.ANY),        # packed_pos
            pl.BlockSpec(memory_space=pl.ANY),        # packed_neg
            pl.BlockSpec((1, bn), nidx),
            pl.BlockSpec((1, bn), nidx),
        ],
        out_specs=pl.BlockSpec((bm, bn), oidx),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, bk), jnp.float32),           # fp32 x landing pad
            pltpu.VMEM((bm, k), jnp.int8),               # persistent codes
            pltpu.VMEM((depth, bk // 8, bn), jnp.uint8),  # plane slots (pos)
            pltpu.VMEM((depth, bk // 8, bn), jnp.uint8),  # plane slots (neg)
            pltpu.VMEM((bk, bn), jnp.int32),             # reconstructed w
            pltpu.VMEM((bm, bn), jnp.int32),             # accumulator
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
        interpret=interpret,
    )(qparams, x, packed_pos, packed_neg, gamma.reshape(1, -1),
      zcol.reshape(1, -1))
