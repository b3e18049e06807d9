"""Pallas TPU kernel: PANN bit-plane matmul (the paper's Eq. 10/11 adapted to
the MXU — see DESIGN.md §2).

Weights are stored as binary bit-planes of the unsigned-split PANN integer
codes: planes_pos/planes_neg of shape (P, K, N) with P = b_R (2..6 bits in
practice, Table 14). Activations are unsigned integer codes (half-range,
App. A.4) in int8.

Two compute modes, numerically identical:

  * ``mode='fused'``  — reconstruct w_q = sum_k 2^k (B+_k - B-_k) in VMEM
    (VPU shifts/adds) and issue a single int8 x int8 MXU pass per tile.
    This is the fast path: the MXU is TPU's cheapest compute primitive.
  * ``mode='planes'`` — one MXU pass per binary plane with separate pos/neg
    int32 accumulators, combined by shift-add and one final subtraction —
    the literal Eq. (10) + Fig. 12(b) dataflow.

Both paths accumulate in int32 and fuse the output dequantization
(y = y_int * s_x * gamma[n]), so the integer result is bit-exact w.r.t. the
reference oracle in ``repro.kernels.ref``.

``pann_matmul_act`` is the fused-prologue variant (ROADMAP item 3): it takes
fp32 activations straight from HBM and computes the affine codes
``clip(round(x/s) + z, 0, n)`` tile-locally in VMEM — the int8 code tensor
never exists in HBM, removing the fp32→int8 round-trip the standalone
``quantize_act`` path pays per projection. The (s, z, n) scalars are computed
ONCE outside the kernel (a cheap global reduction; see ``dispatch``) with the
one ``core.quant`` affine convention, so fused and unfused paths stay
bit-exact. Weight planes are streamed with MANUAL double-buffered DMAs:
plane i+1 is in flight while plane i is being shift-added/multiplied.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _pann_matmul_kernel(x_ref, pos_ref, neg_ref, sx_ref, gamma_ref, zcol_ref,
                        o_ref, acc_ref, *, n_planes: int, k_steps: int,
                        mode: str):
    """Grid = (M/bm, N/bn, K/bk); accumulates over the k dimension."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                      # (bm, bk) int8, non-negative codes

    if mode == "fused":
        # int32 shift-add (Mosaic has no int8 vector arithmetic); the
        # reconstructed codes fit int8 and are cast at the MXU operand
        w = jnp.zeros(pos_ref.shape[1:], jnp.int32)
        for p in range(n_planes):
            w = w + (1 << p) * (pos_ref[p].astype(jnp.int32)
                                - neg_ref[p].astype(jnp.int32))
        acc_ref[...] += jax.lax.dot_general(
            x, w.astype(jnp.int8), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    else:  # 'planes': per-plane addition-only passes, pos/neg separated
        acc_p = jnp.zeros(acc_ref.shape, jnp.int32)
        acc_n = jnp.zeros(acc_ref.shape, jnp.int32)
        for p in range(n_planes):
            shift = jnp.int32(1 << p)
            acc_p += shift * jax.lax.dot_general(
                x, pos_ref[p], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            acc_n += shift * jax.lax.dot_general(
                x, neg_ref[p], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
        acc_ref[...] += acc_p - acc_n   # the one Eq.-(6) subtraction

    @pl.when(k == k_steps - 1)
    def _finalize():
        # the zero-point correction lands in the EXACT int32 accumulator
        # domain (kernels/dispatch: zcol = z * colsum(w_q)); only the two
        # dequant multiplies round
        y = (acc_ref[...] - zcol_ref[...]).astype(jnp.float32)
        o_ref[...] = y * sx_ref[...] * gamma_ref[...]


@functools.partial(jax.jit, static_argnames=("mode", "bm", "bn", "bk",
                                             "interpret"))
def pann_matmul(x_q: Array, planes_pos: Array, planes_neg: Array,
                s_x: Array, gamma: Array, zcol: Array | None = None, *,
                mode: str = "fused", bm: int = 128, bn: int = 128,
                bk: int = 128, interpret: bool = True) -> Array:
    """y[m, n] = ((x_q @ (W+ - W-))[m, n] - zcol[n]) * s_x[m] * gamma[n].

    x_q:        (M, K) int8, unsigned activation codes
    planes_pos: (P, K, N) int8 in {0, 1}
    planes_neg: (P, K, N) int8 in {0, 1}
    s_x:        (M, 1) f32 per-row activation scales
    gamma:      (N,)  f32 per-channel PANN steps
    zcol:       (N,) int32 zero-point row (z * colsum(w_q); None = 0) —
                the asymmetric-activation correction fused into the
                accumulator before dequant (DESIGN.md §4)
    """
    m, k = x_q.shape
    p, k2, n = planes_pos.shape
    assert k == k2 and planes_neg.shape == planes_pos.shape
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    if zcol is None:
        zcol = jnp.zeros((n,), jnp.int32)
    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps)

    kernel = functools.partial(_pann_matmul_kernel, n_planes=p,
                               k_steps=k_steps, mode=mode)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((p, bk, bn), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((p, bk, bn), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_q, planes_pos, planes_neg, s_x, gamma.reshape(1, -1),
      zcol.reshape(1, -1))


# ---------------------------------------------------------------------------
# Fused act-quant prologue + double-buffered plane DMAs
# ---------------------------------------------------------------------------

def _pann_matmul_act_kernel(qp_ref, x_hbm, pos_hbm, neg_hbm, gamma_ref,
                            zcol_ref, o_ref, xbuf, codes, pos_buf, neg_buf,
                            w_ref, acc_ref, xsem, pos_sem, neg_sem, *,
                            n_planes: int, k_steps: int, bk: int, mode: str,
                            depth: int, i_axis: int, j_axis: int,
                            encode_every_step: bool):
    """Grid = (.., .., K/bk), kk innermost; (i, j) axis order is tunable.

    Dataflow per grid step:
      * first visit of a row panel: DMA the (bm, bk) fp32 x chunk from HBM
        and encode it into the persistent (bm, K) int8 ``codes`` scratch
        with the affine map ``clip(round(x/s) + z, 0, n)`` — op-for-op
        ``core.quant.affine_encode``. Later visits re-read ``codes`` from
        VMEM, so the fp32 activations cross HBM exactly once and the codes
        never do. (In 'nmk' grid order with more than one row panel the
        panel is re-encoded per tile — see ``pann_matmul_act``.)
      * every step: the live weight-plane tiles stream through ``depth``
        VMEM slots with manual DMAs — plane p+depth-1's copy is started
        BEFORE plane p's wait, so transfers overlap the current plane's
        VPU shift-add (and MXU pass in 'planes' mode).
      * planes below the runtime ``plane_shift`` scalar (qparams[0, 3]) are
        DEAD: their DMAs are never started and their shift-adds/MXU passes
        are predicated away with ``pl.when``. Plane weights stay the STATIC
        ``2^p``, so a rung view over a max-R store computes exactly
        ``q @ (c >> s << s)`` and dequantizes with the unchanged max-R
        gamma (truncation-consistent views, DESIGN.md §11).
    """
    i, j = pl.program_id(i_axis), pl.program_id(j_axis)
    kk = pl.program_id(2)
    s = qp_ref[0, 0]
    z = qp_ref[0, 1]
    n_clip = qp_ref[0, 2]
    # plane_shift rides as DATA (SMEM scalar) so every ladder rung shares
    # one compiled kernel; (1, 3) callers predate views and mean shift 0
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
            hbm.at[p, pl.ds(kk * bk, bk), pl.ds(j * bn, bn)],
            buf.at[slot], sem.at[slot])

    # Predicated pipeline fill: exactly one branch fires — the first LIVE
    # plane — and primes depth-1 slots from there. Dead planes (p < shift)
    # get no DMA at all: the skip is a real HBM-traffic win, not a masked
    # multiply.
    for p0 in range(n_planes):
        @pl.when(shift == p0)
        def _fill(p0=p0):
            for d in range(depth - 1):
                if p0 + d < n_planes:
                    plane_dma(pos_buf, pos_hbm, pos_sem,
                              (p0 + d) % depth, p0 + d).start()
                    plane_dma(neg_buf, neg_hbm, neg_sem,
                              (p0 + d) % depth, p0 + d).start()

    if mode == "fused":
        # w lives in a VMEM scratch (not a loop-carried register) because
        # the per-plane bodies must be pl.when-predicated — a wait on a
        # never-started copy would hang — and predicated bodies can only
        # mutate refs. It is int32: Mosaic has no int8 vector arithmetic,
        # so the shift-add runs wide and casts at the MXU operand.
        w_ref[...] = jnp.zeros_like(w_ref)
        for p in range(n_planes):
            @pl.when(p >= shift)
            def _accum_plane(p=p, slot=p % depth):
                nxt = p + depth - 1
                if nxt < n_planes:
                    plane_dma(pos_buf, pos_hbm, pos_sem,
                              nxt % depth, nxt).start()
                    plane_dma(neg_buf, neg_hbm, neg_sem,
                              nxt % depth, nxt).start()
                plane_dma(pos_buf, pos_hbm, pos_sem, slot, p).wait()
                plane_dma(neg_buf, neg_hbm, neg_sem, slot, p).wait()
                w_ref[...] += (1 << p) * (
                    pos_buf[slot].astype(jnp.int32)
                    - neg_buf[slot].astype(jnp.int32))
        acc_ref[...] += jax.lax.dot_general(
            x, w_ref[...].astype(jnp.int8), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    else:  # 'planes': per-plane addition-only passes, pos/neg separated
        for p in range(n_planes):
            @pl.when(p >= shift)
            def _accum_plane(p=p, slot=p % depth):
                nxt = p + depth - 1
                if nxt < n_planes:
                    plane_dma(pos_buf, pos_hbm, pos_sem,
                              nxt % depth, nxt).start()
                    plane_dma(neg_buf, neg_hbm, neg_sem,
                              nxt % depth, nxt).start()
                plane_dma(pos_buf, pos_hbm, pos_sem, slot, p).wait()
                plane_dma(neg_buf, neg_hbm, neg_sem, slot, p).wait()
                pw = jnp.int32(1 << p)
                # per-plane pos-neg subtraction is exact in int32, so the
                # Eq.-(6) result is unchanged vs one deferred subtraction
                acc_ref[...] += pw * jax.lax.dot_general(
                    x, pos_buf[slot], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                acc_ref[...] -= pw * jax.lax.dot_general(
                    x, neg_buf[slot], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)

    @pl.when(kk == k_steps - 1)
    def _finalize():
        y = (acc_ref[...] - zcol_ref[...]).astype(jnp.float32)
        o_ref[...] = y * s * gamma_ref[...]


@functools.partial(jax.jit, static_argnames=("mode", "bm", "bn", "bk",
                                             "depth", "grid_order",
                                             "interpret"))
def pann_matmul_act(x: Array, planes_pos: Array, planes_neg: Array,
                    qparams: Array, gamma: Array, zcol: Array | None = None,
                    *, mode: str = "fused", bm: int = 128, bn: int = 128,
                    bk: int = 128, depth: int = 2, grid_order: str = "mnk",
                    interpret: bool = True) -> Array:
    """Fused-prologue bit-plane matmul: quantize-in-kernel, codes never in HBM.

    y[m, n] = ((q(x) @ (W+ - W-))[m, n] - zcol[n]) * s * gamma[n]
    with q(x) = clip(round(x/s) + z, 0, n_lvl) computed in VMEM.

    x:          (M, K) f32 activations (HBM-resident; read once per row panel)
    planes_pos: (P, K, N) int8 in {0, 1}   (HBM; manually multi-buffered)
    planes_neg: (P, K, N) int8 in {0, 1}
    qparams:    (1, 4) f32 SMEM scalars [s, z, n_lvl, plane_shift] —
                (s, z) computed outside with ``core.quant.affine_scale_zp``
                so every backend shares one derivation (the bit-exactness
                contract); ``plane_shift`` is the number of LOW bit-planes
                to skip at runtime (0 = all planes live; a rung view over a
                max-R plane store passes s > 0 and the kernel never DMAs
                the dead planes). (1, 3) is accepted for pre-view callers
                and means plane_shift = 0.
    gamma:      (N,)  f32 per-channel PANN steps
    zcol:       (N,) int32 zero-point/bias row (z * colsum(w_q) [- b_q];
                None = 0), subtracted in the exact int32 accumulator
    depth:      DMA pipeline slots per plane stream (>= 2; autotuned)
    grid_order: "mnk" (row panels outermost; the x prologue encodes each
                panel once) or "nmk" (N outermost; with more than one row
                panel the prologue re-encodes per tile — only ever a win
                when M is a single panel, where both orders are identical
                traffic and the autotuner just picks the faster schedule)
    """
    m, k = x.shape
    p, k2, n = planes_pos.shape
    assert k == k2 and planes_neg.shape == planes_pos.shape
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
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
    # 'nmk' revisits row panels under a changing i, so the persistent codes
    # scratch is only reusable when there is a single row panel
    encode_every_step = (grid_order == "nmk" and m_steps > 1)

    kernel = functools.partial(_pann_matmul_act_kernel, n_planes=p,
                               k_steps=k_steps, bk=bk, mode=mode,
                               depth=depth, i_axis=i_axis, j_axis=j_axis,
                               encode_every_step=encode_every_step)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # qparams
            pl.BlockSpec(memory_space=pl.ANY),        # x (manual DMA)
            pl.BlockSpec(memory_space=pl.ANY),        # planes_pos
            pl.BlockSpec(memory_space=pl.ANY),        # planes_neg
            pl.BlockSpec((1, bn), nidx),
            pl.BlockSpec((1, bn), nidx),
        ],
        out_specs=pl.BlockSpec((bm, bn), oidx),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, bk), jnp.float32),           # fp32 x landing pad
            pltpu.VMEM((bm, k), jnp.int8),               # persistent codes
            pltpu.VMEM((depth, bk, bn), jnp.int8),       # plane slots (pos)
            pltpu.VMEM((depth, bk, bn), jnp.int8),       # plane slots (neg)
            pltpu.VMEM((bk, bn), jnp.int32),             # reconstructed w
            pltpu.VMEM((bm, bn), jnp.int32),             # accumulator
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
        interpret=interpret,
    )(qparams, x, planes_pos, planes_neg, gamma.reshape(1, -1),
      zcol.reshape(1, -1))
