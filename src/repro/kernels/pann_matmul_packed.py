"""Pallas TPU kernel: PANN bit-plane matmul with PACKED plane storage.

The deployment-optimal layout: the binary planes of the unsigned-split PANN
codes are packed 8 bits per byte along K, so weight HBM bytes are
2 * P * K * N / 8 for plane count P. The serving ladder pins P = 7
(``models/serving.LADDER_PLANE_COUNT``): 1.75 byte/weight for BOTH signs,
vs 2 bytes for bf16 and 1 byte for int8 codes.

Layout: packed[p, k8, n] holds bit (k8*8 + j) of plane p in bit j.

The int8 MXU operand is rebuilt with word-wide (SWAR) bit arithmetic, never
one bit per lane: an int32 word of a plane tile (``pltpu.bitcast``) holds
packed rows 4i..4i+3 of one column, i.e. bit j of 32 K rows. Transposing
the (plane x bit) 8x8 bit matrix inside every byte gives, for each j, a
word whose bytes hold the magnitudes of 4 K rows (``_bit_transpose``); a
biased byte subtraction turns the pos/neg magnitudes into int8 two's
complement codes (|code| <= 127, so no byte carries or borrows into the
next), and the words bitcast back to the int8 operand. Its rows come out
permuted within every 256-row group of K: int8 row 256g + 32j + 4s + b
holds K row 256g + 32s + 8b + j. The callers permute x's columns the same
way (``permute_k``); an integer dot does not care about the order, so the
int32 accumulator is exactly the one of ``x_q @ w_q``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

# K rows one reconstruct group covers: 32 packed rows, 8 int32 word rows
# (one vreg of sublanes), 8 bits each.
K_GROUP = 256

_LANES = 128
_BYTE_LSB = 0x01010101
_BYTE_SIGN = -0x7F7F7F80            # 0x80808080 as an int32
# the three block-swap stages of an 8x8 bit transpose: (distance, mask of
# the bits a swap leaves in place in the lower word of a pair)
_TRANSPOSE_STAGES = ((4, 0x0F0F0F0F), (2, 0x33333333), (1, 0x55555555))


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


def permute_k(x: Array) -> Array:
    """Permute x's (M, K) columns into the K order of the SWAR-built
    weight rows: column 256g + 32j + 4s + b takes x's column
    256g + 32s + 8b + j. K % 256 == 0 (pad with zero columns first)."""
    m, k = x.shape
    assert k % K_GROUP == 0, (k, K_GROUP)
    return (x.reshape(m, k // K_GROUP, 8, 4, 8)
            .transpose(0, 1, 4, 2, 3).reshape(m, k))


def _keep_mask(shift) -> Array:
    """Per-byte mask of the magnitude bits of planes >= ``shift`` (the
    live planes of a rung view; int32 scalar)."""
    shift = jnp.asarray(shift, jnp.int32)
    return ((jnp.int32(0x7F) << shift) & 0x7F) * _BYTE_LSB


def _bit_transpose(words: list) -> list:
    """Transpose the 8x8 bit matrix in every byte lane of 8 int32 words:
    bit p of byte b of out[j] = bit j of byte b of words[p]. ``None``
    stands for an all-zero word (planes past the plane count)."""
    w = list(words) + [None] * (8 - len(words))
    for s, m in _TRANSPOSE_STAGES:
        for p in range(8):
            if p & s:
                continue
            a, b = w[p], w[p + s]
            if b is None:
                if a is not None:
                    w[p + s] = (a >> s) & m
                    w[p] = a & m
            elif a is None:
                w[p] = (b & m) << s
                w[p + s] = b & ~m
            else:
                t = ((a >> s) ^ b) & m
                w[p] = a ^ (t << s)
                w[p + s] = b ^ t
    return w


def _group_words(pos: Array, neg: Array, keep) -> Array:
    """One 256-row K group: (P, 32, C) uint8 pos/neg plane rows -> (64, C)
    int32 words of signed int8 codes, 8 word rows per bit j, j-major.
    ``keep`` (int32 scalar or None) masks the dead planes' bits."""
    mags = []
    for planes in (pos, neg):
        words = [pltpu.bitcast(planes[p], jnp.int32)
                 for p in range(planes.shape[0])]
        mags.append(_bit_transpose(words))
    out = []
    for a, n in zip(*mags):
        if keep is not None:
            a, n = a & keep, n & keep
        # per byte: (128 + a) - n in [1, 255] never borrows; ^ 0x80 then
        # leaves a - n in two's complement
        out.append(((a | _BYTE_SIGN) - n) ^ _BYTE_SIGN)
    return jnp.concatenate(out, axis=0)


def swar_tile(pos: Array, neg: Array, shift=None) -> Array:
    """(P, R, C) uint8 packed pos/neg plane rows (P <= 7, R % 32 == 0) ->
    the (8R, C) int8 codes sum_p 2^p (pos_p - neg_p), rows in the
    ``permute_k`` order, planes below ``shift`` dropped. Runs inside a
    kernel or as plain jnp."""
    p, r, _ = pos.shape
    assert p <= 7 and r % (K_GROUP // 8) == 0, pos.shape
    keep = None if shift is None else _keep_mask(shift)
    rows = K_GROUP // 8
    words = [_group_words(pos[:, g * rows:(g + 1) * rows],
                          neg[:, g * rows:(g + 1) * rows], keep)
             for g in range(r // rows)]
    return pltpu.bitcast(jnp.concatenate(words, axis=0), jnp.int8)


def _kernel(x_ref, pos_ref, neg_ref, sx_ref, gamma_ref, zcol_ref, o_ref,
            acc_ref, *, k_steps: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = swar_tile(pos_ref[...], neg_ref[...])
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
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

    x_q (M, K) int8; packed_pos/neg (P, K/8, N) uint8; M % bm == N % bn
    == 0. zcol (N,) int32: zero-point row (z * colsum(w_q); None = 0),
    subtracted in the exact int32 accumulator before the fused dequant.
    ``bk`` is rounded up to a multiple of ``K_GROUP``, and K is padded to
    a multiple of it with zero columns and zero planes (an exact no-op).
    """
    m, k = x_q.shape
    p, k8, n = packed_pos.shape
    assert k8 * 8 == k
    assert m % bm == 0 and n % bn == 0
    if zcol is None:
        zcol = jnp.zeros((n,), jnp.int32)
    bk = -(-bk // K_GROUP) * K_GROUP
    pad = (-k) % bk
    x_q = permute_k(jnp.pad(x_q, ((0, 0), (0, pad))))
    packed_pos = jnp.pad(packed_pos, ((0, 0), (0, pad // 8), (0, 0)))
    packed_neg = jnp.pad(packed_neg, ((0, 0), (0, pad // 8), (0, 0)))
    k_steps = (k + pad) // bk
    kernel = functools.partial(_kernel, k_steps=k_steps)
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
# Fused act-quant prologue + plane DMAs pipelined across grid steps
# ---------------------------------------------------------------------------

def _act_kernel(qp_ref, layer_ref, x_hbm, pos_hbm, neg_hbm, gamma_ref,
                zcol_ref, o_ref, xbuf, codes, pos_buf, neg_buf, w_ref,
                acc_ref, xsem, pos_sem, neg_sem, *,
                grid: tuple[int, int, int], bk: int, depth: int,
                i_axis: int, j_axis: int, encode_every_step: bool):
    """Packed twin of ``pann_matmul._pann_matmul_act_kernel`` (see its
    docstring for the prologue): fp32 x is DMA'd + affine-encoded into a
    persistent VMEM codes panel on its first visit.

    Planes: the store is (L, P, K/8, N) and the call reads layer
    ``layer_ref[0]`` of it (an SMEM scalar, so every layer of a stack
    shares this one kernel). A grid step's (P, bk/8, bn) plane tiles of
    that layer land in one of
    ``depth`` VMEM slots, 128 lanes at a time, so that every (32, 128)
    piece of a plane is one contiguous vreg there. The copies for step
    t+depth-1 start before step t waits on its own, so the plane stream
    runs on across grid steps while the VPU rebuilds the int8 operand
    (``_group_words``, one 256 x 128 piece at a time, into the ``w_ref``
    words) and the MXU takes the dot. All P planes are copied; those
    below the runtime plane_shift scalar (qparams[0, 3]) are masked out
    of the rebuilt codes."""
    i, j = pl.program_id(i_axis), pl.program_id(j_axis)
    kk = pl.program_id(2)
    k_steps = grid[2]
    n_steps = grid[0] * grid[1] * k_steps
    t = (pl.program_id(0) * grid[1] + pl.program_id(1)) * k_steps + kk
    s = qp_ref[0, 0]
    z = qp_ref[0, 1]
    n_clip = qp_ref[0, 2]
    if qp_ref.shape == (1, 4):
        shift = jnp.round(qp_ref[0, 3]).astype(jnp.int32)
    else:
        shift = jnp.int32(0)
    layer = layer_ref[0]
    bm = xbuf.shape[0]
    bk8 = bk // 8
    n_chunks = pos_buf.shape[1]            # bn / 128

    def plane_copies(step):
        """Step ``step``'s pos and neg copies, one per 128-lane chunk."""
        outer = step // k_steps
        j_step = outer % grid[1] if j_axis == 1 else outer // grid[1]
        rows = pl.ds(pl.multiple_of((step % k_steps) * bk8, bk8), bk8)
        slot = step % depth
        return [pltpu.make_async_copy(
                    hbm.at[layer, :, rows, pl.ds(pl.multiple_of(
                        (j_step * n_chunks + c) * _LANES, _LANES), _LANES)],
                    buf.at[slot, c], sem.at[slot])
                for c in range(n_chunks)
                for hbm, buf, sem in ((pos_hbm, pos_buf, pos_sem),
                                      (neg_hbm, neg_buf, neg_sem))]

    @pl.when(t == 0)
    def _fill():
        for d in range(min(depth - 1, n_steps)):
            for cp in plane_copies(d):
                cp.start()

    if depth - 1 < n_steps:
        @pl.when(t + depth - 1 < n_steps)
        def _prefetch():
            for cp in plane_copies(t + depth - 1):
                cp.start()

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

    for cp in plane_copies(t):
        cp.wait()
    slot = t % depth
    keep = _keep_mask(shift)
    rows = K_GROUP // 8
    n_groups = bk // K_GROUP

    # one loop over the (chunk, group) pieces, not an unrolled sweep: the
    # kernel's trace, paid again by every process that compiles or loads
    # the program, stays that of one piece
    def rebuild(i, carry):
        c, g = i // n_groups, i % n_groups
        r = pl.ds(pl.multiple_of(g * rows, rows), rows)
        w_ref[c, pl.ds(pl.multiple_of(g * 8 * 8, 8 * 8), 8 * 8)] = \
            _group_words(pos_buf[slot, c, :, r], neg_buf[slot, c, :, r],
                         keep)
        return carry

    jax.lax.fori_loop(0, n_chunks * n_groups, rebuild, 0)
    w = jnp.concatenate([pltpu.bitcast(w_ref[c], jnp.int8)
                         for c in range(n_chunks)], axis=1)
    acc_ref[...] += jax.lax.dot_general(
        codes[:, pl.ds(kk * bk, bk)], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(kk == k_steps - 1)
    def _done():
        o_ref[...] = ((acc_ref[...] - zcol_ref[...]).astype(jnp.float32)
                      * s * gamma_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "depth",
                                             "grid_order", "interpret"))
def pann_matmul_packed_act(x: Array, packed_pos: Array, packed_neg: Array,
                           qparams: Array, gamma: Array,
                           zcol: Array | None = None, layer=None, *,
                           bm: int = 128, bn: int = 128, bk: int = 256,
                           depth: int = 2,
                           grid_order: str = "mnk",
                           interpret: bool = True) -> Array:
    """Fused-prologue packed-plane matmul: quantize-in-kernel on the
    2*P/8-bytes-per-weight deployment artifact.

    x (M, K) f32 with its columns in ``permute_k`` order; packed_pos/neg
    (L, P, K/8, N) uint8, a stack of L layers' planes, of which the call
    reads ``layer`` (an int32 scalar, traced: every layer shares one
    kernel) where it lies; (P, K/8, N) is the stack of one (L = 1, layer
    0). P <= 7; K % bk == 0, bk % K_GROUP == 0, N % bn == 0, bn % 128 ==
    0.
    qparams (1, 4) f32 SMEM scalars [s, z, n_lvl, plane_shift]
    (``quant.affine_scale_zp`` outside the kernel — the shared
    cross-backend derivation; plane_shift = LOW planes to skip at runtime,
    see ``pann_matmul.pann_matmul_act``; (1, 3) accepted = shift 0).
    zcol (N,) int32: zero-point/bias row, subtracted in the exact int32
    accumulator. ``depth`` = grid steps whose planes are in VMEM at once
    (2: double-buffered); ``grid_order`` as in ``pann_matmul_act``.
    """
    if packed_pos.ndim == 3:
        packed_pos, packed_neg = packed_pos[None], packed_neg[None]
    layer = jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1)
    m, k = x.shape
    _, p, k8, n = packed_pos.shape
    assert packed_neg.shape == packed_pos.shape
    assert k8 * 8 == k and bk % K_GROUP == 0 and p <= 7
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bn % _LANES == 0
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
    kernel = functools.partial(_act_kernel, grid=grid, bk=bk, depth=depth,
                               i_axis=i_axis, j_axis=j_axis,
                               encode_every_step=encode_every_step)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # qparams
            pl.BlockSpec(memory_space=pltpu.SMEM),       # layer
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
            # plane slots: a grid step's planes, 128 lanes a chunk
            pltpu.VMEM((depth, bn // _LANES, p, bk // 8, _LANES), jnp.uint8),
            pltpu.VMEM((depth, bn // _LANES, p, bk // 8, _LANES), jnp.uint8),
            # rebuilt int8 codes, four to an int32 word, a chunk at a time
            pltpu.VMEM((bn // _LANES, bk // 4, _LANES), jnp.int32),
            pltpu.VMEM((bm, bn), jnp.int32),             # accumulator
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
        interpret=interpret,
    )(qparams, layer, x, packed_pos, packed_neg, gamma.reshape(1, -1),
      zcol.reshape(1, -1))
