"""Pallas TPU kernel: one-token GQA decode attention read DIRECTLY off the
packed bit-plane KV cache (docs/kv_cache.md; DESIGN.md §10).

The cache stores K/V as unsigned affine codes, bit-plane-decomposed and
packed 8 bits/byte along head_dim (``kernels.ref.cache_planes`` — NOT the
weight-plane ``pack_planes``, which packs along K). The kernel runs the
exact int32 QK^T with BOTH zero points corrected inside the accumulator
(the serving_linear ``zcol`` convention, applied twice), the fp32 softmax
epilogue in the oracle's exact op sequence (its numerators on a fixed
2^15 grid, so the normalizer is an exact int32 sum that no reduction
order can round differently), then re-quantizes the probabilities to a
fixed 2^14 grid for an exact int32 PV pass — ``sum_s p = 1`` bounds
``pq @ vq`` by ``127 * 2^14``, int32-safe for ANY sequence length.
Bit-identical (fp32) to ``kernels.ref.decode_attention_ref``
(tests/test_kv_cache_quant.py).

TPU layout: the cache is kept in the kernel's layout
(``models.attention.init_cache``): per batch row and plane an
(S, K * hd/8) panel, one position per row, column k * hd/8 + j holding
byte j of KV head k's codes; S is the cache's ``max_len``. Row-major, it
is the layout the TPU itself keeps for the array when K * hd/8 fills
whole 128-byte lane tiles, so the kernel DMAs the planes where they lie
and no transpose or copy of the cache runs outside the custom call.

Grid: (B, K / hb), one batch row and a block of ``hb`` KV heads a cell
(``head_block``, from the shapes alone). A head block at one plane is a
run of hb * hd/8 columns, whole lane tiles (or every head), so each live
plane's live position block is one DMA for the whole head block; the
next cell's copies start before this cell waits on its own.

Live blocks: only the ceil((pos + 1) / 128) position blocks that hold a
cached position are copied, rebuilt and multiplied (an SMEM scalar beside
``pos``). The scores of the other blocks are NEG_INF, as masked positions
are, so their exp rounds to 0 on the 2^15 grid, their probability codes
are 0 and the V scale's maximum runs over valid positions only: the
result is that of reading them. When S is not a multiple of 128 the last
block is copied in whole 32-row 8-bit tiles; a tile's rows past S exist
in memory but hold no position, and like the slot rows no copy fills
they lie past ``pos``: masked scores, probability codes 0. Likewise a
head block of fewer than 128 columns (MQA, or K * hd/8 off the lane
tiles) is copied in whole lane tiles; the padding columns belong to no
head, so their query is zero and their output is dropped. (The
interpreter keeps arrays untiled, so the wrapper hands it the planes
padded to whole tiles, with all-ones bytes in the padding.)

Word-wide rebuild: an int32 word of a (32, hd/8-columns) plane piece
holds positions 4s..4s+3 of one byte column; the 8x8 bit transpose of
``pann_matmul_packed.plane_codes`` turns the planes' words into eight
words, one per bit i of the byte, whose bytes ARE the int8 codes of head
dim 8c + i, four positions to a word. So a block's codes come out as
eight (128, hb * hd/8) int8 matrices, codes[i][t, c] = code of position
t at head dim 8c + i; the wrapper splits the query's head dims the same
way and joins the output's back. Mosaic has no int8 vector arithmetic
and no int32 matmul: codes are only ever int8 at the MXU operands.

One MXU pass a block and bit serves every head of the cell: the query
rows form block-diagonal int8 matrices (row h*G + g holds q[h, g] - z_q
in head h's columns and 0 elsewhere), so QK^T of all hb heads is the sum
of eight (rows, hb*hd/8) x (128, hb*hd/8)^T products, and ``- z_k *
rowsum`` finishes the zero-point correction. PV multiplies the
probability codes' three int8-range limbs (bits 0-6, 7-13, 14), stacked
as rows, by each bit's V codes and keeps the diagonal blocks; the limbs'
int32 products recombine exactly.

Plane skipping: cache codes are <= n_lvl < 2^b, so only the LOW
``planes_active`` planes can be nonzero (the opposite prefix from the
weight kernels, which skip low planes under a view shift). The per-role
active counts ride in as SMEM DATA scalars — derived from the cache's
``k_nlvl``/``v_nlvl`` leaves — so a 2-bit cache rung DMAs 2 planes, not
7, and masks the others' bits out of the rebuilt codes, while every rung
shares one compiled kernel. Skipped planes are all-zero in the cache by
construction, so the jnp oracle needs no planes_active argument.

Whole-S panels: a cell's plane slots hold every position of every plane,
and its scores one row per query of the block over all S positions. No
K-grid accumulation loop is needed at the engines' lengths; a
sequence-blocked online-softmax variant is the follow-up if contexts
outgrow VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pann_matmul_packed import plane_codes
from repro.kernels.ref import (CACHE_BLOCK, CACHE_PLANES, EXP_SCALE,
                               MAX_CACHE_LEN, PROB_SCALE)

Array = jax.Array

NEG_INF = -1e30     # matches models.attention.NEG_INF / ref._CACHE_NEG_INF

_POS_TILE = 32          # rows of a TPU 8-bit tile: one rebuild group
_LANES = 128            # lanes of a TPU tile
_SLOT_BUDGET = 6 << 20  # VMEM of the plane slots: K and V, two cells
_VMEM_LIMIT = 64 << 20  # of v5e's 128 MiB: whole-S slots of long caches
_MAX_ROWS = 64          # query rows of a cell (hb * G)
_BYTE_LSB = 0x01010101


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _slot_bytes(hb: int, hd: int, s: int) -> int:
    """VMEM of a head block's plane slots: K and V, two cells in flight,
    every plane and position."""
    return 2 * 2 * CACHE_PLANES * s * _round_up(hb * hd // 8, _LANES)


def live_blocks(pos, s: int):
    """The 128-position blocks of a cache of ``s`` positions that the
    kernel reads at cache position ``pos``: those up to the one holding
    ``pos`` (a traced scalar in the kernel's wrapper, an int on the host).
    ``live_blocks(s - 1, s)`` is every block of the cache."""
    if isinstance(pos, int):
        pos = min(pos, s - 1)
    return pos // CACHE_BLOCK + 1


def head_block(kh: int, g: int, hd: int, s: int) -> int:
    """KV heads a grid cell takes: the most that divide ``kh``, span whole
    lane tiles of packed columns (or all ``kh`` heads), keep the cell's
    query rows within ``_MAX_ROWS`` and its plane slots within
    ``_SLOT_BUDGET``; the fewest such heads when none fits."""
    legal = [hb for hb in range(1, kh + 1)
             if kh % hb == 0 and (hb * hd // 8 % _LANES == 0 or hb == kh)]
    fits = [hb for hb in legal if hb * g <= _MAX_ROWS
            and _slot_bytes(hb, hd, s) <= _SLOT_BUDGET]
    return max(fits) if fits else min(legal)


def _decode_attention_kernel(sp_ref, qs_ref, q_ref, colh_ref, kp_hbm,
                             ks_ref, kz_ref, vp_hbm, vs_ref, vz_ref, o_ref,
                             kbuf, vbuf, words, qbd_ref, rows_ref, sc_ref,
                             pq_ref, acc_ref, ksem, vsem, *,
                             n_head_blocks: int, g: int, window,
                             softcap: float, exp_scale: float,
                             prob_scale: float):
    """Grid = (B, K / hb): one cell per (batch row, head block).

    ``sp_ref`` [pos, live blocks, live K planes, live V planes];
    ``q_ref`` (G, 8, C) the query minus its zero point, [g, i, c] at head
    dim 8 * (c % hd/8) + i of the block's head c // (hd/8); ``colh_ref``
    (1, C) each column's head in the block (-1 past its heads); score
    rows (M, ...) are query h*G + g, padded to whole tiles."""
    bi, hi = pl.program_id(0), pl.program_id(1)
    n_cells = pl.num_programs(0) * n_head_blocks
    t = bi * n_head_blocks + hi
    slot = t % 2
    pos, n_live = sp_ref[0, 0], sp_ref[0, 1]
    k_pact, v_pact = sp_ref[0, 2], sp_ref[0, 3]
    q_scale = qs_ref[0, 0]
    m, sp = sc_ref.shape                 # lanes: whole position blocks
    s = ks_ref.shape[1]                  # cached positions
    n_blocks = sp // CACHE_BLOCK
    n_full, tail = divmod(s, CACHE_BLOCK)
    n_planes, cols = kbuf.shape[1], kbuf.shape[3]   # whole lane tiles

    def blk(j):
        return pl.ds(pl.multiple_of(j * CACHE_BLOCK, CACHE_BLOCK),
                     CACHE_BLOCK)

    def plane_copies(hbm, buf, sem, pact, cell, cell_slot, start: bool):
        """Start (or wait on) a cell's copies: one per live plane and
        live position block, covering the cell's whole head block."""
        cb, ch = cell // n_head_blocks, cell % n_head_blocks

        def copy(rows):
            for p in range(n_planes):
                @pl.when(p < pact)
                def _(p=p):
                    cp = pltpu.make_async_copy(
                        hbm.at[cb, p, rows, pl.ds(ch * cols, cols)],
                        buf.at[cell_slot, p, rows], sem.at[cell_slot])
                    if start:
                        cp.start()
                    else:
                        cp.wait()

        def block(j, carry):
            pl.when(j < n_live)(lambda: copy(blk(j)))
            return carry

        if n_full:
            jax.lax.fori_loop(0, n_full, block, 0)
        if tail:
            # the last block, live only as the last of all: its rows in
            # whole 8-bit tiles
            pl.when(n_live == n_blocks)(lambda: copy(pl.ds(
                pl.multiple_of((n_live - 1) * CACHE_BLOCK, CACHE_BLOCK),
                _round_up(tail, _POS_TILE))))

    def both(cell, cell_slot, start: bool):
        plane_copies(kp_hbm, kbuf, ksem, k_pact, cell, cell_slot, start)
        plane_copies(vp_hbm, vbuf, vsem, v_pact, cell, cell_slot, start)

    @pl.when(t == 0)
    def _first():
        both(t, 0, True)

    @pl.when(t + 1 < n_cells)
    def _next():
        both(t + 1, 1 - slot, True)

    def rebuild(buf, pact, j) -> list:
        """Block ``j``'s codes, live planes only: eight (128, C) int8
        matrices, one per bit i of the packed bytes."""
        keep = ((jnp.int32(1) << pact) - 1) * _BYTE_LSB
        for grp in range(CACHE_BLOCK // _POS_TILE):
            rows = pl.ds(pl.multiple_of(j * CACHE_BLOCK + grp * _POS_TILE,
                                        _POS_TILE), _POS_TILE)
            for i, w in enumerate(plane_codes(buf[slot, :, rows, :])):
                words[i, pl.ds(grp * 8, 8), :] = w & keep
        return [pltpu.bitcast(words[i], jnp.int8) for i in range(8)]

    # block-diagonal query, one matrix per bit i: row h*G + g holds
    # q[h, g] in head h's columns
    row = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    first_row = colh_ref[...] * g                        # (1, C)
    rowsum = jnp.zeros((m, 1), jnp.int32)
    for i in range(8):
        qb = jnp.zeros((m, cols), jnp.int32)
        for gi in range(g):
            qb = jnp.where(row == first_row + gi, q_ref[gi, i:i + 1], qb)
        rowsum += jnp.sum(qb, axis=1, keepdims=True)     # (M, 1)
        qbd_ref[i] = qb.astype(jnp.int8)

    def valid_at(k_pos):
        valid = k_pos <= pos
        if window is not None:
            valid &= (pos - k_pos) < window
        return valid

    # this batch row's K/V scale and zero-point rows, picked out of their
    # blocks (Mosaic loads no row at an unaligned dynamic index)
    row_blk = ks_ref.shape[0]
    pick = (jax.lax.broadcasted_iota(jnp.int32, (row_blk, 1), 0)
            == bi % row_blk)
    if s < sp:                           # lanes past S: finite, unused
        rows_ref[...] = jnp.zeros_like(rows_ref)
    for i, ref in enumerate((ks_ref, kz_ref, vs_ref, vz_ref)):
        rows_ref[i:i + 1, :s] = jnp.max(
            jnp.where(pick, ref[...], -jnp.inf), axis=0, keepdims=True)

    def row_of(i, lanes=slice(None)):
        """Row ``i`` (K scale, K zero point, V scale, V zero point)."""
        return rows_ref[i:i + 1, lanes]

    plane_copies(kp_hbm, kbuf, ksem, k_pact, t, slot, False)

    # exact int32 QK^T, block by block: (q - z_q) . (k - z_k) is
    # (q - z_q) . k - z_k * rowsum(q - z_q) — codes <= 127 and hd <= 256
    # keep every term int32-safe
    def qk_block(j, carry):
        @pl.when(j < n_live)
        def _live():
            dots = jnp.zeros((m, CACHE_BLOCK), jnp.int32)
            for i, codes in enumerate(rebuild(kbuf, k_pact, j)):
                dots += jax.lax.dot_general(
                    qbd_ref[i], codes, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.int32)    # (M, 128)
            kz = jnp.round(row_of(1, blk(j))).astype(jnp.int32)
            i32 = dots - kz * rowsum
            # fp32 epilogue — the oracle's exact op sequence (ref.py):
            # change both or neither, the parity suite holds them
            # bit-identical
            x = (i32.astype(jnp.float32) * q_scale) * row_of(0, blk(j))
            if softcap > 0:
                x = softcap * jnp.tanh(x / softcap)
            k_pos = j * CACHE_BLOCK + jax.lax.broadcasted_iota(
                jnp.int32, (1, CACHE_BLOCK), 1)
            sc_ref[:, blk(j)] = jnp.where(valid_at(k_pos), x, NEG_INF)

        @pl.when(j >= n_live)
        def _dead():
            sc_ref[:, blk(j)] = jnp.full((m, CACHE_BLOCK), NEG_INF,
                                         jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_blocks, qk_block, 0)

    sc = sc_ref[...]
    mx = jnp.max(sc, axis=-1, keepdims=True)
    eq = jnp.round(jnp.exp(sc - mx) * exp_scale).astype(jnp.int32)
    p_ = eq.astype(jnp.float32) / jnp.sum(
        eq, axis=-1, keepdims=True).astype(jnp.float32)

    # exact int32 PV: rescale every position into the largest valid V scale,
    # re-quantize the probabilities, subtract the V zero point in-accumulator
    vs = row_of(2)                                  # (1, S)
    valid = valid_at(jax.lax.broadcasted_iota(jnp.int32, (1, sp), 1))
    sv_ref = jnp.maximum(jnp.max(jnp.where(valid, vs, 0.0)), 1e-12)
    ratio = vs / sv_ref
    pq = jnp.round(p_ * ratio * prob_scale).astype(jnp.int32)   # (M, S)
    pq_ref[...] = pq
    vz = jnp.round(row_of(3)).astype(jnp.int32)
    corr = jnp.sum(pq * vz, axis=-1, keepdims=True)      # (M, 1)

    plane_copies(vp_hbm, vbuf, vsem, v_pact, t, slot, False)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def pv_block(j, carry):
        @pl.when(j < n_live)
        def _live():
            pb = pq_ref[:, blk(j)]                       # (M, 128)
            limbs = jnp.concatenate([pb & 127, (pb >> 7) & 127, pb >> 14],
                                    axis=0).astype(jnp.int8)
            for i, codes in enumerate(rebuild(vbuf, v_pact, j)):
                r = jax.lax.dot_general(
                    limbs, codes, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)    # (3M, C)
                acc_ref[i] += r[:m] + 128 * r[m:2 * m] + 16384 * r[2 * m:]
        return carry

    jax.lax.fori_loop(0, n_blocks, pv_block, 0)

    # each column keeps its own head's rows: the diagonal blocks
    for gi in range(g):
        out = jnp.concatenate(
            [jnp.sum(jnp.where(row == first_row + gi, acc_ref[i] - corr, 0),
                     axis=0, keepdims=True) for i in range(8)], axis=0)
        o_ref[gi] = out.astype(jnp.float32) * (sv_ref / prob_scale)


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
    spec), except ``pos`` must be a scalar — the engine's caches share
    one ``length`` across the batch — and ``k_pact``/``v_pact`` (traced
    scalar counts of LIVE low planes, from the cache level counts; None =
    all) have no oracle counterpart because the skipped planes are
    all-zero.
    """
    b, kh, g, hd = qq.shape
    _, n_planes, s, kd8 = k_planes.shape
    d8 = hd // 8
    assert kd8 == kh * d8 and d8 * 8 == hd, (qq.shape, k_planes.shape)
    assert v_planes.shape == k_planes.shape
    assert n_planes <= CACHE_PLANES, n_planes
    assert s <= MAX_CACHE_LEN, s
    sp = _round_up(s, CACHE_BLOCK)           # lanes: whole position blocks
    hb = head_block(kh, g, hd, sp)
    nh = kh // hb
    cols = hb * d8                           # packed columns of a block
    cp = _round_up(cols, _LANES)             # ... in whole lane tiles
    m = _round_up(hb * g, _POS_TILE)         # score rows of a cell

    def pact(a):
        a = jnp.float32(n_planes) if a is None else a
        return jnp.clip(jnp.round(jnp.asarray(a, jnp.float32).reshape(())),
                        1, n_planes).astype(jnp.int32)

    pos = jnp.asarray(pos, jnp.int32).reshape(())
    smem = jnp.stack([pos, live_blocks(pos, s), pact(k_pact),
                      pact(v_pact)]).reshape(1, 4)
    qs = jnp.asarray(q_scale, jnp.float32).reshape(1, 1)
    # the query minus its zero point as the rebuilt codes split it:
    # [.., g, i, h * hd/8 + c] is head dim 8c + i of the block's head h
    qd = qq.astype(jnp.int32) - jnp.asarray(q_z, jnp.int32)
    qd = jnp.transpose(qd.reshape(b, nh, hb, g, d8, 8), (0, 1, 3, 5, 2, 4))
    qd = jnp.pad(qd.reshape(b, nh, g, 8, cols),
                 ((0, 0),) * 4 + ((0, cp - cols),))
    heads = np.arange(cp, dtype=np.int32) // d8
    colh = jnp.asarray(np.where(heads < hb, heads, -1)[None])

    if interpret and (s % _POS_TILE or kd8 % _LANES):
        # the padding of the last tiles, which an untiled array lacks
        pad = ((0, 0), (0, 0), (0, _round_up(s, _POS_TILE) - s),
               (0, _round_up(kd8, _LANES) - kd8))
        k_planes, v_planes = (jnp.pad(x, pad, constant_values=255)
                              for x in (k_planes, v_planes))
    kernel = functools.partial(
        _decode_attention_kernel, n_head_blocks=nh, g=g, window=window,
        softcap=softcap, exp_scale=EXP_SCALE, prob_scale=PROB_SCALE)
    # the (B, S) scale/zero rows, 8 batch rows a block (all when B is
    # not a multiple of 8)
    row_blk = 8 if b % 8 == 0 else b
    row_spec = pl.BlockSpec((row_blk, s), lambda bi, hi: (bi // row_blk, 0))
    q_spec = pl.BlockSpec((None, None, g, 8, cp),
                          lambda bi, hi: (bi, hi, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(b, nh),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # pos, blocks, pacts
            pl.BlockSpec(memory_space=pltpu.SMEM),   # q_scale
            q_spec,
            pl.BlockSpec((1, cp), lambda bi, hi: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),    # K planes (manual DMA)
            row_spec, row_spec,                      # K s/z
            pl.BlockSpec(memory_space=pltpu.HBM),    # V planes (manual DMA)
            row_spec, row_spec,                      # V s/z
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, g, 8, cp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, n_planes, sp, cp), jnp.uint8),  # K plane slots
            pltpu.VMEM((2, n_planes, sp, cp), jnp.uint8),  # V plane slots
            pltpu.VMEM((8, CACHE_BLOCK // 4, cp), jnp.int32),  # codes
            pltpu.VMEM((8, m, cp), jnp.int8),            # block-diag query
            pltpu.VMEM((8, sp), jnp.float32),            # this row's s/z
            pltpu.VMEM((m, sp), jnp.float32),            # scores
            pltpu.VMEM((m, sp), jnp.int32),              # probability codes
            pltpu.VMEM((8, m, cp), jnp.int32),           # PV accumulator
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        # the next cell's copies are started by this one: cells run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(smem, qs, qd, colh, k_planes, k_s, k_z, v_planes, v_s, v_z)
    out = out[..., :cols].reshape(b, nh, g, 8, hb, d8)
    out = jnp.transpose(out, (0, 1, 4, 2, 5, 3))
    return out.reshape(b, kh, g, hd)
