"""Differential parity suite for the quantized KV cache (docs/kv_cache.md).

The contract under test: ``kernels.pann_attention.decode_attention`` (Pallas,
interpret mode off-TPU) is BIT-IDENTICAL in fp32 to the jnp int32 oracle
``kernels.ref.decode_attention_ref`` — across dynamic and calibrated
(constant-row) quantizer ranges, ragged sequence lengths, GQA head counts,
sliding windows, softcapping, and every cache bit width the ladder can
produce (fewer-bit rungs write zero high planes into the same 7-plane
layout, which is what makes mid-stream rung switches aval-stable).

Plus property-based round-trip tests for the cache codec itself via the
vendored hypothesis stub (tests/_hypothesis_stub.py; the real package wins
when installed).
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import quant
from repro.kernels import dispatch
from repro.kernels import pann_attention as pa
from repro.kernels import ref
from repro.models import attention as ATT


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _mk_cache_side(rng, b, s, kh, hd, bits, frozen=False):
    """Random packed planes + per-position quantizer rows for one side.

    ``frozen=True`` broadcasts ONE (s, z) across every position — the
    calibrated-range layout ``models.serving`` hoists; otherwise every
    position carries its own (dynamic) row.
    """
    cap = min((1 << bits) - 1, 127)
    codes = rng.integers(0, cap + 1, (b, s, kh, hd))
    planes = ref.pack_cache_codes(jnp.asarray(codes, jnp.int32))
    planes = jnp.moveaxis(planes, 0, 1)          # (B, P, S, K, hd//8)
    if frozen:
        s_row = np.full((b, s), rng.uniform(0.01, 0.1), np.float32)
        z_row = np.full((b, s), float(rng.integers(0, cap + 1)), np.float32)
    else:
        s_row = rng.uniform(0.01, 0.1, (b, s)).astype(np.float32)
        z_row = rng.integers(0, cap + 1, (b, s)).astype(np.float32)
    return planes, jnp.asarray(s_row), jnp.asarray(z_row), codes


def _mk_inputs(seed, b, s, kh, g, hd, bits, frozen=False):
    rng = np.random.default_rng(seed)
    kp, ks, kz, _ = _mk_cache_side(rng, b, s, kh, hd, bits, frozen)
    vp, vs, vz, _ = _mk_cache_side(rng, b, s, kh, hd, bits, frozen)
    qq = jnp.asarray(rng.integers(0, 128, (b, kh, g, hd)), jnp.int32)
    q_z = jnp.int32(rng.integers(0, 128))
    q_scale = jnp.float32(rng.uniform(0.001, 0.05) * hd ** -0.5)
    return qq, q_z, q_scale, kp, ks, kz, vp, vs, vz


# ---------------------------------------------------------------------------
# ref vs Pallas kernel: bit-identical fp32
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,kh,g,hd", [
    (1, 8, 1, 4, 8),       # MQA: one KV head, 4 query groups
    (2, 16, 2, 2, 16),     # GQA 2x2
    (2, 12, 4, 1, 8),      # MHA: group size 1
])
@pytest.mark.parametrize("bits", [2, 4, 7])
def test_kernel_matches_ref_bit_identical(b, s, kh, g, hd, bits):
    args = _mk_inputs(0, b, s, kh, g, hd, bits)
    for pos in (0, s // 2, s - 1):
        want = ref.decode_attention_ref(*args, jnp.int32(pos))
        got = pa.decode_attention(*args, jnp.int32(pos), interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("window,softcap", [(None, 0.0), (5, 0.0),
                                            (None, 30.0), (3, 20.0)])
def test_kernel_matches_ref_window_softcap(window, softcap):
    args = _mk_inputs(1, 2, 16, 2, 2, 8, 4)
    for pos in (2, 9, 15):
        want = ref.decode_attention_ref(*args, jnp.int32(pos),
                                        window=window, softcap=softcap)
        got = pa.decode_attention(*args, jnp.int32(pos), window=window,
                                  softcap=softcap, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_matches_ref_calibrated_rows():
    """Frozen (calibrated) ranges broadcast one (s, z) per side — the
    serving hoist — and must stay bit-identical like dynamic rows."""
    args = _mk_inputs(2, 2, 12, 2, 2, 16, 4, frozen=True)
    want = ref.decode_attention_ref(*args, jnp.int32(7))
    got = pa.decode_attention(*args, jnp.int32(7), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ref_ragged_positions_match_per_batch_kernel_calls():
    """The oracle takes per-batch pos (ragged lanes); the kernel pins one
    scalar. Slicing each batch row out and running the kernel at its own
    pos must reproduce the ragged oracle exactly."""
    b, s, kh, g, hd = 3, 16, 2, 2, 8
    args = _mk_inputs(3, b, s, kh, g, hd, 4)
    pos = jnp.asarray([3, 15, 9], jnp.int32)
    want = ref.decode_attention_ref(*args, pos)
    for i in range(b):
        row = [a[i:i + 1] if getattr(a, "ndim", 0) > 0 else a for a in args]
        got = pa.decode_attention(*row, pos[i], interpret=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want[i:i + 1]))


def test_ref_is_invariant_to_the_order_of_positions():
    """The softmax normalizer is an exact int32 sum, so no reduction order
    (XLA's over S, Mosaic's over the lane-padded row) can round it
    differently: permuting a full cache's positions leaves the output
    bit-identical."""
    b, s, kh, g, hd = 2, 48, 4, 2, 16
    qq, q_z, q_scale, kp, ks, kz, vp, vs, vz = _mk_inputs(4, b, s, kh, g,
                                                           hd, 5)
    perm = np.random.default_rng(4).permutation(s)
    pos = jnp.int32(s - 1)
    want = ref.decode_attention_ref(qq, q_z, q_scale, kp, ks, kz, vp, vs,
                                    vz, pos)
    got = ref.decode_attention_ref(qq, q_z, q_scale, kp[:, :, perm],
                                   ks[:, perm], kz[:, perm], vp[:, :, perm],
                                   vs[:, perm], vz[:, perm], pos)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_rung_switch_zero_high_planes_parity():
    """A rung switch changes only the CODE WIDTH: a 3-bit rung's codes in
    the pinned 7-plane layout leave the high planes zero. Parity must hold
    on exactly that layout (same avals, different values) — the aval
    stability that lets one compiled step straddle a mid-stream switch."""
    lo = _mk_inputs(4, 2, 16, 2, 2, 8, 3)
    hi = _mk_inputs(4, 2, 16, 2, 2, 8, 7)
    # 3-bit inputs really do have zero high planes
    assert int(jnp.max(lo[3][:, 3:])) == 0 and int(jnp.max(lo[6][:, 3:])) == 0
    assert int(jnp.max(hi[3][:, 3:])) > 0
    for args in (lo, hi):
        want = ref.decode_attention_ref(*args, jnp.int32(11))
        got = pa.decode_attention(*args, jnp.int32(11), interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dispatch_backend_parity():
    """kernels.dispatch.decode_attention (the serving entry: quantizes q,
    seals the scalars) must agree bit-for-bit between the jnp ref backend
    and the forced Pallas kernel."""
    rng = np.random.default_rng(5)
    b, s, kh, g, hd = 2, 12, 2, 2, 8
    kp, ks, kz, _ = _mk_cache_side(rng, b, s, kh, hd, 4)
    vp, vs, vz, _ = _mk_cache_side(rng, b, s, kh, hd, 4)
    kv = ATT.QuantKVCache(k_planes=kp, v_planes=vp, k_s=ks, k_z=kz,
                          v_s=vs, v_z=vz, length=jnp.int32(s - 1))
    q = jnp.asarray(rng.standard_normal((b, kh * g, hd)), jnp.float32)
    a = dispatch.decode_attention(q, kv, "ref", num_kv_heads=kh)
    bq = dispatch.decode_attention(q, kv, "fused:force", num_kv_heads=kh)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(bq))


def test_incremental_writes_match_batch_pack():
    """models.attention's masked select-write, applied token by token, must
    leave the exact planes/rows a one-shot pack of the same codes produces
    — so a decode stream's cache state is re-derivable from the prefix
    (what makes the rung-switch replay bit-exact)."""
    rng = np.random.default_rng(6)
    b, t, kh, hd, bits = 2, 5, 2, 8, 4
    n_lvl = jnp.float32((1 << bits) - 1)
    xs = rng.standard_normal((b, t, kh, hd)).astype(np.float32)
    planes = jnp.zeros((b, ref.CACHE_PLANES, t, kh, hd // 8), jnp.uint8)
    s_row = jnp.zeros((b, t), jnp.float32)
    z_row = jnp.zeros((b, t), jnp.float32)
    codes_all = []
    for i in range(t):
        new = jnp.asarray(xs[:, i:i + 1])
        s, z = ATT._cache_rows(new, None, None, n_lvl)
        planes, s_row, z_row = ATT._cache_write(
            planes, s_row, z_row, new, s, z, n_lvl, jnp.int32(i))
        codes_all.append(quant.affine_encode(
            new, s[:, None, None, None], z[:, None, None, None], n_lvl))
    codes = jnp.concatenate(codes_all, axis=1).astype(jnp.int32)
    direct = jnp.moveaxis(ref.pack_cache_codes(codes), 0, 1)
    np.testing.assert_array_equal(np.asarray(planes), np.asarray(direct))


# ---------------------------------------------------------------------------
# property-based codec round trips (vendored hypothesis stub)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(1, 7), st.integers(1, 4), st.integers(1, 6),
       st.integers(0, 10_000))
def test_codec_round_trip(bits, lead, d8, seed):
    """unpack(pack(codes)) == codes for every plane count and shape."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, 1 << bits, (lead, 3, d8 * 8))
    packed = ref.pack_cache_codes(jnp.asarray(codes, jnp.int32),
                                  n_planes=bits)
    assert packed.shape == (bits, lead, 3, d8)
    back = ref.unpack_cache_codes(packed)
    np.testing.assert_array_equal(np.asarray(back), codes)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 7), st.floats(0.1, 8.0), st.integers(0, 10_000))
def test_affine_cache_round_trip_error_bound(bits, spread, seed):
    """Encoding a tensor through the cache codec (affine encode -> pack ->
    unpack -> dequant) reconstructs within half a step everywhere inside
    the range — the codec itself is lossless on the codes."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(-spread, spread, (2, 3, 16)), jnp.float32)
    n_lvl = jnp.float32((1 << bits) - 1)
    lo, hi = quant.act_range_bounds(x, include_zero=True)
    s, z = quant.affine_scale_zp(lo, hi, n_lvl)
    codes = quant.affine_encode(x, s, z, n_lvl).astype(jnp.int32)
    back = ref.unpack_cache_codes(ref.pack_cache_codes(codes,
                                                       n_planes=bits))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(codes))
    deq = (back.astype(jnp.float32) - z) * s
    err = float(jnp.max(jnp.abs(deq - x)))
    assert err <= 0.5 * float(s) * (1 + 1e-5), (err, float(s))
