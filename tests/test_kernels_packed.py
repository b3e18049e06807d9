"""Packed bit-plane PANN kernel vs oracle + pack/unpack roundtrip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pann as pann_core
from repro.core.unsigned import unsigned_split
from repro.kernels import autotune, dispatch, ref
from repro.kernels.pann_matmul_packed import (pack_planes, pann_matmul_packed,
                                              permute_k, swar_tile,
                                              unpack_planes)

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("k,n,p", [(128, 128, 3), (256, 128, 5), (64, 64, 1)])
def test_pack_unpack_roundtrip(k, n, p):
    planes = jnp.asarray(RNG.integers(0, 2, (p, k, n)), jnp.int8)
    packed = pack_planes(planes)
    assert packed.shape == (p, k // 8, n) and packed.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(unpack_planes(packed, k)),
                                  np.asarray(planes))


@pytest.mark.parametrize("m,k,n,n_planes", [(128, 128, 128, 3),
                                            (128, 256, 128, 4),
                                            (256, 128, 256, 2)])
def test_packed_matmul_matches_oracle(m, k, n, n_planes):
    hi = 1 << n_planes
    w_q = jnp.asarray(RNG.integers(-(hi - 1), hi, (k, n)), jnp.float32)
    pos, neg = unsigned_split(w_q)
    pp = pann_core.bitplane_decompose(pos, n_planes)
    pn = pann_core.bitplane_decompose(neg, n_planes)
    x_q = jnp.asarray(RNG.integers(0, 128, (m, k)), jnp.int8)
    s_x = jnp.asarray(RNG.uniform(0.01, 0.1, (m, 1)), jnp.float32)
    gamma = jnp.asarray(RNG.uniform(0.001, 0.01, (n,)), jnp.float32)
    got = pann_matmul_packed(x_q, pack_planes(pp), pack_planes(pn),
                             s_x, gamma, interpret=True)
    want = ref.pann_matmul_ref(x_q, pp, pn, s_x, gamma)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_packed_storage_is_8x_smaller():
    planes = jnp.asarray(RNG.integers(0, 2, (4, 512, 256)), jnp.int8)
    packed = pack_planes(planes)
    assert packed.size * packed.dtype.itemsize \
        == planes.size * planes.dtype.itemsize // 8


# ---------------------------------------------------------------------------
# pack/unpack round-trip edge cases
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 7, 13, 8, 9, 127])
def test_roundtrip_k_not_divisible_by_8(k):
    planes = jnp.asarray(RNG.integers(0, 2, (3, k, 5)), jnp.int8)
    packed = pack_planes(planes)
    assert packed.shape == (3, (k + 7) // 8, 5)
    np.testing.assert_array_equal(np.asarray(unpack_planes(packed, k)),
                                  np.asarray(planes))
    # the padded tail bits must be zero, or matmuls against padded x rows
    # would pick up phantom weights
    full = unpack_planes(packed, packed.shape[-2] * 8)
    np.testing.assert_array_equal(np.asarray(full[:, k:, :]), 0)


def test_roundtrip_single_plane_b_r_1():
    planes = jnp.asarray(RNG.integers(0, 2, (1, 16, 4)), jnp.int8)
    packed = pack_planes(planes)
    assert packed.shape == (1, 2, 4)
    np.testing.assert_array_equal(np.asarray(unpack_planes(packed, 16)),
                                  np.asarray(planes))


def test_roundtrip_empty_planes_all_zero_codes():
    planes = jnp.zeros((4, 24, 6), jnp.int8)
    packed = pack_planes(planes)
    assert int(jnp.sum(packed)) == 0
    np.testing.assert_array_equal(np.asarray(unpack_planes(packed, 24)), 0)


def test_roundtrip_stacked_leading_dims():
    """Serving artifacts stack planes behind scan dims: (L, P, K, N)."""
    planes = jnp.asarray(RNG.integers(0, 2, (2, 3, 11, 4)), jnp.int8)
    packed = pack_planes(planes)
    assert packed.shape == (2, 3, 2, 4)
    np.testing.assert_array_equal(np.asarray(unpack_planes(packed, 11)),
                                  np.asarray(planes))


def test_dtype_invariants():
    planes = jnp.asarray(RNG.integers(0, 2, (2, 9, 3)), jnp.int8)
    packed = pack_planes(planes)
    assert packed.dtype == jnp.uint8
    un = unpack_planes(packed, 9)
    assert un.dtype == jnp.int8
    assert set(np.unique(np.asarray(un))) <= {0, 1}
    # float-typed {0,1} planes pack identically (quantizer output dtype)
    packed_f = pack_planes(planes.astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(packed_f), np.asarray(packed))


# ---------------------------------------------------------------------------
# word-wide (SWAR) rebuild of the int8 operand: bit-exact against the oracle
# ---------------------------------------------------------------------------

def _codes(kind: str, k: int, n: int) -> np.ndarray:
    """int codes in [-127, 127]: random, or the extremes, where every plane
    of one sign is set (a byte carry or borrow would show there)."""
    if kind == "random":
        return RNG.integers(-127, 128, (k, n))
    return np.where(RNG.integers(0, 2, (k, n)) == 1, 127, -127)


def _packed_leaves(codes: np.ndarray):
    pos, neg = unsigned_split(jnp.asarray(codes, jnp.int32))
    return (pack_planes(pann_core.bitplane_decompose(pos, 7)),
            pack_planes(pann_core.bitplane_decompose(neg, 7)))


@pytest.mark.parametrize("shift", [None, 0, 3, 6])
@pytest.mark.parametrize("kind", ["random", "extremes"])
def test_swar_rebuild_with_permuted_x_is_the_int_matmul(kind, shift):
    """Outside any kernel: the rebuilt int8 rows, dotted with x's columns
    in ``permute_k`` order, equal x_q @ w_q in int32 (dead planes below
    ``shift`` masked as ``masked_codes`` masks them)."""
    k, n = 512, 256
    codes = _codes(kind, k, n)
    pp, pn = _packed_leaves(codes)
    w8 = jax.jit(swar_tile, static_argnums=2)(pp, pn, shift)
    assert w8.shape == (k, n) and w8.dtype == jnp.int8
    x_q = jnp.asarray(RNG.integers(0, 128, (8, k)), jnp.int32)
    got = jnp.dot(permute_k(x_q), w8.astype(jnp.int32))
    w = jnp.asarray(codes, jnp.int32)
    if shift is not None:
        w = pann_core.masked_codes(w, shift)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x_q @ w))


def _dispatch_case(m, k, n, kind, shift):
    codes = _codes(kind, k, n)
    pp, pn = _packed_leaves(codes)
    xf = jnp.asarray(RNG.standard_normal((m, k)), jnp.float32)
    s, z, n_lvl = jnp.float32(0.03), jnp.float32(60.0), jnp.float32(127.0)
    gamma = jnp.asarray(RNG.uniform(0.001, 0.01, (n,)), jnp.float32)
    zcol = jnp.asarray(RNG.integers(-1000, 1000, (n,)), jnp.int32)
    sh = None if shift is None else jnp.float32(shift)
    got = dispatch._matmul_packed(xf, pp, pn, s, z, n_lvl, gamma, zcol,
                                  interpret=True, shift=sh)
    want = dispatch._dispatch_ref(xf, jnp.asarray(codes, jnp.int8), s, z,
                                  n_lvl, gamma, zcol, sh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shift", range(7))
def test_packed_dispatch_is_bit_exact_at_every_plane_shift(shift):
    _dispatch_case(4, 512, 384, "random", shift)


@pytest.mark.parametrize("shift", [0, 1])
def test_packed_dispatch_is_bit_exact_at_the_code_extremes(shift):
    _dispatch_case(16, 512, 384, "extremes", shift)


@pytest.mark.parametrize("m,k,n", [(4, 2048, 8192), (16, 2048, 8192),
                                   (16, 1000, 8384)])
def test_packed_dispatch_is_bit_exact_at_serving_widths(m, k, n):
    """Decode row counts at zamba2 widths, and a K that is not a multiple
    of 256 with an N that no 512-lane block divides."""
    _dispatch_case(m, k, n, "random", 1)


# ---------------------------------------------------------------------------
# a layer of a stacked (L, P, K/8, N) store, read where it lies
# ---------------------------------------------------------------------------

SMALL_BLOCKS = autotune.KernelParams(bm=32, bn=128, bk=256, depth=2,
                                     order="mnk")


@pytest.mark.parametrize("k,n,layer,shift,params,where", [
    (512, 384, 0, 0, None, "in_place"),
    (512, 384, 1, 3, None, "in_place"),
    (512, 384, 2, 0, SMALL_BLOCKS, "in_place"),   # 3 x 2 grid steps
    (512, 384, 2, 5, SMALL_BLOCKS._replace(order="nmk"),
     "in_place"),
    (32, 160, 1, 0, None, "copied"),              # K and N padded
    (32, 160, 2, 3, None, "copied"),
])
def test_packed_dispatch_reads_a_stacked_layer(k, n, layer, shift, params,
                                               where):
    """The call on layer ``layer`` of a 3-layer stack equals the call on
    that layer's sliced leaf bit for bit, at plane shift 0 and a rung's;
    it reads the stack in place where K and N fill whole blocks, and
    through a sliced, padded copy elsewhere."""
    leaves = [_packed_leaves(_codes("random", k, n)) for _ in range(3)]
    pp = jnp.stack([a for a, _ in leaves])
    pn = jnp.stack([b for _, b in leaves])
    xf = jnp.asarray(RNG.standard_normal((4, k)), jnp.float32)
    s, z, n_lvl = jnp.float32(0.03), jnp.float32(60.0), jnp.float32(127.0)
    gamma = jnp.asarray(RNG.uniform(0.001, 0.01, (n,)), jnp.float32)
    zcol = jnp.asarray(RNG.integers(-1000, 1000, (n,)), jnp.int32)
    sh = jnp.float32(shift)

    def call(a, b):
        return dispatch._matmul_packed(xf, a, b, s, z, n_lvl, gamma, zcol,
                                       interpret=True, shift=sh,
                                       params=params)

    with dispatch.plane_traffic() as tally:
        got = call(dispatch.StackedPlanes(pp, layer),
                   dispatch.StackedPlanes(pn, layer))
    want = call(pp[layer], pn[layer])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert tally == {"in_place": 0, "copied": 0, where: 2 * pp[0].size}
