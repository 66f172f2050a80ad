"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.ring import RING64  # noqa: F401  (enables x64)
from repro.kernels import ops
from repro.kernels import ref as R


@pytest.mark.parametrize("dtype", [np.uint32, np.uint64])
@pytest.mark.parametrize("shape", [(64, 256, 64), (128, 512, 64),
                                   (64, 1024, 128)])
def test_limb_matmul_sweep(rng, dtype, shape):
    M, K, N = shape
    hi = np.iinfo(dtype).max
    a = rng.randint(0, hi, (M, K), dtype=np.uint64).astype(dtype)
    b = rng.randint(0, hi, (K, N), dtype=np.uint64).astype(dtype)
    got = ops.ring_matmul(jnp.asarray(a), jnp.asarray(b))
    want = R.limb_matmul_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_limb_matmul_wraparound(rng):
    """Products that exceed 2^64 must wrap exactly."""
    a = np.full((64, 256), np.iinfo(np.uint64).max, np.uint64)
    b = np.full((256, 64), np.iinfo(np.uint64).max, np.uint64)
    got = ops.ring_matmul(jnp.asarray(a), jnp.asarray(b))
    want = R.limb_matmul_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("blocks", [(64, 64, 256), (32, 32, 128)])
def test_limb_matmul_block_shapes(rng, blocks):
    bm, bn, bk = blocks
    a = rng.randint(0, 1 << 63, (128, 512), dtype=np.uint64)
    b = rng.randint(0, 1 << 63, (512, 128), dtype=np.uint64)
    got = ops.ring_matmul(jnp.asarray(a), jnp.asarray(b),
                          bm=bm, bn=bn, bk=bk)
    want = R.limb_matmul_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mpc_matmul_fused(rng):
    M, K, N = 64, 128, 64
    mk = lambda *s: rng.randint(0, 1 << 63, s, dtype=np.uint64)
    mx, my = mk(M, K), mk(K, N)
    lx, ly = mk(3, M, K), mk(3, K, N)
    mm, cross, gamma = ops.mpc_matmul_online(
        *map(jnp.asarray, (mx, lx, my, ly)))
    mm_r, cross_r = R.mpc_matmul_fused_ref(*map(jnp.asarray,
                                                (mx, lx, my, ly)))
    np.testing.assert_array_equal(np.asarray(mm), np.asarray(mm_r))
    np.testing.assert_array_equal(np.asarray(cross), np.asarray(cross_r))
    # gamma quadrant = lam_x_sum @ lam_y_sum
    lxs = (lx[0] + lx[1] + lx[2])
    lys = (ly[0] + ly[1] + ly[2])
    gr = R.limb_matmul_ref(jnp.asarray(lxs), jnp.asarray(lys))
    np.testing.assert_array_equal(np.asarray(gamma), np.asarray(gr))


def test_and_level_kernel_matches_protocol(rng):
    """Fused AND-level kernel == core.boolean.and_bshare local math."""
    from repro.core.context import make_context
    from repro.core import boolean as BW
    from repro.core.shares import BShare
    n = 512
    x = rng.randint(0, 1 << 63, n, dtype=np.uint64)
    y = rng.randint(0, 1 << 63, n, dtype=np.uint64)
    ctx = make_context(seed=3)
    xb = BW.share_bool(ctx, x)
    yb = BW.share_bool(ctx, y)
    lamz = rng.randint(0, 1 << 63, (3, n), dtype=np.uint64)
    zero_raw = rng.randint(0, 1 << 63, (2, n), dtype=np.uint64)
    zero = np.stack([zero_raw[0], zero_raw[1],
                     zero_raw[0] ^ zero_raw[1]])    # xors to 0
    out = ops.bool_and_level(jnp.asarray(xb.data), jnp.asarray(yb.data),
                             jnp.asarray(lamz), jnp.asarray(zero))
    got = np.asarray(out[0] ^ out[1] ^ out[2] ^ out[3])
    np.testing.assert_array_equal(got, x & y)


@pytest.mark.parametrize("n", [64, 512])
def test_ppa_msb_kernel(rng, n):
    x = rng.randint(0, 1 << 63, n, dtype=np.uint64)
    y = rng.randint(0, 1 << 63, n, dtype=np.uint64)
    lamz = np.zeros((8, 3, n), np.uint64)
    zero = np.zeros((8, 3, n), np.uint64)
    got = ops.msb_of_sum_words(jnp.asarray(x), jnp.asarray(y),
                               jnp.asarray(lamz), jnp.asarray(zero))
    want = R.ppa_msb_ref(jnp.asarray(x), jnp.asarray(y))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,counter0", [(512, 0), (1024, 12345)])
def test_prf_mask_kernel(n, counter0):
    key = jnp.asarray([0x9E3779B97F4A7C15], jnp.uint64)
    got = ops.lambda_masks(key, n, counter0=counter0)
    klo = jnp.asarray(np.uint64(key[0]) & np.uint64(0xFFFFFFFF), jnp.uint32)
    khi = jnp.asarray(np.uint64(key[0]) >> np.uint64(32), jnp.uint32)
    want = R.prf_mask_ref(klo, khi, counter0, (n,))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prf_mask_statistics():
    """Uniformity sanity: byte histogram roughly flat, no dead bits."""
    key = jnp.asarray([0xDEADBEEFCAFEBABE], jnp.uint64)
    out = np.asarray(ops.lambda_masks(key, 1 << 14))
    bits = np.unpackbits(out.view(np.uint8))
    assert 0.47 < bits.mean() < 0.53
    assert np.all(np.bitwise_or.reduce(out) == np.uint64(2**64 - 1))


@pytest.mark.parametrize("signs", [(1, 1, 1), (1, -1, -1)])
@pytest.mark.parametrize("dtype", [np.uint32, np.uint64])
def test_mult_terms_signed_sum(rng, dtype, signs):
    """out[j] = c[j] + sum_t signs[t] a[j,t] b[j,t] mod 2^ell, with the
    borrows and carries of the u32-word arithmetic exercised by full-range
    operands (n not a multiple of the 128-lane rows)."""
    J, T, n = 2, len(signs), 1000
    hi = np.iinfo(dtype).max
    a = rng.randint(0, hi, (J, T, n), dtype=np.uint64).astype(dtype)
    b = rng.randint(0, hi, (J, T, n), dtype=np.uint64).astype(dtype)
    c = rng.randint(0, hi, (J, n), dtype=np.uint64).astype(dtype)
    got = ops.mult_terms(jnp.asarray(a), jnp.asarray(b), jnp.asarray(c),
                         signs)
    want = c.copy()
    for t, s in enumerate(signs):
        want = want + a[:, t] * b[:, t] if s > 0 else want - a[:, t] * b[:, t]
    np.testing.assert_array_equal(np.asarray(got), want)
