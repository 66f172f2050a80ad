"""Correctness of the 4PC protocols vs the plaintext oracle (paper III/IV).

Fixed-point products carry the paper's probabilistic 1-LSB truncation error
(2^-13 with frac=13); tolerances are a few LSBs.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import protocols as PR
from repro.core import conversions as CV
from repro.core.context import make_context
from repro.core.ring import RING64, RING32
from repro.core.shares import AShare

LSB = 2.0 ** -13


def enc_share(ctx, x):
    return PR.share(ctx, ctx.ring.encode(x))


# ---------------------------------------------------------------------------
# Sharing semantics
# ---------------------------------------------------------------------------
class TestSharing:
    def test_share_reveal_roundtrip(self, ctx, rng):
        x = rng.randn(7, 3) * 10
        xs = enc_share(ctx, x)
        np.testing.assert_allclose(ctx.ring.decode(xs.reveal()), x,
                                   atol=LSB)

    @pytest.mark.parametrize("owner", [0, 1, 2, 3])
    def test_share_any_owner(self, ctx, rng, owner):
        x = rng.randn(5)
        xs = PR.share(ctx, ctx.ring.encode(x), owner=owner)
        np.testing.assert_allclose(ctx.ring.decode(xs.reveal()), x, atol=LSB)

    def test_shares_are_masked(self, ctx, rng):
        """m_v alone reveals nothing: it is uniformly random-looking, not v."""
        x = np.zeros(1000)
        xs = enc_share(ctx, x)
        m = np.asarray(xs.m)
        # if m leaked v it would be constant zero
        assert len(np.unique(m)) > 990

    def test_lambda_components_sum(self, ctx, rng):
        x = rng.randn(6)
        xs = enc_share(ctx, x)
        m = np.asarray(xs.m, np.uint64)
        lam = np.asarray(xs.lam_sum, np.uint64)
        v = (m - lam).astype(np.int64) / ctx.ring.scale
        np.testing.assert_allclose(v, x, atol=LSB)

    def test_ash_by_p0(self, ctx, rng):
        v = ctx.ring.encode(rng.randn(4, 4))
        sh = PR.ash_by_p0(ctx, v)
        assert sh.shape[0] == 3
        np.testing.assert_array_equal(
            np.asarray(sh[0] + sh[1] + sh[2]), np.asarray(v))


# ---------------------------------------------------------------------------
# Linear (local) gates
# ---------------------------------------------------------------------------
class TestLinearity:
    def test_add_sub_neg(self, ctx, rng):
        x, y = rng.randn(5), rng.randn(5)
        xs, ys = enc_share(ctx, x), enc_share(ctx, y)
        np.testing.assert_allclose(
            ctx.ring.decode((xs + ys).reveal()), x + y, atol=2 * LSB)
        np.testing.assert_allclose(
            ctx.ring.decode((xs - ys).reveal()), x - y, atol=2 * LSB)
        np.testing.assert_allclose(
            ctx.ring.decode((-xs).reveal()), -x, atol=LSB)

    def test_public_constant_add(self, ctx, rng):
        x = rng.randn(5)
        xs = enc_share(ctx, x)
        c = ctx.ring.encode(2.5)
        np.testing.assert_allclose(
            ctx.ring.decode((xs + c).reveal()), x + 2.5, atol=LSB)

    def test_public_int_mul(self, ctx, rng):
        x = rng.randn(5)
        xs = enc_share(ctx, x)
        np.testing.assert_allclose(
            ctx.ring.decode(xs.mul_public(7).reveal()), 7 * x, atol=7 * LSB)

    def test_linear_costs_zero(self, rng):
        c = make_context(RING64)
        xs, ys = enc_share(c, rng.randn(3)), enc_share(c, rng.randn(3))
        before = c.tally.totals()
        _ = xs + ys - xs.mul_public(3)
        assert c.tally.totals() == before  # local ops are free


# ---------------------------------------------------------------------------
# Multiplication family
# ---------------------------------------------------------------------------
class TestMult:
    def test_mult(self, ctx, rng):
        x, y = rng.randn(8) * 5, rng.randn(8) * 5
        z = PR.mult(ctx, enc_share(ctx, x), enc_share(ctx, y))
        # no truncation: result carries 2f fractional bits
        got = np.asarray(ctx.ring.to_signed(z.reveal()), np.int64) \
            / ctx.ring.scale ** 2
        np.testing.assert_allclose(got, x * y, atol=2e-3)

    def test_mult_tr(self, ctx, rng):
        x, y = rng.randn(100) * 8, rng.randn(100) * 8
        z = PR.mult_tr(ctx, enc_share(ctx, x), enc_share(ctx, y))
        np.testing.assert_allclose(ctx.ring.decode(z.reveal()), x * y,
                                   atol=1e-2)

    def test_dotp(self, ctx, rng):
        x, y = rng.randn(4, 64), rng.randn(4, 64)
        z = PR.dotp(ctx, enc_share(ctx, x), enc_share(ctx, y))
        got = np.asarray(ctx.ring.to_signed(z.reveal()), np.int64) \
            / ctx.ring.scale ** 2
        np.testing.assert_allclose(got, np.sum(x * y, -1), atol=1e-2)

    def test_matmul_tr(self, ctx, rng):
        a, b = rng.randn(9, 17), rng.randn(17, 5)
        z = PR.matmul_tr(ctx, enc_share(ctx, a), enc_share(ctx, b))
        np.testing.assert_allclose(ctx.ring.decode(z.reveal()), a @ b,
                                   atol=2e-2)

    def test_batched_matmul_tr(self, ctx, rng):
        a, b = rng.randn(3, 6, 7), rng.randn(3, 7, 4)
        z = PR.matmul_tr(ctx, enc_share(ctx, a), enc_share(ctx, b))
        np.testing.assert_allclose(ctx.ring.decode(z.reveal()),
                                   a @ b, atol=2e-2)

    def test_truncation_lsb_error_bound(self, ctx, rng):
        """Pi_MultTr's error is +-1 LSB with high probability (paper V-A)."""
        x = rng.randn(4096)
        y = rng.randn(4096)
        z = PR.mult_tr(ctx, enc_share(ctx, x), enc_share(ctx, y))
        err = np.abs(ctx.ring.decode(z.reveal()) - x * y)
        # encoding error of x,y contributes ~|x|+|y| LSBs; few-LSB bound
        assert np.quantile(err, 0.999) < 16 * LSB

    def test_collapse_mode_equivalent(self, rng):
        """Component-collapsed evaluation computes the same product (PRF
        streams differ because collapse skips Pi_Zero draws, so the +-1 LSB
        truncation noise may differ; values agree to 2 LSB)."""
        a, b = rng.randn(5, 6), rng.randn(6, 4)
        c1 = make_context(RING64, seed=3)
        c2 = make_context(RING64, seed=3, collapse=True)
        z1 = PR.matmul_tr(c1, enc_share(c1, a), enc_share(c1, b))
        z2 = PR.matmul_tr(c2, enc_share(c2, a), enc_share(c2, b))
        np.testing.assert_allclose(
            np.asarray(c1.ring.decode(z1.reveal())),
            np.asarray(c2.ring.decode(z2.reveal())), atol=4 * LSB)

    def test_collapse_mode_same_cost(self, rng):
        """collapse is an HLO-flop optimization only: tallies identical."""
        a, b = rng.randn(5, 6), rng.randn(6, 4)
        c1 = make_context(RING64, seed=3)
        c2 = make_context(RING64, seed=3, collapse=True)
        PR.matmul_tr(c1, enc_share(c1, a), enc_share(c1, b))
        PR.matmul_tr(c2, enc_share(c2, a), enc_share(c2, b))
        assert c1.tally.totals() == c2.tally.totals()

    def test_standalone_truncation(self, ctx, rng):
        x = rng.randn(32) * 3
        xs = enc_share(ctx, x)
        prod = PR.mult(ctx, xs, enc_share(ctx, np.ones(32)))
        t = PR.truncate_share(ctx, prod)
        np.testing.assert_allclose(ctx.ring.decode(t.reveal()), x, atol=1e-2)


# ---------------------------------------------------------------------------
# Offline/online twin-trace split (the paradigm itself)
# ---------------------------------------------------------------------------
class TestOfflineOnline:
    def test_split_matches_fused(self, rng):
        a, b = rng.randn(4, 8), rng.randn(8, 2)

        def program(ctx):
            xs = PR.share(ctx, ctx.ring.encode(a))
            ys = PR.share(ctx, ctx.ring.encode(b))
            z = PR.matmul_tr(ctx, xs, ys)
            return PR.mult_tr(ctx, z, z)

        fused = make_context(RING64, seed=11)
        want = fused.ring.decode(program(fused).reveal())

        off = make_context(RING64, seed=11, mode="offline")
        program(off)                      # records materials
        on = make_context(RING64, seed=11, mode="online")
        on.materials = off.materials      # ship preprocessing
        got = on.ring.decode(program(on).reveal())
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_online_phase_p0_free(self, rng):
        """online comm of Pi_Mult involves only P1-P3 (3 elements)."""
        c = make_context(RING64)
        xs = enc_share(c, rng.randn(1))
        ys = enc_share(c, rng.randn(1))
        base = c.tally.online.bits
        PR.mult(c, xs, ys)
        assert c.tally.online.bits - base == 3 * 64


# ---------------------------------------------------------------------------
# Malicious-security abort semantics
# ---------------------------------------------------------------------------
class TestMalicious:
    def test_no_tamper_no_abort(self, ctx, rng):
        z = PR.mult_tr(ctx, enc_share(ctx, rng.randn(3)),
                       enc_share(ctx, rng.randn(3)))
        _ = z.reveal()
        assert not bool(ctx.abort_flag())

    def test_tamper_aborts(self, rng):
        """Flipping one consistency-check operand sets the abort flag --
        the Fig. 5 fair-reconstruction path."""
        c = make_context(RING64)
        good = c.ring.encode(rng.randn(4))
        bad = good + jnp.asarray(1, c.ring.dtype)
        c.check_equal(good, bad, "tamper")
        assert bool(c.abort_flag())

    def test_checks_accumulate(self, ctx, rng):
        PR.mult(ctx, enc_share(ctx, rng.randn(2)),
                enc_share(ctx, rng.randn(2)))
        assert len(ctx.checks) > 0


# ---------------------------------------------------------------------------
# 32-bit ring
# ---------------------------------------------------------------------------
class TestRing32:
    def test_mult_tr_ring32(self, ctx32, rng):
        """Guarded r sampling (protocols.TRUNC_GUARD) keeps the opened
        z - r from wrapping mod 2^32, so the Fig. 18 truncation error stays
        at the 1-LSB probabilistic level even at ell=32, frac=13."""
        x, y = rng.randn(50), rng.randn(50)
        z = PR.mult_tr(ctx32, PR.share(ctx32, ctx32.ring.encode(x)),
                       PR.share(ctx32, ctx32.ring.encode(y)))
        np.testing.assert_allclose(ctx32.ring.decode(z.reveal()), x * y,
                                   atol=1e-2)

    def test_wraparound_semantics(self, ctx32):
        big = np.asarray([2.0 ** 17], np.float64)
        xs = PR.share(ctx32, ctx32.ring.encode(big))
        z = PR.mult_tr(ctx32, xs, xs)   # 2^34 * 2^13 >> 2^31: wraps
        v = ctx32.ring.decode(z.reveal())
        assert np.all(np.isfinite(v))   # wraps silently, never NaN


# ---------------------------------------------------------------------------
# Ring matmul: exact mod 2^ell against Python integers.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("fill", ["random", "all_ones"])
@pytest.mark.parametrize("k", [1, 784, 980])
@pytest.mark.parametrize("ring", [RING32, RING64], ids=["ell32", "ell64"])
def test_ring_matmul_exact(ring, k, fill, batched):
    """Ring.matmul == Python-integer matmul mod 2^ell, with jnp.matmul
    batching/broadcasting; all-ones operands (2^ell - 1) are the worst
    case for every digit and carry."""
    rng = np.random.RandomState(k)
    sa, sb = ((2, 1, 4, k), (3, k, 5)) if batched else ((4, k), (k, 5))
    if fill == "random":
        a = rng.randint(0, 1 << 62, sa, dtype=np.uint64) * 4 + 3
        b = rng.randint(0, 1 << 62, sb, dtype=np.uint64) * 4 + 1
        a, b = a.astype(ring.np_dtype), b.astype(ring.np_dtype)
    else:
        a = np.full(sa, (1 << ring.ell) - 1, ring.np_dtype)
        b = np.full(sb, (1 << ring.ell) - 1, ring.np_dtype)
    got = np.asarray(ring.matmul(jnp.asarray(a), jnp.asarray(b)))
    want = np.matmul(a.astype(object), b.astype(object)) % (1 << ring.ell)
    assert got.dtype == ring.np_dtype and got.shape == want.shape
    assert (got.astype(object) == want).all()


@pytest.mark.parametrize("ring", [RING32, RING64], ids=["ell32", "ell64"])
def test_encode_decode_host_and_traced_agree(ring):
    """Host values encode/decode on the host in IEEE f64 (XLA:TPU only
    emulates f64); traced values take the device path.  On the CPU the
    two are the same function, bit for bit, including ties."""
    import jax
    rng = np.random.RandomState(3)
    x = np.concatenate([rng.randn(1000) * 30.0,
                        (np.arange(-8, 8) + 0.5) / ring.scale])
    host = np.asarray(ring.encode(x))
    traced = np.asarray(jax.jit(ring.encode)(x))
    assert host.dtype == ring.np_dtype
    assert np.array_equal(host, traced)
    assert np.array_equal(host, np.round(x * ring.scale).astype(
        ring.np_sdtype).astype(ring.np_dtype))
    back = ring.decode(host)
    assert isinstance(back, np.ndarray)
    assert np.array_equal(back, np.asarray(jax.jit(ring.decode)(host)))
    np.testing.assert_allclose(back, x, atol=0.5 / ring.scale)
