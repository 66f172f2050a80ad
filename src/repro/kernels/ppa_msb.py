"""Fused local arithmetic of one boolean-world PPA / AND level.

The boolean world's secure AND (Fig. 4 over Z_2) has a communication step
per level, which no kernel can remove -- but each level's LOCAL work
(gamma = lam_x lam_y monomials, the m'_z parts, the Sklansky smear masks)
is ~10 word-ops per element that XLA would otherwise run as separate
HBM-roundtrip elementwise kernels.  This kernel fuses the whole level in
VMEM: one read of the 8 input streams, one write of the m_z output.

Layout: bit-sliced words; data stacks are (4, n) = (m, l1, l2, l3), each
word split into u32 tiles (words.py): the kernel is bitwise, so every
32-bit word is independent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import words as WD


def _and_level_kernel(x_ref, y_ref, lamz_ref, zero_ref, out_ref):
    """m_z' parts of the word AND: out = (sum_i parts_i) ^ (m_x & m_y).
    x/y: (4, W, br, 128) share stacks; lamz: (3, ...) fresh output
    lambdas; zero: (3, ...) Pi_Zero shares randomizing gamma."""
    x = x_ref[...]
    y = y_ref[...]
    lamz = lamz_ref[...]
    zs = zero_ref[...]
    mx, lx1, lx2, lx3 = x[0], x[1], x[2], x[3]
    my, ly1, ly2, ly3 = y[0], y[1], y[2], y[3]
    # gamma split per Fig. 4 (XOR/AND world)
    g2 = (lx2 & ly2) ^ (lx2 & ly3) ^ (lx3 & ly2) ^ zs[0]
    g3 = (lx3 & ly3) ^ (lx3 & ly1) ^ (lx1 & ly3) ^ zs[1]
    g1 = (lx1 & ly1) ^ (lx1 & ly2) ^ (lx2 & ly1) ^ zs[2]
    p1 = (lx1 & my) ^ (mx & ly1) ^ g1 ^ lamz[0]
    p2 = (lx2 & my) ^ (mx & ly2) ^ g2 ^ lamz[1]
    p3 = (lx3 & my) ^ (mx & ly3) ^ g3 ^ lamz[2]
    m_z = p1 ^ p2 ^ p3 ^ (mx & my)
    for k, v in enumerate((m_z, lamz[0], lamz[1], lamz[2])):
        out_ref[k] = v


@functools.partial(jax.jit, static_argnames=("interpret",))
def and_level(x: jax.Array, y: jax.Array, lamz: jax.Array,
              zero: jax.Array, interpret: bool):
    """x, y: (4, n) boolean share stacks -> (4, n) output share stack
    (the AND's m_z plus its lambda components).  One fused VMEM pass over
    u32 word tiles (words.py)."""
    n = x.shape[1]
    r, br = WD.tile_rows(n, 64)
    tiles = [WD.to_tiles(v, r) for v in (x, y, lamz, zero)]
    spec = lambda k: pl.BlockSpec(                           # noqa: E731
        (k,) + tiles[0].shape[1:2] + (br, WD.LANES),
        lambda i: WD.block_index(0, 0, i, 0))
    out = pl.pallas_call(
        _and_level_kernel,
        grid=(r // br,),
        in_specs=[spec(4), spec(4), spec(3), spec(3)],
        out_specs=spec(4),
        out_shape=jax.ShapeDtypeStruct(tiles[0].shape, jnp.uint32),
        interpret=interpret,
    )(*tiles)
    return WD.from_tiles(out, n)


def ppa_msb(x: jax.Array, y: jax.Array, lamz_levels: jax.Array,
            zero_levels: jax.Array, interpret: bool) -> jax.Array:
    """Full Sklansky msb(x+y) driver over PUBLIC words (the kernel-level
    oracle target: each level's AND via the fused kernel with lambda = 0).
    x, y: (n,) ring words; returns the msb bit of x+y per word.

    For the MPC layers the driver in core/boolean.py owns the comm rounds;
    this fused variant is the single-device hot path (the per-level local
    math matches and_level exactly, asserted against ref.ppa_msb_ref)."""
    import math
    ell = x.dtype.itemsize * 8
    n = x.shape[0]
    zero4 = jnp.zeros((4, n), x.dtype)

    def AND(a, b, lvl):
        xa = zero4.at[0].set(a)
        yb = zero4.at[0].set(b)
        out = and_level(xa, yb, lamz_levels[lvl], zero_levels[lvl],
                        interpret=interpret)
        return out[0] ^ out[1] ^ out[2] ^ out[3]

    g = AND(x, y, 0)
    p = x ^ y
    for k in range(int(math.log2(ell))):
        half = 1 << k
        block = half * 2
        bnd = 0
        upper = 0
        for pos in range(ell):
            if pos % block == half - 1:
                bnd |= 1 << pos
            if pos % block >= half:
                upper |= 1 << pos
        bndc = jnp.asarray(bnd, x.dtype)
        upperc = jnp.asarray(upper, x.dtype)
        gb = _smear(g & bndc, half)
        pb = _smear(p & bndc, half)
        pu = p & upperc
        g = g ^ AND(pu, gb, k + 1)
        p = (p & ~upperc) ^ AND(pu, pb, k + 1)
    s = x ^ y ^ (g << 1)
    return (s >> (ell - 1)) & jnp.asarray(1, x.dtype)


def _smear(v, width):
    out = v << 1
    j = 1
    while j < width:
        out = out | (out << j)
        j <<= 1
    return out
