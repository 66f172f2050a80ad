"""Ring elements as 32-bit words, for kernel bodies.

Mosaic, the TPU kernel compiler, has no 64-bit types: a kernel that takes
a u64 operand, or whose index map returns int64, is refused.  So no
kernel sees a u64 array.  The ``ops.py`` wrappers split each ring operand
into u32 words before the call and join them after, and a ring element
inside a kernel is a tuple of u32 words, low word first: one word for
Z_2^32, two for Z_2^64.  The arithmetic below wraps mod 2^(32 * words).

Layout: a ring array of shape (..., n) becomes a u32 array of shape
(..., W, R, 128), W words, the n elements zero-padded to R full
(8 x 128)-aligned rows; ``to_tiles`` / ``from_tiles`` convert.
"""
from __future__ import annotations

import jax.numpy as jnp

LANES = 128
SUBLANES = 8


def ceil_to(d: int, m: int) -> int:
    return -(-d // m) * m


def block_index(*idx):
    """Index-map result as int32 (under x64 a Python 0 would be int64)."""
    return tuple(jnp.asarray(i, jnp.int32) for i in idx)


# ---------------------------------------------------------------------------
# Outside kernels: ring arrays <-> u32 words.
# ---------------------------------------------------------------------------
def split(x) -> tuple:
    """Ring array (u32 or u64) -> tuple of u32 word arrays, low first."""
    if x.dtype == jnp.uint32:
        return (x,)
    assert x.dtype == jnp.uint64, x.dtype
    return ((x & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
            (x >> jnp.uint64(32)).astype(jnp.uint32))


def join(words):
    """Inverse of ``split`` over the leading axis of a (W, ...) array."""
    if words.shape[0] == 1:
        return words[0]
    return (words[1].astype(jnp.uint64) << jnp.uint64(32)) \
        | words[0].astype(jnp.uint64)


def tile_rows(n: int, rows: int) -> tuple:
    """(R, rows per block) for n elements: R full rows of 128 lanes, a
    multiple of the block, which is at most ``rows`` and a multiple of 8."""
    r = ceil_to(max(-(-n // LANES), 1), SUBLANES)
    br = min(r, rows)
    return ceil_to(r, br), br


def to_tiles(x, r: int):
    """(..., n) ring array -> (..., W, R, 128) u32 words, zero-padded."""
    w = jnp.stack(split(x), axis=-2)                    # (..., W, n)
    pad = r * LANES - w.shape[-1]
    w = jnp.pad(w, [(0, 0)] * (w.ndim - 1) + [(0, pad)])
    return w.reshape(w.shape[:-1] + (r, LANES))


def from_tiles(t, n: int):
    """(..., W, R, 128) u32 words -> (..., n) ring array."""
    w = t.reshape(t.shape[:-2] + (-1,))[..., :n]        # (..., W, n)
    return join(jnp.moveaxis(w, -2, 0))


# ---------------------------------------------------------------------------
# Inside kernels: arithmetic on tuples of u32 words.
# ---------------------------------------------------------------------------
def _carry(a, b, s):
    """Carry out of the u32 sum s = a + b, as 0/1."""
    return ((a & b) | ((a | b) & ~s)) >> 31


def add(x: tuple, y: tuple) -> tuple:
    lo = x[0] + y[0]
    if len(x) == 1:
        return (lo,)
    return (lo, x[1] + y[1] + _carry(x[0], y[0], lo))


def sub(x: tuple, y: tuple) -> tuple:
    lo = x[0] - y[0]
    if len(x) == 1:
        return (lo,)
    borrow = ((~x[0] & y[0]) | (~(x[0] ^ y[0]) & lo)) >> 31
    return (lo, x[1] - y[1] - borrow)


def mul_wide(a, b) -> tuple:
    """Full 64-bit product of two u32 arrays as (low, high) words, from
    16-bit halves (the VPU multiplies 32 x 32 -> low 32 bits only)."""
    a0, a1 = a & 0xFFFF, a >> 16
    b0, b1 = b & 0xFFFF, b >> 16
    ll, hh = a0 * b0, a1 * b1
    lh, hl = a0 * b1, a1 * b0
    mid = lh + hl
    mid_carry = _carry(lh, hl, mid)
    lo = ll + (mid << 16)
    lo_carry = _carry(ll, mid << 16, lo)
    return lo, hh + (mid >> 16) + (mid_carry << 16) + lo_carry


def mul(x: tuple, y: tuple) -> tuple:
    if len(x) == 1:
        return (x[0] * y[0],)
    lo, hi = mul_wide(x[0], y[0])
    return (lo, hi + x[0] * y[1] + x[1] * y[0])


def shl(x, bits: int, words: int) -> tuple:
    """u32 array x, shifted left by a static ``bits`` into ``words`` words
    (bits past the top word are dropped)."""
    out = []
    for w in range(words):
        lo_bit = 32 * w                  # this word holds bits [lo_bit, +32)
        if bits >= lo_bit + 32 or bits + 32 <= lo_bit:
            out.append(jnp.zeros_like(x))
        elif bits >= lo_bit:
            out.append(x << (bits - lo_bit))
        else:                            # the high part of x spills here
            out.append(x >> (lo_bit - bits))
    return tuple(out)
