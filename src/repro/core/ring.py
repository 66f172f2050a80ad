"""Ring Z_{2^ell} arithmetic and fixed-point encoding.

Trident operates over the ring Z_{2^ell} (ell = 64 in the paper) with signed
two's-complement fixed point: the top bit is the sign, the low ``frac`` bits
are the fractional part (paper/SecureML convention: frac = 13).

All share components are stored as unsigned integers of the ring width;
addition/multiplication wrap mod 2^ell natively.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

# 64-bit types (the u64 ring) are enabled where the package is imported
# (repro/__init__.py).

# Ring matmul digits: signed base-256, each in [-128, 128), so one digit
# product is at most 2^14 in magnitude and an int32 sum of fewer than 2^17
# of them is exact.  Longer contractions are cut into chunks of _K_CHUNK.
_DIGIT_BITS = 8
_K_CHUNK = 1 << 16


@dataclasses.dataclass(frozen=True)
class Ring:
    """Configuration of the algebraic ring + fixed-point embedding."""

    ell: int = 64          # ring bit width (32 or 64)
    frac: int = 13         # fractional bits of the fixed-point embedding

    def __post_init__(self):
        if self.ell not in (32, 64):
            raise ValueError(f"unsupported ring width {self.ell}")
        if not 0 <= self.frac < self.ell - 1:
            raise ValueError(f"bad frac {self.frac} for ell {self.ell}")

    # --- dtypes -----------------------------------------------------------
    @property
    def dtype(self):
        return jnp.uint64 if self.ell == 64 else jnp.uint32

    @property
    def sdtype(self):
        return jnp.int64 if self.ell == 64 else jnp.int32

    @property
    def np_dtype(self):
        return np.uint64 if self.ell == 64 else np.uint32

    @property
    def np_sdtype(self):
        return np.int64 if self.ell == 64 else np.int32

    @property
    def bytes(self) -> int:
        return self.ell // 8

    @property
    def scale(self) -> int:
        return 1 << self.frac

    # --- casts ------------------------------------------------------------
    def to_unsigned(self, x: jax.Array) -> jax.Array:
        return x.astype(self.dtype)

    def to_signed(self, x: jax.Array) -> jax.Array:
        return x.astype(self.sdtype)

    # --- fixed point ------------------------------------------------------
    # Encode and decode run on the host wherever the value is readable
    # there (not a tracer): XLA:TPU emulates f64 with pairs of f32, whose
    # shorter significand rounds some values differently from IEEE f64 --
    # on a v5e, device encode disagreed with numpy on 363 of 285184 of the
    # NN's values.  Inside a trace the device's f64 is all there is.
    def encode(self, x) -> jax.Array:
        """float -> ring fixed point (round to nearest even)."""
        if isinstance(x, jax.core.Tracer):
            v = jnp.round(jnp.asarray(x, jnp.float64) * self.scale)
            return v.astype(self.sdtype).astype(self.dtype)
        v = np.round(np.asarray(x, np.float64) * self.scale)
        return jnp.asarray(v.astype(self.np_sdtype).astype(self.np_dtype))

    def decode(self, v):
        """ring fixed point -> float64 (a numpy array for host values)."""
        if isinstance(v, jax.core.Tracer):
            return self.to_signed(v).astype(jnp.float64) / self.scale
        return np.asarray(v).astype(self.np_sdtype) / self.scale

    def encode_int(self, x) -> jax.Array:
        """integer -> ring element (no fractional scaling)."""
        return jnp.asarray(x).astype(self.sdtype).astype(self.dtype)

    def decode_int(self, v: jax.Array) -> jax.Array:
        return self.to_signed(v)

    # --- ring ops (all wrap mod 2^ell by dtype semantics) ------------------
    def add(self, a, b):
        return (a + b).astype(self.dtype)

    def sub(self, a, b):
        return (a - b).astype(self.dtype)

    def neg(self, a):
        return (-self.to_signed(a)).astype(self.dtype)

    def mul(self, a, b):
        return (a * b).astype(self.dtype)

    def matmul(self, a, b):
        """a @ b mod 2^ell with ``jnp.matmul`` semantics (batched,
        broadcasting).  Built from int8 x int8 -> int32 dots, which every
        XLA backend lowers (XLA:TPU has no u64 dot); exact for any
        contraction length."""
        return _ring_matmul(jnp.asarray(a, self.dtype),
                            jnp.asarray(b, self.dtype), self)

    def msb(self, a) -> jax.Array:
        """Most significant bit (the fixed-point sign) as 0/1 ring element."""
        return (a >> (self.ell - 1)).astype(self.dtype)

    def truncate(self, a, bits: int | None = None) -> jax.Array:
        """Arithmetic (sign-preserving) right shift by `bits` (default frac)."""
        bits = self.frac if bits is None else bits
        return (self.to_signed(a) >> bits).astype(self.dtype)

    def low_bits(self, a, bits: int) -> jax.Array:
        mask = (1 << bits) - 1
        return (a & self.dtype.dtype.type(mask)).astype(self.dtype)

    def const(self, value: float) -> jax.Array:
        return self.encode(value)


RING64 = Ring(ell=64, frac=13)
RING32 = Ring(ell=32, frac=13)


def _digits(x, ring: Ring):
    """Signed base-256 digits of x, stacked on a new axis -3: int8 d_i in
    [-128, 128) with x == sum_i d_i 256^i mod 2^ell (the bytes of
    x + 0x8080..80, less 128)."""
    n = ring.ell // _DIGIT_BITS
    bias = sum(128 << (_DIGIT_BITS * i) for i in range(n))
    shifts = jnp.arange(n, dtype=ring.dtype)[:, None, None] * _DIGIT_BITS
    y = (x + jnp.asarray(bias, ring.dtype))[..., None, :, :]
    return (((y >> shifts) & 0xFF).astype(jnp.int32) - 128).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("ring",))
def _ring_matmul(a, b, ring: Ring):
    if b.ndim == 1:
        return _ring_matmul(a, b[:, None], ring)[..., 0]
    if a.ndim == 1:
        return _ring_matmul(a[None, :], b, ring)[..., 0, :]
    k = a.shape[-1]
    if k > _K_CHUNK:
        out = None
        for s in range(0, k, _K_CHUNK):
            part = _ring_matmul(a[..., s:s + _K_CHUNK],
                                b[..., s:s + _K_CHUNK, :], ring)
            out = part if out is None else out + part
        return out
    # a @ b = sum_{i,j} 256^(i+j) (A_i @ B_j) mod 2^ell: one int8 dot for
    # every digit pair, weighted and summed in the ring (pairs with
    # i + j >= n weigh 0 mod 2^ell).
    n = ring.ell // _DIGIT_BITS
    p = jnp.einsum("...imk,...jkn->...imjn", _digits(a, ring),
                   _digits(b, ring), preferred_element_type=jnp.int32)
    ij = np.add.outer(np.arange(n), np.arange(n))
    weight = np.where(ij < n, 1 << (_DIGIT_BITS * np.minimum(ij, n - 1)), 0)
    weight = jnp.asarray(weight.astype(ring.np_dtype)[:, None, :, None])
    terms = p.astype(ring.sdtype).astype(ring.dtype) * weight
    return terms.sum(axis=(-4, -2), dtype=ring.dtype)
