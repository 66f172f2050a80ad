"""Jitted public wrappers around the Pallas kernels.

The platform decides how a kernel runs: on the CPU backend the body is
interpreted (it executes as ordinary XLA ops -- correctness-exact, which
is how the tests run), on any other backend it is compiled by Mosaic.
There is no switch.

These wrappers are also the kernels' u64 boundary and make them total
over arbitrary shapes.  Mosaic has no 64-bit types, so every ring operand
is split into u32 words before the call and joined after (words.py).  The
raw kernels assert TPU-legal, block-aligned extents (docs/KERNELS.md), so
the wrappers zero-pad up to the next legal extent and slice the result --
exact for ring matmul (padded rows/columns contribute zero products) and
trivially exact for the elementwise / counter-indexed kernels (the pad
region is discarded).  The runtime's pallas kernel backend
(repro.runtime.kernel_backend) calls exclusively through here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import words as WD
from .gamma_parts import and_terms as _and_terms, mult_terms as _mult_terms
from .limb_matmul import ring_matmul as _ring_matmul
from .mpc_matmul_fused import (mpc_matmul_fused as _mpc_matmul_fused,
                               mpc_matmul_grid as _mpc_matmul_grid)
from .ppa_msb import and_level as _and_level, ppa_msb as _ppa_msb
from .prf_mask import prf_mask as _prf_mask

ROWS = 64           # rows of 128 words per block of the elementwise kernels


def _interpret() -> bool:
    """Interpret kernels only where the default backend is the CPU."""
    return jax.default_backend() == "cpu"


def ring_matmul(a, b, bm: int = 64, bn: int = 128, bk: int = 256):
    """A @ B mod 2^ell on the MXU (4-bit limb decomposition) for 2-D
    ring arrays of any shape."""
    return _ring_matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=_interpret())


def mpc_matmul_online(mx, lx, my, ly):
    """Fused online-phase products (mm, cross, gamma)."""
    return _mpc_matmul_fused(mx, lx, my, ly, interpret=_interpret())


def mpc_matmul_grid(xs, ys):
    """All-pairs x_i @ y_j quadrants in one limb pass (see
    mpc_matmul_fused.mpc_matmul_grid); xs/ys are sequences of equally
    shaped (M, K) / (K, N) operands."""
    return _mpc_matmul_grid(tuple(xs), tuple(ys), interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("signs", "interpret"))
def _grouped_mult(a, b, c, signs, interpret):
    n = a.shape[-1]
    r, br = WD.tile_rows(n, ROWS)
    out = _mult_terms(WD.to_tiles(a, r), WD.to_tiles(b, r),
                      WD.to_tiles(c, r), signs, br=br, interpret=interpret)
    return WD.from_tiles(out, n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped_and(a, b, c, interpret):
    n = a.shape[-1]
    r, br = WD.tile_rows(n, ROWS)
    out = _and_terms(WD.to_tiles(a, r), WD.to_tiles(b, r),
                     WD.to_tiles(c, r), br=br, interpret=interpret)
    return WD.from_tiles(out, n)


def mult_terms(a, b, c, signs):
    """Grouped fused-FMA ring kernel: out[j] = sum_t signs[t] * a[j,t,:] *
    b[j,t,:] + c[j,:] mod 2^ell.  a, b: (J, T, n); c: (J, n); any n."""
    return _grouped_mult(a, b, c, tuple(signs), _interpret())


def and_terms(a, b, c):
    """XOR-world twin of ``mult_terms``: out[j] = XOR_t (a[j,t,:] &
    b[j,t,:]) ^ c[j,:] on bit-packed words."""
    return _grouped_and(a, b, c, _interpret())


def bool_and_level(x, y, lamz, zero):
    """Fused local math of one boolean AND level on share stacks."""
    return _and_level(x, y, lamz, zero, interpret=_interpret())


def msb_of_sum_words(x, y, lamz_levels, zero_levels):
    """msb(x + y) per word via the fused Sklansky driver."""
    return _ppa_msb(x, y, lamz_levels, zero_levels, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("n", "counter0", "interpret"))
def _masks(key, n, counter0, interpret):
    r, br = WD.tile_rows(n, ROWS)
    lo, hi = WD.split(jnp.asarray(key, jnp.uint64).reshape(()))
    key_tile = jnp.stack([jnp.full((br, WD.LANES), lo),
                          jnp.full((br, WD.LANES), hi)])
    out = _prf_mask(key_tile, r, counter0=counter0, br=br,
                    interpret=interpret)
    return WD.from_tiles(out, n)


def lambda_masks(key, n, counter0=0):
    """Keyed-lambda mask regeneration (squares counter PRF): (n,) u64
    from a (1,) u64 key.  The stream is counter-indexed, so generating to
    the next block-legal length and slicing is bit-exact."""
    return _masks(key, n, counter0, _interpret())
