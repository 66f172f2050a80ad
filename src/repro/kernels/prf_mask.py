"""Counter-mode PRF lambda-mask generation in-kernel ("keyed-lambda").

The keyed-lambda representation (docs/KERNELS.md) stores only m_W for
serving weights and regenerates lambda from (key, counter) at the point of
use, trading HBM bytes for VPU flops.  This kernel generates a tile of
ring-uniform masks from a 64-bit key and a counter base using the
`squares` counter RNG (Widynski 2020) -- 4 rounds of mul/add/rotate, pure
VPU, no table state.  It stands in for the paper's fixed-key AES-CTR F_k
(F's only protocol-relevant property is pseudorandomness; documented).

The 64-bit words are held as (low, high) u32 pairs (``words.py``), so the
rotation by 32 is a swap of the pair.  Matches ref.prf_mask_ref
bit-exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import words as WD


def _rot32(x: tuple) -> tuple:
    return (x[1], x[0])


def _squares_kernel(key_ref, out_ref, *, counter0: int):
    _, br, lanes = out_ref.shape
    idx = (pl.program_id(0) * (br * lanes)
           + jax.lax.broadcasted_iota(jnp.int32, (br, lanes), 0) * lanes
           + jax.lax.broadcasted_iota(jnp.int32, (br, lanes), 1))
    ctr = WD.add((idx.astype(jnp.uint32),
                  jnp.zeros((br, lanes), jnp.uint32)),
                 (jnp.full((br, lanes), counter0 & 0xFFFFFFFF, jnp.uint32),
                  jnp.full((br, lanes), counter0 >> 32, jnp.uint32)))
    key = (key_ref[0], key_ref[1])                    # (br, 128) each
    x = WD.mul(ctr, key)
    y = x
    z = WD.add(y, key)
    x = _rot32(WD.add(WD.mul(x, x), y))
    x = _rot32(WD.add(WD.mul(x, x), z))
    x = _rot32(WD.add(WD.mul(x, x), y))
    t = WD.add(WD.mul(x, x), z)
    x = _rot32(t)
    r = WD.add(WD.mul(x, x), y)                       # t ^ (r >> 32)
    out_ref[0] = t[0] ^ r[1]
    out_ref[1] = t[1]


@functools.partial(jax.jit,
                   static_argnames=("counter0", "rows", "br", "interpret"))
def prf_mask(key_tile: jax.Array, rows: int, counter0: int, br: int,
             interpret: bool) -> jax.Array:
    """key_tile: (2, br, 128) u32, the key's (low, high) words broadcast
    over one block -> (2, rows, 128) u32 words of the stream elements
    counter0, counter0 + 1, ... in row-major order."""
    assert rows % br == 0 and br % WD.SUBLANES == 0, (rows, br)
    return pl.pallas_call(
        functools.partial(_squares_kernel, counter0=counter0),
        grid=(rows // br,),
        in_specs=[pl.BlockSpec(key_tile.shape,
                               lambda i: WD.block_index(0, 0, 0))],
        out_specs=pl.BlockSpec((2, br, WD.LANES),
                               lambda i: WD.block_index(0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((2, rows, WD.LANES), jnp.uint32),
        interpret=interpret,
    )(key_tile)
