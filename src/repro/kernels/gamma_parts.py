"""Fused elementwise gamma-piece / online-part kernels (both worlds).

``mpc_matmul_fused.py`` fuses the *matmul*-shaped local work of a secure
multiplication; this module is its elementwise twin for Pi_Mult / Pi_DotP
and the XOR-world AND (the local math of one boolean AND / PPA level, the
party-sliced form of ``ppa_msb.and_level``).

A party's local work in one round of Pi_Mult (Fig. 4) is a handful of
grouped bilinear monomials:

  * offline, gamma piece j:   sum_t  lam_x[a_t] * lam_y[b_t]  + mask_j
  * online,  part j:          -lam_x[j] m_y - m_x lam_y[j]    + (gamma_j
                              + lam_z_j), plus m_x m_y for the m_z combine

i.e. per piece/part: T in {2, 3} products, one grouped reduction, one
constant.  XLA would dispatch each monomial as its own elementwise kernel
(an HBM round-trip per term); these kernels read every operand once and
write one output per group:

    mult_terms(a, b, c, signs):  out[j] = sum_t signs[t] a[j,t] b[j,t] + c[j]
    and_terms(a, b, c):          out[j] = XOR_t (a[j,t] & b[j,t]) ^ c[j]

Layouts: the operands arrive as u32 word tiles (``words.py``): a, b are
(J, T, W, R, 128) stacked operand groups (J = pieces/parts this party
computes this round, batched into ONE launch), c is (J, W, R, 128).  Ring
arithmetic mod 2^ell on the words is exact, and XOR/AND are bitwise, so
both kernels are bit-exact against the per-term jnp evaluation order --
the property the runtime's cross-backend identity contract rests on
(docs/KERNELS.md).
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from . import words as WD


def _mult_terms_kernel(a_ref, b_ref, c_ref, out_ref, *, signs):
    n_groups, _, n_words = a_ref.shape[:3]
    for j in range(n_groups):
        acc = tuple(c_ref[j, w] for w in range(n_words))   # (br, 128) each
        for t, s in enumerate(signs):
            term = WD.mul(tuple(a_ref[j, t, w] for w in range(n_words)),
                          tuple(b_ref[j, t, w] for w in range(n_words)))
            acc = WD.sub(acc, term) if s < 0 else WD.add(acc, term)
        for w in range(n_words):
            out_ref[j, w] = acc[w]


def _and_terms_kernel(a_ref, b_ref, c_ref, out_ref):
    acc = c_ref[...]                                   # (J, W, br, 128)
    for t in range(a_ref.shape[1]):
        acc = acc ^ (a_ref[:, t] & b_ref[:, t])
    out_ref[...] = acc


def _grouped_call(kernel, a, b, c, br: int, interpret: bool):
    J, T, W, R, lanes = a.shape
    assert R % br == 0 and br % WD.SUBLANES == 0, (R, br)
    return pl.pallas_call(
        kernel,
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((J, T, W, br, lanes),
                         lambda i: WD.block_index(0, 0, 0, i, 0)),
            pl.BlockSpec((J, T, W, br, lanes),
                         lambda i: WD.block_index(0, 0, 0, i, 0)),
            pl.BlockSpec((J, W, br, lanes),
                         lambda i: WD.block_index(0, 0, i, 0)),
        ],
        out_specs=pl.BlockSpec((J, W, br, lanes),
                               lambda i: WD.block_index(0, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(c.shape, c.dtype),
        interpret=interpret,
    )(a, b, c)


@functools.partial(jax.jit, static_argnames=("signs", "br", "interpret"))
def mult_terms(a: jax.Array, b: jax.Array, c: jax.Array, signs: tuple,
               br: int, interpret: bool):
    """out[j] = sum_t signs[t] * a[j,t] * b[j,t] + c[j]  (mod 2^ell) on
    word tiles: a, b (J, T, W, R, 128); c (J, W, R, 128); signs: static
    length-T tuple of +-1; ``br`` rows per block."""
    kernel = functools.partial(_mult_terms_kernel, signs=signs)
    return _grouped_call(kernel, a, b, c, br, interpret)


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def and_terms(a: jax.Array, b: jax.Array, c: jax.Array, br: int,
              interpret: bool):
    """out[j] = XOR_t (a[j,t] & b[j,t]) ^ c[j] on word tiles."""
    return _grouped_call(_and_terms_kernel, a, b, c, br, interpret)
