"""Ring matmul (Z_2^32 / Z_2^64) on the TPU MXU via 4-bit limb decomposition.

TPU MXUs multiply bf16/f32/int8, not u32/u64 -- XLA emulates wide-integer
dot products on the VPU, orders of magnitude under the matmul roofline.
This kernel adapts the CryptGPU/Piranha float-limb idea to the MXU
(docs/KERNELS.md):

  * the operands arrive as u32 words (``words.py``: one word per element
    for u32, two for u64); split each element into L 4-bit limbs (L = 8
    for u32, 16 for u64), embedded exactly in bf16;
  * per limb i of A, ONE MXU matmul against the stacked limbs of B that
    survive mod 2^ell:
        A_i (bm, bk) @ [B_0 | ... | B_{L-1-i}] (bk, (L-i)*bn)
    -- the L(L+1)/2 limb-pair products with i + j < L, at full MXU rate.
    Exactness: products < 2^8 and bk <= 2^16 keep every f32 accumulation
    inside its 24-bit exact-integer window;
  * the VPU combine folds the products back mod 2^ell on u32 words:
        C = sum_s (sum_{i+j=s} P_ij) << 4s.

Grid: (M/bm, N/bn, K/bk) with revisiting accumulation on the k axis.
Blocks follow the TPU tiling: bm a multiple of 16 (bf16 rows), bn and bk
multiples of 128 (lanes).  VMEM at the default bm=64, bn=128, bk=256,
u64: A and B word blocks 256 KB, B limb stack 1 MB, one product block
512 KB, 16 u32 sums 512 KB -- well inside a v5e core's 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import words as WD

ROW_ALIGN = 16      # bf16 sublane tile
K_MAX = 1 << 16     # f32 exactness window of one k block


def _limb(word, q: int):
    """4-bit limb q of a u32 array, exact in bf16."""
    return ((word >> (4 * q)) & 15).astype(jnp.int32).astype(jnp.bfloat16)


def _limb_kernel(a_ref, b_ref, out_ref):
    """One (bm, bn) output tile; the k grid axis accumulates into out_ref."""
    n_words, _, bn = b_ref.shape
    n_limbs = 8 * n_words
    a_l = [_limb(a_ref[i // 8], i % 8) for i in range(n_limbs)]
    b_stack = jnp.concatenate(
        [_limb(b_ref[j // 8], j % 8) for j in range(n_limbs)], axis=1)

    # ---- MXU: every limb pair with i + j < L, grouped by shift s ---------
    sums = [None] * n_limbs
    for i in range(n_limbs):
        p = jax.lax.dot_general(
            a_l[i], b_stack[:, :(n_limbs - i) * bn],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        p = p.astype(jnp.int32).astype(jnp.uint32)
        for j in range(n_limbs - i):
            blk = p[:, j * bn:(j + 1) * bn]
            s = i + j
            sums[s] = blk if sums[s] is None else sums[s] + blk

    # ---- VPU combine mod 2^ell on u32 words ------------------------------
    acc = None
    for s, part in enumerate(sums):
        term = WD.shl(part, 4 * s, n_words)
        acc = term if acc is None else WD.add(acc, term)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        for w in range(n_words):
            out_ref[w] = acc[w]

    @pl.when(pl.program_id(2) != 0)
    def _acc():
        prev = tuple(out_ref[w] for w in range(n_words))
        new = WD.add(prev, acc)
        for w in range(n_words):
            out_ref[w] = new[w]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def limb_matmul(a: jax.Array, b: jax.Array, bm: int, bn: int, bk: int,
                interpret: bool) -> jax.Array:
    """C = A @ B mod 2^ell on u32 words: a (W, M, K), b (W, K, N) ->
    (W, M, N), W = 1 (u32) or 2 (u64).  Extents must be multiples of the
    blocks, and the blocks TPU-legal (``ops.ring_matmul`` pads)."""
    assert a.dtype == b.dtype == jnp.uint32, (a.dtype, b.dtype)
    W, M, K = a.shape
    N = b.shape[2]
    assert b.shape[:2] == (W, K), (a.shape, b.shape)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    assert bm % ROW_ALIGN == 0 and bn % WD.LANES == 0 \
        and bk % WD.LANES == 0, ("blocks must follow the TPU tiling",
                                 bm, bn, bk)
    assert bk <= K_MAX, "f32 exactness window"
    return pl.pallas_call(
        _limb_kernel,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((W, bm, bk),
                         lambda m, n, k: WD.block_index(0, m, k)),
            pl.BlockSpec((W, bk, bn),
                         lambda m, n, k: WD.block_index(0, k, n)),
        ],
        out_specs=pl.BlockSpec((W, bm, bn),
                               lambda m, n, k: WD.block_index(0, m, n)),
        out_shape=jax.ShapeDtypeStruct((W, M, N), jnp.uint32),
        interpret=interpret,
    )(a, b)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def ring_matmul(a: jax.Array, b: jax.Array, *, bm: int = 64, bn: int = 128,
                bk: int = 256, interpret: bool) -> jax.Array:
    """A (M, K) @ B (K, N) mod 2^ell for u32/u64 ring arrays of any 2-D
    shape: the blocks are rounded up to the TPU tiling (and cut down to
    the padded extents), the operands split into u32 words and
    zero-padded to whole blocks (exact: padded rows/columns contribute
    zero products), and the result joined and sliced back."""
    assert a.dtype == b.dtype and a.dtype in (jnp.uint32, jnp.uint64)
    M, K = a.shape
    N = b.shape[1]
    bm = min(WD.ceil_to(bm, ROW_ALIGN), WD.ceil_to(M, ROW_ALIGN))
    bn = min(WD.ceil_to(bn, WD.LANES), WD.ceil_to(N, WD.LANES))
    bk = min(WD.ceil_to(bk, WD.LANES), WD.ceil_to(K, WD.LANES), K_MAX)
    mp, np_, kp = WD.ceil_to(M, bm), WD.ceil_to(N, bn), WD.ceil_to(K, bk)
    aw = jnp.pad(jnp.stack(WD.split(a)), ((0, 0), (0, mp - M), (0, kp - K)))
    bw = jnp.pad(jnp.stack(WD.split(b)), ((0, 0), (0, kp - K), (0, np_ - N)))
    out = limb_matmul(aw, bw, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return WD.join(out[:, :M, :N])
