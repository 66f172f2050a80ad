"""Fused online-phase MPC matmul: all Pi_MatMulTr local products in one
kernel pass over the operand tiles.

The online phase of a secure matmul needs (collapsed layout,
docs/KERNELS.md):
    mm    = m_x @ m_y
    cross = lam_x_sum @ m_y + m_x @ lam_y_sum
i.e. 3 matmuls sharing 4 operands.  Done naively that is 6 operand-tile
reads from HBM; fusing via limb-stacking reads each operand ONCE:

    [m_x ; lam_x] (2*bm, bk)  @  [m_y | lam_y] (bk, 2*bn)

one limb_matmul-style MXU pass yields the 4 quadrant products
(m@m, m@lam_y, lam_x@m, lam_x@lam_y); the combine keeps the three needed
(the 4th quadrant is the offline gamma term -- the offline trace uses it,
the online trace discards it; with the stacked pass it is free).

HBM traffic: 4 operand tiles instead of 6 reads + one fused output pass
=> ~1.5x arithmetic-intensity gain on the online critical path, plus the
kernel-launch/roundtrip fusion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .limb_matmul import ring_matmul


@functools.partial(jax.jit, static_argnames=("interpret",))
def mpc_matmul_grid(xs: tuple, ys: tuple, *, interpret: bool):
    """All-pairs ring matmuls in ONE limb pass: stack P left operands
    (M, K) by rows and Q right operands (K, N) by columns,

        [x_0 ; ... ; x_{P-1}] (P*M, K)  @  [y_0 | ... | y_{Q-1}] (K, Q*N)

    and return the P x Q quadrant blocks [i][j] = x_i @ y_j mod 2^ell.
    Each operand's limbs are expanded once and every pairing runs at MXU
    rate -- this is how a party's whole same-round matmul workload (mm +
    its two online parts, or a gamma piece's term sum) becomes a single
    kernel launch.  ``ring_matmul`` zero-pads to block-legal extents
    (exact for matmul), so arbitrary shapes are accepted."""
    P, Q = len(xs), len(ys)
    M = xs[0].shape[0]
    N = ys[0].shape[1]
    p = ring_matmul(jnp.concatenate(xs, axis=0), jnp.concatenate(ys, axis=1),
                    interpret=interpret)
    return [[p[i * M:(i + 1) * M, j * N:(j + 1) * N] for j in range(Q)]
            for i in range(P)]


@functools.partial(jax.jit, static_argnames=("interpret",))
def mpc_matmul_fused(mx: jax.Array, lx: jax.Array, my: jax.Array,
                     ly: jax.Array, *, interpret: bool):
    """mx: (M,K); lx: (3,M,K) lambda stack; my: (K,N); ly: (3,K,N).
    Returns (mm, cross, gamma_term):
        mm         = mx @ my
        cross      = lam_x_sum @ my + mx @ lam_y_sum
        gamma_term = lam_x_sum @ lam_y_sum   (offline gamma, free here)
    all mod 2^ell.  The 2x2 special case of ``mpc_matmul_grid``."""
    dt = mx.dtype
    lxs = (lx[0] + lx[1] + lx[2]).astype(dt)
    lys = (ly[0] + ly[1] + ly[2]).astype(dt)
    p = mpc_matmul_grid((mx, lxs), (my, lys), interpret=interpret)
    mm = p[0][0]
    cross = p[1][0] + p[0][1]
    gamma = p[1][1]
    return mm, cross.astype(dt), gamma
