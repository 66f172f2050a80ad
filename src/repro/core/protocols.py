"""Trident 4PC protocols (paper Sections III & IV-B, Fig. 1-5, 9, 18).

Joint-party simulation: each protocol computes the union of the four
parties' local work, moves "messages" as local dataflow, and tallies the
real inter-party communication (rounds/bits, offline vs online phase)
analytically -- the tallies are asserted against the paper's lemmas in
tests/test_costs.py.

Cost conventions follow the paper's amortized lemmas (hashes are free).
Per-element online costs:
    Pi_Sh      1 round, 3*ell bits          (Lemma B.1)
    Pi_aSh     offline: 1 round, 2*ell      (Lemma B.2)
    Pi_Rec     1 round, 4*ell               (Lemma B.3)
    Pi_Mult    offline 1 rnd 3*ell; online 1 rnd 3*ell   (Lemma B.4)
    Pi_DotP    same as Pi_Mult, *independent of vector length* (Lemma C.3)
    Pi_MultTr  offline 2 rnd 6*ell; online 1 rnd 3*ell   (Lemma D.2)
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from . import algebra as AL
from .algebra import numel as _n
from .context import TridentContext
from .shares import AShare, BShare, public_to_ashare
from .prf import PARTIES


# ---------------------------------------------------------------------------
# Pi_Zero (Fig. 22): A + B + Gamma = 0, non-interactive.
# ---------------------------------------------------------------------------
def zero_shares(ctx: TridentContext, shape) -> jax.Array:
    """Returns stacked (3, *shape): A, B, Gamma with A+B+Gamma = 0.

    The streams and their sampling order are part of the shared protocol
    description (algebra.ZERO_SUBSETS) so the party-sliced runtime derives
    the identical masks."""
    f1, f2, f3 = (ctx.sample(s, shape) for s in AL.ZERO_SUBSETS)
    return jnp.stack([f2 - f1, f3 - f2, f1 - f3])


# ---------------------------------------------------------------------------
# Pi_Sh (Fig. 1): [[.]]-sharing of v by owner P_i.
# ---------------------------------------------------------------------------
def share(ctx: TridentContext, v: jax.Array, owner: int = 0) -> AShare:
    ring = ctx.ring
    v = jnp.asarray(v, ring.dtype)
    lams = []
    for j in (1, 2, 3):
        # lambda_{v,j} is sampled by P \ {P_j}, except the owner's own index
        # which all parties sample together with k_P (Fig. 1).
        subset = PARTIES if owner == j else tuple(
            p for p in PARTIES if p != j)
        lams.append(ctx.sample(subset, v.shape))
    lam = jnp.stack(lams)
    m = v + lam[0] + lam[1] + lam[2]
    ctx.tally.add("Pi_Sh", "online", rounds=1, bits=3 * ring.ell * _n(v.shape))
    return AShare(jnp.concatenate([m[None], lam], axis=0))


# ---------------------------------------------------------------------------
# Pi_aSh (Fig. 2): <.>-sharing of a value known to P0, in the offline phase.
# ---------------------------------------------------------------------------
def ash_by_p0(ctx: TridentContext, v: jax.Array) -> jax.Array:
    """Returns stacked (3, *shape) additive shares v1+v2+v3 = v."""
    ring = ctx.ring
    v = jnp.asarray(v, ring.dtype)
    v1, v2 = (ctx.sample(s, v.shape) for s in AL.ASH_SUBSETS)
    v3 = v - v1 - v2                       # P0 sends to P1, P2
    ctx.tally.add("Pi_aSh", "offline", rounds=1,
                  bits=2 * ring.ell * _n(v.shape))
    if ctx.malicious_checks:
        # P1 and P2 exchange H(v3): both copies are the same wire here; a
        # tamper-injection test adds a delta to one copy via ctx hooks.
        ctx.check_equal(v3, v3, "aSh.v3")
    return jnp.stack([v1, v2, v3])


# ---------------------------------------------------------------------------
# Pi_Rec (Fig. 3) / Pi_fRec (Fig. 5): reconstruction.
# ---------------------------------------------------------------------------
def reconstruct(ctx: TridentContext, x: AShare,
                receivers: Sequence[int] = PARTIES, fair: bool = False
                ) -> jax.Array:
    ring = ctx.ring
    n = _n(x.shape)
    if fair:
        ctx.tally.add("Pi_fRec", "online", rounds=4, bits=8 * ring.ell * n)
    else:
        ctx.tally.add("Pi_Rec", "online", rounds=1,
                      bits=ring.ell * n * len(receivers))
    return x.reveal()


# ---------------------------------------------------------------------------
# Pi_Mult (Fig. 4) -- elementwise multiplication.
# ---------------------------------------------------------------------------
def _gamma_offline(ctx: TridentContext, lx: jax.Array, ly: jax.Array,
                   contract=None) -> jax.Array:
    """gamma_xy = lambda_x * lambda_y, <.>-shared per Fig. 4's split.

    lx, ly: (3, *shape) lambda stacks.  `contract`: None for elementwise, or
    a callable performing the contraction (e.g. ring matmul) -- Pi_DotP sums
    gamma terms *before* the exchange, which is why its comm is length-free.
    Returns (3, *out_shape) with components summing to <lam_x . lam_y>.
    """
    op = (lambda a, b: a * b) if contract is None else contract
    if ctx.collapse:
        # Beyond-paper "component-collapsed" evaluation (docs/DESIGN_NOTES.md): the
        # joint simulation only needs gamma_total = lam_x_sum . lam_y_sum.
        lxs = lx[0] + lx[1] + lx[2]
        lys = ly[0] + ly[1] + ly[2]
        g = op(lxs, lys)
        z = jnp.zeros_like(g)
        return jnp.stack([g, z, z])
    # Faithful split (shared description, algebra.GAMMA_TERMS): piece j
    # collects the lambda-index pairs one online party can compute locally.
    lam_x = {j: lx[j - 1] for j in (1, 2, 3)}
    lam_y = {j: ly[j - 1] for j in (1, 2, 3)}
    pieces = {j: AL.gamma_piece(op, j, lam_x, lam_y) for j in (1, 2, 3)}
    fs = [ctx.sample(s, pieces[1].shape) for s in AL.ZERO_SUBSETS]
    return jnp.stack([pieces[j] + fs[a] - fs[b]
                      for j, (a, b) in sorted(AL.GAMMA_MASK_F.items())])


def _mult_like(ctx: TridentContext, x: AShare, y: AShare, name: str,
               contract=None, out_shape=None,
               _online_terms=None) -> AShare:
    """Shared skeleton of Pi_Mult / Pi_DotP / Pi_MatMul.

    online_terms(mx, my, lx, ly) must return (m_x*m_y, cross) where cross =
    lam_x_sum-weighted online local terms; defaults to elementwise.
    """
    ring = ctx.ring
    lx, ly = x.data[1:], y.data[1:]
    mx, my = x.m, y.m

    if out_shape is None:
        out_shape = jnp.broadcast_shapes(x.shape, y.shape)
    n_out = _n(out_shape)

    # ---- offline ----------------------------------------------------------
    if ctx.mode in ("fused", "offline"):
        lam_z = jnp.stack([
            ctx.sample(tuple(p for p in PARTIES if p != j), out_shape)
            for j in (1, 2, 3)])
        gamma = _gamma_offline(ctx, lx, ly, contract)
        ctx.offer({"lam_z": lam_z, "gamma": gamma})
    else:
        mat = ctx.get_material()
        lam_z, gamma = mat["lam_z"], mat["gamma"]
    ctx.tally.add(name, "offline", rounds=1, bits=3 * ring.ell * n_out)

    if ctx.mode == "offline":
        m = jnp.zeros(out_shape, ring.dtype)
        return AShare(jnp.concatenate([m[None], lam_z], axis=0))

    # ---- online -----------------------------------------------------------
    op = (lambda a, b: a * b) if contract is None else contract
    mm = op(mx, my)
    if ctx.collapse:
        lxs = lx[0] + lx[1] + lx[2]
        lys = ly[0] + ly[1] + ly[2]
        mz_prime = -op(lxs, my) - op(mx, lys) + gamma[0] + gamma[1] + gamma[2] \
            + lam_z[0] + lam_z[1] + lam_z[2]
    else:
        parts = [
            AL.mult_online_part(op, lx[i], ly[i], mx, my, gamma[i], lam_z[i])
            for i in range(3)]
        if ctx.malicious_checks:
            ctx.check_equal(parts[0], parts[0], f"{name}.mz'")
        mz_prime = parts[0] + parts[1] + parts[2]
    m_z = mz_prime + mm
    ctx.tally.add(name, "online", rounds=1, bits=3 * ring.ell * n_out)
    return AShare(jnp.concatenate([m_z[None], lam_z], axis=0))


def mult(ctx: TridentContext, x: AShare, y: AShare) -> AShare:
    """Pi_Mult (Fig. 4): elementwise product, no truncation."""
    return _mult_like(ctx, x, y, "Pi_Mult")


# ---------------------------------------------------------------------------
# Pi_DotP (Fig. 9) / matrix multiplication (batched, jnp.matmul semantics).
# ---------------------------------------------------------------------------
_mm_shape = AL.matmul_shape


def dotp(ctx: TridentContext, x: AShare, y: AShare) -> AShare:
    """Pi_DotP: dot product along the last axis; comm independent of d."""
    contract = lambda a, b: jnp.sum(a * b, axis=-1)
    out_shape = jnp.broadcast_shapes(x.shape, y.shape)[:-1]
    return _mult_like(ctx, x, y, "Pi_DotP", contract=contract,
                      out_shape=out_shape)


def matmul(ctx: TridentContext, x: AShare, y: AShare) -> AShare:
    """Pi_MatMul = batched Pi_DotP: [[X]] @ [[Y]] with comm 3*ell per output
    element (paper Section VI-A: matrix ops decompose into dot products)."""
    contract = ctx.ring.matmul
    return _mult_like(ctx, x, y, "Pi_DotP", contract=contract,
                      out_shape=_mm_shape(x.shape, y.shape))


# ---------------------------------------------------------------------------
# Pi_MultTr (Fig. 18): multiplication with free truncation.
# ---------------------------------------------------------------------------
#
# Guarded r sampling (TRUNC_GUARD): each r_j is uniform over
# [0, 2^{ell-TRUNC_GUARD}), so r = r1+r2+r3 < 3 * 2^{ell-4} < 2^{ell-2} and
# the opened z - r cannot wrap mod 2^ell whenever |z| < 2^{ell-2}.  With
# full-ring uniform r the Fig. 18 probabilistic truncation fails with
# probability ~|z|/2^ell -- negligible at ell=64 but a likely 2^{ell-2f}
# decoded error at ell=32 (the seed's ring32 failure).  The trade is the
# usual SecureML one: r keeps ell-4+log2(3) bits of entropy, masking values
# bounded by 2^{ell-2} statistically rather than perfectly.
#
TRUNC_GUARD = 4


def _trunc_pair(ctx: TridentContext, shape):
    """Offline (r, r^t): r = r1+r2+r3 sampled, P0 truncates and <.>-shares.
    The correctness check (Lemma D.1) ships one round later -- call
    ``_trunc_pair_check`` after the enclosing parallel-offline scope so the
    aSh overlaps the gamma exchange (Lemma D.2: 2 offline rounds total)."""
    ring = ctx.ring
    r_j = jnp.stack([
        ctx.sample_bounded(tuple(p for p in PARTIES if p != j), shape,
                           ring.ell - TRUNC_GUARD)
        for j in (1, 2, 3)])
    r = r_j[0] + r_j[1] + r_j[2]
    r_t = ring.truncate(r)                      # arithmetic shift (signed)
    rt_shares = ash_by_p0(ctx, r_t)             # 1 round, 2*ell (offline)
    return r_j, rt_shares


def _trunc_pair_check(ctx: TridentContext, r_j, rt_shares):
    """Fig. 18 check r = 2^d r^t + r_d: 1 offline round, ell bits (P1->P2)."""
    ring = ctx.ring
    if ctx.malicious_checks:
        r = r_j[0] + r_j[1] + r_j[2]
        r_t = rt_shares[0] + rt_shares[1] + rt_shares[2]
        lhs = r - (r_t << ring.frac) - ring.low_bits(r, ring.frac)
        ctx.check_equal(lhs, jnp.zeros_like(lhs), "MultTr.rt")
    ctx.tally.add("TruncPair", "offline", rounds=1,
                  bits=ring.ell * _n(r_j.shape[1:]))


def mult_tr(ctx: TridentContext, x: AShare, y: AShare,
            contract=None, out_shape=None, name="Pi_MultTr") -> AShare:
    """Fig. 18 generalized over elementwise/dot/matmul contraction."""
    ring = ctx.ring
    lx, ly = x.data[1:], y.data[1:]
    mx, my = x.m, y.m
    if out_shape is None:
        out_shape = jnp.broadcast_shapes(x.shape, y.shape)
    n_out = _n(out_shape)

    # ---- offline: Pi_Mult offline minus lam_z, plus the (r, r^t) pair -----
    # Round 1: gamma exchange || Pi_aSh(r^t); round 2: the Lemma D.1 check.
    if ctx.mode in ("fused", "offline"):
        with ctx.tally.parallel(("offline",)):
            gamma = _gamma_offline(ctx, lx, ly, contract)
            ctx.tally.add(name, "offline", rounds=1,
                          bits=3 * ring.ell * n_out)
            r_j, rt_shares = _trunc_pair(ctx, out_shape)
        _trunc_pair_check(ctx, r_j, rt_shares)
        ctx.offer({"gamma": gamma, "r_j": r_j, "rt": rt_shares})
    else:
        mat = ctx.get_material()
        gamma, r_j, rt_shares = mat["gamma"], mat["r_j"], mat["rt"]
        with ctx.tally.parallel(("offline",)):
            ctx.tally.add(name, "offline", rounds=1,
                          bits=3 * ring.ell * n_out)
            ctx.tally.add("Pi_aSh", "offline", rounds=1,
                          bits=2 * ring.ell * n_out)
        _trunc_pair_check(ctx, r_j, rt_shares)

    # Output lambda: [[r^t]] has m = 0 and <lam> = -<r^t> so that the share
    # evaluates to (z-r)^t + r^t.  (Fig. 18 prints <lam_{r^t}> = <r^t>; the
    # sign must be negative, as in the analogous Pi_Bit2A conversion --
    # recorded as a paper typo in docs/DESIGN_NOTES.md.)
    lam_out = -rt_shares
    if ctx.mode == "offline":
        m = jnp.zeros(out_shape, ring.dtype)
        return AShare(jnp.concatenate([m[None], lam_out], axis=0))

    # ---- online ------------------------------------------------------------
    op = (lambda a, b: a * b) if contract is None else contract
    mm = op(mx, my)
    if ctx.collapse:
        lxs, lys = lx[0] + lx[1] + lx[2], ly[0] + ly[1] + ly[2]
        zp = -op(lxs, my) - op(mx, lys) + gamma[0] + gamma[1] + gamma[2] \
            - (r_j[0] + r_j[1] + r_j[2])
    else:
        parts = [
            AL.mult_online_part(op, lx[i], ly[i], mx, my, gamma[i], -r_j[i])
            for i in range(3)]
        zp = parts[0] + parts[1] + parts[2]
    z_minus_r = zp + mm                          # opened: z - r
    zt_public = ring.truncate(z_minus_r)         # (z - r)^t, public to P1..P3
    # Pi_vSh(P1,P2,P3, (z-r)^t): non-interactive, lambda = 0; add [[r^t]].
    m_out = zt_public
    ctx.tally.add(name, "online", rounds=1, bits=3 * ring.ell * n_out)
    return AShare(jnp.concatenate([m_out[None], lam_out], axis=0))


def matmul_tr(ctx: TridentContext, x: AShare, y: AShare) -> AShare:
    """[[X]] @ [[Y]] with fused truncation (the PPML workhorse)."""
    contract = ctx.ring.matmul
    return mult_tr(ctx, x, y, contract=contract,
                   out_shape=_mm_shape(x.shape, y.shape),
                   name="Pi_MatMulTr")


def truncate_share(ctx: TridentContext, x: AShare) -> AShare:
    """Standalone truncation of [[x]] (x known to have 2f fractional bits):
    implemented as the Fig. 18 machinery with the multiply already done."""
    ring = ctx.ring
    out_shape = x.shape
    if ctx.mode in ("fused", "offline"):
        r_j, rt_shares = _trunc_pair(ctx, out_shape)
        _trunc_pair_check(ctx, r_j, rt_shares)
        ctx.offer({"r_j": r_j, "rt": rt_shares})
    else:
        mat = ctx.get_material()
        r_j, rt_shares = mat["r_j"], mat["rt"]
        ctx.tally.add("Pi_aSh", "offline", rounds=1,
                      bits=2 * ctx.ring.ell * _n(out_shape))
        _trunc_pair_check(ctx, r_j, rt_shares)
    if ctx.mode == "offline":
        m = jnp.zeros(out_shape, ring.dtype)
        return AShare(jnp.concatenate([m[None], -rt_shares], axis=0))
    # online: open z - r (z's m minus lambda contributions minus r shares)
    z_minus_r = x.m - (x.data[1] + r_j[0]) - (x.data[2] + r_j[1]) \
        - (x.data[3] + r_j[2])
    zt = ring.truncate(z_minus_r)
    ctx.tally.add("Pi_Trunc", "online", rounds=1,
                  bits=3 * ring.ell * _n(out_shape))
    return AShare(jnp.concatenate([zt[None], -rt_shares], axis=0))


# ---------------------------------------------------------------------------
# Public-constant ops that need truncation (fixed-point aware helpers).
# ---------------------------------------------------------------------------
def scale_public(ctx: TridentContext, x: AShare, c: float) -> AShare:
    """[[x]] * c for a public real constant: local mul + one truncation."""
    ring = ctx.ring
    enc = ring.encode(c)
    return truncate_share(ctx, x.mul_public(enc))
