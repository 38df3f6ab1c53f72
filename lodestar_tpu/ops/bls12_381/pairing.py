"""Optimal ate pairing on limb tensors — the TPU signature-verification core.

The Miller loop is a single ``lax.scan`` over the 64-bit BLS parameter with
the projective sparse-line formulas validated CPU-side in
lodestar_tpu/crypto/bls/pairing_proj.py (see its docstring for the
derivation).  The final exponentiation uses the x-adic hard-part chain
validated in lodestar_tpu/crypto/bls/pairing.py::hard_part_x_chain.

Batching: all inputs carry leading batch axes; a batch of Miller loops is
one compiled program (the TPU analogue of the reference's per-worker batch
verification, packages/beacon-node/src/chain/bls/multithread/worker.ts:32).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lodestar_tpu.crypto.bls.fields import ABS_X
from . import fp, tower as tw

# MSB-first bits of |x| after the leading bit (the loop starts at T = Q).
_X_BITS = np.array([int(b) for b in bin(ABS_X)[3:]], dtype=np.uint32)


def _line_sparse(c0, d1, d2, shape_ref):
    z = jnp.zeros_like(shape_ref[0])
    zero2 = (z, jnp.zeros_like(z))
    return ((c0, zero2, zero2), (zero2, d1, d2))


def _f2_mul_small(a, k: int):
    """a * k for tiny static k via additions (k in 2..9)."""
    acc = a
    for _ in range(k - 1):
        acc = tw.f2_add(acc, a)
    return acc


def _f2_mul_fp_limb(a, xp):
    """Fp2 * Fp (xp is an Fp limb tensor)."""
    return (fp.mont_mul(a[0], xp), fp.mont_mul(a[1], xp))


def _dbl_step(t, xp, yp):
    """Projective doubling step; returns (sparse line at P, 2T).

    Formulas: pairing_proj.py::_dbl_step (validated vs the affine oracle).
    """
    X, Y, Z = t
    xx = tw.f2_sqr(X)
    yy = tw.f2_sqr(Y)
    x3 = tw.f2_mul(xx, X)
    yyz = tw.f2_mul(yy, Z)
    yz = tw.f2_mul(Y, Z)
    # line: c0 = -2 xi Y Z^2 yP ; d1 = 2Y^2Z - 3X^3 ; d2 = 3 X^2 Z xP
    c0 = tw.f2_neg(tw.f2_mul_by_xi(_f2_mul_fp_limb(tw.f2_dbl(tw.f2_mul(yz, Z)), yp)))
    d1 = tw.f2_sub(tw.f2_dbl(yyz), _f2_mul_small(x3, 3))
    d2 = _f2_mul_fp_limb(_f2_mul_small(tw.f2_mul(xx, Z), 3), xp)
    # update
    x3_9 = _f2_mul_small(x3, 9)
    yyz_8 = _f2_mul_small(yyz, 8)
    Xn = tw.f2_mul(tw.f2_dbl(tw.f2_mul(tw.f2_mul(X, Y), Z)), tw.f2_sub(x3_9, yyz_8))
    Yn = tw.f2_sub(
        tw.f2_mul(x3_9, tw.f2_sub(_f2_mul_small(yyz, 4), _f2_mul_small(x3, 3))),
        _f2_mul_small(tw.f2_sqr(yyz), 8),
    )
    Zn = _f2_mul_small(tw.f2_mul(tw.f2_mul(yy, Y), tw.f2_mul(tw.f2_sqr(Z), Z)), 8)
    return _line_sparse(c0, d1, d2, c0), (Xn, Yn, Zn)


def _add_step(t, q, xp, yp):
    """Projective mixed-addition step; returns (sparse line at P, T+Q)."""
    X, Y, Z = t
    x2, y2 = q
    theta = tw.f2_sub(tw.f2_mul(y2, Z), Y)
    lam = tw.f2_sub(tw.f2_mul(x2, Z), X)
    c0 = tw.f2_neg(tw.f2_mul_by_xi(_f2_mul_fp_limb(lam, yp)))
    d1 = tw.f2_sub(tw.f2_mul(lam, y2), tw.f2_mul(theta, x2))
    d2 = _f2_mul_fp_limb(theta, xp)
    ll = tw.f2_sqr(lam)
    lll = tw.f2_mul(ll, lam)
    llx = tw.f2_mul(ll, X)
    n = tw.f2_sub(tw.f2_sub(tw.f2_mul(tw.f2_sqr(theta), Z), tw.f2_dbl(llx)), lll)
    Xn = tw.f2_mul(lam, n)
    Yn = tw.f2_sub(tw.f2_mul(theta, tw.f2_sub(llx, n)), tw.f2_mul(lll, Y))
    Zn = tw.f2_mul(lll, Z)
    return _line_sparse(c0, d1, d2, c0), (Xn, Yn, Zn)


def miller_loop(q_aff, p_aff):
    """f_{|x|,Q}(P) conjugated for x < 0.

    q_aff: affine G2 ((x0,x1),(y0,y1)) Fp2 limb tuples, batched.
    p_aff: affine G1 (x, y) Fp limb tensors, batched.

    PRECONDITION: inputs must be finite affine points.  Infinity inputs
    produce garbage limbs; verify.py masks such entries out of the product
    (multi_miller_product / pairing_check) before they reach a reduction.
    """
    xq, yq = q_aff
    xp, yp = p_aff
    one2 = (jnp.broadcast_to(fp.one_mont(), xq[0].shape), jnp.zeros_like(xq[0]))
    t0 = fp.vary_like((xq, yq, one2), q_aff, p_aff)
    f0 = fp.vary_like(tw.f12_one(shape=xp.shape[:-1]), q_aff, p_aff)
    bits = jnp.asarray(_X_BITS)

    def body(carry, bit):
        f, t = carry
        line, t = _dbl_step(t, xp, yp)
        f = tw.f12_mul(tw.f12_sqr(f), line)

        def with_add(ft):
            f, t = ft
            line, t2 = _add_step(t, (xq, yq), xp, yp)
            return (tw.f12_mul(f, line), t2)

        f, t = jax.lax.cond(bit != 0, with_add, lambda ft: ft, (f, t))
        return (f, t), None

    (f, _), _ = jax.lax.scan(body, (f0, t0), bits)
    return tw.f12_conj(f)


# ---------------------------------------------------------------------------
# final exponentiation (x-adic chain, mirrors oracle hard_part_x_chain)
# ---------------------------------------------------------------------------


def _cyclotomic_pow_abs_x(a):
    """a^|x| by square-and-multiply over the static 64-bit parameter."""
    bits = jnp.asarray(np.array([int(b) for b in bin(ABS_X)[2:]], dtype=np.uint32))
    one = fp.vary_like(tw.f12_one(shape=jax.tree.leaves(a)[0].shape[:-1]), a)

    def body(acc, bit):
        acc = tw.f12_sqr(acc)
        acc = jax.lax.cond(bit != 0, lambda x: tw.f12_mul(x, a), lambda x: x, acc)
        return acc, None

    acc, _ = jax.lax.scan(body, one, bits)
    return acc


def _pow_neg_x(a):
    return tw.f12_conj(_cyclotomic_pow_abs_x(a))


def final_exponentiation(f):
    """f^((p^6-1)(p^2+1) * 3(p^4-p^2+1)/r) — same chain as the oracle.

    The hard part needs FIVE x-adic exponentiations.  Naively each becomes
    its own 64-iteration scan and XLA compiles five copies of the (large)
    square-and-multiply body — measured ~418 s of the TPU compile budget.
    Instead ONE outer scan runs the pow with a per-step epilogue selected
    by ``lax.switch``: the pow body compiles once (~5x compile saving),
    the tiny epilogues are the only duplicated code.

      step0: t0 = conj(x^|x| * x)         x = m
      step1: t1 = conj(x^|x| * x)         x = t0
      step2: a  = conj(x^|x|) * frob(x,1) x = t1
      step3: b  = conj(x^|x|)             x = a
      step4: t4 = conj(x^|x|) * frob(prev,2) * conj(prev)   prev = a
    """
    # easy part
    f1 = tw.f12_mul(tw.f12_conj(f), tw.f12_inv(f))
    m = tw.f12_mul(tw.f12_frobenius(f1, 2), f1)

    # Per-step epilogues computed UNCONDITIONALLY and selected by the step
    # counter: an earlier lax.switch version compiled each of the 5
    # branches as its own optimized subcomputation (~2x of the pairing's
    # XLA time).  Since conj distributes over mul (conj(p*x) = pc*conj(x)),
    # every epilogue is pc * y1 * y2 with y1/y2 chosen by selects — TWO
    # f12_mul in the body instead of four:
    #   k 0,1: y1 = conj(x)       y2 = 1      (t = conj(p * x))
    #   k 2:   y1 = frob(x, 1)    y2 = 1
    #   k 3:   y1 = 1             y2 = 1      (t = conj(p))
    #   k 4:   y1 = frob(prev,2)  y2 = conj(prev)
    def body(carry, k):
        x, prev = carry
        p = _cyclotomic_pow_abs_x(x)
        pc = tw.f12_conj(p)
        one = tw.f12_one(shape=jax.tree.leaves(x)[0].shape[:-1])
        y1 = tw.f12_select(k == 4, tw.f12_frobenius(prev, 2), one)
        y1 = tw.f12_select(k == 2, tw.f12_frobenius(x, 1), y1)
        y1 = tw.f12_select(k <= 1, tw.f12_conj(x), y1)
        y2 = tw.f12_select(k == 4, tw.f12_conj(prev), one)
        out = tw.f12_mul(tw.f12_mul(pc, y1), y2)
        return (out, x), None

    (t4, _), _ = jax.lax.scan(body, (m, m), jnp.arange(5))
    return tw.f12_mul(t4, tw.f12_mul(tw.f12_sqr(m), m))


def pairing(p_aff, q_aff):
    """e(P, Q) for finite affine inputs (batched)."""
    return final_exponentiation(miller_loop(q_aff, p_aff))
