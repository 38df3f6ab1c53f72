"""G1/G2 group ops on limb tensors: branchless Jacobian arithmetic + scans.

Points are tuples ``(X, Y, Z)`` of field elements (Fp limb tensors for G1,
Fp2 tuples for G2), Jacobian coordinates, ``Z == 0`` meaning infinity.
All control flow is data-independent: the add formula computes both the
add and double paths and selects — the XLA-friendly version of the oracle's
branching (lodestar_tpu/crypto/bls/curve.py `_CurveOps`), mirroring the
role of blst's group ops in the reference client's pubkey aggregation
(packages/beacon-node/src/chain/bls/utils.ts:5).

Scalar multiplication scans over a *runtime* bit tensor — the 64-bit
random-linear-combination coefficients of batch verification arrive as data
(chain/bls/maybeBatch.ts:17), not as compile-time constants.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import fp, tower as tw


class FieldOps(NamedTuple):
    """Field-op vtable so G1 (Fp) and G2 (Fp2) share one set of formulas."""

    add: callable
    sub: callable
    mul: callable
    sqr: callable
    neg: callable
    is_zero: callable
    select: callable
    zeros_like: callable
    one_like: callable


F1 = FieldOps(
    add=fp.add,
    sub=fp.sub,
    mul=fp.mont_mul,
    sqr=fp.mont_sqr,
    neg=fp.neg,
    is_zero=fp.is_zero,
    select=fp.select,
    zeros_like=lambda a: jnp.zeros_like(a),
    one_like=lambda a: jnp.broadcast_to(fp.one_mont(), a.shape),
)

F2 = FieldOps(
    add=tw.f2_add,
    sub=tw.f2_sub,
    mul=tw.f2_mul,
    sqr=tw.f2_sqr,
    neg=tw.f2_neg,
    is_zero=tw.f2_is_zero,
    select=tw.f2_select,
    zeros_like=lambda a: (jnp.zeros_like(a[0]), jnp.zeros_like(a[1])),
    one_like=lambda a: (jnp.broadcast_to(fp.one_mont(), a[0].shape), jnp.zeros_like(a[1])),
)


def is_inf(F: FieldOps, pt):
    return F.is_zero(pt[2])


def inf_like(F: FieldOps, pt):
    return (F.one_like(pt[0]), F.one_like(pt[1]), F.zeros_like(pt[2]))


def pt_select(F: FieldOps, cond, a, b):
    return tuple(F.select(cond, x, y) for x, y in zip(a, b))


def jac_double(F: FieldOps, pt):
    """EFD dbl-2009-l (a=0); infinity/2-torsion handled by select."""
    X1, Y1, Z1 = pt
    A = F.sqr(X1)
    B = F.sqr(Y1)
    C = F.sqr(B)
    D = F.sub(F.sqr(F.add(X1, B)), F.add(A, C))
    D = F.add(D, D)
    E = F.add(F.add(A, A), A)
    Fq = F.sqr(E)
    X3 = F.sub(Fq, F.add(D, D))
    C8 = F.add(C, C)
    C8 = F.add(C8, C8)
    C8 = F.add(C8, C8)
    Y3 = F.sub(F.mul(E, F.sub(D, X3)), C8)
    Z3 = F.mul(F.add(Y1, Y1), Z1)
    out = (X3, Y3, Z3)
    bad = F.is_zero(Z1) | F.is_zero(Y1)
    return pt_select(F, bad, inf_like(F, pt), out)


def jac_add(F: FieldOps, p1, p2):
    """Complete Jacobian addition: handles inf, equal and opposite inputs.

    Computes the generic add and the doubling path and selects — constant
    shape, no data-dependent branching (EFD add-2007-bl + dbl fallback).
    """
    X1, Y1, Z1 = p1
    X2, Y2, Z2 = p2
    Z1Z1 = F.sqr(Z1)
    Z2Z2 = F.sqr(Z2)
    U1 = F.mul(X1, Z2Z2)
    U2 = F.mul(X2, Z1Z1)
    S1 = F.mul(F.mul(Y1, Z2), Z2Z2)
    S2 = F.mul(F.mul(Y2, Z1), Z1Z1)
    H = F.sub(U2, U1)
    Rr = F.sub(S2, S1)
    HH = F.sqr(H)
    HHH = F.mul(H, HH)
    V = F.mul(U1, HH)
    X3 = F.sub(F.sub(F.sqr(Rr), HHH), F.add(V, V))
    Y3 = F.sub(F.mul(Rr, F.sub(V, X3)), F.mul(S1, HHH))
    Z3 = F.mul(F.mul(Z1, Z2), H)
    added = (X3, Y3, Z3)

    same_x = F.is_zero(H)
    same_y = F.is_zero(Rr)
    doubled = jac_double(F, p1)

    out = pt_select(F, same_x & same_y, doubled, added)          # P + P
    out = pt_select(F, same_x & ~same_y, inf_like(F, p1), out)   # P + (-P)
    out = pt_select(F, is_inf(F, p1), p2, out)
    out = pt_select(F, is_inf(F, p2), p1, out)
    return out


def jac_neg(F: FieldOps, pt):
    return (pt[0], F.neg(pt[1]), pt[2])


def from_affine(F: FieldOps, aff, inf_mask=None):
    """(x, y) -> (x, y, 1); where inf_mask is set, the point at infinity."""
    x, y = aff
    one = F.one_like(x)
    pt = (x, y, one)
    if inf_mask is not None:
        pt = pt_select(F, inf_mask, (one, one, F.zeros_like(x)), pt)
    return pt


def scalar_mul_bits(F: FieldOps, pt, bits):
    """[k]P with k given as an MSB-first bit tensor of shape (..., NBITS).

    Scans over the bit axis; the batch lives in the leading axes of both
    ``pt`` and ``bits``.
    """
    nbits = bits.shape[-1]
    bits_s = jnp.moveaxis(bits, -1, 0)  # (NBITS, ...batch)

    def body(acc, bit):
        acc = jac_double(F, acc)
        acc_plus = jac_add(F, acc, pt)
        acc = pt_select(F, bit != 0, acc_plus, acc)
        return acc, None

    acc0 = fp.vary_like(inf_like(F, pt), pt, bits)
    acc, _ = jax.lax.scan(body, acc0, bits_s)
    return acc


def to_affine(F: FieldOps, pt, f_inv):
    """Jacobian -> affine; infinity yields (0, 0) plus a mask.

    ``f_inv`` is the field inversion (fp.inv or tower.f2_inv); inv(0) = 0 so
    infinity stays finite garbage that callers mask out.
    """
    X, Y, Z = pt
    zinv = f_inv(Z)
    zinv2 = F.sqr(zinv)
    x = F.mul(X, zinv2)
    y = F.mul(Y, F.mul(zinv, zinv2))
    return (x, y), is_inf(F, pt)


# point-batch reduction lives in verify.py (jac_reduce_add — any batch size)

# trace-once caching (opcache.py): group ops are the widest re-traced
# bodies outside the field layer — a jac_add site binds ~16 field products.
# F / f_inv are static (hashable vtables / function objects).
from .opcache import cached as _cached

jac_double = _cached(jac_double, static_argnums=(0,))
jac_add = _cached(jac_add, static_argnums=(0,))
scalar_mul_bits = _cached(scalar_mul_bits, static_argnums=(0,))
to_affine = _cached(to_affine, static_argnums=(0, 2))
from_affine = _cached(from_affine, static_argnums=(0,))


# ---------------------------------------------------------------------------
# host-side encoding helpers (oracle points -> limb tensors)
# ---------------------------------------------------------------------------


def encode_g1_affine(points):
    """List of oracle AffineG1 (None = inf) -> ((B,NL),(B,NL)) + inf mask."""
    xs, ys, inf = [], [], []
    for pt in points:
        if pt is None:
            xs.append(0)
            ys.append(0)
            inf.append(True)
        else:
            xs.append(pt[0])
            ys.append(pt[1])
            inf.append(False)
    ex = np.stack([fp.encode_int(v) for v in xs])
    ey = np.stack([fp.encode_int(v) for v in ys])
    return (jnp.asarray(ex), jnp.asarray(ey)), jnp.asarray(np.array(inf))


def encode_g2_affine(points):
    """List of oracle AffineG2 -> Fp2-pair limb tensors + inf mask."""
    x0, x1, y0, y1, inf = [], [], [], [], []
    for pt in points:
        if pt is None:
            x0.append(0), x1.append(0), y0.append(0), y1.append(0)
            inf.append(True)
        else:
            (a0, a1), (b0, b1) = pt
            x0.append(a0), x1.append(a1), y0.append(b0), y1.append(b1)
            inf.append(False)
    e = lambda vs: jnp.asarray(np.stack([fp.encode_int(v) for v in vs]))
    return ((e(x0), e(x1)), (e(y0), e(y1))), jnp.asarray(np.array(inf))


def scalars_to_bits(scalars, nbits=64) -> jnp.ndarray:
    """Host: list of python ints -> (B, nbits) MSB-first uint32 bit tensor."""
    out = np.zeros((len(scalars), nbits), dtype=np.uint32)
    for i, s in enumerate(scalars):
        for j in range(nbits):
            out[i, nbits - 1 - j] = (s >> j) & 1
    return jnp.asarray(out)
