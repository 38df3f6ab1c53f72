"""Fp2/Fp6/Fp12 tower arithmetic over limb tensors (JAX pytrees).

Elements are nested tuples of Fp limb tensors, mirroring the oracle's layout
(lodestar_tpu/crypto/bls/fields.py):
  Fp2  = (c0, c1)          u^2 = -1
  Fp6  = (a0, a1, a2)      v^3 = xi = 1 + u
  Fp12 = (b0, b1)          w^2 = v

SIMD structure: every tower multiplication gathers the *independent* Fp
products of its Karatsuba layer into ONE stacked ``fp.mont_mul`` call
(an f12_mul is 54 Fp products but only 3 mont_mul instances in the HLO:
one per tower level).  This keeps compiled program size O(formula depth)
instead of O(product count) — both an XLA-compile-time requirement and the
right shape for the TPU VPU, which wants wide element-wise ops.

Frobenius coefficients (gamma1[i] = xi^(i(p-1)/6)) are computed at import
time with the oracle's exact integer arithmetic and embedded as Montgomery
limb constants — no transcription risk.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lodestar_tpu.crypto.bls import fields as _orc
from . import fp
from .limbs import int_to_limbs, to_mont_int

# ---------------------------------------------------------------------------
# host encode/decode helpers (tests, constants)
# ---------------------------------------------------------------------------


def _const(x: int) -> jnp.ndarray:
    """Python int mod p -> device Montgomery limb constant."""
    return jnp.asarray(int_to_limbs(to_mont_int(x % _orc.P)))


def encode_fp2(a) -> tuple:
    return (_const(a[0]), _const(a[1]))


def encode_fp6(a) -> tuple:
    return tuple(encode_fp2(c) for c in a)


def encode_fp12(a) -> tuple:
    return tuple(encode_fp6(c) for c in a)


def _dec(x) -> int:
    return fp.decode(np.asarray(x))


def decode_fp2(a):
    return (_dec(a[0]), _dec(a[1]))


def decode_fp6(a):
    return tuple(decode_fp2(c) for c in a)


def decode_fp12(a):
    return tuple(decode_fp6(c) for c in a)


# ---------------------------------------------------------------------------
# stacking helpers: N same-shaped pytrees -> one pytree with leading axis N
# ---------------------------------------------------------------------------


def _stack(items):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *items)


def _unstack(tree, n):
    return [jax.tree.map(lambda t: t[i], tree) for i in range(n)]


def outlined(fn):
    """On the CPU backend, wrap ``fn`` in a length-1 lax.scan.

    XLA:CPU's compile time is superlinear in flat graph size; a full pairing
    inlines to ~10^5 elementwise ops and takes hours to compile.  A scan body
    is compiled as its own subcomputation, so outlining each tower op keeps
    every flat region small.  On TPU (where the compiler handles large fused
    graphs well, and fusion is where the performance is) the wrapper is a
    no-op.
    """
    import functools

    @functools.wraps(fn)
    def wrapper(*args):
        # fp._target_platform honours the LODESTAR_TPU_FP_PLATFORM pin
        if fp._target_platform() != "cpu":
            return fn(*args)
        xs = jax.tree.map(lambda t: t[None], args)
        _, out = jax.lax.scan(lambda c, x: (c, fn(*x)), jnp.uint32(0), xs)
        return jax.tree.map(lambda t: t[0], out)

    return wrapper


# ---------------------------------------------------------------------------
# Fp2
# ---------------------------------------------------------------------------


def f2_zeros(shape=()):
    return (fp.zeros(shape), fp.zeros(shape))


def f2_one(shape=()):
    return (fp.one_mont(shape), fp.zeros(shape))


def f2_add(a, b):
    return (fp.add(a[0], b[0]), fp.add(a[1], b[1]))


def f2_sub(a, b):
    return (fp.sub(a[0], b[0]), fp.sub(a[1], b[1]))


def f2_neg(a):
    return (fp.neg(a[0]), fp.neg(a[1]))


def f2_dbl(a):
    return f2_add(a, a)


def f2_mul(a, b):
    """Karatsuba: fused pallas kernel on TPU, one 3-way stacked mont_mul
    on the XLA path."""
    if fp._use_pallas():
        from . import pallas_fp

        return pallas_fp.f2_mul(a, b)
    lo = (fp.add(a[0], a[1]), fp.add(b[0], b[1]))
    A = jnp.stack([a[0], a[1], lo[0]])
    B = jnp.stack([b[0], b[1], lo[1]])
    T = fp.mont_mul(A, B)
    t0, t1, t2 = T[0], T[1], T[2]
    return (fp.sub(t0, t1), fp.sub(fp.sub(t2, t0), t1))


def f2_sqr(a):
    if fp._use_pallas():
        from . import pallas_fp

        return pallas_fp.f2_sqr(a)
    A = jnp.stack([fp.add(a[0], a[1]), a[0]])
    B = jnp.stack([fp.sub(a[0], a[1]), a[1]])
    T = fp.mont_mul(A, B)
    return (T[0], fp.add(T[1], T[1]))


def f2_conj(a):
    return (a[0], fp.neg(a[1]))


def f2_mul_fp(a, k):
    """Fp2 * Fp: one stacked mont_mul."""
    T = fp.mont_mul(jnp.stack([a[0], a[1]]), jnp.stack([k, k]))
    return (T[0], T[1])


def f2_mul_by_xi(a):
    # xi = 1 + u
    return (fp.sub(a[0], a[1]), fp.add(a[0], a[1]))


def f2_inv(a):
    T = fp.mont_mul(jnp.stack([a[0], a[1]]), jnp.stack([a[0], a[1]]))
    norm = fp.add(T[0], T[1])
    ninv = fp.inv(norm)
    U = fp.mont_mul(jnp.stack([a[0], a[1]]), jnp.stack([ninv, ninv]))
    return (U[0], fp.neg(U[1]))


def f2_is_zero(a):
    return fp.is_zero(a[0]) & fp.is_zero(a[1])


def f2_eq(a, b):
    return fp.eq(a[0], b[0]) & fp.eq(a[1], b[1])


def f2_select(cond, a, b):
    return (fp.select(cond, a[0], b[0]), fp.select(cond, a[1], b[1]))


# ---------------------------------------------------------------------------
# Fp6
# ---------------------------------------------------------------------------


def f6_zeros(shape=()):
    return (f2_zeros(shape), f2_zeros(shape), f2_zeros(shape))


def f6_one(shape=()):
    return (f2_one(shape), f2_zeros(shape), f2_zeros(shape))


def f6_add(a, b):
    return tuple(f2_add(x, y) for x, y in zip(a, b))


def f6_sub(a, b):
    return tuple(f2_sub(x, y) for x, y in zip(a, b))


def f6_neg(a):
    return tuple(f2_neg(x) for x in a)


def f6_mul(a, b):
    """Toom-style: 6 independent Fp2 products in one stacked f2_mul."""
    a0, a1, a2 = a
    b0, b1, b2 = b
    # pre-adds, batched: [(a1+a2), (a0+a1), (a0+a2)] and same for b
    pa = _stack([a1, a0, a0])
    pa2 = _stack([a2, a1, a2])
    pb = _stack([b1, b0, b0])
    pb2 = _stack([b2, b1, b2])
    sa = f2_add(pa, pa2)
    sb = f2_add(pb, pb2)
    s = _unstack(sa, 3)
    t = _unstack(sb, 3)
    # products: t0=a0b0, t1=a1b1, t2=a2b2, m12=(a1+a2)(b1+b2),
    #           m01=(a0+a1)(b0+b1), m02=(a0+a2)(b0+b2)
    P = f2_mul(_stack([a0, a1, a2, s[0], s[1], s[2]]),
               _stack([b0, b1, b2, t[0], t[1], t[2]]))
    t0, t1, t2, m12, m01, m02 = _unstack(P, 6)
    c0 = f2_add(t0, f2_mul_by_xi(f2_sub(f2_sub(m12, t1), t2)))
    c1 = f2_add(f2_sub(f2_sub(m01, t0), t1), f2_mul_by_xi(t2))
    c2 = f2_add(f2_sub(f2_sub(m02, t0), t2), t1)
    return (c0, c1, c2)


def f6_sqr(a):
    return f6_mul(a, a)


def f6_mul_by_v(a):
    return (f2_mul_by_xi(a[2]), a[0], a[1])


def f6_inv(a):
    a0, a1, a2 = a
    # layer 1: squares and cross products in one stacked f2_mul
    P = f2_mul(_stack([a0, a2, a1, a1, a0, a0]),
               _stack([a0, a2, a1, a2, a1, a2]))
    s0, s2, s1, a12, a01, a02 = _unstack(P, 6)
    c0 = f2_sub(s0, f2_mul_by_xi(a12))
    c1 = f2_sub(f2_mul_by_xi(s2), a01)
    c2 = f2_sub(s1, a02)
    # layer 2: t = a0 c0 + xi(a1 c2 + a2 c1)
    Q = f2_mul(_stack([a0, a1, a2]), _stack([c0, c2, c1]))
    q0, q1, q2 = _unstack(Q, 3)
    t = f2_add(q0, f2_mul_by_xi(f2_add(q1, q2)))
    tinv = f2_inv(t)
    R = f2_mul(_stack([c0, c1, c2]),
               _stack([tinv, tinv, tinv]))
    r0, r1, r2 = _unstack(R, 3)
    return (r0, r1, r2)


def f6_is_zero(a):
    return f2_is_zero(a[0]) & f2_is_zero(a[1]) & f2_is_zero(a[2])


def f6_select(cond, a, b):
    return tuple(f2_select(cond, x, y) for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# Fp12
# ---------------------------------------------------------------------------


def f12_zeros(shape=()):
    return (f6_zeros(shape), f6_zeros(shape))


def f12_one(shape=()):
    return (f6_one(shape), f6_zeros(shape))


def f12_add(a, b):
    return (f6_add(a[0], b[0]), f6_add(a[1], b[1]))


def f12_sub(a, b):
    return (f6_sub(a[0], b[0]), f6_sub(a[1], b[1]))


def f12_mul(a, b):
    """Karatsuba over Fp6: 3 independent f6 products, one stacked call."""
    a0, a1 = a
    b0, b1 = b
    P = f6_mul(_stack([a0, a1, f6_add(a0, a1)]),
               _stack([b0, b1, f6_add(b0, b1)]))
    t0, t1, t01 = _unstack(P, 3)
    c0 = f6_add(t0, f6_mul_by_v(t1))
    c1 = f6_sub(f6_sub(t01, t0), t1)
    return (c0, c1)


def f12_sqr(a):
    a0, a1 = a
    P = f6_mul(_stack([a0, f6_add(a0, a1)]),
               _stack([a1, f6_add(a0, f6_mul_by_v(a1))]))
    t, c0 = _unstack(P, 2)
    c0 = f6_sub(f6_sub(c0, t), f6_mul_by_v(t))
    c1 = f6_add(t, t)
    return (c0, c1)


def f12_conj(a):
    return (a[0], f6_neg(a[1]))


def f12_inv(a):
    a0, a1 = a
    P = f6_mul(_stack([a0, a1]), _stack([a0, a1]))
    s0, s1 = _unstack(P, 2)
    t = f6_sub(s0, f6_mul_by_v(s1))
    tinv = f6_inv(t)
    Q = f6_mul(_stack([a0, a1]), _stack([tinv, tinv]))
    q0, q1 = _unstack(Q, 2)
    return (q0, f6_neg(q1))


def f12_is_one(a):
    c00 = a[0][0]
    eq_one = fp.eq(c00[0], jnp.broadcast_to(fp.one_mont(), c00[0].shape)) & fp.is_zero(c00[1])
    return eq_one & f2_is_zero(a[0][1]) & f2_is_zero(a[0][2]) & f6_is_zero(a[1])


def f12_select(cond, a, b):
    return (f6_select(cond, a[0], b[0]), f6_select(cond, a[1], b[1]))


# ---------------------------------------------------------------------------
# Frobenius (coefficients computed from the oracle at import time)
# ---------------------------------------------------------------------------

_GAMMA1_CONST = [encode_fp2(g) for g in _orc.GAMMA1]


def _to_wcoeffs(a):
    (a0, a1, a2), (b0, b1, b2) = a
    return [a0, b0, a1, b1, a2, b2]


def _from_wcoeffs(c):
    return ((c[0], c[2], c[4]), (c[1], c[3], c[5]))


def f12_frobenius(a, power: int = 1):
    out = a
    for _ in range(power % 12):
        coeffs = _to_wcoeffs(out)
        gammas = [jax.tree.map(lambda t: jnp.broadcast_to(t, coeffs[0][0].shape), g)
                  for g in _GAMMA1_CONST]
        conj = [f2_conj(c) for c in coeffs]
        P = f2_mul(_stack(conj), _stack(gammas))
        coeffs = _unstack(P, 6)
        out = _from_wcoeffs(coeffs)
    return out


# ---------------------------------------------------------------------------
# Program-build containment, two layers:
#   * `outlined` (CPU only): length-1 scan bodies keep XLA:CPU's
#     superlinear fusion/simplification passes fed with small flat
#     regions (the inlined full pairing takes 30+ min / tens of GB
#     there).  On TPU the wrapper no-ops.
#   * `opcache.cached` (all platforms): each op's jaxpr is traced ONCE
#     per argument shape and replayed at every further call site —
#     without it, every site re-traces the pallas kernel / scan body
#     (~0.75 s per f2_mul site on the 1-CPU bench host; build time, not
#     XLA optimization, dominated the cold-compile blowups of rounds
#     1-3).  See opcache.py for measurements.
# ---------------------------------------------------------------------------

from .opcache import cached as _cached

f2_mul = _cached(outlined(f2_mul))
f2_sqr = _cached(outlined(f2_sqr))
f2_inv = _cached(outlined(f2_inv))
f6_mul = _cached(outlined(f6_mul))
f6_sqr = _cached(outlined(f6_sqr))
f6_inv = _cached(outlined(f6_inv))
f12_mul = _cached(outlined(f12_mul))
f12_sqr = _cached(outlined(f12_sqr))
f12_inv = _cached(outlined(f12_inv))
f12_frobenius = _cached(f12_frobenius, static_argnums=(1,))
f12_select = _cached(f12_select)
f12_conj = _cached(f12_conj)
