"""Fp arithmetic over limb tensors — the innermost TPU kernel layer.

Every value is a uint32 tensor ``(..., NLIMBS)`` of radix-2^13 limbs in
Montgomery form, canonical (each limb < 2^13, value < p).  Ops broadcast over
leading axes, so a batch of field elements is just a leading dimension — the
TPU-native analogue of the reference's per-core BLS worker data parallelism
(packages/beacon-node/src/chain/bls/multithread/index.ts:98).

Design note on carry handling: carry/borrow propagation is NOT a sequential
scan here.  A pairing is ~10^5 field ops; giving each one a ``lax.scan``
produces thousands of XLA while-subcomputations and intractable compile
times.  Instead, carries resolve with a branch-free BROADCAST-COMPARE
formulation: carry_in[i] = OR_{j<i} (generate[j] AND limbs j+1..i-1 all
propagate), where the "all propagate" predicate is a prefix-count equality
computed with ONE tiny static matmul (cumulative sum by lower-triangular
matrix).  This yields ~10 elementwise HLO ops on a (..., N, N) tile per
carry resolution — no concatenate/pad chains, which XLA:CPU's fusion and
algebraic-simplifier passes handle pathologically slowly (measured ~1 s of
compile time PER shift-by-concat op, vs milliseconds for dots/elementwise),
and no log-depth shift networks.  On TPU the (30, 30) tile is VPU-friendly.

Limb shifts (multiply/divide by the radix) are likewise static matmuls
(x @ SHIFT) instead of concatenates, for the same compile-time reason.

Overflow soundness is machine-checked, not hand-audited: the
``limb-bounds`` lodelint rule (tools/lint/rules_bounds.py) abstract-
interprets this module and proves, per assignment, that no uint32
expression can reach 2^32 and no implicit dtype promotion sneaks in.
Entry points carry machine-readable ``@bounds:`` contracts in their
docstrings — grammar and suppression semantics in docs/LINT.md
("lodelint v4").  The headline CIOS bound the rule re-derives on every
run — a column receives at most ``2*NLIMBS*(2^13-1)^2 + carry < 2^32``
— is also recomputed from the actual LIMB_BITS/NLIMBS constants by
tests/test_limb_bounds_audit.py, so a radix change cannot leave a
stale audit behind.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .limbs import (
    LIMB_BITS,
    MASK,
    NLIMBS,
    N0INV,
    NPRIME_LIMBS,
    ONE_MONT,
    P_LIMBS,
    R2_LIMBS,
)

_u32 = jnp.uint32

# Unroll the 30-step CIOS loop into straight-line code (no while loop).
# Measured on TPU v5e (B=256 miller loop): scanned CIOS compiles ~40%
# faster AND runs ~15% faster than unrolled (450ms vs 532ms) — the scan
# body is compiled once and the TPU pipeline keeps it fed; unrolling only
# bloats the HLO.  Default False.
CIOS_UNROLL = False

# Device-constant views of host numpy constants (closed over inside jit).
_P = jnp.asarray(P_LIMBS, dtype=_u32)
_R2 = jnp.asarray(R2_LIMBS, dtype=_u32)
_ONE_M = jnp.asarray(ONE_MONT, dtype=_u32)

# Static limb-axis shift matrices (see module docstring): shifts as dots.
_SHIFT_UP_M = jnp.asarray(np.eye(NLIMBS, k=1, dtype=np.uint32))    # x @ M -> limb k = x[k-1]
_SHIFT_DOWN_M = jnp.asarray(np.eye(NLIMBS, k=-1, dtype=np.uint32))  # x @ M -> limb k = x[k+1]
_E0 = jnp.asarray(np.eye(NLIMBS, dtype=np.uint32)[0])


def zeros(shape=()) -> jnp.ndarray:
    return jnp.zeros((*shape, NLIMBS), dtype=_u32)


def one_mont(shape=()) -> jnp.ndarray:
    return jnp.broadcast_to(_ONE_M, (*shape, NLIMBS))


def vary_like(tree, *refs):
    """Cast ``tree``'s leaves to vary over every shard_map axis any of
    ``refs`` varies over.  A scan whose initial carry is a constant
    (the identity, zeros) but whose body mixes in sharded inputs needs
    this under ``check_vma=True``; outside shard_map it is a no-op."""
    axes = frozenset().union(*(jax.typeof(r).vma for r in jax.tree.leaves(refs)))
    if not axes:
        return tree

    def cast(t):
        missing = tuple(sorted(axes - jax.typeof(t).vma))
        return jax.lax.pcast(t, missing, to="varying") if missing else t

    return jax.tree.map(cast, tree)


# ---------------------------------------------------------------------------
# carry / borrow primitives (log-depth, no scans)
# ---------------------------------------------------------------------------


def _dot(x, m):
    """x @ m along the limb axis (static 0/1 uint32 matrix).

    Written as broadcast-multiply + reduce-sum rather than dot_general:
    XLA:CPU codegens integer matmuls slowly (no Eigen path), while the
    elementwise form compiles in milliseconds and fuses; on TPU the
    (..., N, N) tile is trivially vectorized."""
    return (x[..., :, None] * m).sum(axis=-2)


def _shift_up(x):
    """Limb k of result = limb k-1 of x (i.e. multiply by 2^13), zero-fill."""
    return _dot(x, _SHIFT_UP_M)


def _carry_pass(x):
    """One parallel carry pass: limbs shrink toward canonical."""
    return (x & MASK) + _shift_up(x >> LIMB_BITS)


_SHIFT_CACHE: dict = {}


def _shift_lo(x, d: int):
    """shifted[i] = x[i-d], zero-filled below (toward less significant).

    TPU: pad/slice (free sublane/lane moves).  CPU: a cached static
    shift-matrix dot — XLA:CPU's fusion/simplification passes take ~1s of
    compile time PER pad-of-slice op, and the Kogge-Stone carry resolves
    emit several per field op; dots compile in milliseconds there."""
    if _target_platform() == "cpu":
        n = x.shape[-1]
        key = (n, d)
        m = _SHIFT_CACHE.get(key)
        if m is None:
            # cache the NUMPY matrix (a jnp constant created inside one
            # trace must not be reused across traces — tracer leak)
            m = np.eye(n, k=d, dtype=np.uint32)
            _SHIFT_CACHE[key] = m
        return _dot(x, m)
    pad = [(0, 0)] * (x.ndim - 1) + [(d, 0)]
    return jnp.pad(x[..., : x.shape[-1] - d], pad)


def _propagate(g, pr):
    """Branch-free single-bit carry/borrow propagation (any limb width).

    g[j]:  limb j generates (uint32 0/1).   pr[j]: limb j propagates.
    g and pr must be disjoint (a generating limb cannot also propagate).
    Returns (carry_in per limb, total carry-out), where
      carry_in[i] = OR_{j<i} ( g[j] AND pr[j+1..i-1] all set ).

    Kogge-Stone parallel prefix over the carry operator
      (G, P) combine-with-lower (G', P')  =  (G | (P & G'), P & P')
    log2(n) doubling steps of pure elementwise ops on (..., n) tensors —
    linear work per step, no quadratic (n, n) intermediates (an earlier
    prefix-count formulation built (..., n, n) masks; at a stacked-f12
    batch width that materialized ~100 MB per carry resolve and dominated
    kernel runtime).
    """
    n = g.shape[-1]
    G, P = g, pr
    d = 1
    while d < n:
        G = G | (P & _shift_lo(G, d))
        P = P & _shift_lo(P, d)
        d <<= 1
    carry_in = _shift_lo(G, 1)               # carry INTO limb i = G[i-1]
    total = G[..., -1]
    return carry_in, total


def _resolve_single_carries(t):
    """Exact canonicalization for limbs with single-bit carries (any width).

    Precondition: every limb of t is <= 2^14 - 2, so carry-out per limb is
    0 or 1 even with an incoming carry.  Callers stay within bound: add()
    feeds limbs <= 2*MASK = 2^14 - 2; sub() feeds d + P <= 2*MASK;
    _norm_wide / mont_mul feed limbs <= MASK + ~64 after two carry passes.
    """
    g = (t >> LIMB_BITS).astype(_u32)          # t >= 2^13 -> generates
    pr = (t == MASK).astype(_u32)              # t == mask -> propagates
    carry_in, _ = _propagate(g, pr)
    return (t + carry_in) & MASK


def _norm_wide(u):
    """Canonicalize limbs up to 2^32 (mont_mul output): 2 passes + resolve."""
    u = _carry_pass(u)   # limbs <= mask + 2^19
    u = _carry_pass(u)   # limbs <= mask + 61 < 2^14
    return _resolve_single_carries(u)


def _borrow_sub(a, b):
    """(a - b) mod 2^390 on canonical limbs; returns (limbs, borrow_flag).

    borrow_flag (uint32 0/1 shaped (...,)) is 1 iff a < b.
    """
    g = (a < b).astype(_u32)
    pr = (a == b).astype(_u32)
    borrow_in, borrow_out = _propagate(g, pr)
    limbs = (a + _u32(1 << LIMB_BITS) - b - borrow_in) & MASK
    return limbs, borrow_out


def _cond_sub_p(t):
    """Canonicalize t in [0, 2p) -> [0, p) (canonical limbs in)."""
    d, borrow = _borrow_sub(t, jnp.broadcast_to(_P, t.shape))
    return jnp.where((borrow != 0)[..., None], t, d)


# ---------------------------------------------------------------------------
# ring ops
# ---------------------------------------------------------------------------


def _flat_leading(fn):
    """Collapse all leading axes to ONE before running `fn`, restore after.

    The tower stacks products into leading axes ((3,6,3,B,NLIMBS) for an
    f12 mul), and the carry machinery adds two more (..., N, N) — XLA's
    layout/fusion passes are superlinear in tensor rank, and rank-7
    intermediates were measured to dominate compile time.  Every fp entry
    point therefore runs rank<=3 internally."""
    import functools

    @functools.wraps(fn)
    def wrapped(a, b):
        a, b = jnp.broadcast_arrays(a, b)
        lead = a.shape[:-1]
        if len(lead) > 1:
            a2 = a.reshape((-1, a.shape[-1]))
            b2 = b.reshape((-1, b.shape[-1]))
            return fn(a2, b2).reshape((*lead, -1))
        return fn(a, b)

    return wrapped


@_flat_leading
def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a + b mod p on canonical limbs.

    @bounds: a [0, 2^13-1], b [0, 2^13-1] -> [0, 2^13-1]
    """
    return _cond_sub_p(_resolve_single_carries(a + b))


@_flat_leading
def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b mod p on canonical limbs.

    @bounds: a [0, 2^13-1], b [0, 2^13-1] -> [0, 2^13-1]
    """
    d, borrow = _borrow_sub(a, b)
    # Where a < b the limbs represent a-b+2^390; adding p and dropping the
    # top carry (exactly 2^390) yields a-b+p in [0, p).
    dp = _resolve_single_carries(d + _P)
    return jnp.where((borrow != 0)[..., None], dp, d)


def neg(a: jnp.ndarray) -> jnp.ndarray:
    """-a mod p.

    @bounds: a [0, 2^13-1] -> [0, 2^13-1]
    """
    return sub(jnp.zeros_like(a), a)


def dbl(a: jnp.ndarray) -> jnp.ndarray:
    """2a mod p.

    @bounds: a [0, 2^13-1] -> [0, 2^13-1]
    """
    return add(a, a)


def _cios_step(u, a_i, b):
    u = u + a_i[..., None] * b
    m = (u[..., 0] * _u32(N0INV)) & MASK
    u = u + m[..., None] * _P
    carry = u[..., 0] >> LIMB_BITS
    # shift down one limb (drop the now-zero column 0) and add the carry
    # into the new limb 0 — as a dot, not a concatenate (see module note)
    return _dot(u, _SHIFT_DOWN_M) + carry[..., None] * _E0


def mont_mul_cios(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Montgomery product via the serial CIOS scan (kept as the reference
    implementation / fallback; the default mont_mul is the parallel
    full-product reduction below).

    @bounds: a [0, 2^13-1], b [0, 2^13-1] -> [0, 2^13-1]
    """
    a, b = jnp.broadcast_arrays(a, b)
    if CIOS_UNROLL:
        u = jnp.zeros_like(b)
        for i in range(NLIMBS):
            u = _cios_step(u, a[..., i], b)
    else:
        a_s = jnp.moveaxis(a, -1, 0)
        # zeros that vary over whatever a or b varies over (shard_map)
        u, _ = jax.lax.scan(lambda u, ai: (_cios_step(u, ai, b), None),
                            jnp.zeros_like(a + b), a_s)
    return _cond_sub_p(_norm_wide(u))


# ---------------------------------------------------------------------------
# parallel Montgomery multiplication (no serial limb scan)
#
# The CIOS scan above serializes 30 dependent steps per product; a pairing
# is ~10^5 products, so the whole program becomes a chain of ~10^7 tiny VPU
# ops and runs latency-bound (~1 us/op on v5e) regardless of batch size.
# Here the product and the Montgomery reduction are each ONE wide data-
# parallel expression over (..., 2N)-limb tensors:
#
#     U  = a * b                      (schoolbook convolution, 59 limbs)
#     mu = (U mod R) * N' mod R       (low-half convolution, R = 2^390)
#     T  = U + mu * p                 (T = 0 mod R by construction)
#     out = T / R  in [0, 2p) -> cond_sub
#
# Convolutions are gather+multiply+reduce (no data-dependent control flow);
# carries resolve with the branch-free passes/_propagate machinery.  Every
# intermediate fits uint32: products are <= 8223 * 8191 * 30 < 2^31 and the
# sum U + mu*p <= 2^31 + 8223 (see the per-step bounds in comments).
# ---------------------------------------------------------------------------

_NPRIME = jnp.asarray(NPRIME_LIMBS, dtype=_u32)
_WIDE = 2 * NLIMBS - 1  # 59 limbs in a raw product


def _conv_idx(out_width: int) -> np.ndarray:
    """IDX[i, k] = k - i where valid else NLIMBS (a zero slot): gathers
    shifted copies of a zero-padded multiplicand so that
    sum_i a[i] * b_pad[IDX[i, k]] = (a conv b)[k]."""
    idx = np.full((NLIMBS, out_width), NLIMBS, dtype=np.int32)
    for i in range(NLIMBS):
        for k in range(out_width):
            j = k - i
            if 0 <= j < NLIMBS:
                idx[i, k] = j
    return idx


_IDX_FULL = jnp.asarray(_conv_idx(_WIDE))     # (30, 59)
_IDX_LOW = jnp.asarray(_conv_idx(NLIMBS))     # (30, 30): product mod R

def _conv(a, b, idx):
    """Limb convolution sum_{i+j=k} a_i*b_j via one gather + one reduce.

    Output limb k <= 30 * max(a) * max(b); callers keep that < 2^32.
    """
    b_pad = jnp.concatenate([b, jnp.zeros_like(b[..., :1])], axis=-1)
    shifts = b_pad[..., idx]                  # (..., 30, out_width)
    return (a[..., :, None] * shifts).sum(axis=-2)


def _carry_widen(x, grow: int = 1):
    """One carry pass that widens by `grow` limbs (no truncation)."""
    lo = x & MASK
    hi = x >> LIMB_BITS
    pad_tail = [(0, 0)] * (x.ndim - 1) + [(0, grow)]
    pad_head = [(0, 0)] * (x.ndim - 1) + [(1, grow - 1)]
    return jnp.pad(lo, pad_tail) + jnp.pad(hi, pad_head)


def _carry_trunc(x):
    """One carry pass at fixed width (drops the top carry: mod 2^(13*n))."""
    lo = x & MASK
    hi = x >> LIMB_BITS
    return lo + jnp.pad(hi[..., :-1], [(0, 0)] * (x.ndim - 1) + [(1, 0)])


# Pallas-fused mont_mul on TPU backends (see pallas_fp.py);
# LODESTAR_TPU_PALLAS=0 opts out.  Decided at trace time: CPU (tests,
# virtual mesh) keeps the XLA paths below.  LODESTAR_TPU_FP_PLATFORM
# names the platform outright where the default backend is not the one
# the program is built for (CPU-pinned tests and dryruns, deviceless
# compiles for a described TPU).
import os as _os

PALLAS = _os.environ.get("LODESTAR_TPU_PALLAS", "1") != "0"


def _target_platform() -> str:
    override = _os.environ.get("LODESTAR_TPU_FP_PLATFORM")
    if override:
        return override
    # a backend that fails to start raises here: it never reads as "cpu"
    return jax.default_backend()


def _use_pallas() -> bool:
    return PALLAS and _target_platform() == "tpu"


@_flat_leading
def mont_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Montgomery product a*b*R^{-1} mod p, canonical output.

    @bounds: a [0, 2^13-1], b [0, 2^13-1] -> [0, 2^13-1]

    Backend dispatch (trace-time):
      * tpu  -> Pallas fused kernel (pallas_fp.py; bandwidth-optimal)
      * else -> serial CIOS scan (mont_mul_cios): XLA:CPU compiles the
        small scan body in seconds, while the parallel pad/concat form
        below takes *hours* in its fusion/simplification passes (the
        dryrun's sharded program never finished compiling)
      * the parallel XLA form stays available as mont_mul_parallel for
        ablation and as the reference the Pallas kernel is tested against
    """
    if _use_pallas():
        from . import pallas_fp

        return pallas_fp.mont_mul(a, b)
    if _target_platform() != "tpu":
        # CPU: CIOS scan by default; LODESTAR_TPU_CPU_PARALLEL_FP=1 selects
        # the scan-free conv form (fewer, flatter XLA:CPU computations —
        # compile-time experiment knob, safe either way: both forms are
        # differential-tested)
        if _os.environ.get("LODESTAR_TPU_CPU_PARALLEL_FP") == "1":
            return mont_mul_parallel(a, b)
        return mont_mul_cios(a, b)
    return mont_mul_parallel(a, b)


@_flat_leading
def mont_mul_parallel(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """The parallel (no serial limb scan) XLA expression form.

    @bounds: a [0, 2^13-1], b [0, 2^13-1] -> [0, 2^13-1]
    """
    # U = a*b: 59 limbs <= 30*8191^2 < 2^31
    u = _conv(a, b, _IDX_FULL)
    # two widening passes: limbs <= 8191 + 31 (=: B1), width 61
    u = _carry_widen(_carry_widen(u))
    # mu = (U mod R) * N' mod R: low conv of U's low half; since dropping
    # limbs >= 30 only removes multiples of R, the result is exact mod R.
    # products <= B1 * 8191 * 30 < 2^31
    mu = _conv(u[..., :NLIMBS], jnp.broadcast_to(_NPRIME, a.shape), _IDX_LOW)
    mu = _carry_trunc(_carry_trunc(mu))       # limbs <= B1, exact mod R
    # T = U + mu*p: mu*p limbs <= B1 * 8191 * 30 < 2^31 - B1
    mp = _conv(mu, jnp.broadcast_to(_P, a.shape), _IDX_FULL)
    pad = [(0, 0)] * (u.ndim - 1) + [(0, 2)]
    t = u + jnp.pad(mp, pad)                  # width 61
    # exact canonicalization: T = 0 mod R, so limbs 0..29 cancel to zero
    # and T/R is literally limbs 30..59 of the canonical form
    t = _carry_widen(_carry_widen(t))         # width 63, limbs <= 2^14 - 2
    t = _resolve_single_carries(t)
    res = t[..., NLIMBS : 2 * NLIMBS]         # T/R < 2p (Montgomery bound)
    return _cond_sub_p(res)


def mont_sqr(a: jnp.ndarray) -> jnp.ndarray:
    """a^2 in Montgomery form.

    @bounds: a [0, 2^13-1] -> [0, 2^13-1]
    """
    return mont_mul(a, a)


def to_mont(a: jnp.ndarray) -> jnp.ndarray:
    """Plain limbs (value < p) -> Montgomery form.

    @bounds: a [0, 2^13-1] -> [0, 2^13-1]
    """
    return mont_mul(a, jnp.broadcast_to(_R2, a.shape))


def from_mont(a: jnp.ndarray) -> jnp.ndarray:
    """Montgomery form -> plain canonical limbs.

    @bounds: a [0, 2^13-1] -> [0, 2^13-1]
    """
    one = jnp.zeros_like(a).at[..., 0].set(1)
    return mont_mul(a, one)


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    """Canonical limbs -> bool (...,)."""
    return jnp.all(a == 0, axis=-1)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(a == b, axis=-1)


def select(cond: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """where(cond, a, b) with cond shaped (...,) against (..., NLIMBS)."""
    return jnp.where(cond[..., None], a, b)


# ---------------------------------------------------------------------------
# fixed-exponent powers (exponent is a compile-time python int)
# ---------------------------------------------------------------------------


def _exp_bits(e: int) -> np.ndarray:
    """MSB-first bit array of a positive python int."""
    return np.array([int(c) for c in bin(e)[2:]], dtype=np.uint32)


def mont_pow_fixed(a: jnp.ndarray, e: int) -> jnp.ndarray:
    """a^e in Montgomery form (a Montgomery in, result Montgomery out).

    Fixed-window (4-bit) exponentiation: precompute a^0..a^15 once, then
    scan the exponent's nibbles MSB-first with 4 squarings + ONE
    table-lookup multiply per window.  Halves the multiply count of the
    bitwise square-and-multiply ladder (the Fermat inversions a^(p-2) are
    ~15% of the whole verification program's op count).

    @bounds: a [0, 2^13-1], e host -> [0, 2^13-1]
    """
    if e == 0:
        return jnp.broadcast_to(_ONE_M, a.shape)
    if e < 16:
        # tiny exponents: plain ladder
        acc = jnp.broadcast_to(_ONE_M, a.shape)
        for bit in _exp_bits(e):
            acc = mont_mul(acc, acc)
            if bit:
                acc = mont_mul(acc, a)
        return acc

    # nibble digits, MSB-first, padded to whole windows
    ndigits = (e.bit_length() + 3) // 4
    digits = np.array(
        [(e >> (4 * (ndigits - 1 - i))) & 0xF for i in range(ndigits)],
        dtype=np.int32,
    )

    # table a^0 .. a^15 stacked on a new leading axis (one-time 14 muls)
    pows = [jnp.broadcast_to(_ONE_M, a.shape), a]
    sq = mont_mul(a, a)
    pows.append(sq)
    for _ in range(13):
        pows.append(mont_mul(pows[-1], a))
    table = jnp.stack(pows)  # (16, ..., NLIMBS)

    def body(acc, d):
        for _ in range(4):
            acc = mont_mul(acc, acc)
        acc = mont_mul(acc, table[d])
        return acc, None

    acc = vary_like(jnp.broadcast_to(_ONE_M, a.shape), a)
    acc, _ = jax.lax.scan(body, acc, jnp.asarray(digits))
    return acc


def inv(a: jnp.ndarray) -> jnp.ndarray:
    """Multiplicative inverse via Fermat (a^(p-2)); a in Montgomery form.

    inv(0) returns 0 (callers guard; matches constant-shape control flow).

    @bounds: a [0, 2^13-1] -> [0, 2^13-1]
    """
    from lodestar_tpu.crypto.bls.fields import P

    return mont_pow_fixed(a, P - 2)


# ---------------------------------------------------------------------------
# trace-once caching (see opcache.py): every public mutating op's jaxpr is
# built once per argument shape and replayed — call sites stop paying the
# pallas-kernel / scan-body re-trace tax that dominated cold program-build
# time on 1-CPU hosts.
# ---------------------------------------------------------------------------

from .opcache import cached as _cached

add = _cached(add)
sub = _cached(sub)
mont_mul = _cached(mont_mul)
mont_mul_cios = _cached(mont_mul_cios)
mont_mul_parallel = _cached(mont_mul_parallel)
mont_pow_fixed = _cached(mont_pow_fixed, static_argnums=(1,))


# host<->device element helpers -------------------------------------------------


def encode_int(x: int) -> np.ndarray:
    """Host: python int mod p -> canonical Montgomery limbs (numpy)."""
    from lodestar_tpu.crypto.bls.fields import P
    from .limbs import int_to_limbs, to_mont_int

    return int_to_limbs(to_mont_int(x % P))


def decode(limbs) -> int:
    """Host: Montgomery limb array -> python int in [0, p)."""
    from .limbs import from_mont_int, limbs_to_int

    return from_mont_int(limbs_to_int(np.asarray(limbs)))
