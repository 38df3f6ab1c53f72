"""Differential tests: device pairing + batched verification vs the oracle.

Closes the round-1 gap: ops/bls12_381/{curve,pairing,verify}.py executed
end-to-end against crypto/bls (the CPU oracle), on the CPU backend with the
same code paths that run on TPU.  Mirrors the role of the reference's BLS
spec-test runner (packages/beacon-node/test/spec/bls/bls.ts:8) and the
worker's batch/retry semantics (chain/bls/multithread/worker.ts:32-108).

A whole pairing or verify program takes XLA:CPU over 8 GB to compile, so
those tests are `slow`.  Their stages stay in tier 1, each its own small
program compared bit for bit with the oracle: the Miller loop's doubling
and addition steps (TestMillerSteps) and the final exponentiation's
pow-by-|x| (TestFinalExponentiationStage).
"""
import random

import numpy as np
import pytest
import jax

from lodestar_tpu.crypto.bls import api, curve as oc, fields as of, pairing as op
from lodestar_tpu.crypto.bls import pairing_proj as opp
from lodestar_tpu.crypto.bls.fields import R
from lodestar_tpu.ops.bls12_381 import curve as dc, fp, pairing as dp, tower as tw, verify as dv


def _rand_g1(seed):
    k = (seed * 0x9E3779B97F4A7C15 + 1) % R
    return oc.g1.to_affine(oc.g1.mul_scalar(oc.G1_GEN_JAC, k))


def _rand_g2(seed):
    k = (seed * 0xC2B2AE3D27D4EB4F + 7) % R
    return oc.g2.to_affine(oc.g2.mul_scalar(oc.G2_GEN_JAC, k))


def _stack(trees):
    return jax.tree.map(lambda *xs: np.stack(xs), *trees)


def _lane(tree, i):
    return jax.tree.map(lambda t: np.asarray(t)[i], tree)


def _fp_lanes(vals):
    return np.stack([fp.encode_int(v) for v in vals])


@pytest.fixture(scope="module")
def keys():
    sks = [api.SecretKey.from_bytes(bytes([0] * 31 + [i + 1])) for i in range(4)]
    return [(sk, sk.to_public_key()) for sk in sks]


class TestDeviceCurve:
    def test_scalar_mul_matches_oracle(self):
        pts = [oc.G1_GEN, _rand_g1(3)]
        scalars = [5, 0xDEADBEEFCAFEBABE]
        aff, inf = dc.encode_g1_affine(pts)
        bits = dc.scalars_to_bits(scalars, 64)
        out = jax.jit(lambda a, i, b: dc.scalar_mul_bits(dc.F1, dc.from_affine(dc.F1, a, i), b))(
            aff, inf, bits
        )
        got_aff, got_inf = dc.to_affine(dc.F1, out, fp.inv)
        for j, (pt, k) in enumerate(zip(pts, scalars)):
            want = oc.g1.to_affine(oc.g1.mul_scalar(oc.g1.from_affine(pt), k))
            assert not bool(got_inf[j])
            assert fp.decode(np.asarray(got_aff[0][j])) == want[0]
            assert fp.decode(np.asarray(got_aff[1][j])) == want[1]

    def test_jac_add_handles_inf_and_doubling(self):
        g = oc.G1_GEN
        two_g = oc.g1.to_affine(oc.g1.mul_scalar(oc.G1_GEN_JAC, 2))
        aff, inf = dc.encode_g1_affine([g, g, None])
        p = dc.from_affine(dc.F1, aff, inf)
        a = jax.tree.map(lambda t: t[0], p)
        b = jax.tree.map(lambda t: t[1], p)
        z = jax.tree.map(lambda t: t[2], p)
        add = jax.jit(lambda x, y: dc.jac_add(dc.F1, x, y))  # one compile
        s = add(a, b)  # G + G
        (x, y), isinf = dc.to_affine(dc.F1, s, fp.inv)
        assert not bool(isinf)
        assert fp.decode(np.asarray(x)) == two_g[0]
        assert fp.decode(np.asarray(y)) == two_g[1]
        s2 = add(a, z)  # G + inf
        (x2, y2), isinf2 = dc.to_affine(dc.F1, s2, fp.inv)
        assert not bool(isinf2)
        assert fp.decode(np.asarray(x2)) == g[0]

    def test_batch_inv(self):
        vals = [1, 2, 12345, 0, 7]
        enc = np.stack([fp.encode_int(v) for v in vals])
        out = jax.jit(lambda x: dv._batch_inv(dc.F1, x))(np.asarray(enc))
        from lodestar_tpu.crypto.bls.fields import P

        for i, v in enumerate(vals):
            got = fp.decode(np.asarray(out)[i])
            want = pow(v, -1, P) if v else 0
            assert got == want, f"inv mismatch at {i}"


def _proj_g2(aff, z):
    """Affine G2 point -> homogeneous projective (x z, y z, z)."""
    return (of.f2_mul(aff[0], z), of.f2_mul(aff[1], z), z)


@pytest.fixture(scope="module")
def miller_step_inputs():
    """Two lanes of a Miller-loop step's inputs, oracle side and device
    side: T a projective G2 point with Z != 1, Q affine G2, P affine G1."""
    rng = random.Random(0x5EED)
    ts = [_proj_g2(_rand_g2(31 + i), (rng.randrange(of.P), rng.randrange(of.P)))
          for i in range(2)]
    qs = [_rand_g2(41 + i) for i in range(2)]
    ps = [_rand_g1(51 + i) for i in range(2)]
    dev = (
        _stack([tuple(tw.encode_fp2(c) for c in t) for t in ts]),
        _stack([(tw.encode_fp2(q[0]), tw.encode_fp2(q[1])) for q in qs]),
        _fp_lanes([p[0] for p in ps]),
        _fp_lanes([p[1] for p in ps]),
    )
    return (ts, qs, ps), dev


def _assert_step_matches(out, want_per_lane):
    line, t = out
    for i, (want_line, want_t) in enumerate(want_per_lane):
        assert tw.decode_fp12(_lane(line, i)) == want_line, f"line, lane {i}"
        got_t = tuple(tw.decode_fp2(c) for c in _lane(t, i))
        assert got_t == want_t, f"point, lane {i}"


class TestMillerSteps:
    """The two step bodies of pairing.miller_loop's scan against the
    projective oracle (crypto/bls/pairing_proj.py), exact in every
    coordinate: the sparse line and the updated point."""

    def test_dbl_step_matches_projective_oracle(self, miller_step_inputs):
        (ts, _, ps), (t, _, xp, yp) = miller_step_inputs
        out = jax.jit(dp._dbl_step)(t, xp, yp)
        _assert_step_matches(
            out, [opp._dbl_step(ti, pi[0], pi[1]) for ti, pi in zip(ts, ps)]
        )

    def test_add_step_matches_projective_oracle(self, miller_step_inputs):
        (ts, qs, ps), (t, q, xp, yp) = miller_step_inputs
        out = jax.jit(dp._add_step)(t, q, xp, yp)
        _assert_step_matches(
            out,
            [opp._add_step(ti, qi, pi[0], pi[1]) for ti, qi, pi in zip(ts, qs, ps)],
        )


class TestFinalExponentiationStage:
    def test_pow_abs_x_matches_oracle(self):
        """The hard part's pow-by-|x| (the body every step of
        final_exponentiation's scan runs) on cyclotomic-subgroup
        elements, the oracle's easy-part outputs, against the oracle's
        cyclotomic square-and-multiply."""
        rng = random.Random(0xF1E)
        rf2 = lambda: (rng.randrange(of.P), rng.randrange(of.P))
        ms = [op._easy_part(((rf2(), rf2(), rf2()), (rf2(), rf2(), rf2())))
              for _ in range(2)]
        out = jax.jit(dp._cyclotomic_pow_abs_x)(_stack([tw.encode_fp12(m) for m in ms]))
        for i, m in enumerate(ms):
            assert tw.decode_fp12(_lane(out, i)) == of.f12_cyclotomic_pow_x(m), i


@pytest.mark.slow
class TestDevicePairing:
    """Whole pairing programs: XLA:CPU passes 8 GB compiling one."""

    def test_pairing_generator_vs_oracle(self):
        p_aff, _ = dc.encode_g1_affine([oc.G1_GEN])
        q_aff, _ = dc.encode_g2_affine([oc.G2_GEN])
        out = jax.jit(dp.pairing)(p_aff, q_aff)
        got = tw.decode_fp12(jax.tree.map(lambda t: np.asarray(t)[0], out))
        want = op.pairing(oc.G1_GEN, oc.G2_GEN)
        assert got == want

    def test_pairing_random_points_batched(self):
        ps = [_rand_g1(11), _rand_g1(12)]
        qs = [_rand_g2(21), _rand_g2(22)]
        p_aff, _ = dc.encode_g1_affine(ps)
        q_aff, _ = dc.encode_g2_affine(qs)
        out = jax.jit(dp.pairing)(p_aff, q_aff)
        for i in range(2):
            got = tw.decode_fp12(jax.tree.map(lambda t: np.asarray(t)[i], out))
            want = op.pairing(ps[i], qs[i])
            assert got == want, f"pairing mismatch at batch index {i}"

    def test_pairing_check_bilinear_cancellation(self):
        # e(aG1, G2) * e(-G1, aG2) == 1
        a = 0x1234567
        p1 = oc.g1.to_affine(oc.g1.mul_scalar(oc.G1_GEN_JAC, a))
        q2 = oc.g2.to_affine(oc.g2.mul_scalar(oc.G2_GEN_JAC, a))
        neg_g1 = oc.g1.to_affine(oc.g1.neg_pt(oc.G1_GEN_JAC))
        p_aff, p_inf = dc.encode_g1_affine([p1, neg_g1])
        q_aff, q_inf = dc.encode_g2_affine([oc.G2_GEN, q2])
        ok = jax.jit(dv.pairing_check)(p_aff, p_inf, q_aff, q_inf)
        assert bool(ok)
        # and the same with a corrupted scalar fails
        q2bad = oc.g2.to_affine(oc.g2.mul_scalar(oc.G2_GEN_JAC, a + 1))
        q_aff2, q_inf2 = dc.encode_g2_affine([oc.G2_GEN, q2bad])
        ok2 = jax.jit(dv.pairing_check)(p_aff, p_inf, q_aff2, q_inf2)
        assert not bool(ok2)

    def test_infinity_pairs_masked_to_identity(self):
        # batch of [e(G1,G2), e(inf, G2), e(G1, inf)] -> product == the
        # single G1/G2 Miller value.  The device implements the PROJECTIVE
        # sparse-line formulas, whose raw Miller value differs from the
        # affine oracle's by a subfield factor (killed by the final
        # exponentiation) — so compare against the projective oracle.
        p_aff, p_inf = dc.encode_g1_affine([oc.G1_GEN, None, oc.G1_GEN])
        q_aff, q_inf = dc.encode_g2_affine([oc.G2_GEN, oc.G2_GEN, None])
        mask = ~(p_inf | q_inf)
        f = jax.jit(dv.multi_miller_product)(q_aff, p_aff, mask)
        got = tw.decode_fp12(jax.tree.map(lambda t: np.asarray(t), f))
        want = opp.miller_loop_proj(oc.G2_GEN, oc.G1_GEN)
        assert got == want
        # and the full pairings agree with the affine oracle
        e_dev = tw.decode_fp12(
            jax.tree.map(
                lambda t: np.asarray(t), jax.jit(dp.final_exponentiation)(f)
            )
        )
        assert e_dev == op.final_exponentiation(
            op.miller_loop(oc.G2_GEN, oc.G1_GEN)
        )


class TestDeviceVerify:
    @pytest.mark.slow  # the whole verify program
    def test_batch_verify_valid(self, keys):
        sets = [
            api.SignatureSet(pk, bytes([i]) * 32, sk.sign(bytes([i]) * 32))
            for i, (sk, pk) in enumerate(keys[:3])
        ]
        rand = [3, 5, 7]
        assert api.verify_multiple_signature_sets(sets, rand)
        assert dv.verify_signature_sets_device(sets, rand)

    @pytest.mark.slow  # the whole verify program
    def test_batch_verify_one_corrupted(self, keys):
        sk0, pk0 = keys[0]
        sk1, pk1 = keys[1]
        good = api.SignatureSet(pk0, b"m0" * 16, sk0.sign(b"m0" * 16))
        bad = api.SignatureSet(pk1, b"m1" * 16, sk0.sign(b"m1" * 16))  # wrong key
        rand = [3, 5]
        assert not api.verify_multiple_signature_sets([good, bad], rand)
        assert not dv.verify_signature_sets_device([good, bad], rand)

    @pytest.mark.slow  # the whole per-set verify program
    def test_verify_each_splits_good_from_bad(self, keys):
        sk0, pk0 = keys[0]
        sk1, pk1 = keys[1]
        good = api.SignatureSet(pk0, b"a" * 32, sk0.sign(b"a" * 32))
        bad = api.SignatureSet(pk1, b"b" * 32, sk0.sign(b"b" * 32))
        out = dv.verify_each_device([good, bad])
        assert out == [True, False]

    def test_empty_and_infinity_rejected(self, keys):
        # rejected on the host before any program runs: no compile
        assert dv.verify_signature_sets_device([]) is False
        sk0, pk0 = keys[0]
        inf_sig = api.Signature(None)
        s = api.SignatureSet(pk0, b"x" * 32, inf_sig)
        assert dv.verify_signature_sets_device([s]) is False
