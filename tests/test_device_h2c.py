"""Device hash-to-curve (ops/bls12_381/h2c.py) vs the Python oracle.

The oracle is pinned to the RFC 9380 vectors (test_bls_oracle.py), so
bit-equality here transitively pins the device pipeline to the RFC.

The whole pipeline, ``hash_to_g2_from_fields``, takes XLA:CPU over 8 GB
to compile, so its test is `slow`.  Its stages are tested alone in tier 1:
``map_to_curve_g2``, and clear_cofactor's [|x|]-multiplication and psi.
"""
import numpy as np
import pytest

import jax

from lodestar_tpu.crypto.bls import hash_to_curve as oh2c
from lodestar_tpu.crypto.bls.curve import g2, psi
from lodestar_tpu.crypto.bls.fields import ABS_X
from lodestar_tpu.ops.bls12_381 import curve as cv, fp, h2c, tower as tw, verify as dv


def _decode_f2(t):
    return (fp.decode(np.asarray(t[0])), fp.decode(np.asarray(t[1])))


def _encode_f2_batch(vals):
    import jax.numpy as jnp

    e = lambda xs: jnp.asarray(np.stack([fp.encode_int(v) for v in xs]))
    return (e([v[0] for v in vals]), e([v[1] for v in vals]))


def _jac_to_affine_int(jac):
    """Decode one lane of a device Jacobian G2 batch to oracle affine."""
    x = _decode_f2(jax.tree.map(lambda t: np.asarray(t), jac[0]))
    y = _decode_f2(jac[1])
    z = _decode_f2(jac[2])
    return g2.to_affine((x, y, z))


@pytest.mark.usefixtures("heavy_compile")  # ~4.7 GB to compile
def test_map_to_curve_matches_oracle():
    msgs = [bytes([i]) * 32 for i in range(3)]
    us = [u for m in msgs for u in oh2c.hash_to_field_fp2(m, 2)]
    enc = _encode_f2_batch(us)
    out = jax.jit(h2c.map_to_curve_g2)(enc)
    for i, u in enumerate(us):
        exp = oh2c.map_to_curve_g2(u)
        got = (
            _decode_f2(jax.tree.map(lambda t: t[i], out[0])),
            _decode_f2(jax.tree.map(lambda t: t[i], out[1])),
        )
        assert got == exp, i


@pytest.fixture(scope="module")
def cofactor_inputs():
    """Q0 + Q1 of two messages, the point clear_cofactor is handed
    (Jacobian, Z != 1): oracle points and the device batch."""
    pts = []
    for m in (b"\x21" * 32, b"\x42" * 32):
        q0, q1 = (g2.from_affine(oh2c.map_to_curve_g2(u)) for u in oh2c.hash_to_field_fp2(m, 2))
        pts.append(g2.add_pts(q0, q1))
    jac = jax.tree.map(
        lambda *xs: np.stack(xs),
        *[tuple(tw.encode_fp2(c) for c in pt) for pt in pts],
    )
    return pts, jac


def _lane_affine(out, i):
    return _jac_to_affine_int(jax.tree.map(lambda t: np.asarray(t)[i], out))


@pytest.mark.usefixtures("heavy_compile")  # ~5.3 GB to compile
def test_mul_abs_x_matches_oracle(cofactor_inputs):
    """[|x|]P, the scalar-mul scan clear_cofactor runs twice.  The whole
    clear_cofactor program (over 8 GB) is covered by the slow test of
    hash_to_g2_from_fields below."""
    pts, jac = cofactor_inputs
    out = jax.jit(h2c._mul_abs_x)(jac)
    for i, pt in enumerate(pts):
        assert _lane_affine(out, i) == g2.to_affine(g2.mul_scalar(pt, ABS_X)), i


def test_psi_matches_oracle(cofactor_inputs):
    """clear_cofactor's inversion-free psi and psi^2 against the
    oracle's affine psi."""
    pts, jac = cofactor_inputs
    once, twice = jax.jit(lambda p: (h2c._psi(p), h2c._psi(h2c._psi(p))))(jac)
    for i, pt in enumerate(pts):
        assert _lane_affine(once, i) == g2.to_affine(psi(pt)), i
        assert _lane_affine(twice, i) == g2.to_affine(psi(psi(pt))), i


@pytest.mark.slow  # the whole pipeline: over 8 GB to compile
def test_hash_to_g2_from_fields_matches_oracle():
    msgs = [bytes([7 + i]) * 32 for i in range(4)]
    u0, u1 = h2c.encode_field_draws(msgs, 4)
    jac = jax.jit(h2c.hash_to_g2_from_fields)(u0, u1)
    for i, m in enumerate(msgs):
        lane = jax.tree.map(lambda t: np.asarray(t)[i], jac)
        assert _jac_to_affine_int(lane) == g2.to_affine(oh2c.hash_to_g2(m)), i


@pytest.mark.slow  # the whole hashed-verify program
def test_verify_signature_sets_hashed():
    from lodestar_tpu.crypto.bls import api
    from lodestar_tpu.ops.bls12_381 import verify as dvv

    B = 4
    sets = []
    for i in range(B):
        sk = api.SecretKey.from_bytes((i + 11).to_bytes(32, "big"))
        msg = bytes([i]) * 32
        sets.append(api.SignatureSet(sk.to_public_key(), msg, sk.sign(msg)))
    pk_aff, pk_inf, sig_aff, sig_inf, active = dvv._encode_pk_sig(sets, B)
    u0, u1 = h2c.encode_field_draws([s.message for s in sets], B)
    rand = [(2 * i + 3) | 1 for i in range(B)]
    bits = cv.scalars_to_bits(rand, 64)
    fn = jax.jit(dvv.verify_signature_sets_hashed)
    assert bool(fn(pk_aff, pk_inf, u0, u1, sig_aff, sig_inf, bits, active))
    import jax.numpy as jnp

    bad_sig = jax.tree.map(lambda t: jnp.roll(t, 1, axis=0), sig_aff)
    assert not bool(fn(pk_aff, pk_inf, u0, u1, bad_sig, sig_inf, bits, active))
