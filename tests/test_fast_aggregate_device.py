"""Device fastAggregateVerify (BASELINE config 2 shape; reference
bls.test.ts fastAggregateVerify + aggregatePubkeys):
on-device pubkey aggregation + one 2-pair pairing check, differential
against the oracle.

The whole program takes XLA:CPU over 8 GB to compile, so its tests are
`slow`; its pubkey aggregation stage is tested alone in tier 1 and its
pairing check's stages in tests/test_pairing_jax.py.
"""
import jax
import numpy as np
import pytest

from lodestar_tpu.crypto.bls import api, curve as oc
from lodestar_tpu.ops.bls12_381 import curve as cv, fp, verify as dv


def _keys(n, base=300):
    sks = [api.SecretKey.from_bytes((base + i).to_bytes(32, "big")) for i in range(n)]
    return sks, [sk.to_public_key() for sk in sks]


@jax.jit
def _pubkey_sum(pk_aff, pk_inf, active):
    """The pubkey aggregation verify.fast_aggregate_verify opens with."""
    pk_jac = cv.from_affine(cv.F1, pk_aff, pk_inf | ~active)
    return cv.to_affine(cv.F1, dv.jac_reduce_add(cv.F1, pk_jac), fp.inv)


def test_device_pubkey_sum_matches_oracle():
    """Eight lanes, one program: five keys with one repeated (the
    doubling path) and two padding lanes; then a key and its negation,
    whose sum is the point at infinity."""
    _, pks = _keys(5)
    neg = api.PublicKey(oc.g1.to_affine(oc.g1.neg_pt(oc.g1.from_affine(pks[1].point))))
    for keys, want_inf in ((pks + [pks[0]], False), ([pks[1], neg], True)):
        pts = [pk.point for pk in keys] + [None] * (8 - len(keys))
        active = np.arange(8) < len(keys)
        (x, y), inf = _pubkey_sum(*cv.encode_g1_affine(pts), active)
        assert bool(inf) is want_inf
        if not want_inf:
            want = api.aggregate_public_keys(keys).point
            assert (fp.decode(np.asarray(x)), fp.decode(np.asarray(y))) == want


@pytest.mark.slow  # the whole program
def test_fast_aggregate_verify_device_matches_oracle():
    msg = b"\x55" * 32
    sks, pks = _keys(5)
    agg = api.aggregate_signatures([sk.sign(msg) for sk in sks])
    assert api.fast_aggregate_verify(pks, msg, agg)
    assert dv.fast_aggregate_verify_device(pks, msg, agg)
    # wrong message rejects
    assert not dv.fast_aggregate_verify_device(pks, b"\x66" * 32, agg)
    # missing signer rejects
    assert not dv.fast_aggregate_verify_device(pks[:-1], msg, agg)


@pytest.mark.slow  # the whole program
def test_fast_aggregate_verify_device_edge_cases():
    msg = b"\x77" * 32
    sks, pks = _keys(3)
    agg = api.aggregate_signatures([sk.sign(msg) for sk in sks])
    assert dv.fast_aggregate_verify_device([], msg, agg) is False
    # single signer degenerates to plain verify
    one = sks[0].sign(msg)
    assert dv.fast_aggregate_verify_device([pks[0]], msg, one)
    assert not dv.fast_aggregate_verify_device([pks[1]], msg, one)
