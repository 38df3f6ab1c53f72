"""The reference in the program's place, for runs of the harness on the CPU.

``ReferenceBackend`` stands where ``lodestar_tpu.ops.bls12_381.verify``
stands for ``DeviceBlsVerifier`` (its ``encode_job``, ``execute_batch`` and
``verify_each_device``), and computes with ``benchmark.reference``.  Its
``fault`` plants one fault of the timed path:

- ``unit_coefficients``: the control.  The batch check's random
  coefficients are all 1, the cheaper step a later change could be tempted
  by; a pair of signatures whose errors cancel then passes.
- ``half_left_out``: only the first half of each job's sets is checked.
- ``other_half_left_out``: only the second half of each job's sets is
  checked.
- ``flipped``: every verdict is inverted where it is produced.
- ``no_op``: the device step returns "valid" without checking anything.
"""
from __future__ import annotations

import random
from typing import List, Optional

from prometheus_client import CollectorRegistry

from benchmark.harness import System
from benchmark.reference import curve as rc
from benchmark.reference import hash_to_curve as rh
from benchmark.reference import pairing as rp

FAULTS = ("unit_coefficients", "half_left_out", "other_half_left_out", "flipped", "no_op")
_NEG_G1 = rc.g1.to_affine(rc.g1.neg_pt(rc.G1_GEN_JAC))


def batch_verify(sets, coefficients: List[int]) -> bool:
    """prod e(r_i pk_i, H(m_i)) * e(-G1, sum r_i sig_i) == 1."""
    pairs, acc = [], rc.INF_G2
    for s, r in zip(sets, coefficients):
        pk = rc.g1.to_affine(rc.g1.mul_scalar(rc.g1.from_affine(s.public_key.point), r))
        pairs.append((pk, rh.hash_to_g2_affine(s.message)))
        acc = rc.g2.add_pts(acc, rc.g2.mul_scalar(rc.g2.from_affine(s.signature.point), r))
    pairs.append((_NEG_G1, rc.g2.to_affine(acc)))
    return rp.multi_pairing_is_one(pairs)


class ReferenceBackend:
    def __init__(self, fault: Optional[str] = None, seed: int = 0):
        if fault is not None and fault not in FAULTS:
            raise ValueError(fault)
        self.fault = fault
        self._rng = random.Random(seed)

    def _checked(self, sets) -> range:
        """The positions of a job that the program checks."""
        half = len(sets) // 2
        if self.fault == "half_left_out":
            return range(half)
        if self.fault == "other_half_left_out":
            return range(half, len(sets))
        return range(len(sets))

    def encode_job(self, sets, bucket=None):
        return list(sets)

    def execute_batch(self, sets) -> bool:
        if self.fault == "no_op":
            return True
        checked = [sets[i] for i in self._checked(sets)]
        if self.fault == "unit_coefficients":
            coefficients = [1] * len(checked)
        else:
            coefficients = [self._rng.getrandbits(64) | 1 for _ in checked]
        ok = batch_verify(checked, coefficients)
        return not ok if self.fault == "flipped" else ok

    def verify_each_device(self, sets, bucket=None) -> List[bool]:
        checked = self._checked(sets)
        out = [batch_verify([s], [1]) if i in checked else True for i, s in enumerate(sets)]
        return [not v for v in out] if self.fault == "flipped" else out


def reference_system(fault: Optional[str] = None, max_sets_per_job: int = 8) -> System:
    """A ``System`` whose verifier is the real pool over ``ReferenceBackend``."""
    from lodestar_tpu.chain.bls.device_pool import DeviceBlsVerifier, governed_steady_width
    from lodestar_tpu.chain.bls.interface import VerifyOptions
    from lodestar_tpu.chain.bls.metrics import BlsPoolMetrics
    from lodestar_tpu.crypto.bls import api

    registry = CollectorRegistry()
    verifier = DeviceBlsVerifier(
        metrics=BlsPoolMetrics(registry=registry),
        _backend=ReferenceBackend(fault),
        max_sets_per_job=max_sets_per_job,
    )
    return System(
        verifier=verifier,
        verify_options=lambda batchable: VerifyOptions(batchable=batchable),
        make_set=lambda pk, msg, sig: api.SignatureSet(api.PublicKey(pk), msg, api.Signature(sig)),
        registry=registry,
        device={"platform": "cpu", "kind": "reference in the program's place", "count": 1},
        job_width=governed_steady_width(max_sets_per_job),
    )
