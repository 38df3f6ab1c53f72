"""The benchmark's reference BLS against published answers."""
import random

from benchmark.reference import hash_to_curve as rh
from benchmark.tests.fakes import batch_verify
from benchmark.traffic import distinct_sets


def test_hash_to_g2_rfc9380_k1_empty_message():
    # RFC 9380 appendix K.1, BLS12381G2_XMD:SHA-256_SSWU_RO_, msg = ""
    x, y = rh.hash_to_g2_affine(b"", b"QUUX-V01-CS02-with-BLS12381G2_XMD:SHA-256_SSWU_RO_")
    assert x == (
        0x0141EBFBDCA40EB85B87142E130AB689C673CF60F1A3E98D69335266F30D9B8D4AC44C1038E9DCDD5393FAF5C41FB78A,
        0x05CB8437535E20ECFFAEF7752BADDF98034139C38452458BAEEFAB379BA13DFF5BF5DD71B72418717047F5B0F37DA03D,
    )
    assert y == (
        0x0503921D7F6A12805E72940B963C0CF3471C7B2A524950CA195D11062EE75EC076DAF2D4BC358C4B190C0C98064FDD92,
        0x12424AC32561493F3FE3C260708A12B7C620E7BE00099A974E259DDC7D1F6395C3C811CDD19F1E8DBF3E9ECFDCBAB8D6,
    )


def test_generated_sets_and_tampering():
    seed, n = 2**40 + 3, 6
    sets = distinct_sets.sign_chunk(seed, 0, 0, n)
    assert sets == distinct_sets.sign_chunk(seed, 0, 0, n)  # same seed, same sets
    assert all(distinct_sets.reference_verify(*s[:3]) for s in sets[:2])
    pos = distinct_sets.tamper_positions(seed, 0, n, n)
    changed = distinct_sets.tamper(seed, 0, "wrong_message", sets, pos)
    assert [p for p, _ in changed] == pos
    assert not any(distinct_sets.reference_verify(*s[:3]) for _, s in changed)
    changed = distinct_sets.tamper(seed, 0, "cancelling_pair", sets, pos)
    pair = [c for _, c in changed]
    assert not any(distinct_sets.reference_verify(*s[:3]) for s in pair)

    class Set:
        def __init__(self, s):
            self.public_key = type("K", (), {"point": s[0]})
            self.message = s[1]
            self.signature = type("S", (), {"point": s[2]})

    # a plain sum lets the pair through; random coefficients do not
    assert batch_verify([Set(s) for s in pair], [1, 1])
    rng = random.Random(seed)
    assert not batch_verify([Set(s) for s in pair], [rng.getrandbits(64) | 1 for _ in pair])
