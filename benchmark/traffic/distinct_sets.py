"""Closed-loop traffic of signature sets, each over a message of its own.

One client sends a request of the configuration's ``segment.signature_sets``
sets, waits for the verdict, and sends the next: the shape of a node that
verifies a range-sync segment's blocks in one call.  Every set has its own
key and message, so nothing in a request repeats.  Every request of the
timed window is valid.

Parameters (the traffic file):

- ``fresh_requests``: distinct requests made from the seed.  A window that
  needs more sends them again, in the same order.
- ``warmup_sets``: sets in the warm-up request, which holds one set signed
  over another message, so that it loads every program the run takes: the
  batch program and the per-set split.
- ``checked_after_window``: how each request sent after the window closes
  is made invalid, one request per entry, one of ``wrong_message`` (two
  sets signed over other messages) and ``cancelling_pair`` (two sets whose
  signatures are moved by +D and -D, so that only a batch check with random
  coefficients rejects them).  These requests go through the same verifier
  and programs as the window's, and their verdicts count for ``correct``.
- ``batchable``: the ``VerifyOptions.batchable`` of every request.

A request checked after the window is fresh request k's sets with two sets
changed.  The two lie in one half of one device job: the first half for
k = 0, the second for k = 1, and so on in turn.  The job width is the
pool's own (``make_checks``), and the job is drawn from the seed among the
full ones.  So a program that checks only one half of each job misses
every tampered set of one of the requests, and accepts it.

Keys are 64-bit: the verifier sees only points, whose cost does not depend
on the key, and a short key makes signing four times cheaper.  Everything
is drawn from ``(seed, request, position)``, so the same seed gives the same
requests whatever the number of worker processes.

This module imports no JAX and nothing of the system under test: the worker
processes that run it sign with ``benchmark.reference``.
"""
from __future__ import annotations

import hashlib
import random
import time
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.reference import curve as rc
from benchmark.reference import hash_to_curve as rh

WARMUP = -1  # request index of the warm-up request
KEY_BITS = 64
CHUNK = 250  # sets per worker job


def _rng(seed: int, request: int, position: int, what: str) -> random.Random:
    digest = hashlib.sha256(f"{seed}:{request}:{position}:{what}".encode()).digest()
    return random.Random(digest)


def _sign_set(seed: int, request: int, position: int) -> tuple:
    """(pk affine, message, sig affine, secret key) of one valid set."""
    rng = _rng(seed, request, position, "set")
    sk = rng.getrandbits(KEY_BITS) | (1 << (KEY_BITS - 1))
    msg = rng.randbytes(32)
    pk = rc.g1.to_affine(rc.g1.mul_scalar(rc.G1_GEN_JAC, sk))
    sig = rc.g2.to_affine(rc.g2.mul_scalar(rh.hash_to_g2(msg), sk))
    return pk, msg, sig, sk


def sign_chunk(seed: int, request: int, start: int, stop: int) -> List[tuple]:
    """Worker job: the valid sets at positions [start, stop) of a request."""
    return [_sign_set(seed, request, i) for i in range(start, stop)]


def tamper_positions(seed: int, request: int, n_sets: int, job_width: int) -> List[int]:
    """Two positions, drawn from the seed, in one half of one full job of
    ``job_width`` sets: the first half for an even ``request``, the second
    for an odd one."""
    rng = _rng(seed, request, 0, "tamper")
    full_jobs = n_sets // job_width
    half = job_width // 2
    if full_jobs < 1 or half < 2:
        raise ValueError(f"{n_sets} sets hold no full job of {job_width} with two sets a half")
    base = rng.randrange(full_jobs) * job_width + (half if request % 2 else 0)
    return sorted(base + p for p in rng.sample(range(half), 2))


def _resign(seed: int, request: int, s: tuple, position: int) -> tuple:
    """The set ``s`` with its signature made over another message."""
    pk, msg, _, sk = s
    other = _rng(seed, request, position, "other").randbytes(32)
    return pk, msg, rc.g2.to_affine(rc.g2.mul_scalar(rh.hash_to_g2(other), sk)), sk


def tamper(seed: int, request: int, kind: str, sets: List[tuple],
           positions: Sequence[int]) -> List[Tuple[int, tuple]]:
    """The sets at ``positions`` made invalid by ``kind``, as (position,
    set) pairs."""
    if kind == "wrong_message":
        return [(p, _resign(seed, request, sets[p], p)) for p in positions]
    if kind == "cancelling_pair":
        a, b = positions
        d = rc.g2.mul_scalar(rc.G2_GEN_JAC, _rng(seed, request, 0, kind).getrandbits(255) | 1)
        out = []
        for p, shift in ((a, d), (b, rc.g2.neg_pt(d))):
            pk, msg, sig, sk = sets[p]
            moved = rc.g2.to_affine(rc.g2.add_pts(rc.g2.from_affine(sig), shift))
            out.append((p, (pk, msg, moved, sk)))
        return out
    raise ValueError(f"unknown invalid kind {kind!r}")


class Request:
    """One request: its sets as the reference made them (``raw``: pk,
    message, sig, secret key) and in the system's own type (``bound``),
    whether it is valid, and which positions were tampered with."""

    __slots__ = ("index", "raw", "bound", "valid", "tampered", "kind")

    def __init__(self, index: int, raw: List[tuple], kind: Optional[str], tampered: List[int]):
        self.index = index
        self.raw = raw
        self.bound: List = []
        self.kind = kind
        self.valid = kind is None
        self.tampered = tampered


class Traffic:
    """The requests of one run, made in worker processes from the seed."""

    def __init__(self, params: dict, seed: int, config: dict):
        self.seed = seed
        self.n_sets = int(config["segment"]["signature_sets"])
        self.fresh = int(params["fresh_requests"])
        self.warmup_sets = int(params["warmup_sets"])
        self.check_kinds: List[str] = list(params["checked_after_window"])
        self.batchable = bool(params["batchable"])
        if len(self.check_kinds) > self.fresh:
            raise ValueError("each request checked after the window needs a fresh request")
        self.requests: List[Request] = []
        self.checks: List[Request] = []
        self.warmup: Optional[Request] = None
        self.generate_s = 0.0

    # -- making the data ------------------------------------------------

    def make(self, pool, on_warmup=None) -> None:
        """Sign every request on ``pool`` (a ``multiprocessing`` pool) and
        block until the data is complete.  ``on_warmup(request)`` is called
        as soon as the warm-up request is ready, before the others."""
        t0 = time.perf_counter()
        plan = [(WARMUP, self.warmup_sets)] + [(i, self.n_sets) for i in range(self.fresh)]
        jobs = [
            (req, [
                pool.apply_async(sign_chunk, (self.seed, req, a, min(n, a + CHUNK)))
                for a in range(0, n, CHUNK)
            ])
            for req, n in plan
        ]
        for req, chunks in jobs:
            raw = [s for job in chunks for s in job.get()]
            if req == WARMUP:
                p = _rng(self.seed, req, 0, "tamper").randrange(len(raw))
                (changed,) = tamper(self.seed, req, "wrong_message", raw, [p])
                raw[p] = changed[1]
                self.warmup = Request(req, raw, "wrong_message", [p])
                if on_warmup is not None:
                    on_warmup(self.warmup)
            else:
                self.requests.append(Request(req, raw, None, []))
        self.generate_s = time.perf_counter() - t0

    def make_checks(self, job_width: int) -> None:
        """The requests sent after the window: fresh request k's sets with
        two of them tampered by ``checked_after_window[k]``, placed by the
        pool's ``job_width``."""
        if self.checks:
            return
        for k, kind in enumerate(self.check_kinds):
            base = self.requests[k]
            positions = tamper_positions(self.seed, k, len(base.raw), job_width)
            raw = list(base.raw)
            for p, s in tamper(self.seed, k, kind, raw, positions):
                raw[p] = s
            self.checks.append(Request(k, raw, kind, positions))

    @staticmethod
    def bind(requests: Sequence[Request], make_set) -> None:
        """Give each request its sets in the system's own type, made with
        ``make_set(pk, message, sig)``."""
        for r in requests:
            r.bound = [make_set(pk, msg, sig) for pk, msg, sig, _ in r.raw]

    # -- the client -----------------------------------------------------

    async def _send(self, verify, r: Request, i: int, clock) -> dict:
        sent = clock()
        try:
            verdict = await verify(r.bound, self.batchable)
            error = None
        except Exception as e:  # a request that fails is counted, not fatal
            verdict, error = None, f"{type(e).__name__}: {e}"
        return {
            "request": i, "fresh": r.index, "kind": r.kind, "sets": len(r.bound),
            "due": sent, "sent": sent, "done": clock(), "verdict": verdict,
            "expected": r.valid, "error": error,
        }

    async def drive(self, verify, seconds: float, clock=time.monotonic) -> dict:
        """Closed loop, one client: send request i when request i-1 has its
        verdict, while fewer than ``seconds`` have passed.  The window ends
        at the verdict of the last request sent.  ``verify(sets, batchable)``
        is the coroutine under test."""
        records = []
        start = clock()
        while clock() - start < seconds:
            i = len(records)
            records.append(await self._send(verify, self.requests[i % len(self.requests)], i, clock))
        end = records[-1]["done"] if records else clock()
        return {"start": start, "end": end, "records": records, "lateness_s": 0.0}

    async def after_window(self, verify, clock=time.monotonic) -> List[dict]:
        """Send each invalid request once, one after another."""
        return [await self._send(verify, r, i, clock) for i, r in enumerate(self.checks)]

    # -- what the reference checks after the window --------------------

    def reference_sample(self, records: Sequence[dict], k: int) -> List[tuple]:
        """Sets for the reference to verify one by one: every tampered set
        of the requests checked after the window, and ``k`` valid sets drawn
        from the seed among the window's requests.  Each is ((pk, msg,
        sig), valid)."""
        out = [(r.raw[p][:3], False) for r in self.checks for p in r.tampered]
        sent = sorted({rec["fresh"] for rec in records if rec["kind"] is None})
        rng = _rng(self.seed, 0, 0, "sample")
        for _ in range(k if sent else 0):
            r = self.requests[rng.choice(sent)]
            out.append((r.raw[rng.randrange(len(r.raw))][:3], True))
        return out


def reference_verify(pk, msg: bytes, sig) -> bool:
    """The plain check e(pk, H(m)) == e(G1, sig), with the reference."""
    from benchmark.reference import pairing as rp

    neg_g1 = rc.g1.to_affine(rc.g1.neg_pt(rc.G1_GEN_JAC))
    return rp.multi_pairing_is_one([(pk, rh.hash_to_g2_affine(msg)), (neg_g1, sig)])


def check_set(item: tuple) -> bool:
    """Worker job: does the reference agree that this set is (in)valid?"""
    (pk, msg, sig), valid = item
    return reference_verify(pk, msg, sig) == valid
