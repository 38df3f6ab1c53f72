"""Synthetic gossip firehose against the device verifier pool policy.

VERDICT r3 weak #4: the pool must sustain node-shaped load with p99
request latency under the 1 s gossip budget.  The device is simulated
with the latency model measured on TPU v5e in round 4 (a ~350 ms
sequential-scan floor plus a mild per-set term) so the POLICY — window
flushes, job packing, queue behavior under load — is what's under test;
the kernel itself is timed by bench.py on hardware.
"""
import asyncio
import random
import time

from lodestar_tpu.chain.bls import DeviceBlsVerifier, VerifyOptions
from lodestar_tpu.crypto.bls.api import PublicKey, Signature, SignatureSet
from lodestar_tpu.utils import gather_settled


class ModelledDevice:
    """Latency-modelled fake device: a POLICY test double, not kernel
    evidence.  The constants ARE the pool's governor model
    (device_pool.MODEL_FLOOR_S/PER_SET_S — one re-fit updates both the
    governor and this double), fitted to the round-4 builder-session
    bench (628 ms @1024, ~1 s @4096 end-to-end), which the driver never
    measured on the chip."""

    from lodestar_tpu.chain.bls.device_pool import MODEL_FLOOR_S, MODEL_PER_SET_S

    FLOOR_S = MODEL_FLOOR_S
    PER_SET_S = MODEL_PER_SET_S

    def __init__(self):
        self.jobs = []

    def encode_job(self, sets, rand=None, bucket=None):
        # host encode is cheap next to the device stage (and overlaps
        # it in the pipelined pool); model it as free
        return ("enc", list(sets))

    def execute_batch(self, enc):
        # run_in_executor calls this in a worker thread: block like the
        # real chip would
        _, sets = enc
        time.sleep(self.FLOOR_S + self.PER_SET_S * len(sets))
        self.jobs.append(len(sets))
        return True

    def verify_each_device(self, sets, bucket=None):
        time.sleep(self.FLOOR_S + self.PER_SET_S * len(sets))
        return [True] * len(sets)


def _dummy_set():
    return SignatureSet(PublicKey((1, 2)), b"m" * 32, Signature(((1, 2), (3, 4))))




def test_firehose_p99_under_one_second():
    """Offered load ~2,500 sets/s for ~3 s of simulated gossip bursts."""
    pool = DeviceBlsVerifier(_backend=ModelledDevice())
    rng = random.Random(7)
    latencies = []

    async def one_request(n_sets):
        t0 = time.monotonic()
        ok = await pool.verify_signature_sets(
            [_dummy_set()] * n_sets, VerifyOptions(batchable=True)
        )
        latencies.append(time.monotonic() - t0)
        assert ok

    async def go():
        tasks = []
        # ~100 bursts of 1-50 sets arriving over ~3 s => ~2,500 sets/s
        for _ in range(100):
            tasks.append(asyncio.ensure_future(one_request(rng.randint(1, 50))))
            await asyncio.sleep(rng.uniform(0.01, 0.05) * 0.6)
        await gather_settled(*tasks)

    asyncio.get_event_loop_policy().new_event_loop().run_until_complete(go())

    latencies.sort()
    p99 = latencies[int(0.99 * (len(latencies) - 1))]
    assert p99 < 1.0, f"p99 {p99:.3f}s over the 1s gossip budget"
    # the window must be packing requests into large jobs, not trickling
    dev = pool._dv
    assert max(dev.jobs) > 100, f"no large jobs formed: {dev.jobs}"


def test_latency_governor_caps_job_width():
    """The width governor (device_pool._latency_width_cap) must keep
    steady-state jobs at or near the budget-derived width — aligned to
    the pool compile rung the raw width pads into (ISSUE 5: an
    unaligned cap like 882 would otherwise mint program shapes the AOT
    warm registry never compiled) — while still reverting to
    (rung-aligned) max-width drain under genuine overload."""
    from lodestar_tpu.chain.bls import device_pool as dp
    from lodestar_tpu.ops.bls12_381 import buckets as bk

    pool = DeviceBlsVerifier(_backend=ModelledDevice())
    budget_width = int(
        (dp.LATENCY_BUDGET_S / 2 - dp.MODEL_FLOOR_S) / dp.MODEL_PER_SET_S
    )

    # steady state: cap = budget width aligned up to the rung it would
    # pad into anyway (same padded program, more sets served)
    pool._buffer_sigs = budget_width // 2
    assert pool._latency_width_cap() == bk.pool_bucket(
        max(dp.MIN_JOB_WIDTH, budget_width)
    )
    cap = pool._steady_width_cap()
    assert cap in bk.POOL_BUCKETS
    # one max-size request's chunks + a capped job's worth of bystanders
    # must NOT count as overload (re-fusion guard)
    pool._buffer_sigs = dp.MAX_SIGNATURE_SETS_PER_JOB + cap
    assert pool._latency_width_cap() == cap
    # genuine overload: beyond that -> max-width drain
    pool._buffer_sigs = dp.MAX_SIGNATURE_SETS_PER_JOB + cap + 1
    assert pool._latency_width_cap() == bk.align_down(
        dp.MAX_SIGNATURE_SETS_PER_JOB
    )


def test_governed_pool_keeps_jobs_in_budget_at_offered_load():
    """At ~1,500 sets/s offered load every dispatched job must fit the
    latency budget: t(width) = FLOOR + PER_SET*width <= budget/2."""
    from lodestar_tpu.chain.bls import device_pool as dp

    pool = DeviceBlsVerifier(_backend=ModelledDevice())
    rng = random.Random(11)
    latencies = []

    async def one_request(n_sets):
        t0 = time.monotonic()
        ok = await pool.verify_signature_sets(
            [_dummy_set()] * n_sets, VerifyOptions(batchable=True)
        )
        latencies.append(time.monotonic() - t0)
        assert ok

    async def go():
        tasks = []
        for _ in range(60):
            tasks.append(asyncio.ensure_future(one_request(rng.randint(1, 50))))
            await asyncio.sleep(rng.uniform(0.01, 0.05) * 0.7)
        await gather_settled(*tasks)

    asyncio.get_event_loop_policy().new_event_loop().run_until_complete(go())

    budget_width = int(
        (dp.LATENCY_BUDGET_S / 2 - dp.MODEL_FLOOR_S) / dp.MODEL_PER_SET_S
    )
    dev = pool._dv
    assert dev.jobs, "no jobs dispatched"
    oversize = [w for w in dev.jobs if w > budget_width]
    assert not oversize, f"jobs exceeded the governed width: {oversize}"
    latencies.sort()
    p99 = latencies[int(0.99 * (len(latencies) - 1))]
    assert p99 < 1.0, f"p99 {p99:.3f}s over budget with governor active"


def test_wide_single_request_is_chunked_to_governed_width():
    """One 1,500-set batchable request (a full block's signature sets)
    must not ride through as a single over-budget job — the pool chunks
    it to the governed width at enqueue."""
    from lodestar_tpu.chain.bls import device_pool as dp

    pool = DeviceBlsVerifier(_backend=ModelledDevice())
    cap = pool._steady_width_cap()

    async def go():
        ok = await pool.verify_signature_sets(
            [_dummy_set()] * 1500, VerifyOptions(batchable=True)
        )
        assert ok

    asyncio.get_event_loop_policy().new_event_loop().run_until_complete(go())
    dev = pool._dv
    assert dev.jobs and max(dev.jobs) <= cap, (
        f"wide request dispatched over the governed width: {dev.jobs}"
    )
    assert sum(dev.jobs) == 1500
