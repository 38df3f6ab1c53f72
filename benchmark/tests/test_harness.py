"""The harness on the CPU, at a tiny size, with the reference in the
program's place: sound runs come out correct, the control and each fault of
the timed path come out not correct, and a run off a TPU prints no result."""
import dataclasses
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import harness
from benchmark.tests.fakes import reference_system
from benchmark.traffic import distinct_sets

SETS = 16  # two jobs of 8 under the stand-in pool's width
SEEDS = (7, 2**33 + 5)


def tiny_cell() -> harness.Cell:
    cell = harness.Cell.load("range_sync.segment")
    config = dict(cell.config, segment=dict(cell.config["segment"], signature_sets=SETS))
    traffic = dict(cell.traffic, fresh_requests=2, warmup_sets=SETS // 2 + 1)
    return dataclasses.replace(cell, config=config, traffic=traffic)


def run_tiny(seed: int, fault=None) -> harness.Outcome:
    """One run whose window sends exactly one request, then the checks."""
    return harness.execute(tiny_cell(), seed, 1e-3, False,
                           lambda: (reference_system(fault), None), n_workers=2,
                           t_start=time.monotonic())


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_run_is_correct(seed):
    out = run_tiny(seed)
    assert out.result["correct"] is True, out.checks
    kinds = [r["kind"] for r in out.records]
    assert kinds == [None, "cancelling_pair", "wrong_message"]
    assert out.result["attempted"] == 3 and out.result["failed"] == 0
    assert out.checks["wrong_verdicts"]["value"] == 0
    assert set(out.result["metrics"]) == {"sets_per_s", "setup_s"}
    assert list(out.result)[-1] == "checks"


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("fault", ["unit_coefficients", "flipped", "no_op", "half_left_out",
                                   "other_half_left_out"])
def test_fault_is_not_correct(fault, seed):
    # unit_coefficients is the control
    out = run_tiny(seed, fault=fault)
    assert out.result["correct"] is False
    assert out.checks["wrong_verdicts"]["value"] >= 1


def test_traced_run_reads_layers_and_no_device_on_the_cpu():
    """A ``--trace 1`` run takes its slice after the window's first second
    and reads the per-layer metrics; with the reference in the program's
    place no operation runs on a device, so the trace gives no busy time."""
    seed = SEEDS[1]
    assert harness.trace_start(seed) == harness.trace_start(seed)
    assert harness.TRACE_START_S <= harness.trace_start(seed) < (
        harness.TRACE_START_S + harness.TRACE_PHASE_S)
    out = harness.execute(tiny_cell(), seed, 1e-3, True, lambda: (reference_system(), None),
                          n_workers=2, t_start=time.monotonic())
    assert out.result["correct"] is True, out.checks
    assert set(out.result["metrics"]) == {"pool.sets_per_job.sync", "encode.ms_per_job.sync"}
    assert "busy_s" not in out.result["device"] and "breakdown" not in out.result


def test_tampered_sets_fill_one_half_of_one_job_at_the_cell_shape():
    """At the cell's own request size and the pool's own job width, every
    seed puts the two tampered sets of the first request checked after the
    window in the first half of one full job, and those of the second in
    the second half of one: a program that checks only one half of each
    job accepts one of the two requests."""
    from lodestar_tpu.chain.bls.device_pool import governed_steady_width

    cell = harness.Cell.load("range_sync.segment")
    n, width = cell.config["segment"]["signature_sets"], governed_steady_width()
    for seed in [*range(200), 2**33 + 5, 2**31 + 11]:
        assert len(cell.traffic["checked_after_window"]) >= 2
        for request in (0, 1):
            a, b = distinct_sets.tamper_positions(seed, request, n, width)
            job, half = a // width, width // 2
            assert a != b and b // width == job and (job + 1) * width <= n
            assert {(a - job * width) // half, (b - job * width) // half} == {request}


def test_preflight_refuses_without_tpu():
    with pytest.raises(harness.Refused):
        harness.preflight(1)


def test_command_off_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = harness.ROOT
    p = subprocess.run(
        [sys.executable, "-m", "benchmark", "--workload", "range_sync.segment",
         "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
    assert "no TPU" in p.stderr
