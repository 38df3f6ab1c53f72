"""The program and its control on the same requests, for several seeds, in
one process on the chip.

    python3 -m benchmark.control --workload range_sync.segment \\
        --seeds 201,202,203 --seconds 20

For each seed the cell's traffic is made once and sent twice, each time
through a fresh ``DeviceBlsVerifier`` and a window of ``--seconds``:

- ``program``: the verifier as a run builds it;
- ``control``: the same pool and programs, with the batch check's random
  coefficients all 1 (``verify.encode_job(..., rand=[1, ...])``), the
  cheaper step a later change could be tempted by.  Two signatures whose
  errors cancel in a plain sum then pass.

Each window prints one JSON line with the compared numbers (``checks``),
from which ``PERF.md`` takes the lower reading (the program's largest) and
the upper one (the control's smallest).  The programs are compiled once,
for all seeds, and the next seed's traffic is signed while this one runs.
The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import threading
import time

from benchmark import harness


class UnitCoefficients:
    """The verify module with every random coefficient set to 1."""

    def __init__(self, dv):
        self._dv = dv

    def encode_job(self, sets, bucket=None):
        return self._dv.encode_job(sets, rand=[1] * len(sets), bucket=bucket)

    def execute_batch(self, job):
        return self._dv.execute_batch(job)

    def verify_each_device(self, sets, bucket=None):
        return self._dv.verify_each_device(sets, bucket=bucket)


def control_system(device: dict) -> harness.System:
    from lodestar_tpu.ops.bls12_381 import verify as dv

    return harness.production_system(device, backend=UnitCoefficients(dv))


def main() -> None:
    ap = argparse.ArgumentParser(prog="python3 -m benchmark.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]

    cell = harness.Cell.load(args.workload)
    os.environ["LODESTAR_TPU_PRESET"] = cell.config["preset"]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.CACHE_DIR)
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(harness.workers() - 1) as sign_pool, ctx.Pool(1) as check_pool:
        def make(seed: int):
            traffic = cell.generator.Traffic(cell.traffic, seed, cell.config)
            thread = threading.Thread(target=traffic.make, args=(sign_pool,), daemon=True)
            thread.start()
            return traffic, thread

        pending = make(seeds[0])
        device = harness.preflight(cell.chips)
        compile_log = harness.CompileLog()
        compile_log.install()
        ready = threading.Event()
        ready.set()
        for i, seed in enumerate(seeds):
            traffic, thread = pending
            thread.join()
            if i + 1 < len(seeds):
                pending = make(seeds[i + 1])
            for side, build in (("program", harness.production_system),
                                ("control", control_system)):
                out = harness.run(cell, args.seconds, None, build(device), check_pool, traffic,
                                  ready, ready, time.monotonic(), compile_log)
                print(json.dumps({
                    "seed": seed, "side": side, "correct": out.result["correct"],
                    "checks": out.checks, "metrics": out.result["metrics"],
                    "requests": [(r["fresh"], r["kind"], r["verdict"], r["expected"]) for r in out.records],
                }), flush=True)
        sign_pool.terminate()
        check_pool.terminate()
        sign_pool.join()
        check_pool.join()


if __name__ == "__main__":
    main()
