"""Multi-device sharded verification (SURVEY §2.5 row 1: pjit/shard_map
data parallelism over the signature batch with an ICI reduction of the
Miller products before one shared final exponentiation).

Runs the driver's dryrun entry in-process semantics: the same
`__graft_entry__.dryrun_multichip` subprocess the driver executes, on the
8-device virtual CPU mesh.  Shares its XLA cache entry with the driver's
run, so after the first compile this is cheap.
"""
import subprocess
import sys

import pytest


@pytest.mark.slow  # cold XLA:CPU compile of the whole sharded program
def test_dryrun_multichip_8():
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)",
        ],
        cwd=".",
        capture_output=True,
        timeout=5200,
    )
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]


def test_checked_reduced_step_lowers_with_one_gather_collective():
    """The reduced sharded step builds under jax.shard_map's default
    check_vma=True: the gather is a psum of per-device slot stacks, so
    its result is typed replicated and out_specs=P() type-checks.  The
    lowered program reduces over the mesh with an all-reduce.  Bit
    equality with the unchecked body is in test_mesh_smoke.py, which
    already compiles this program.  Trace and lower only."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from lodestar_tpu.ops.bls12_381 import curve as cv, sharded

    mesh = Mesh(np.array(jax.devices("cpu")[:2]), ("sp",))
    pk_aff, pk_inf = cv.encode_g1_affine([None] * 4)
    bits = cv.scalars_to_bits([3, 5, 7, 9], 4)
    active = np.ones(4, bool)
    text = (
        jax.jit(sharded.build_reduced_step(mesh))
        .lower(pk_aff, pk_inf, bits, active)
        .as_text()
    )
    assert "all_reduce" in text or "all-reduce" in text


def test_sharded_programs_keep_the_replication_check():
    """No shard_map in ops/bls12_381/sharded.py turns check_vma off:
    replication of every out_specs=P() result is checked by jax at
    trace time, not asserted by a lint suppression."""
    import inspect

    from lodestar_tpu.ops.bls12_381 import sharded

    src = inspect.getsource(sharded)
    assert src.count("jax.shard_map,") == 2
    assert "check_vma=" not in src
    assert "lodelint: disable=replicated-escape" not in src
