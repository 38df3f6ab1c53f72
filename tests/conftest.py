"""Test harness config: virtual 8-device CPU mesh + minimal preset.

Multi-chip TPU hardware isn't available in CI; sharding correctness is
validated on a host-platform device mesh exactly as the driver's
``dryrun_multichip`` does.  Must run before any ``import jax``.

Like the reference's test suite (beacon-node/test/setupPreset.ts forces
LODESTAR_PRESET=minimal), consensus tests run on the minimal preset; the
blst-produced interop fixtures embedded in tests/test_state_kats.py were
generated under it.

A persistent JAX compilation cache makes the (expensive, single-core) XLA
CPU compiles of the pairing kernels a one-time cost across test runs.
"""
import os

# tests are CPU-only: the backend, and the fp engine's kernel choice
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["LODESTAR_TPU_FP_PLATFORM"] = "cpu"
os.environ.setdefault("LODESTAR_TPU_PRESET", "minimal")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# ONE cache-config path for every entry point (ISSUE 5): node, bench,
# tests, __graft_entry__ and diagnose_cache all call aot.cache.configure
from lodestar_tpu.aot import cache as _aot_cache  # noqa: E402

_aot_cache.configure()


# ---------------------------------------------------------------------------
# suite tiering (VERDICT r4 next #8): a driver-class 1-core host gets a
# green signal from `pytest -m fast` in minutes; `-m kernel` isolates the
# compile-heavy XLA files; `-m e2e` the multi-process/network runs.
# Assigned centrally by filename so per-file pytestmark lines (skipif
# preset guards etc.) stay untouched.  `slow` is set per test, never per
# file: it marks a whole-pipeline XLA:CPU compile (a pairing, a verify
# program, the full hash-to-curve) that alone takes over 300 s or 8 GB,
# and `-m 'not slow'` leaves it out; its stages keep cheaper tests.
# ---------------------------------------------------------------------------
import fcntl  # noqa: E402

import pytest  # noqa: E402

_KERNEL_FILES = {
    "test_fp_jax.py",
    "test_tower_jax.py",
    "test_pairing_jax.py",
    "test_pallas_fp.py",
    "test_fast_aggregate_device.py",
    "test_device_h2c.py",
    "test_sharded_verify.py",
    "test_tpu_compile.py",
}
_E2E_FILES = {
    "test_two_process_net.py",
    "test_cli_node.py",
    "test_network_sim.py",
    "test_range_sync_chain.py",
    "test_spec_conformance.py",
    "test_api_and_validator_client.py",
    "test_sync_committee_vc.py",
    "test_blinded_block_flow.py",
    "test_checkpoint_sync_and_builder.py",
    "test_discovery_and_merge.py",
    "test_blspool_process.py",
    "test_blspool_swarm.py",
    "test_wire_transport.py",
    "test_dryrun_artifact.py",
    "test_official_vectors.py",
    "test_mock_el_process.py",
}
# correct but minutes-long single-process suites: neither fast nor e2e;
# they run unmarked
_PLAIN_FILES = {
    "test_merge_forks.py",
    "test_beacon_chain.py",
    "test_dev_chain.py",
    "test_validator.py",
    "test_light_client.py",
    "test_backfill.py",
    "test_known_answers.py",
    "test_state_kats.py",
    "test_external_vectors.py",
    "test_bls_oracle.py",
    "test_bls_verifier_service.py",
    "test_spec_harness.py",
    "test_gossip_validation.py",
    "test_sync_committee_gossip.py",
    "test_pairing_proj.py",
    "test_state_proof_route.py",
    "test_native_h2c.py",
    "test_bls_pool_firehose.py",
}
# The quick tier is EXPLICIT opt-in (ADVICE r5 / lodelint fast-tier-
# default): an unlisted file runs unmarked (plain tier) and turns
# tests/test_lodelint.py::test_every_test_file_is_tiered red until it is
# placed in exactly one list above or below — a compile-heavy suite can
# no longer slip into tier-1 by simply not being listed anywhere.
_FAST_FILES = {
    "test_adversarial_el.py",
    "test_altair.py",
    "test_aot.py",
    "test_bls_conformance_vectors.py",
    "test_blspool.py",
    "test_dashboards.py",
    "test_db.py",
    "test_engine_http.py",
    "test_eth1.py",
    "test_eth1_http.py",
    "test_faults.py",
    "test_fork_choice.py",
    "test_gossip_scoring.py",
    "test_incremental_merkle.py",
    "test_kzg.py",
    "test_lifecycle_regressions.py",
    "test_limb_bounds_audit.py",
    "test_lodelint.py",
    "test_mesh_smoke.py",
    "test_metrics.py",
    "test_native.py",
    "test_networks.py",
    "test_ops_tooling.py",
    "test_optimistic_sync.py",
    "test_subnets.py",
    "test_swarm.py",
}

def pytest_collection_modifyitems(config, items):
    for item in items:
        name = os.path.basename(str(item.fspath))
        if name in _KERNEL_FILES:
            item.add_marker(pytest.mark.kernel)
        elif name in _E2E_FILES:
            item.add_marker(pytest.mark.e2e)
        elif name in _FAST_FILES:
            item.add_marker(pytest.mark.fast)
        # anything else runs unmarked (plain tier): an UNLISTED file can
        # never gain the fast marker.  tests/test_lodelint.py::
        # test_every_test_file_is_tiered fails (a normal red test, not an
        # aborted run) until the file is listed in exactly one tier.


@pytest.fixture
def heavy_compile(tmp_path_factory):
    """Run the test holding an inter-process lock, so that at most one
    of the tier's heaviest XLA:CPU compiles (about 5 GB each) runs at a
    time across xdist workers (`--dist load` ignores `xdist_group`).
    The lock file sits in the run's shared base temp directory."""
    path = tmp_path_factory.getbasetemp().parent / "heavy-compile.lock"
    with open(path, "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        yield
