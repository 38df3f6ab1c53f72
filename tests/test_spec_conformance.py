"""Conformance breadth: generated official-layout suites for every
operation × fork plus sanity/finality/fork/rewards/fork_choice, consumed
by the same runners that would read a real consensus-spec-tests release.

Reference counterpart: test/spec/presets/*.ts over the downloaded
vectors (specTestVersioning.ts:17-32).  Self-generated vectors are a
regression oracle (generation and verification share the
operation_specs table but serialize through the full SSZ round trip and
re-execute the state transition from decoded bytes); independent
evidence lives in tests/test_external_vectors.py and the KAT suites.

Each suite is read by one test, so each test generates only the suites
it reads: a run builds every suite once, whichever workers run the tests.
"""
import os

import pytest

from lodestar_tpu.params import ACTIVE_PRESET_NAME, FORK_SEQ, ForkName
from lodestar_tpu.spec_test import run_directory_spec_test
from lodestar_tpu.spec_test import fixtures as fx
from lodestar_tpu.spec_test.runners import (
    make_finality_runner,
    make_fork_choice_runner,
    make_fork_upgrade_runner,
    make_operations_runner,
    make_rewards_runner,
    make_sanity_blocks_runner,
    make_sanity_slots_runner,
)

pytestmark = [
    pytest.mark.e2e,
    pytest.mark.skipif(ACTIVE_PRESET_NAME != "minimal", reason="minimal preset only"),
]

FORKS = fx.ALL_FORKS


def _suite_root(gen_root, fork, runner, handler):
    return os.path.join(gen_root, fork.value, runner, handler, "pyspec_tests")


@pytest.mark.parametrize("fork", FORKS, ids=[f.value for f in FORKS])
def test_operations_all_handlers(tmp_path, fork):
    gen_root = str(tmp_path)
    fx.gen_operations(gen_root, fork)
    cfg = fx.config_for(fork)
    specs = fx.operation_specs(fork)
    ran = 0
    for handler, (stem, op_t, apply_fn) in specs.items():
        root = _suite_root(gen_root, fork, "operations", handler)
        if not os.path.isdir(root):
            continue
        # pass apply_fn straight through — a wrapper would hide the
        # optional `case` kwarg (execution.yaml engine verdicts) from the
        # runner's signature check
        runner = make_operations_runner(cfg, fork, stem, op_t, apply_fn)
        res = run_directory_spec_test(
            root, runner, suite=f"{fork.value}/operations/{handler}"
        )
        res.assert_ok()
        ran += len(res.passed)
    assert ran >= 10, f"{fork.value}: too few operation cases ran ({ran})"


@pytest.mark.parametrize("fork", FORKS, ids=[f.value for f in FORKS])
def test_sanity(tmp_path, fork):
    gen_root = str(tmp_path)
    fx.gen_sanity(gen_root, fork)
    cfg = fx.config_for(fork)
    run_directory_spec_test(
        _suite_root(gen_root, fork, "sanity", "slots"),
        make_sanity_slots_runner(cfg, fork),
        suite=f"{fork.value}/sanity/slots",
    ).assert_ok()
    run_directory_spec_test(
        _suite_root(gen_root, fork, "sanity", "blocks"),
        make_sanity_blocks_runner(cfg, fork),
        suite=f"{fork.value}/sanity/blocks",
    ).assert_ok()


@pytest.mark.parametrize(
    "fork", [f for f in FORKS if f is not ForkName.phase0],
    ids=[f.value for f in FORKS if f is not ForkName.phase0],
)
def test_fork_upgrade(tmp_path, fork):
    gen_root = str(tmp_path)
    fx.gen_fork_upgrade(gen_root, fork)
    fn = fx.upgrade_ladder()[fork]
    pre_fork = FORKS[FORKS.index(fork) - 1]
    cfg = fx.config_for(pre_fork)
    run_directory_spec_test(
        _suite_root(gen_root, fork, "fork", "fork"),
        make_fork_upgrade_runner(cfg, pre_fork, fn),
        suite=f"{fork.value}/fork",
    ).assert_ok()


@pytest.mark.parametrize(
    "fork",
    [f for f in FORKS if FORK_SEQ[f] >= FORK_SEQ[ForkName.altair]],
    ids=[f.value for f in FORKS if FORK_SEQ[f] >= FORK_SEQ[ForkName.altair]],
)
def test_rewards(tmp_path, fork):
    gen_root = str(tmp_path)
    fx.gen_rewards(gen_root, fork)
    cfg = fx.config_for(fork)
    run_directory_spec_test(
        _suite_root(gen_root, fork, "rewards", "basic"),
        make_rewards_runner(cfg, fork),
        suite=f"{fork.value}/rewards/basic",
        uses_post=False,
    ).assert_ok()


@pytest.mark.parametrize(
    "fork", [ForkName.phase0, FORKS[-1]], ids=["phase0", FORKS[-1].value]
)
def test_finality(tmp_path, fork):
    gen_root = str(tmp_path)
    fx.gen_finality(gen_root, fork)
    cfg = fx.config_for(fork)
    run_directory_spec_test(
        _suite_root(gen_root, fork, "finality", "finality"),
        make_finality_runner(cfg, fork),
        suite=f"{fork.value}/finality",
    ).assert_ok()


@pytest.mark.parametrize(
    "fork", [ForkName.phase0, FORKS[-1]], ids=["phase0", FORKS[-1].value]
)
def test_fork_choice(tmp_path, fork):
    gen_root = str(tmp_path)
    fx.gen_fork_choice(gen_root, fork)
    cfg = fx.config_for(fork)
    run_directory_spec_test(
        _suite_root(gen_root, fork, "fork_choice", "on_block"),
        make_fork_choice_runner(cfg, fork),
        suite=f"{fork.value}/fork_choice/on_block",
        uses_post=False,
    ).assert_ok()
