"""Second-process mock EL (mirrors tests/test_cli_node.py): the mock
EL server runs as its own OS process behind real TCP, and the
production HTTP clients — HttpExecutionEngine with JWT auth and
HttpEth1Provider feeding the deposit tracker — drive it over the wire.

This is the closest this host gets to "a beacon node talking to geth":
nothing is shared in-process, every byte crosses HTTP.
"""
import asyncio
import json
import os
import select
import signal
import subprocess
import sys

import pytest

from lodestar_tpu.params import ACTIVE_PRESET_NAME, ForkName

pytestmark = pytest.mark.skipif(
    ACTIVE_PRESET_NAME != "minimal", reason="minimal preset only"
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JWT_SECRET = bytes(range(32))


@pytest.fixture
def el_process(tmp_path):
    jwt_file = tmp_path / "jwt.hex"
    jwt_file.write_text("0x" + JWT_SECRET.hex() + "\n")
    env = dict(
        os.environ,
        LODESTAR_TPU_PRESET="minimal",
        PYTHONPATH=REPO,
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "lodestar_tpu.testing.mock_el_server",
            "--port", "0", "--jwt-secret-file", str(jwt_file),
            "--deposits", "3", "--blocks", "6",
        ],
        env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
    )
    try:
        # a child that hangs silently must fail the test, not stall it
        ready, _, _ = select.select([proc.stdout], [], [], 120)
        assert ready, "mock EL server announced no port in 120 s"
        line = proc.stdout.readline().decode()
        assert line, "mock EL server died before announcing its port"
        yield json.loads(line)["url"]
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


class TestSecondProcessMockEl:
    def test_engine_round_trip_and_deposit_sync_over_tcp(self, el_process):
        from lodestar_tpu.config import minimal_chain_config as cfg
        from lodestar_tpu.eth1 import Eth1DepositDataTracker
        from lodestar_tpu.eth1.http_provider import HttpEth1Provider
        from lodestar_tpu.execution.engine import HttpExecutionEngine
        from lodestar_tpu.execution.serde import fork_of_payload
        from lodestar_tpu.types import ssz

        url = el_process

        async def go():
            eng = HttpExecutionEngine(url, jwt_secret=JWT_SECRET)
            provider = HttpEth1Provider(url, log_chunk_size=4)
            try:
                # connect-time handshake against the other process
                caps = await eng.exchange_capabilities()
                assert "engine_getPayloadV2" in caps

                # capella production round trip across the process boundary
                attrs = {
                    "fork": ForkName.capella,
                    "timestamp": 777,
                    "prev_randao": b"\x0b" * 32,
                    "withdrawals": [
                        ssz.capella.Withdrawal(
                            index=0, validator_index=1,
                            address=b"\x0c" * 20, amount=9,
                        )
                    ],
                }
                pid = (
                    await eng.notify_forkchoice_update(
                        b"\x0d" * 32, b"\x0d" * 32, b"\x0d" * 32,
                        payload_attributes=attrs,
                    )
                ).payload_id
                assert pid is not None
                payload = await eng.get_payload(pid)
                assert fork_of_payload(payload) is ForkName.capella
                assert len(payload.withdrawals) == 1
                status = await eng.notify_new_payload(payload)
                assert status.status.value == "VALID"
                assert bytes(status.latest_valid_hash) == bytes(
                    payload.block_hash
                )

                # deposit tracking across the process boundary
                tracker = Eth1DepositDataTracker(provider, cfg)
                n = await tracker.update()
                assert n == 3
                assert tracker.tree.count() == 3
                assert tracker.deposit_events[2].index == 2
            finally:
                await eng.close()
                await provider.close()

        asyncio.run(go())
