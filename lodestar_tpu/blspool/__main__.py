"""Second-process sidecar entry::

    python -m lodestar_tpu.blspool serve --port 0 --verifier auto

prints ``{"url": ..., "port": ...}`` on stdout once listening (the
tests/test_cli_node.py announce idiom), then serves until killed.
``--verifier auto`` resolves exactly like the beacon CLI: the device
pool when an accelerator backend is live, the host oracle otherwise.
"""
from __future__ import annotations

import argparse
import json


def build_inner_verifier(choice: str):
    from lodestar_tpu.cli.main import resolve_verifier_choice

    if resolve_verifier_choice(choice) == "device":
        from lodestar_tpu.chain.bls import DeviceBlsVerifier
        from lodestar_tpu.chain.bls.metrics import BlsPoolMetrics

        return DeviceBlsVerifier(metrics=BlsPoolMetrics.get())
    from lodestar_tpu.chain.bls import SingleThreadBlsVerifier

    return SingleThreadBlsVerifier()


def build_http_server(verifier: str = "auto", tenant_quota=None):
    """The sidecar ``serve`` runs: the inner verifier behind the
    multi-tenant server, bound to HTTP (not yet listening)."""
    from .http import BlsPoolHttpServer
    from .metrics import BlsPoolSidecarMetrics
    from .server import DEFAULT_TENANT_QUOTA, BlsPoolServer

    server = BlsPoolServer(
        build_inner_verifier(verifier),
        metrics=BlsPoolSidecarMetrics.get(),
        tenant_quota=tenant_quota or DEFAULT_TENANT_QUOTA,
    )
    return BlsPoolHttpServer(server)


def main(argv=None) -> int:
    import asyncio

    from .server import DEFAULT_TENANT_QUOTA

    parser = argparse.ArgumentParser(prog="python -m lodestar_tpu.blspool")
    sub = parser.add_subparsers(dest="command")
    serve = sub.add_parser("serve", help="serve the BLS pool over HTTP")
    serve.add_argument("--host", default="127.0.0.1")
    serve.add_argument("--port", type=int, default=0)
    serve.add_argument(
        "--verifier", choices=["auto", "oracle", "device"], default="auto"
    )
    serve.add_argument(
        "--tenant-quota", type=int, default=DEFAULT_TENANT_QUOTA[0],
        help="per-tenant admitted signature sets per quota window",
    )
    serve.add_argument(
        "--tenant-quota-ms", type=int, default=DEFAULT_TENANT_QUOTA[1],
        help="GCRA quota window in milliseconds",
    )
    args = parser.parse_args(argv)
    if args.command != "serve":
        parser.print_help()
        return 2

    http = build_http_server(
        args.verifier, (args.tenant_quota, args.tenant_quota_ms)
    )

    async def run():
        url = await http.start(args.host, args.port)
        print(json.dumps({"url": url, "port": http.port}), flush=True)
        try:
            while True:
                await asyncio.sleep(3600)
        finally:
            await http.close()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
