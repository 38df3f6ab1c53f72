"""Chip smoke: the served BLS verification path on one TPU, end to end.

    python chip_smoke.py

One process, the mainnet preset, fixed sizes, no options.  It drives
the path a user runs, ``BlsVerifier`` -> ``DeviceBlsVerifier`` -> the
AOT-registered ``hashed``/``each`` programs, through two entry points:

* phase A, the sidecar: built as ``python -m lodestar_tpu.blspool serve
  --verifier device`` builds it, listening on 127.0.0.1 in this
  process, fed ``codec.encode_request`` bodies over HTTP.  Verdicts are
  checked against the host oracle (``crypto/bls``);
* phase B, the node: ``lodestar_tpu.cli.main dev --verifier device
  --validators 64 --slots 4``; every block's signature sets verify on
  the device and the head advances every slot.

It exits non-zero, and prints no result, unless JAX's first device is a
TPU, the Pallas kernels, device hash-to-curve and the native library are
all in use, and every job stayed on the device tier.  The last line of
stdout is ``{"ok": true, "device": {...}}``; earlier lines start with
``smoke:``.  Compile happens once per program, up front, and is reported
with its persistent-cache hit or miss; the cache is where
``$JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``.
"""
import os

os.environ["LODESTAR_TPU_PRESET"] = "mainnet"  # before lodestar_tpu loads

import asyncio  # noqa: E402
import contextlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import random  # noqa: E402
import time  # noqa: E402

SEED = 21
SMALL_SETS = 100  # the 128 rung
LARGE_SETS = 1000  # the 1024 rung, the width the pool drains gossip at
SAMPLED = 8  # oracle spot checks at the 1024 rung
# the only programs this run may compile: (kernel, bucket)
PROGRAMS = (("hashed", 128), ("hashed", 1024), ("each", 128))
NODE_ARGS = ["dev", "--verifier", "device", "--validators", "64", "--slots", "4"]


def say(what: str, **fields) -> None:
    print(f"smoke: {what} {json.dumps(fields, sort_keys=True)}", flush=True)


def check(ok: bool, why: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {why}")


def device_info() -> dict:
    """JAX's devices; exits unless the first one is a TPU."""
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found, JAX sees {info}")
    return info


def counter(name: str) -> float:
    from prometheus_client import REGISTRY

    return REGISTRY.get_sample_value(name) or 0.0


class CacheLog:
    """Persistent-cache events per program, read through the aot.cache
    spy: which compiles hit, missed, or happened late."""

    def __init__(self):
        self.events = []  # (cache_key, kind)

    def __call__(self, kind: str, cache_key: str, seconds: float) -> None:
        self.events.append((cache_key, kind))

    def kinds_since(self, mark: int, prefix: str) -> set:
        return {k for key, k in self.events[mark:] if key.startswith(prefix)}


def compile_programs(cache_log: CacheLog) -> None:
    """Load or compile each program, through the served dispatch path,
    and run it once on its registered example arguments, so every later
    dispatch reuses it.  One after another: compiled from parallel
    threads on a TPU v5e, two of the three programs traced to different
    cache keys on the next run, and the phase took no less time."""
    import jax

    from lodestar_tpu.aot import registry

    for kernel, bucket in PROGRAMS:
        prog = registry.Program(kernel, bucket)
        mark = len(cache_log.events)
        t0 = time.perf_counter()
        jax.block_until_ready(registry.call(kernel, *prog.example_args()))
        seconds = time.perf_counter() - t0
        kinds = cache_log.kinds_since(mark, f"jit_{prog.fn_name()}-")
        say("compile", program=prog.key, seconds=seconds,
            cache_events=sorted(kinds))


def make_sets(rng: random.Random, n: int):
    """n valid sets under distinct keys: (secret keys, sets)."""
    from lodestar_tpu.crypto.bls import api

    keys, sets = [], []
    for _ in range(n):
        sk = api.SecretKey.key_gen(rng.randbytes(32))
        msg = rng.randbytes(32)
        keys.append(sk)
        sets.append(api.SignatureSet(sk.to_public_key(), msg, sk.sign(msg)))
    return keys, sets


async def phase_sidecar(rng: random.Random) -> list:
    """Phase A.  Returns the verification responses."""
    import aiohttp

    from lodestar_tpu.blspool import codec
    from lodestar_tpu.blspool.__main__ import build_http_server
    from lodestar_tpu.crypto.bls import api

    t0 = time.perf_counter()
    keys, small = make_sets(rng, SMALL_SETS)
    _, large = make_sets(rng, LARGE_SETS)
    # one signature replaced by its key's valid signature over ANOTHER
    # message
    bad_at = rng.randrange(SMALL_SETS)
    other_msg = rng.randbytes(32)
    wrong_sig = keys[bad_at].sign(other_msg)
    tampered = list(small)
    tampered[bad_at] = api.SignatureSet(
        small[bad_at].public_key, small[bad_at].message, wrong_sig
    )
    say("signed", sets=SMALL_SETS + LARGE_SETS + 1,
        seconds=time.perf_counter() - t0)

    t0 = time.perf_counter()
    truth = [api.verify_signature_set(s) for s in tampered]
    check(truth == [i != bad_at for i in range(SMALL_SETS)],
          "oracle disagrees with how the 128-rung bodies were built")
    check(api.verify_signature_set(small[bad_at]),
          "oracle rejects the set that was replaced")
    sampled = rng.sample(range(LARGE_SETS), SAMPLED)
    check(all(api.verify_signature_set(large[i]) for i in sampled),
          "oracle rejects a sampled 1024-rung set")
    check(api.verify(small[bad_at].public_key, other_msg, wrong_sig),
          "the replacing signature is not valid over its own message")
    say("oracle", pairing_checks=SMALL_SETS + SAMPLED + 2,
        seconds=time.perf_counter() - t0)

    http = build_http_server("device")
    inner = http.server._verifier
    check(type(inner).__name__ == "DeviceBlsVerifier",
          f"the sidecar's verifier is {type(inner).__name__}")
    splits = []  # (bucket, per-set verdicts) of every per-set split

    def each_device(all_sets, bucket, _orig=inner._each_device):
        verdicts = _orig(all_sets, bucket)
        splits.append((bucket, list(verdicts)))
        return verdicts

    inner._each_device = each_device
    url = await http.start("127.0.0.1", 0)
    responses = []
    try:
        async with aiohttp.ClientSession() as session:

            async def post(name: str, body: bytes, n_sets: int) -> dict:
                t = time.perf_counter()
                async with session.post(url + "/verify", data=body) as r:
                    check(r.status == 200, f"{name}: HTTP {r.status}")
                    resp = codec.decode_response(await r.read())
                say("request", name=name, sets=n_sets,
                    seconds=time.perf_counter() - t, response=resp)
                return resp

            for name, sets, want in (
                ("valid@128", small, True),
                ("valid@1024", large, True),
                ("one-invalid@128", tampered, False),
            ):
                n_split = len(splits)
                resp = await post(
                    name, codec.encode_request("smoke", sets), len(sets)
                )
                responses.append(resp)
                check(resp["ok"] and resp["valid"] is want,
                      f"{name}: expected valid={want}, got {resp}")
                check(resp["coalesced_width"] == len(sets),
                      f"{name}: coalesced with something else: {resp}")
                if want:
                    check(len(splits) == n_split,
                          f"{name}: a valid body took the per-set split")
            expected = [i != bad_at for i in range(SMALL_SETS)]
            check(bool(splits), "the invalid body never reached the "
                  "per-set split")
            for bucket, verdicts in splits:
                check(bucket == 128, f"per-set split ran at {bucket}")
                check(verdicts == expected,
                      "per-set split names "
                      f"{[i for i, v in enumerate(verdicts) if not v]}, "
                      f"the replaced set is {bad_at}")
            say("per-set split", runs=len(splits), invalid_index=bad_at)

            garbage = await post("garbage", b"\x00not a request", 0)
            check(not garbage["ok"]
                  and garbage.get("error", "").startswith(codec.ERR_BAD_REQUEST),
                  f"garbage body: {garbage}")
    finally:
        await http.close()
    return responses


def phase_node() -> None:
    """Phase B: the dev node through its CLI entry point."""
    from lodestar_tpu.cli.main import main as cli_main

    jobs0 = counter("lodestar_tpu_bls_pool_jobs_started_total")
    sets0 = counter("lodestar_tpu_bls_pool_sig_sets_total")
    out = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(out):
        rc = cli_main(NODE_ARGS)
    seconds = time.perf_counter() - t0
    lines = out.getvalue().splitlines()
    for line in lines:
        say("node", line=line)
    check(rc == 0, f"dev node exited {rc}")
    check(any("verifier=device" in ln for ln in lines),
          "dev node did not resolve to the device verifier")
    slots = [json.loads(ln) for ln in lines if ln.startswith('{"slot"')]
    check([s["slot"] for s in slots] == [1, 2, 3, 4],
          f"slots imported: {[s['slot'] for s in slots]}")
    check(len({s["root"] for s in slots}) == 4, "the head did not advance")
    verified = [s["verified_sets"] for s in slots]
    check(all(b > a for a, b in zip([0] + verified, verified)),
          f"a block verified no signature sets: {verified}")
    jobs = counter("lodestar_tpu_bls_pool_jobs_started_total") - jobs0
    on_device = counter("lodestar_tpu_bls_pool_sig_sets_total") - sets0
    check(jobs >= 4 and on_device == verified[-1],
          f"{verified[-1]} block sets, {on_device} through {jobs} device jobs")
    say("node done", blocks=4, sets=verified[-1], device_jobs=jobs,
        seconds=seconds)


def preflight() -> dict:
    """The device and the facts the chip path needs, before any phase."""
    device = device_info()
    import jax

    from lodestar_tpu import native
    from lodestar_tpu.ops.bls12_381 import fp, verify

    facts = {
        "jax": jax.__version__,
        **device,
        "pallas": fp._use_pallas(),
        "device_h2c": verify.use_device_h2c(),
        "native": native.available(),
    }
    say("device", **facts)
    for fact in ("pallas", "device_h2c", "native"):
        check(facts[fact] is True, f"{fact} is off on the chip")
    return device


def run(device: dict) -> None:
    from lodestar_tpu.aot import cache as aot_cache, registry
    from lodestar_tpu.chain.bls import breaker as brk

    cache_dir = aot_cache.configure()
    cache_log = CacheLog()
    aot_cache.install_cache_spy(cache_log)
    say("cache", dir=cache_dir)
    compile_programs(cache_log)
    served_from = len(cache_log.events)

    rng = random.Random(SEED)
    responses = asyncio.run(phase_sidecar(rng))
    phase_node()

    prefixes = tuple(
        f"jit_{registry.Program(k, b).fn_name()}-" for k, b in PROGRAMS
    )
    late = {
        k for key, k in cache_log.events[served_from:]
        if key.startswith(prefixes)
    }
    check(not late & {"miss", "put", "exec_miss", "exec_put"},
          "a verify program compiled after the compile phase")
    record = brk.process_degradation()
    say("degradation", **record,
        device_faults=counter("lodestar_tpu_bls_pool_device_faults_total"),
        late_cache_events=sorted(late))
    check(record["worst_tier"] == brk.TIER_DEVICE,
          f"a job left the device: worst tier {record['worst_tier']}")
    check(record["breaker_state"] == brk.CLOSED, "the breaker opened")
    check(counter("lodestar_tpu_bls_pool_device_faults_total") == 0,
          "device faults were counted")
    tiers = {r["degradation_tier"] for r in responses}
    check(tiers == {brk.TIER_DEVICE}, f"response tiers {tiers}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    run(preflight())
