"""Diagnose XLA:CPU persistent-cache health for the dryrun/bench programs.

Round-5 post-mortem tooling: four rounds of MULTICHIP timeouts came down
to ONE failure mode this script makes visible — a cache entry whose KEY
matches the current program but whose AOT payload fails deserialization
on the running host.  JAX counts the failed load as a cache hit, falls
back to a full recompile, and never rewrites the key, so the poisoned
entry silently costs hours in every fresh process.

Usage:
    python tools/diagnose_cache.py            # probe round-trip health
    python tools/diagnose_cache.py --list     # biggest entries + ages

The probe compiles a small throwaway program into a TEMP cache dir, then
reloads it in a fresh subprocess: `round-trip OK` means serialization
works for small entries on this host; the cpu_aot_loader E-lines about
machine features (`+prefer-no-gather ...`) are NON-FATAL noise for
entries that load.  Large (100 MB-class) entries can still fail — if a
program with a warm-looking entry recompiles anyway, delete that entry
and re-warm, or rely on the dryrun's reduced-step fallback.
"""
from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from lodestar_tpu.aot import cache as _aot_cache  # noqa: E402

# ONE cache-location source of truth (ISSUE 5): the same repo_cache_dir
# every other entry point gets from aot.cache.configure()
CACHE = _aot_cache.repo_cache_dir()

_CHILD = r"""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
sys.path.insert(0, sys.argv[2])
from lodestar_tpu.aot import cache as aot_cache
# probe cache lives in a TEMP dir (round-trip isolation) with the
# min-compile threshold at 0 so the tiny probe program gets an entry
aot_cache.configure(sys.argv[1], min_compile_time_secs=0.0)

@jax.jit
def f(x):
    # large enough that COMPILE dominates trace overhead — the warm/cold
    # ratio check needs a compile-bound cold run to be meaningful
    for i in range(60):
        x = jnp.tanh(x @ x) + jnp.sin(x) * (1.0 + i)
    return x.sum()

t0 = time.time()
r = f(jnp.ones((256, 256), jnp.float32))
r.block_until_ready()
print(f"RESULT {float(r):.3f} elapsed {time.time() - t0:.2f}s")
"""


def _run_child(cache_dir: str) -> str:
    try:
        out = subprocess.run(
            [sys.executable, "-c", _CHILD, cache_dir, REPO],
            capture_output=True, text=True, timeout=300,
        )
    except subprocess.TimeoutExpired:
        # the pathology this tool exists for: a hung compile/cache load
        return "PROBE TIMEOUT: child exceeded 300s — compile or cache load is hanging"
    return out.stdout + out.stderr


def probe() -> int:
    with tempfile.TemporaryDirectory(prefix="cacheprobe_") as d:
        first = _run_child(d)
        if "RESULT" not in first:
            print("probe FAILED to compile:\n" + first[-1500:])
            return 1
        cold = float(first.split("elapsed")[1].split("s")[0])
        second = _run_child(d)
        if "RESULT" not in second:
            print("probe FAILED to reload:\n" + second[-1500:])
            return 1
        warm = float(second.split("elapsed")[1].split("s")[0])
        feature_lines = second.count("cpu_aot_loader")
        # a real cache hit must beat the compile by a clear RATIO — an
        # absolute floor would green-light silent recompiles on hosts
        # where the probe itself compiles fast
        if warm > cold * 0.6:
            print(
                f"WARNING: warm {warm:.2f}s vs cold {cold:.2f}s — cache "
                "reloads may be failing on this host (poisoned-entry class)"
            )
            return 2
        print(
            f"round-trip OK: cold {cold:.2f}s -> warm {warm:.2f}s "
            f"({feature_lines} machine-feature warnings, non-fatal)"
        )
    return 0


def list_entries() -> int:
    if not os.path.isdir(CACHE):
        print(f"no cache dir at {CACHE}")
        return 1
    entries = []
    for name in os.listdir(CACHE):
        p = os.path.join(CACHE, name)
        if os.path.isfile(p):
            st = os.stat(p)
            entries.append((st.st_size, st.st_mtime, name))
    entries.sort(reverse=True)
    now = time.time()
    print(f"{len(entries)} entries, total "
          f"{sum(s for s, _, _ in entries) / 1e9:.2f} GB")
    for size, mtime, name in entries[:15]:
        age_h = (now - mtime) / 3600
        print(f"  {size / 1e6:9.1f} MB  {age_h:7.1f}h  {name[:80]}")
    return 0


if __name__ == "__main__":
    sys.exit(list_entries() if "--list" in sys.argv else probe())
