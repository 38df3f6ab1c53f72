"""Check ``benchmark/trace.py`` on a trace recorded here, on the CPU.

    JAX_PLATFORMS=cpu python3 -m benchmark.check_trace

First the interval arithmetic on intervals whose answer is known, then the
whole reduction on a real profiler trace: a jitted program run five times
with idle sleeps between, inside the slice that the harness marks, and
once before and once after it.  On the CPU the XLA operations run on the host, in
the thread of the PjRt CPU client, whose events stand in for a TPU's
program events here.
Exits non-zero on the first check that fails.
"""
import os
import shutil
import tempfile
import time

from benchmark import trace as tr

RUNS = 5
SLEEP_S = 0.05


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"check_trace FAILED: {what}")
    print(f"ok: {what}")


def arithmetic() -> None:
    iv = [(0.0, 1.0, "a"), (0.5, 2.0, "b"), (3.0, 4.0, "a"), (3.5, 3.6, "c")]
    check(tr.merge(iv) == [(0.0, 2.0), (3.0, 4.0)], "merge joins overlapping intervals")
    check(abs(tr.busy(iv) - 3.0) < 1e-12, "busy is the length of the union")
    check(tr.gaps(iv) == [(2.0, 3.0)], "gaps lie between merged intervals")
    check(tr.gaps(iv, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)],
          "a window's idle edges are gaps too")
    check(tr.clip(iv, 0.5, 3.2) == [(0.5, 1.0, "a"), (0.5, 2.0, "b"), (3.0, 3.2, "a")],
          "clip keeps the parts inside the window")
    per = tr.per_name(iv, tr.module_name)
    check(per["a"] == {"seconds": 2.0, "count": 2}, "per-name totals and counts")
    check(tr.module_name("jit_verify(12)") == "jit_verify", "module run ids are dropped")
    host = [(1.9, 3.1, "encode"), (0.0, 4.0, "request")]
    check(tr.name_gaps([(2.0, 3.0)], host) == [["encode", 1.0]],
          "a gap takes the name of the host event that fills it, not of the whole request")


def recorded() -> None:
    """A program run ``RUNS`` times with sleeps between, traced with the
    harness's slice annotation around the middle runs only."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        for _ in range(4):
            x = jnp.sin(x) @ x
        return x

    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    out = tempfile.mkdtemp(prefix="check-trace-")
    try:
        jax.profiler.start_trace(out)
        f(x).block_until_ready()  # before the slice: left out
        time.sleep(SLEEP_S)
        with jax.profiler.TraceAnnotation(tr.SLICE):
            t0 = time.monotonic()
            for _ in range(RUNS):
                f(x).block_until_ready()
                time.sleep(SLEEP_S)
            window = time.monotonic() - t0
        f(x).block_until_ready()  # after the slice: left out
        jax.profiler.stop_trace()
        s = tr.summarize(
            out, 1e9,  # the slice's own length must replace this
            device_plane=lambda n: n == "/host:CPU",
            busy_line=lambda n: n.startswith("tf_XLAPjRtCpuClient"),
            host_plane=lambda n: n == "/host:CPU",
        )
    finally:
        shutil.rmtree(out, ignore_errors=True)
    check(s is not None, "the CPU trace holds XLA operations")
    print(f"    busy_s={s['busy_s']} window_s={s['window_s']} host window={window}"
          f" ops={s['device_ops'][:3]}")
    check(abs(s["window_s"] - window) < 0.01, "the window is the slice's own event")
    check(0 < s["busy_s"] < window - RUNS * SLEEP_S * 0.9,
          "busy time is above 0 and leaves out the sleeps")
    check(len(s["idle_gaps"]) >= RUNS, "every sleep in the slice shows as an idle gap")
    check(all(g[1] >= SLEEP_S * 0.9 for g in s["idle_gaps"][:RUNS]),
          "the longest gaps are the sleeps, the last one cut by the slice's end")
    secs = [v for _, v in s["device_ops"]]
    check(len(secs) <= tr.TOP and secs == sorted(secs, reverse=True),
          "the top operations are ranked by their time")
    dots = sum(v["count"] for n, v in s["modules"].items() if n.startswith("dot"))
    check(dots == 4 * RUNS, "the operations before and after the slice are left out")


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    arithmetic()
    recorded()
    print("check_trace: all checks passed")
