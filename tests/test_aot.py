"""AOT compile-lifecycle subsystem tests (ISSUE 5).

Covers the registry enumeration, the resumable warmer + freshness
manifest (staleness on source-hash change, per-program banking under a
budget), and the cache configure/spy plumbing — all with throwaway
TINY jit programs in tmp cache dirs, so nothing here compiles a
pairing kernel or touches the repo's real .jax_cache.
"""
import json
import os

import pytest

from lodestar_tpu.aot import cache as aot_cache
from lodestar_tpu.aot import registry, warm
from lodestar_tpu.ops.bls12_381 import buckets as bk


@pytest.fixture
def tmp_cache(tmp_path):
    """Point jax's persistent cache at a tmp dir; ALWAYS restore the
    repo cache afterwards (other test files rely on it)."""
    d = str(tmp_path / "cache")
    prev = aot_cache.repo_cache_dir()
    aot_cache.configure(d, min_compile_time_secs=0.0)
    yield d
    aot_cache.configure(prev)


class TinyProg:
    """warm.py duck-type of registry.Program with a millisecond-compile
    function (shape varies by bucket so each bucket is a new program)."""

    def __init__(self, kernel="tiny", bucket=4, salt=1.0):
        self.kernel = kernel
        self.bucket = bucket
        self.salt = salt

    @property
    def key(self):
        return f"{self.kernel}/b{self.bucket}"

    def fn(self):
        import jax

        salt = self.salt

        def tiny_kernel(x):
            return (x * salt).sum()

        return jax.jit(tiny_kernel)

    def fn_name(self):
        return "tiny_kernel"

    def example_args(self):
        import jax.numpy as jnp

        return (jnp.zeros((self.bucket,), jnp.float32),)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_core_covers_bench_and_pool(self):
        from lodestar_tpu.chain.bls import device_pool as dp

        keys = registry.registered_keys(device_h2c=False)
        # bench stages (device-h2c kernel, both stage widths)
        for b in registry.bench_buckets():
            assert f"hashed/b{b}" in keys
        # every pool dispatch rung up to the overload drain width
        drain = bk.align_down(dp.MAX_SIGNATURE_SETS_PER_JOB)
        for b in bk.POOL_BUCKETS:
            if b <= drain:
                assert f"batch/b{b}" in keys
        # the governed steady width itself must be a registered rung
        steady = dp.governed_steady_width()
        assert f"batch/b{steady}" in keys

    def test_full_scope_superset_includes_fallback(self):
        core = set(registry.registered_keys(device_h2c=False))
        full = set(registry.registered_keys("full", device_h2c=False))
        assert core < full
        assert any(k.startswith("each/") for k in full)
        # dedupe: one entry per key even though scopes overlap
        progs = registry.registered_programs("full", device_h2c=False)
        assert len(progs) == len({p.key for p in progs})

    def test_h2c_mode_selects_kernel(self):
        tpu_keys = registry.registered_keys(device_h2c=True)
        assert any(k.startswith("hashed/") for k in tpu_keys)
        assert not any(k.startswith("batch/") for k in tpu_keys)

    def test_jitted_is_memoized_shared_wrapper(self):
        from lodestar_tpu.ops.bls12_381 import verify as dv

        assert registry.jitted("batch") is registry.jitted("batch")
        # verify.py's historical module attributes ARE the registry objects
        assert dv._jit_batch is registry.jitted("batch")
        assert dv._jit_hashed is registry.jitted("hashed")
        with pytest.raises(KeyError):
            registry.jitted("nope")

    def test_jitted_before_verify_import_shares_wrapper(self):
        """jitted() called BEFORE ops/bls12_381/verify.py is imported
        must hand out the same wrapper verify.py's module attributes
        got: ensure_kernels() triggers the verify import, whose module
        body calls jitted() reentrantly — a second wrapper minted by
        the outer frame would silently split the trace cache by import
        order.  Needs a fresh process (this one already imported
        verify)."""
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        code = (
            "from lodestar_tpu.aot import registry\n"
            "w = registry.jitted('batch')\n"
            "import lodestar_tpu.ops.bls12_381.verify as dv\n"
            "assert dv._jit_batch is registry.jitted('batch')\n"
            "assert dv._jit_batch is w\n"
        )
        env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=240,
            env=env,
        )
        assert proc.returncode == 0, proc.stderr

    def test_bench_buckets_follow_env(self, monkeypatch):
        monkeypatch.setenv("BENCH_BATCH_MAX", "512")
        assert registry.bench_buckets() == [512]
        monkeypatch.setenv("BENCH_BATCH_MAX", "4096")
        assert registry.bench_buckets() == [1024, 4096]

    def test_sharded_programs_enumerable_with_mesh_keys(self):
        """ISSUE 19: the extracted sharded verify is registered as
        (kernel, bucket, mesh_size) entries so warm/--check cover it.
        The test env forces an 8-device virtual CPU mesh, so every
        supported geometry must enumerate; keys carry the @m suffix;
        example avals reuse the batch shapes in sharded.py arg order
        (active before bits)."""
        from lodestar_tpu.ops.bls12_381 import sharded

        full = registry.registered_programs("full", device_h2c=False)
        got = {(p.kernel, p.bucket, p.mesh_size) for p in full if p.mesh_size}
        want = {
            ("sharded", b, m)
            for b in sharded.SHARDED_BUCKETS
            for m in sharded.SUPPORTED_MESH_SIZES
        }
        assert got == want
        sh = [p for p in full if p.mesh_size]
        assert {p.key for p in sh} == {
            f"sharded/b{b}@m{m}" for (_, b, m) in want
        }
        assert all(p.fn_name() == "sharded_verify" for p in sh)
        # sharded entries are full-scope only (a cold sharded pairing
        # compile costs hours on XLA:CPU — docs/AOT.md)
        core = registry.registered_programs(device_h2c=False)
        assert not any(p.mesh_size for p in core)
        # example args: 8-tuple, bits last (sharded.py arg order)
        p = min(sh, key=lambda p: p.bucket)
        args = p.example_args()
        assert len(args) == 8
        assert args[6].dtype == bool and args[6].shape == (p.bucket,)


# ---------------------------------------------------------------------------
# warm + manifest
# ---------------------------------------------------------------------------


class TestWarm:
    def test_warm_then_check_roundtrip(self, tmp_cache):
        progs = [TinyProg(bucket=4), TinyProg(bucket=8)]
        report = warm.warm_programs(
            progs, tmp_cache, min_compile_time_secs=0.0, do_export=False,
            log=lambda m: None,
        )
        assert report["compiled"] == ["tiny/b4", "tiny/b8"]
        ok, rows = warm.check_programs(progs, tmp_cache)
        assert ok, rows
        # second run skips everything (resumable no-op)
        report2 = warm.warm_programs(
            progs, tmp_cache, min_compile_time_secs=0.0, do_export=False,
            log=lambda m: None,
        )
        assert report2["skipped"] == ["tiny/b4", "tiny/b8"]
        assert not report2["compiled"]

    def test_budget_banks_finished_programs(self, tmp_cache):
        """A warm run stopped by the budget must bank every finished
        program: the next invocation skips them and continues."""
        progs = [TinyProg(bucket=4), TinyProg(bucket=8), TinyProg(bucket=16)]
        report = warm.warm_programs(
            progs, tmp_cache, budget_s=0.0, min_compile_time_secs=0.0,
            do_export=False, log=lambda m: None,
        )
        # budget 0: the first program still runs (budget checks happen
        # BEFORE starting a program), the rest defer
        assert report["compiled"] == ["tiny/b4"]
        assert report["deferred"] == ["tiny/b8", "tiny/b16"]
        ok, rows = warm.check_programs(progs, tmp_cache)
        assert not ok
        assert dict(rows)["tiny/b4"] == "warm"
        # resume: only the deferred programs compile
        report2 = warm.warm_programs(
            progs, tmp_cache, min_compile_time_secs=0.0, do_export=False,
            log=lambda m: None,
        )
        assert report2["skipped"] == ["tiny/b4"]
        assert report2["compiled"] == ["tiny/b8", "tiny/b16"]

    def test_source_hash_change_goes_stale(self, tmp_cache, monkeypatch):
        """ISSUE 5 satellite: editing a kernel-relevant source must fail
        `warm --check` until re-warmed — never silently serve a manifest
        stamped for different code."""
        progs = [TinyProg(bucket=4)]
        warm.warm_programs(
            progs, tmp_cache, min_compile_time_secs=0.0, do_export=False,
            log=lambda m: None,
        )
        ok, _ = warm.check_programs(progs, tmp_cache)
        assert ok
        monkeypatch.setattr(warm, "source_fingerprint", lambda: "deadbeef")
        ok, rows = warm.check_programs(progs, tmp_cache)
        assert not ok
        assert dict(rows)["tiny/b4"] == "stale"
        # re-warm under the new fingerprint re-stamps the manifest (the
        # persistent cache itself is untouched, so this is a fast reload)
        report = warm.warm_programs(
            progs, tmp_cache, min_compile_time_secs=0.0, do_export=False,
            log=lambda m: None,
        )
        assert report["compiled"] == ["tiny/b4"]
        ok, _ = warm.check_programs(progs, tmp_cache)
        assert ok

    def test_missing_cache_entry_detected(self, tmp_cache):
        """A manifest entry whose on-disk cache files were lost (pruned
        LRU, copied tree) reports missing, not warm."""
        progs = [TinyProg(bucket=4)]
        warm.warm_programs(
            progs, tmp_cache, min_compile_time_secs=0.0, do_export=False,
            log=lambda m: None,
        )
        manifest = warm.load_manifest(tmp_cache)
        keys = manifest["entries"]["tiny/b4"].get("cache_keys") or []
        assert keys, "spy captured no cache keys for the warmed program"
        for k in keys:
            for suffix in ("", "-cache"):
                p = os.path.join(tmp_cache, k + suffix)
                if os.path.isfile(p):
                    os.unlink(p)
        ok, rows = warm.check_programs(progs, tmp_cache)
        assert not ok
        assert dict(rows)["tiny/b4"] == "missing"

    def test_manifest_atomic_and_schema_guard(self, tmp_cache):
        path = warm.manifest_path(tmp_cache)
        os.makedirs(tmp_cache, exist_ok=True)
        with open(path, "w") as fh:
            fh.write("{ truncated garbage")
        assert warm.load_manifest(tmp_cache) == {"schema": warm.SCHEMA, "entries": {}}
        with open(path, "w") as fh:
            json.dump({"schema": -1, "entries": {"x": {}}}, fh)
        assert warm.load_manifest(tmp_cache)["entries"] == {}


class TestBenchPlatformGate:
    """bench.py reports no chip number it did not take: off a TPU it
    exits non-zero before any stage and prints no JSON line."""

    @staticmethod
    def _bench():
        import importlib.util
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "bench", os.path.join(repo, "bench.py")
        )
        mod = importlib.util.module_from_spec(spec)
        sys.modules.setdefault("bench", mod)
        spec.loader.exec_module(mod)
        return mod

    def test_device_info_names_platform_kind_count(self):
        import jax

        info = self._bench().device_info()
        assert info == {
            "platform": "cpu",
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        }

    def test_require_tpu_refuses_cpu(self):
        with pytest.raises(SystemExit, match="no TPU"):
            self._bench().require_tpu()

    def test_main_on_cpu_exits_nonzero_without_a_result_line(self, capsys):
        with pytest.raises(SystemExit) as e:
            self._bench().main()
        assert e.value.code not in (0, None)
        assert "{" not in capsys.readouterr().out


# ---------------------------------------------------------------------------
# cache config + spy
# ---------------------------------------------------------------------------


class TestCacheConfig:
    def test_configure_points_jax_at_dir(self, tmp_cache):
        import jax

        assert jax.config.jax_compilation_cache_dir == tmp_cache

    @pytest.mark.parametrize("from_env", [True, False])
    def test_cache_dir_placed_by_jax_env_var(self, tmp_path, from_env):
        """With $JAX_COMPILATION_CACHE_DIR set, configure() leaves jax's
        cache where the variable puts it and writes nothing under the
        checkout's fixed dir (stood in for by a tmp path); without it,
        the dir is <checkout>/.jax_cache.  A fresh process: jax reads
        the variable when it starts."""
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env_dir, fixed = str(tmp_path / "from_env"), str(tmp_path / "fixed")
        code = f"""
import json, os, jax, jax.numpy as jnp
from lodestar_tpu.aot import cache
want_default = cache.DEFAULT_CACHE_DIR
cache.DEFAULT_CACHE_DIR = {fixed!r}
got = cache.configure(min_compile_time_secs=0.0)
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
print(json.dumps([got, jax.config.jax_compilation_cache_dir, want_default]))
"""
        env = {
            k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")
        }
        env.update(JAX_PLATFORMS="cpu", PYTHONPATH=repo)
        if from_env:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        proc = subprocess.run(
            [sys.executable, "-c", code], env=env, cwd=repo,
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        got, jax_dir, want_default = json.loads(proc.stdout.splitlines()[-1])
        assert want_default == os.path.join(repo, ".jax_cache")
        if from_env:
            assert got == jax_dir == env_dir
            assert os.listdir(env_dir)
            assert not os.path.exists(fixed)
        else:
            assert got == jax_dir == fixed

    def test_pin_cache_key_env(self):
        env = {"XLA_FLAGS": "--xla_whatever", "OTHER": "1"}
        aot_cache.pin_cache_key_env(env)
        assert "XLA_FLAGS" not in env
        assert env["OTHER"] == "1"

    def test_spy_counts_miss_then_hit(self, tmp_cache):
        """The persistent-cache spy must see a put+miss on first compile
        and a hit when a fresh trace reloads the same program."""
        events = []
        aot_cache.install_cache_spy(lambda *e: events.append(e))
        aot_cache.reset_stats()
        prog = TinyProg(bucket=32, salt=3.25)
        prog.fn()(*prog.example_args())  # compile -> miss + put
        stats = aot_cache.cache_stats()
        assert stats["misses"] >= 1
        assert stats["puts"] >= 1
        prog2 = TinyProg(bucket=32, salt=3.25)
        prog2.fn()(*prog2.example_args())  # fresh jit object -> cache hit
        assert aot_cache.cache_stats()["hits"] >= 1
        kinds = {e[0] for e in events}
        assert {"miss", "put", "hit"} <= kinds

    def test_spy_callback_removal(self):
        """remove_cache_spy_callback releases the callback (and its pool,
        in the DeviceBlsVerifier close() path) — events stop arriving."""
        events = []
        cb = lambda *e: events.append(e)  # noqa: E731
        aot_cache.install_cache_spy(cb)
        aot_cache._emit("hit", "k-spy-removal", 0.1)
        assert events
        aot_cache.remove_cache_spy_callback(cb)
        n = len(events)
        aot_cache._emit("hit", "k-spy-removal", 0.1)
        assert len(events) == n
        # removing twice is a no-op, not an error
        aot_cache.remove_cache_spy_callback(cb)

    def test_put_fault_leaves_cache_cold(self, tmp_cache):
        """aot.cache.put chaos: an injected write failure must not break
        compilation (jax absorbs it with a warning) but the entry is
        never persisted — a fresh identical trace misses, not hits."""
        import warnings

        from lodestar_tpu.testing import faults

        aot_cache.install_cache_spy()
        aot_cache.reset_stats()
        prog = TinyProg(bucket=16, salt=7.5)
        with faults.inject("aot.cache.put") as plan:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", UserWarning)
                prog.fn()(*prog.example_args())  # compiles despite the fault
        assert plan.fired >= 1
        assert aot_cache.cache_stats()["puts"] == 0
        faults.reset()
        aot_cache.reset_stats()
        prog2 = TinyProg(bucket=16, salt=7.5)
        prog2.fn()(*prog2.example_args())
        stats = aot_cache.cache_stats()
        assert stats["hits"] == 0, "a failed put must not leave an entry"
        assert stats["misses"] >= 1 and stats["puts"] >= 1

    def test_entry_exists_both_layouts(self, tmp_path):
        d = str(tmp_path)
        open(os.path.join(d, "k1-cache"), "w").close()
        open(os.path.join(d, "k2"), "w").close()
        assert aot_cache.entry_exists(d, "k1")
        assert aot_cache.entry_exists(d, "k2")
        assert not aot_cache.entry_exists(d, "k3")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


class TestCli:
    def test_check_empty_cache_fails(self, tmp_cache, capsys):
        from lodestar_tpu.aot.__main__ import main

        rc = main(["warm", "--check", "--json", "--cache-dir", tmp_cache])
        assert rc == 1
        out = json.loads(capsys.readouterr().out)
        assert out["ok"] is False
        assert len(out["programs"]) >= 5


# ---------------------------------------------------------------------------
# self-healing cache (ISSUE 7 tentpole b)
# ---------------------------------------------------------------------------


def _entry_file(cache_dir, key):
    paths = aot_cache.entry_paths(cache_dir, key)
    assert paths, f"no on-disk entry for {key}"
    return paths[0]


class TestCacheSelfHeal:
    def _warm_two(self, tmp_cache):
        progs = [TinyProg(bucket=4, salt=1.5), TinyProg(bucket=8, salt=1.5)]
        warm.warm_programs(
            progs, tmp_cache, min_compile_time_secs=0.0, do_export=False,
            log=lambda m: None,
        )
        manifest = warm.load_manifest(tmp_cache)
        for p in progs:
            keys = manifest["entries"][p.key]["cache_keys"]
            assert keys, f"no cache key captured for {p.key}"
            assert manifest["entries"][p.key]["entry_sha256"], (
                "no entry hash recorded at warm time"
            )
        return progs, manifest

    def test_corrupt_entry_check_fails_heal_quarantines_and_fixes(self, tmp_cache):
        """Acceptance: a synthetically corrupted entry is detected,
        quarantined with its bytes preserved, and `warm --check` fails
        before / passes after `warm --heal` — healthy entries
        untouched."""
        progs, manifest = self._warm_two(tmp_cache)
        victim, healthy = progs
        vkey = manifest["entries"][victim.key]["cache_keys"][0]
        hkey = manifest["entries"][healthy.key]["cache_keys"][0]
        vpath = _entry_file(tmp_cache, vkey)
        hpath = _entry_file(tmp_cache, hkey)
        healthy_bytes = open(hpath, "rb").read()

        # poison the victim's entry (truncate + garbage, like a killed
        # mid-write or bit-rotted 111 MB pairing entry)
        original = open(vpath, "rb").read()
        corrupt = original[: len(original) // 2] + b"\xde\xad\xbe\xef"
        with open(vpath, "wb") as fh:
            fh.write(corrupt)

        ok, rows = warm.check_programs(progs, tmp_cache)
        assert not ok, "--check trusted a corrupt entry"
        assert dict(rows)[victim.key] == "corrupt"
        assert dict(rows)[healthy.key] == "warm"

        report = warm.heal_programs(
            progs, tmp_cache, min_compile_time_secs=0.0, do_export=False,
            log=lambda m: None,
        )
        assert victim.key in report["healed"]
        assert healthy.key in report["healthy"]
        # the corrupt bytes are preserved in quarantine, never deleted
        qfiles = aot_cache.quarantined_files(tmp_cache)
        assert qfiles, "nothing quarantined"
        assert any(open(q, "rb").read() == corrupt for q in qfiles), (
            "quarantine did not preserve the corrupt bytes"
        )
        # healed: a fresh, loadable entry exists again under the key
        assert aot_cache.entry_exists(tmp_cache, vkey)
        assert open(_entry_file(tmp_cache, vkey), "rb").read() != corrupt
        # healthy entry untouched byte-for-byte
        assert open(hpath, "rb").read() == healthy_bytes
        ok, rows = warm.check_programs(progs, tmp_cache)
        assert ok, f"--check still failing after heal: {rows}"

    def test_spy_load_failure_quarantines_and_recompiles(self, tmp_cache):
        """End-to-end self-heal through the spy: an entry that EXISTS
        but fails deserialization (injected at the cache.get seam) is
        quarantined and transparently recompiled — jax's
        never-rewrites-a-failed-load-key behavior can no longer wedge a
        program (the five-round multichip failure mode)."""
        from lodestar_tpu.testing import faults

        prog = TinyProg(bucket=16, salt=7.25)
        aot_cache.install_cache_spy()
        prog.fn()(*prog.example_args())  # compile -> put on disk
        keys = [
            k for k, kind in aot_cache.observed_keys().items()
            if k.startswith("jit_tiny_kernel-")
        ]
        assert keys
        key = keys[-1]
        path_before = _entry_file(tmp_cache, key)
        errors_before = aot_cache.cache_stats()["load_errors"]
        try:
            # times=2: the spy retries a failed load once before
            # quarantining, so a poisoned entry fails BOTH attempts
            with faults.inject("aot.cache.get", times=2):
                # a FRESH jit object must consult the persistent cache
                TinyProg(bucket=16, salt=7.25).fn()(*prog.example_args())
        finally:
            faults.reset()
        assert aot_cache.cache_stats()["load_errors"] == errors_before + 1
        # the poisoned file moved to quarantine and a fresh entry was
        # rewritten under the same key (miss -> compile -> put)
        assert aot_cache.quarantined_files(tmp_cache)
        assert aot_cache.entry_exists(tmp_cache, key), (
            "failed-load key was not rewritten"
        )
        # and a third run loads clean (no new load errors)
        TinyProg(bucket=16, salt=7.25).fn()(*prog.example_args())
        assert aot_cache.cache_stats()["load_errors"] == errors_before + 1

    def test_self_heal_keeps_check_honest(self, tmp_cache):
        """An in-process self-heal (spy quarantine + recompile) must
        re-stamp the manifest's entry hash: the healed bytes need not
        match the warm-time fingerprint, and without the re-stamp the
        next `warm --check` would call the healthy healed entry
        corrupt — and `--heal` would re-pay the compile for nothing."""
        from lodestar_tpu.testing import faults

        progs, manifest = self._warm_two(tmp_cache)
        victim = progs[0]
        try:
            with faults.inject("aot.cache.get", times=2):
                # a fresh jit object consults the persistent cache; the
                # injected load failure (both attempts — the spy
                # retries once) triggers quarantine + recompile + put +
                # manifest hash re-stamp
                victim.fn()(*victim.example_args())
        finally:
            faults.reset()
        assert aot_cache.quarantined_files(tmp_cache), "self-heal did not fire"
        ok, rows = warm.check_programs(progs, tmp_cache)
        assert ok, f"--check distrusts the self-healed entry: {rows}"

    def test_transient_load_error_is_absorbed_without_quarantine(self, tmp_cache):
        """A ONE-off load failure (flaky disk read) is retried, not
        quarantined: evicting a healthy multi-minute entry over a
        transient I/O hiccup would be self-inflicted damage."""
        from lodestar_tpu.testing import faults

        prog = TinyProg(bucket=32, salt=9.5)
        aot_cache.install_cache_spy()
        prog.fn()(*prog.example_args())  # compile -> put on disk
        errors_before = aot_cache.cache_stats()["load_errors"]
        q_before = len(aot_cache.quarantined_files(tmp_cache))
        try:
            with faults.inject("aot.cache.get", times=1):  # fails ONCE
                TinyProg(bucket=32, salt=9.5).fn()(*prog.example_args())
        finally:
            faults.reset()
        assert aot_cache.cache_stats()["load_errors"] == errors_before
        assert len(aot_cache.quarantined_files(tmp_cache)) == q_before

    def test_check_without_hashes_skips_content_reads(self, tmp_cache):
        """The pool's startup freshness gauge uses check_hashes=False:
        corruption is invisible to it (that is --check/--heal's job),
        existence/freshness still is not."""
        progs, manifest = self._warm_two(tmp_cache)
        key = manifest["entries"][progs[0].key]["cache_keys"][0]
        with open(_entry_file(tmp_cache, key), "ab") as fh:
            fh.write(b"rot")
        ok, rows = warm.check_programs(progs, tmp_cache, check_hashes=False)
        assert ok, rows  # content rot not inspected on this path
        ok, rows = warm.check_programs(progs, tmp_cache)
        assert not ok and dict(rows)[progs[0].key] == "corrupt"

    def test_heal_respects_budget(self, tmp_cache):
        """--budget-s on heal mirrors warm: the first round-trip always
        runs, the rest defer for the next invocation."""
        progs = [TinyProg(bucket=4), TinyProg(bucket=8), TinyProg(bucket=16)]
        report = warm.heal_programs(
            progs, tmp_cache, budget_s=0.0, min_compile_time_secs=0.0,
            do_export=False, log=lambda m: None,
        )
        done = (
            report["healthy"] + report["healed"] + report["stale_rewarmed"]
        )
        assert done == ["tiny/b4"]
        assert report["deferred"] == ["tiny/b8", "tiny/b16"]

    def test_refresh_entry_hash_skips_when_warm_lock_held(self, tmp_cache):
        """The spy's manifest re-stamp must not race a live warm run:
        with .aot.lock held it skips instead of clobbering entries the
        warm run is banking."""
        import fcntl

        progs, manifest = self._warm_two(tmp_cache)
        key = manifest["entries"][progs[0].key]["cache_keys"][0]
        lock_fh = open(os.path.join(tmp_cache, ".aot.lock"), "w")
        try:
            fcntl.flock(lock_fh, fcntl.LOCK_EX | fcntl.LOCK_NB)
            assert warm.refresh_entry_hash(tmp_cache, key) is False
        finally:
            lock_fh.close()

    def test_heal_cli_flag(self, tmp_cache, capsys):
        from lodestar_tpu.aot.__main__ import main

        # --heal on an empty cache recompiles everything it can — use
        # --json to check the report shape without real kernels: the
        # registry's programs would compile for minutes, so instead
        # verify the flag parses and the lock path works by healing an
        # EMPTY program list via a monkeypatched registry
        import lodestar_tpu.aot.__main__ as cli_mod
        from lodestar_tpu.aot import registry as reg_mod

        orig = reg_mod.registered_programs
        reg_mod.registered_programs = lambda scope="core": []
        try:
            rc = main(["warm", "--heal", "--json", "--cache-dir", tmp_cache])
        finally:
            reg_mod.registered_programs = orig
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert set(out) >= {"healthy", "healed", "stale_rewarmed", "quarantined"}
