"""The executable store (lodestar_tpu/aot/exec_store.py) behind
``registry.call``: a program's compiled executable is written on its
first dispatch and loaded, with nothing traced or lowered, in a later
process.

A toy jitted function stands in for the BLS kernels, whose whole
pipelines XLA:CPU cannot compile inside tier 1.  Every test keeps JAX's
compilation cache, and so the store, under its own ``tmp_path``.
"""
import json
import os
import pickle
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from lodestar_tpu.aot import cache as aot_cache
from lodestar_tpu.aot import exec_store, registry, warm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOY_SRC = """
traces = []


def toy(a, pair):
    traces.append(a.shape)  # runs once per trace, never on a cache hit
    return a * 2 + pair[0] - pair[1]
"""


def fresh_toy():
    """A new ``toy`` function object, defined exactly as the subprocess
    below defines it: JAX's in-process caches know nothing of it.  Its
    ``traces`` global lists the traces of it."""
    namespace = {}
    exec(TOY_SRC, namespace)
    return namespace["toy"]


def toy_traces():
    return registry._KERNELS["toy"].__globals__["traces"]


def toy_args(n=8):
    return (
        np.arange(n, dtype=np.float32),
        (np.ones(n, np.float32), np.full(n, 3, np.float32)),
    )


def toy_expected(n=8):
    return np.arange(n, dtype=np.float32) * 2 - 2


@pytest.fixture
def events():
    """The ``exec_*`` events the store reports while the test runs."""
    seen = []

    def on_event(kind, key, seconds):
        if kind.startswith("exec_"):
            seen.append((kind, key))

    aot_cache.install_cache_spy(on_event)
    yield seen
    aot_cache.remove_cache_spy_callback(on_event)


@pytest.fixture
def store(tmp_path, monkeypatch):
    """A registry holding only the toy kernel, with nothing served yet,
    and JAX's compilation cache at ``tmp_path/cache``.  Yields the
    store's directory."""
    monkeypatch.setattr(registry, "_KERNELS", {"toy": fresh_toy()})
    monkeypatch.setattr(registry, "_JITTED", {})
    monkeypatch.setattr(registry, "_SERVED", {})
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_enabled = jax.config.jax_enable_compilation_cache
    cache_dir = tmp_path / "cache"
    aot_cache.configure(str(cache_dir))
    yield cache_dir / exec_store.STORE_DIR
    jax.config.update("jax_enable_compilation_cache", prev_enabled)
    aot_cache.configure(prev_dir)


def entries(directory):
    return sorted(os.listdir(directory)) if os.path.isdir(directory) else []


def kinds(events):
    return [kind for kind, _ in events]


# ---------------------------------------------------------------------------
# miss, put, then a hit in a fresh process
# ---------------------------------------------------------------------------

FRESH_PROCESS = TOY_SRC + """
import json, sys
import jax
import numpy as np

traced = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, seconds, **kw: traced.append([event, kw.get("fun_name", "")])
)
from lodestar_tpu.aot import cache as aot_cache, registry

aot_cache.configure(sys.argv[1])
registry.register_kernels(toy=toy)
seen = []
aot_cache.install_cache_spy(lambda kind, key, s: seen.append(kind))
n = 8
out = registry.call(
    "toy",
    np.arange(n, dtype=np.float32),
    (np.ones(n, np.float32), np.full(n, 3, np.float32)),
)
print(json.dumps({"out": np.asarray(out).tolist(), "events": seen, "traced": traced,
                  "toy_traces": len(traces)}))
"""


def test_host_and_device_arguments_share_a_program(store, events):
    registry.call("toy", *toy_args())
    on_device = jax.tree.map(jax.device_put, toy_args())
    out = registry.call("toy", *on_device)
    np.testing.assert_array_equal(np.asarray(out), toy_expected())
    assert kinds(events) == ["exec_miss", "exec_put"]
    assert len(registry._SERVED) == 1


def test_miss_put_then_hit_in_fresh_process(store, events):
    compiles = []

    def on_duration(event, seconds, **kw):
        if "toy" in kw.get("fun_name", "") and not event.endswith("jaxpr_trace_duration"):
            compiles.append(event.rsplit("/", 1)[-1])

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        out = registry.call("toy", *toy_args())
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    np.testing.assert_array_equal(np.asarray(out), toy_expected())
    # the miss traces, lowers and compiles once, as the jit call alone
    # does: serializing reuses JAX's in-memory caches
    assert toy_traces() == [(8,)]
    assert sorted(compiles) == ["backend_compile_duration", "jaxpr_to_mlir_module_duration"]
    assert kinds(events) == ["exec_miss", "exec_put"]
    key = events[0][1]
    assert key.startswith("jit_toy-") and events[1][1] == key
    assert entries(store) == [key]
    # a later call in this process is a lookup: nothing loads or compiles
    registry.call("toy", *toy_args())
    assert kinds(events) == ["exec_miss", "exec_put"]
    assert toy_traces() == [(8,)]

    proc = subprocess.run(
        [sys.executable, "-c", FRESH_PROCESS, str(store.parent)],
        capture_output=True, text=True, timeout=240, cwd=REPO,
        env={**os.environ, "PYTHONPATH": REPO},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["out"] == toy_expected().tolist()
    assert "exec_hit" in got["events"]
    assert not {"exec_miss", "exec_put", "exec_load_error"} & set(got["events"])
    toy_compiles = [
        event for event, fun in got["traced"]
        if "toy" in fun and (
            event.endswith("jaxpr_trace_duration")
            or event.endswith("jaxpr_to_mlir_module_duration")
        )
    ]
    assert toy_compiles == []
    assert got["toy_traces"] == 0


# ---------------------------------------------------------------------------
# the key
# ---------------------------------------------------------------------------


def _change_aval(monkeypatch):
    return toy_args(16)


def _change_source(monkeypatch):
    monkeypatch.setattr(warm, "source_fingerprint", lambda: "0" * 64)
    return toy_args()


def _change_opcache_env(monkeypatch):
    monkeypatch.setenv("LODESTAR_TPU_CPU_PARALLEL_FP", "1")
    return toy_args()


def _change_xla_flags(monkeypatch):
    monkeypatch.setenv(
        "XLA_FLAGS", os.environ.get("XLA_FLAGS", "") + " --xla_cpu_enable_fast_math=false"
    )
    return toy_args()


def _key(args):
    return exec_store.entry_key("jit_toy", *exec_store.signature(args))


@pytest.mark.parametrize(
    "change",
    [_change_aval, _change_source, _change_opcache_env, _change_xla_flags],
    ids=["aval", "source_fingerprint", "opcache_env", "xla_flags"],
)
def test_key_changes_with(change, monkeypatch):
    before = _key(toy_args())
    assert _key(toy_args()) == before
    assert _key(change(monkeypatch)) != before


def test_kernel_imports_are_fingerprinted():
    """Every module of this repo that importing the kernels loads is a
    source the store's key fingerprints: an edit anywhere else cannot
    change a compiled kernel."""
    code = (
        "import importlib, json, sys\n"
        "for m in ('verify', 'h2c', 'pallas_fp', 'sharded'):\n"
        "    importlib.import_module('lodestar_tpu.ops.bls12_381.' + m)\n"
        "print(json.dumps(sorted(\n"
        "    m.__file__ for n, m in sys.modules.items()\n"
        "    if n.split('.')[0] == 'lodestar_tpu' and getattr(m, '__file__', None))))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=240, cwd=REPO, env={**os.environ, "PYTHONPATH": REPO},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    loaded = {
        os.path.relpath(f, REPO) for f in json.loads(proc.stdout.splitlines()[-1])
    }
    assert "lodestar_tpu/ops/bls12_381/verify.py" in loaded
    assert loaded - set(warm.source_files()) == set()


# ---------------------------------------------------------------------------
# what an entry holds, and what a write removes
# ---------------------------------------------------------------------------


def test_entry_is_a_compressed_serialized_executable(store):
    from jax.experimental import serialize_executable

    registry.call("toy", *toy_args())
    (name,) = entries(store)
    entry = pickle.loads((store / name).read_bytes())
    assert entry["codec"] == ("zstd" if exec_store.zstandard else "zlib")
    payload = exec_store._decompress(entry["codec"], entry["executable"])
    assert len(entry["executable"]) < len(payload)
    devices = [d for d in jax.devices() if d.id in entry["devices"]]
    compiled = serialize_executable.deserialize_and_load(
        payload, entry["in_tree"], entry["out_tree"], execution_devices=devices
    )
    np.testing.assert_array_equal(np.asarray(compiled(*toy_args())), toy_expected())


def test_write_removes_the_programs_entries_of_other_environments(store, events, monkeypatch):
    registry.call("toy", *toy_args())
    registry.call("toy", *toy_args(16))
    old = entries(store)
    assert len(old) == 2
    # another program's entry of another environment is not this write's
    other = store / ("jit_other-" + "0" * 16 + "-" + "1" * 64)
    other.write_bytes(b"")

    monkeypatch.setattr(warm, "source_fingerprint", lambda: "0" * 64)
    registry._SERVED.clear()  # a new process start after a source change
    registry.call("toy", *toy_args())
    (new,) = set(entries(store)) - set(old) - {other.name}
    assert entries(store) == sorted([new, other.name])
    assert exec_store.holds(str(store.parent), new)
    assert not exec_store.holds(str(store.parent), old[0])


# ---------------------------------------------------------------------------
# ``aot warm`` writes the entry a served call loads
# ---------------------------------------------------------------------------


class ToyProg:
    """The toy as the warm tool sees a registered program."""

    kernel, bucket, key = "toy", 8, "toy/b8"

    def fn(self):
        return registry.jitted("toy")

    def fn_name(self):
        return "toy"

    def example_args(self):
        return toy_args()


def test_warm_tool_writes_the_served_entry(store, events):
    cache_dir = str(store.parent)
    warm.warm_programs([ToyProg()], cache_dir, min_compile_time_secs=0.0,
                       do_export=False, log=lambda m: None)
    assert kinds(events) == ["exec_put"]
    assert warm.check_programs([ToyProg()], cache_dir) == (True, [("toy/b8", "warm")])

    out = registry.call("toy", *toy_args())  # the node's first dispatch
    np.testing.assert_array_equal(np.asarray(out), toy_expected())
    assert kinds(events) == ["exec_put", "exec_hit"]

    # without the entry the node would trace again: not warm
    for name in entries(store):
        os.remove(store / name)
    assert warm.check_programs([ToyProg()], cache_dir) == (False, [("toy/b8", "missing")])


def _signed_sets(n):
    from lodestar_tpu.crypto.bls import api

    sets = []
    for i in range(n):
        sk = api.SecretKey.key_gen(bytes([i + 1]) * 32)
        msg = bytes([i]) * 32
        sets.append(api.SignatureSet(sk.to_public_key(), msg, sk.sign(msg)))
    return sets


@pytest.mark.parametrize("kernel", ["hashed", "each"])
def test_warm_example_args_have_the_served_signature(kernel, monkeypatch):
    """The warm tool's entry for a program is the one the pool's dispatch
    loads only if the example arguments select the same program."""
    from lodestar_tpu.ops.bls12_381 import verify as dv
    from lodestar_tpu.ops.bls12_381.buckets import bucket_size

    sets = _signed_sets(2)
    bucket = bucket_size(len(sets))
    if kernel == "hashed":
        monkeypatch.setenv("LODESTAR_TPU_DEVICE_H2C", "1")
        served = dv.encode_job(sets, bucket=bucket).args
    else:
        served = dv._encode_sets(sets, bucket)
    example = registry.Program(kernel, bucket).example_args()
    assert exec_store.signature(served) == exec_store.signature(example)


# ---------------------------------------------------------------------------
# a bad entry is a miss
# ---------------------------------------------------------------------------


def _truncate(path):
    data = path.read_bytes()
    path.write_bytes(data[: len(data) // 2])


def _garbage(path):
    path.write_bytes(b"not a pickle" * 8)


def _wrong_digest(path):
    entry = pickle.loads(path.read_bytes())
    entry["sha256"] = "0" * 64
    path.write_bytes(pickle.dumps(entry))


@pytest.mark.parametrize(
    "damage", [_truncate, _garbage, _wrong_digest],
    ids=["truncated", "garbage", "wrong_digest"],
)
def test_bad_entry_degrades_to_a_miss(damage, store, events):
    registry.call("toy", *toy_args())
    (name,) = entries(store)
    damage(store / name)

    registry._SERVED.clear()  # a new process start
    del events[:]
    out = registry.call("toy", *toy_args())
    np.testing.assert_array_equal(np.asarray(out), toy_expected())
    assert kinds(events) == ["exec_load_error", "exec_put"]

    # the entry was rewritten whole: the next process start loads it
    registry._SERVED.clear()  # a new process start
    del events[:]
    out = registry.call("toy", *toy_args())
    np.testing.assert_array_equal(np.asarray(out), toy_expected())
    assert kinds(events) == ["exec_hit"]
    assert entries(store) == [name]


def test_bad_entry_is_removed_when_it_cannot_be_rewritten(store, events, monkeypatch):
    registry.call("toy", *toy_args())
    (name,) = entries(store)
    _truncate(store / name)
    monkeypatch.setattr(exec_store, "_put", lambda path, key, compiled: None)

    registry._SERVED.clear()  # a new process start
    del events[:]
    out = registry.call("toy", *toy_args())
    np.testing.assert_array_equal(np.asarray(out), toy_expected())
    assert kinds(events) == ["exec_load_error"]
    assert entries(store) == []


# ---------------------------------------------------------------------------
# off where JAX has no compilation cache
# ---------------------------------------------------------------------------


def _no_cache_dir():
    jax.config.update("jax_compilation_cache_dir", None)


def _cache_disabled():
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.mark.parametrize(
    "switch_off", [_no_cache_dir, _cache_disabled], ids=["no_dir", "disabled"]
)
def test_off_without_a_compilation_cache(switch_off, store, events):
    switch_off()
    assert exec_store.store_dir() is None
    out = registry.call("toy", *toy_args())
    np.testing.assert_array_equal(np.asarray(out), toy_expected())
    assert events == []
    assert not store.exists()
    # the jit wrapper itself serves
    assert list(registry._SERVED.values()) == [registry.jitted("toy")]


# ---------------------------------------------------------------------------
# concurrency
# ---------------------------------------------------------------------------


def test_threads_on_first_dispatch_compile_once(store, events, monkeypatch):
    n_threads = 4
    barrier = threading.Barrier(n_threads)
    results, errors, entered = [], [], []
    load_or_compile = exec_store.load_or_compile

    def slow_load_or_compile(*a):
        # hold the first dispatch open long enough for every thread to
        # reach it
        entered.append(threading.get_ident())
        time.sleep(0.3)
        return load_or_compile(*a)

    monkeypatch.setattr(exec_store, "load_or_compile", slow_load_or_compile)

    def dispatch():
        try:
            barrier.wait()
            results.append(np.asarray(registry.call("toy", *toy_args())))
        except Exception as e:  # surfaced by the assertion below
            errors.append(e)

    threads = [threading.Thread(target=dispatch) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert errors == []
    assert len(results) == n_threads
    for r in results:
        np.testing.assert_array_equal(r, toy_expected())
    assert len(entered) == 1
    assert kinds(events) == ["exec_miss", "exec_put"]
    assert len(entries(store)) == 1


# ---------------------------------------------------------------------------
# the served verify calls dispatch through registry.call
# ---------------------------------------------------------------------------


@pytest.fixture
def recorded_dispatch(monkeypatch):
    """The store replaced by a recorder, so that nothing compiles: each
    entry is (program name, argument shapes)."""
    from lodestar_tpu.ops.bls12_381 import verify  # noqa: F401 (registers kernels)

    monkeypatch.setattr(registry, "_SERVED", {})
    calls = []

    def fake_load_or_compile(name, jitted, args, treedef, avals):
        def run(*a):
            calls.append((name, [tuple(x.shape) for x in jax.tree.leaves(a)]))
            width = a[-1].shape[0]
            return np.ones(width, dtype=bool) if name == "jit_verify_each" else np.True_

        return run

    monkeypatch.setattr(exec_store, "load_or_compile", fake_load_or_compile)
    return calls


@pytest.mark.parametrize(
    "kind, program",
    [("hashed", "jit_verify_signature_sets_hashed"),
     ("batch", "jit_verify_signature_sets")],
)
def test_execute_batch_dispatches_through_registry_call(kind, program, recorded_dispatch):
    from lodestar_tpu.ops.bls12_381 import verify as dv

    args = tuple(np.zeros((4, 2), np.uint32) for _ in range(7)) + (np.ones(4, bool),)
    assert dv.execute_batch(dv.EncodedJob(kind, 3, 4, args)) is True
    assert [name for name, _ in recorded_dispatch] == [program]
    # a rejected job never reaches the device
    assert dv.execute_batch(dv.EncodedJob("reject", 1, 0, None)) is False
    assert len(recorded_dispatch) == 1


def test_verify_each_device_dispatches_through_registry_call(recorded_dispatch, monkeypatch):
    from lodestar_tpu.ops.bls12_381 import verify as dv

    monkeypatch.setattr(
        dv, "_encode_sets",
        lambda sets, size: tuple(np.zeros((size, 2), np.uint32) for _ in range(6))
        + (np.ones(size, bool),),
    )
    assert dv.verify_each_device(["s1", "s2", "s3"], bucket=8) == [True] * 3
    assert recorded_dispatch == [("jit_verify_each", [(8, 2)] * 6 + [(8,)])]
