"""lodelint gate + per-rule fixture tests.

Two jobs:
  1. ``test_repo_is_clean`` runs the analyzer over the same paths as
     ``python -m tools.lint`` and fails tier-1 on any non-baselined
     finding — the standing static-analysis gate.
  2. Per-rule positive/negative fixtures, including one fixture per
     ADVICE-r5 satellite defect reproducing the exact pre-fix pattern,
     so the rules provably catch the bugs they were built from.

Pure AST work — no jax import, no compiles; belongs in the fast tier.
"""
import textwrap

from tools.lint import RULES, check_source, core


def lint(src: str, path: str = "lodestar_tpu/mod.py", rule: str = None):
    ids = [rule] if rule else None
    return check_source(textwrap.dedent(src), path, rule_ids=ids)


def rules_hit(src: str, path: str = "lodestar_tpu/mod.py"):
    return {f.rule for f in lint(src, path)}


def test_rule_catalog_size():
    # the analyzer ships a real rule set, not a stub
    assert len(RULES) >= 8, sorted(RULES)


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------


def test_repo_is_clean():
    findings, _ = core.run(core.DEFAULT_PATHS, baseline_path=core.DEFAULT_BASELINE)
    assert not findings, "lodelint findings (fix or baseline):\n" + "\n".join(
        f.render() for f in findings
    )


def test_every_test_file_is_tiered():
    """The quick tier is explicit opt-in (ADVICE r5): every test file must
    appear in exactly one of conftest's tier lists, so a compile-heavy new
    suite can't silently enter `-m fast`.  Enforced here as a normal test
    failure instead of a collection-time abort."""
    import os

    from tests import conftest as cf

    tiers = {
        "_KERNEL_FILES": cf._KERNEL_FILES,
        "_E2E_FILES": cf._E2E_FILES,
        "_PLAIN_FILES": cf._PLAIN_FILES,
        "_FAST_FILES": cf._FAST_FILES,
    }
    listed = [f for names in tiers.values() for f in names]
    dupes = {f for f in listed if listed.count(f) > 1}
    assert not dupes, f"test files in more than one tier list: {sorted(dupes)}"
    test_dir = os.path.join(core.REPO_ROOT, "tests")
    present = {
        f
        for f in os.listdir(test_dir)
        if f.startswith("test_") and f.endswith(".py")
    }
    unlisted = present - set(listed)
    assert not unlisted, (
        f"test file(s) not assigned a tier in tests/conftest.py: "
        f"{sorted(unlisted)} — add each to exactly one of "
        f"{'/'.join(tiers)} (fast is explicit opt-in)"
    )
    ghosts = set(listed) - present
    assert not ghosts, f"tier lists name missing files: {sorted(ghosts)}"


# ---------------------------------------------------------------------------
# async rules
# ---------------------------------------------------------------------------


def test_swallowed_cancel_positive():
    src = """
    import asyncio
    async def f():
        try:
            await g()
        except asyncio.CancelledError:
            pass
    """
    assert [f.rule for f in lint(src, rule="swallowed-cancel")]


def test_swallowed_cancel_positive_bare_except():
    src = """
    async def f():
        try:
            await g()
        except:
            pass
    """
    assert [f.rule for f in lint(src, rule="swallowed-cancel")]


def test_swallowed_cancel_negative_reraise():
    src = """
    import asyncio
    async def f():
        try:
            await g()
        except asyncio.CancelledError:
            raise
        except Exception:
            pass
    """
    assert not lint(src, rule="swallowed-cancel")


def test_swallowed_cancel_negative_reraise_bound_name():
    # `raise e` of the bound handler variable propagates cancellation too
    src = """
    import asyncio
    async def f():
        try:
            await g()
        except asyncio.CancelledError as e:
            cleanup()
            raise e
    """
    assert not lint(src, rule="swallowed-cancel")


def test_swallowed_cancel_negative_stop_idiom():
    # cancelling your own task and awaiting it is the one place
    # swallowing CancelledError is correct
    src = """
    import asyncio
    async def stop(self):
        self._task.cancel()
        try:
            await self._task
        except asyncio.CancelledError:
            pass
    """
    assert not lint(src, rule="swallowed-cancel")


def test_swallowed_cancel_negative_sync_def():
    src = """
    def f():
        try:
            g()
        except BaseException:
            pass
    """
    assert not lint(src, rule="swallowed-cancel")


def test_gather_exceptions_positive():
    src = """
    import asyncio
    async def f(aws):
        return await asyncio.gather(*aws)
    """
    assert [f.rule for f in lint(src, rule="gather-exceptions")]


def test_gather_exceptions_positive_explicit_false():
    # spelling out the default is still the hazard, not a mitigation
    src = """
    import asyncio
    async def f(aws):
        return await asyncio.gather(*aws, return_exceptions=False)
    """
    assert [f.rule for f in lint(src, rule="gather-exceptions")]


def test_gather_exceptions_negative():
    src = """
    import asyncio
    async def f(aws):
        return await asyncio.gather(*aws, return_exceptions=True)
    async def g(a):
        return await asyncio.gather(a)  # no fan-out, nothing to detach
    """
    assert not lint(src, rule="gather-exceptions")


def test_task_no_ref_positive():
    src = """
    import asyncio
    def f(coro):
        asyncio.create_task(coro)
    """
    assert [f.rule for f in lint(src, rule="task-no-ref")]


def test_task_no_ref_negative():
    src = """
    import asyncio
    def f(self, coro):
        task = asyncio.ensure_future(coro)
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)
    """
    assert not lint(src, rule="task-no-ref")


def test_blocking_async_positive():
    src = """
    import time
    async def f():
        time.sleep(1.0)
    """
    assert [f.rule for f in lint(src, rule="blocking-async")]


def test_blocking_async_positive_from_import_and_alias():
    src = """
    from time import sleep
    import requests as rq
    async def f():
        sleep(1.0)
        rq.get("http://x")
    """
    assert len(lint(src, rule="blocking-async")) == 2


def test_blocking_async_negative():
    src = """
    import asyncio, time
    async def f():
        await asyncio.sleep(1.0)
    def g():
        time.sleep(1.0)  # sync context: fine
    """
    assert not lint(src, rule="blocking-async")


# ---------------------------------------------------------------------------
# jax rules
# ---------------------------------------------------------------------------


def test_jit_in_func_positive():
    src = """
    import jax
    def f(x):
        g = jax.jit(h)
        return g(x)
    """
    assert [f.rule for f in lint(src, rule="jit-in-func")]


def test_jit_in_func_positive_partial_in_loop():
    src = """
    import jax
    from functools import partial
    for cfg in configs:
        fns.append(partial(jax.jit, static_argnums=(0,))(h))
    """
    assert [f.rule for f in lint(src, rule="jit-in-func")]


def test_jit_in_func_negative_module_level_and_memo():
    src = """
    import jax
    from functools import lru_cache
    g = jax.jit(h)
    @lru_cache(maxsize=None)
    def factory(n):
        return jax.jit(make_kernel(n))
    """
    assert not lint(src, rule="jit-in-func")


def test_jit_in_func_negative_in_tests_dir():
    src = """
    import jax
    def test_kernel():
        g = jax.jit(h)
    """
    assert not lint(src, path="tests/test_kernel.py", rule="jit-in-func")


def test_unregistered_jit_positive_module_scope():
    # the exact pre-ISSUE-5 pattern from ops/bls12_381/verify.py: ad-hoc
    # module-level jit closures the warm tool can't enumerate
    src = """
    import jax
    _jit_batch = jax.jit(verify_signature_sets)
    """
    assert [f.rule for f in lint(src, rule="unregistered-jit")]


def test_unregistered_jit_positive_decorator():
    src = """
    import jax
    @jax.jit
    def kernel(x):
        return x
    """
    assert [f.rule for f in lint(src, rule="unregistered-jit")]


def test_unregistered_jit_negative_registry_and_scope():
    src = """
    import jax
    _jit = jax.jit(fn)
    """
    # the registry itself is the one allowed construction site
    assert not lint(
        src, path="lodestar_tpu/aot/registry.py", rule="unregistered-jit"
    )
    # outside lodestar_tpu/ (tools, tests, bench) is out of scope
    assert not lint(src, path="tools/probe.py", rule="unregistered-jit")
    assert not lint(src, path="tests/test_x.py", rule="unregistered-jit")


def test_unregistered_jit_negative_in_function():
    # in-function construction is jit-in-func's finding, not this rule's
    src = """
    import jax
    import functools
    @functools.lru_cache(maxsize=None)
    def jitted(kernel):
        return jax.jit(KERNELS[kernel])
    """
    assert not lint(src, rule="unregistered-jit")


def test_static_unhashable_positive():
    src = """
    import jax
    f = jax.jit(g, static_argnums=(1,))
    f(x, [1, 2])
    """
    assert [f.rule for f in lint(src, rule="static-unhashable")]


def test_static_unhashable_positive_argnames():
    src = """
    import jax
    from functools import partial
    @partial(jax.jit, static_argnames=("shape",))
    def g(x, shape):
        return x
    g(x, shape=[8, 8])
    """
    assert [f.rule for f in lint(src, rule="static-unhashable")]


def test_static_unhashable_negative():
    src = """
    import jax
    f = jax.jit(g, static_argnums=(1,))
    f(x, (1, 2))
    f(y, n)
    """
    assert not lint(src, rule="static-unhashable")


HOT = "lodestar_tpu/ops/bls12_381/mod.py"


def test_host_sync_positive():
    src = """
    import jax.numpy as jnp
    def f(x):
        out = jnp.dot(x, x)
        return float(out)
    """
    assert [f.rule for f in lint(src, path=HOT, rule="host-sync")]


def test_host_sync_positive_tolist():
    src = """
    def f(x):
        return x.tolist()
    """
    assert [f.rule for f in lint(src, path=HOT, rule="host-sync")]


def test_host_sync_negative_on_device():
    src = """
    import jax.numpy as jnp
    def f(x):
        out = jnp.dot(x, x)
        return out
    def g(n):
        return int(n) + 1  # host int, not a device value
    """
    assert not lint(src, path=HOT, rule="host-sync")


def test_host_sync_negative_outside_hot_path():
    src = """
    import jax.numpy as jnp
    def f(x):
        out = jnp.dot(x, x)
        return float(out)
    """
    assert not lint(src, path="lodestar_tpu/cli/main.py", rule="host-sync")


def test_bench_sync_positive():
    src = """
    import time
    import jax.numpy as jnp
    def timed(x):
        t0 = time.perf_counter()
        out = jnp.dot(x, x)
        return time.perf_counter() - t0
    """
    assert [f.rule for f in lint(src, path="bench_kernels.py", rule="bench-sync")]


def test_bench_sync_negative():
    src = """
    import time
    import jax.numpy as jnp
    def timed(x):
        t0 = time.perf_counter()
        out = jnp.dot(x, x)
        out.block_until_ready()
        return time.perf_counter() - t0
    """
    assert not lint(src, path="bench_kernels.py", rule="bench-sync")


# ---------------------------------------------------------------------------
# repo-process rules (each fixture reproduces an ADVICE-r5 defect pre-fix)
# ---------------------------------------------------------------------------


def test_fast_tier_default_positive_conftest_r5():
    # tests/conftest.py:109 pre-fix: unlisted files fell through to fast
    src = """
    def pytest_collection_modifyitems(config, items):
        for item in items:
            name = basename(item)
            if name in _KERNEL_FILES:
                item.add_marker(pytest.mark.kernel)
            elif name in _E2E_FILES:
                item.add_marker(pytest.mark.e2e)
            elif name not in _SLOW_FILES:
                item.add_marker(pytest.mark.fast)
    """
    assert [f.rule for f in lint(src, rule="fast-tier-default")]


def test_fast_tier_default_positive_unconditional():
    # the limiting case of the fallthrough hazard: no governing If at all
    src = """
    def pytest_collection_modifyitems(config, items):
        for item in items:
            item.add_marker(pytest.mark.fast)
    """
    assert [f.rule for f in lint(src, rule="fast-tier-default")]


def test_fast_tier_default_positive_nested_if_under_else():
    # hiding the marking behind an inner `if` inside a bare else is still
    # the fallthrough hazard
    src = """
    def pytest_collection_modifyitems(config, items):
        for item in items:
            name = basename(item)
            if name in _KERNEL_FILES:
                item.add_marker(pytest.mark.kernel)
            else:
                if name.endswith(".py"):
                    item.add_marker(pytest.mark.fast)
    """
    assert [f.rule for f in lint(src, rule="fast-tier-default")]


def test_fast_tier_default_negative_explicit_opt_in():
    src = """
    def pytest_collection_modifyitems(config, items):
        for item in items:
            name = basename(item)
            if name in _KERNEL_FILES:
                item.add_marker(pytest.mark.kernel)
            elif name in _FAST_FILES:
                item.add_marker(pytest.mark.fast)
    """
    assert not lint(src, rule="fast-tier-default")


def test_min_min_sub_positive_bench_stf_r5():
    # bench_stf.py:290 pre-fix: htr_ms = min(e2e) - min(stf), negative-able
    src = """
    epoch_s = min(stf_times)
    epoch_e2e_s = min(e2e_times)
    htr_ms = round((epoch_e2e_s - epoch_s) * 1e3, 1)
    """
    assert [f.rule for f in lint(src, rule="min-min-sub")]


def test_min_min_sub_negative_direct_timing():
    src = """
    htr_times.append(t2 - t1)
    htr_ms = round(min(htr_times) * 1e3, 1)
    clamped = max(0.0, target - now)
    """
    assert not lint(src, rule="min-min-sub")


def test_min_min_sub_negative_same_list_spread():
    # spread/jitter over ONE sample list mixes no iterations
    src = """
    spread = max(times) - min(times)
    lo = min(times)
    hi = max(times)
    jitter = hi - lo
    """
    assert not lint(src, rule="min-min-sub")


def test_rc_sign_test_positive_graft_r5():
    # __graft_entry__.py:256 pre-fix: any rc<0 signal death rode the
    # segfault fallback; the rc>0 branch is the telltale sign test
    src = """
    rc = proc.returncode
    if rc is not None and rc > 0:
        raise RuntimeError(f"dryrun subprocess failed rc={rc}")
    if rc is not None:
        fallback()
    """
    assert [f.rule for f in lint(src, rule="rc-sign-test")]


def test_rc_sign_test_negative_signal_set():
    src = """
    rc = proc.returncode
    if rc == 0:
        return
    if rc is not None and -rc not in FALLBACK_SIGNALS:
        raise RuntimeError("unexpected failure class")
    """
    assert not lint(src, rule="rc-sign-test")


def test_satellite_header_tracker_pattern_r5():
    # chain_header_tracker.py:46 pre-fix: one-shot SSE subscription with
    # a broad except swallowing CancelledError alongside Exception
    src = """
    import asyncio
    class ChainHeaderTracker:
        async def _run(self):
            try:
                async with self._session.get(self.base_url) as resp:
                    async for raw in resp.content:
                        self.head_slot = int(raw)
            except (asyncio.CancelledError, Exception):
                pass  # tracker is best-effort
    """
    assert [f.rule for f in lint(src, rule="swallowed-cancel")]


def test_satellite_device_pool_pattern_r5():
    # device_pool.py:108 pre-fix: chunked wide request gathered without
    # return_exceptions — a failed chunk detached its siblings
    src = """
    import asyncio
    class DeviceBlsVerifier:
        async def verify_signature_sets(self, sets, cap):
            chunks = [list(sets[i : i + cap]) for i in range(0, len(sets), cap)]
            results = await asyncio.gather(*(self._enqueue(c) for c in chunks))
            return all(results)
    """
    assert [f.rule for f in lint(src, rule="gather-exceptions")]


# ---------------------------------------------------------------------------
# framework mechanics
# ---------------------------------------------------------------------------


def test_inline_suppression():
    src = """
    import asyncio
    def f(coro):
        asyncio.create_task(coro)  # lodelint: disable=task-no-ref
    """
    assert not lint(src, rule="task-no-ref")


def test_file_suppression():
    src = """
    # lodelint: disable-file=task-no-ref
    import asyncio
    def f(coro):
        asyncio.create_task(coro)
    def g(coro):
        asyncio.create_task(coro)
    """
    assert not lint(src, rule="task-no-ref")


def test_suppression_is_rule_specific():
    src = """
    import asyncio
    def f(coro):
        asyncio.create_task(coro)  # lodelint: disable=gather-exceptions
    """
    assert [f.rule for f in lint(src, rule="task-no-ref")]


def test_suppression_in_string_literal_is_inert():
    # a directive spelled inside a string (e.g. THIS test file's fixtures)
    # must not disable the rule for the real enclosing file
    src = '''
    import asyncio
    FIXTURE = """
    # lodelint: disable-file=task-no-ref
    """
    def f(coro):
        asyncio.create_task(coro)
    '''
    assert [f.rule for f in lint(src, rule="task-no-ref")]


def test_missing_lint_path_errors():
    import pytest

    with pytest.raises(FileNotFoundError):
        list(core.iter_py_files(["no_such_dir_xyz"]))
    with pytest.raises(FileNotFoundError):
        list(core.iter_py_files(["README.md"]))  # exists, not a .py file


def test_empty_dir_lint_path_errors(tmp_path):
    # a dir that EXISTS but holds no .py files (sources moved out) must
    # not lint nothing and stay green
    import pytest

    (tmp_path / "notes.txt").write_text("no python here")
    with pytest.raises(FileNotFoundError):
        list(core.iter_py_files([str(tmp_path)]))


def test_scoped_write_baseline_keeps_out_of_scope_entries(tmp_path):
    bl = tmp_path / "baseline.json"
    old_a = core.Finding(path="a.py", line=1, col=0, rule="task-no-ref", message="m")
    old_b = core.Finding(path="b.py", line=2, col=0, rule="host-sync", message="m")
    core.write_baseline([old_a, old_b], str(bl))
    # regenerating with scope {a.py} (now clean) must not discard b.py
    keep = {
        key: n for key, n in core.load_baseline(str(bl)).items() if key[0] != "a.py"
    }
    core.write_baseline([], str(bl), keep=keep)
    assert core.load_baseline(str(bl)) == {("b.py", "host-sync"): 1}


def test_parse_error_is_a_finding():
    findings = lint("def broken(:\n", rule=None)
    assert [f.rule for f in findings] == ["parse-error"]


def test_baseline_roundtrip(tmp_path):
    f1 = core.Finding(path="a.py", line=3, col=0, rule="task-no-ref", message="m")
    f2 = core.Finding(path="a.py", line=9, col=0, rule="task-no-ref", message="m")
    bl = tmp_path / "baseline.json"
    core.write_baseline([f1], str(bl))
    budget = core.load_baseline(str(bl))
    assert budget == {("a.py", "task-no-ref"): 1}
    # one is grandfathered, the second of the same (path, rule) still fails
    fresh = []
    for f in sorted([f1, f2]):
        key = (f.path, f.rule)
        if budget.get(key, 0) > 0:
            budget[key] -= 1
        else:
            fresh.append(f)
    assert fresh == [f2]


def test_docs_list_every_rule():
    import os

    docs = os.path.join(core.REPO_ROOT, "docs", "LINT.md")
    with open(docs, "r", encoding="utf-8") as fh:
        text = fh.read()
    missing = [r for r in RULES if f"`{r}`" not in text]
    assert not missing, f"docs/LINT.md missing rule(s): {missing}"


# ---------------------------------------------------------------------------
# interprocedural rules (callgraph + effects; ISSUE 4)
# ---------------------------------------------------------------------------

from tools.lint import callgraph, effects  # noqa: E402


def test_transitive_blocking_positive_deep_chain():
    # the defect class per-file blocking-async cannot see: the primitive
    # sits two calls below the async def
    src = """
    import time
    async def f():
        helper()
    def helper():
        inner()
    def inner():
        time.sleep(1)
    """
    fs = lint(src, rule="transitive-blocking")
    assert [f.rule for f in fs] == ["transitive-blocking"]
    # the finding carries the full chain down to the primitive
    assert len(fs[0].chain) == 3
    assert "time.sleep" in fs[0].chain[-1]
    assert fs[0].effects == ("blocks",)


def test_transitive_blocking_negative_executor_and_clean():
    # passing the helper INTO run_in_executor is the fix, not a call edge;
    # a clean helper chain has no effect to inherit
    src = """
    import asyncio, time
    def blocking():
        time.sleep(1)
    async def ok():
        await asyncio.get_running_loop().run_in_executor(None, blocking)
    async def ok2():
        pure()
    def pure():
        return 1
    """
    assert not lint(src, rule="transitive-blocking")


def test_transitive_blocking_negative_direct_is_per_file_territory():
    # a DIRECT blocking call in the async def belongs to blocking-async
    src = """
    import time
    async def f():
        time.sleep(1)
    """
    assert not lint(src, rule="transitive-blocking")
    assert lint(src, rule="blocking-async")


def test_transitive_blocking_threading_lock_root():
    # the db/controller.py shape: async path -> sync helper that takes a
    # threading.Lock (contended, it parks the whole loop)
    src = """
    import threading
    class Store:
        def __init__(self):
            self._lock = threading.Lock()
        def put(self, k, v):
            with self._lock:
                pass
    class Svc:
        def __init__(self):
            self.store = Store()
        async def handle(self):
            self.store.put(b"k", b"v")
    """
    fs = lint(src, rule="transitive-blocking")
    assert [f.rule for f in fs] == ["transitive-blocking"]
    assert "threading lock" in fs[0].chain[-1]


def test_transitive_blocking_root_suppression_quiets_all_callers():
    # suppressing at the ROOT effect site (the reviewed exception) keeps
    # every transitive caller quiet — the db/controller.py pattern
    src = """
    import time
    async def f():
        helper()
    async def g():
        helper()
    def helper():
        time.sleep(1)  # lodelint: disable=transitive-blocking
    """
    assert not lint(src, rule="transitive-blocking")


def test_transitive_host_sync_positive_cross_file():
    # hot-path entry reaches a .tolist() living in a util module: the
    # stall per-file host-sync cannot see (it only scans hot files)
    hot = callgraph.summary_for_source(
        textwrap.dedent(
            """
            from lodestar_tpu.helpers import pull
            def verify(x):
                return pull(x)
            """
        ),
        "lodestar_tpu/ops/bls12_381/fixture_verify.py",
    )
    util = callgraph.summary_for_source(
        textwrap.dedent(
            """
            def pull(x):
                return x.tolist()
            """
        ),
        "lodestar_tpu/helpers_fixture.py",
    )
    # import target must match the util module name
    hot["imports"]["pull"] = "lodestar_tpu.helpers_fixture.pull"
    project = callgraph.build_project([hot, util])
    fs = RULES["transitive-host-sync"].check_project(project)
    assert [f.rule for f in fs] == ["transitive-host-sync"]
    assert "tolist" in fs[0].chain[-1]
    assert fs[0].path.startswith("lodestar_tpu/ops/")


def test_transitive_host_sync_negative_outside_hot_path():
    # the same chain from a non-hot entry point is not a finding
    src = """
    def caller(x):
        return pull(x)
    def pull(x):
        return x.tolist()
    """
    assert not lint(src, path="lodestar_tpu/cli/main_fixture.py",
                    rule="transitive-host-sync")


def test_await_in_critical_positive_lost_update():
    src = """
    async def f(self):
        v = self.count
        await g()
        self.count = v + 1
    """
    fs = lint(src, rule="await-in-critical")
    assert [f.rule for f in fs] == ["await-in-critical"]


def test_await_in_critical_negative_locked_and_reset():
    # an asyncio.Lock held across the sequence guards it; writing a bare
    # constant (flag reset) is idempotent, not a lost update
    src = """
    async def guarded(self):
        async with self._lock:
            v = self.count
            await g()
            self.count = v + 1
    async def reset(self):
        if self.count:
            await g()
        self.count = None
    async def no_await_between(self):
        v = self.count
        self.count = v + 1
        await g()
    """
    assert not lint(src, rule="await-in-critical")


def test_await_in_critical_negative_exclusive_branches():
    # read and write sit in opposite arms of the same if: they never run
    # in the same call, so positional order alone is not a race
    src = """
    async def f(self, cond):
        if cond:
            v = self.count
            return v
        else:
            await g()
            self.count = compute()
    """
    assert not lint(src, rule="await-in-critical")


def test_await_in_critical_positive_check_then_act_in_if_test():
    # the read sits in the `if` TEST, which executes together with the
    # taken arm — it is not an exclusive branch, and check-then-act
    # across an await is the rule's flagship race (double-init /
    # double-decrement when two tasks pass the check before either
    # writes)
    init = """
    async def f(self):
        if self.conn is None:
            self.conn = await connect()
    """
    fs = lint(init, rule="await-in-critical")
    assert [f.rule for f in fs] == ["await-in-critical"]
    decrement = """
    async def f(self):
        if self.count > 0:
            await h()
            self.count = self.count - 1
    """
    fs = lint(decrement, rule="await-in-critical")
    assert [f.rule for f in fs] == ["await-in-critical"]


def test_await_in_critical_positive_blockish_with_is_not_a_guard():
    # 'block' embeds 'lock': an async with over a non-lock resource must
    # not silently suppress a real read->await->write race
    src = """
    async def f(self):
        async with self.block_fetcher.session():
            v = self.count
            await g()
            self.count = v + 1
    """
    fs = lint(src, rule="await-in-critical")
    assert [f.rule for f in fs] == ["await-in-critical"]


def test_lock_discipline_positive_bare_acquire():
    src = """
    import threading
    _lock = threading.Lock()
    def bad():
        _lock.acquire()
        work()
        _lock.release()
    """
    fs = lint(src, rule="lock-discipline")
    assert [f.rule for f in fs] == ["lock-discipline"]


def test_lock_discipline_positive_threading_lock_in_async():
    src = """
    import threading
    class S:
        def __init__(self):
            self._lock = threading.Lock()
        async def f(self):
            with self._lock:
                await g()
    """
    fs = lint(src, rule="lock-discipline")
    assert len(fs) == 1 and "across an await" in fs[0].message


def test_lock_discipline_negative_try_finally_and_sync_with():
    src = """
    import threading
    _lock = threading.Lock()
    def good():
        _lock.acquire()
        try:
            work()
        finally:
            _lock.release()
    def also_good():
        with _lock:
            work()
    """
    assert not lint(src, rule="lock-discipline")


def test_lock_discipline_name_heuristic_word_boundary():
    # 'block' embeds 'lock': a .acquire() on a block-named non-lock is
    # not flagged, while genuinely lock-named objects still are
    src = """
    def not_a_lock(self):
        self.block_writer.acquire()
        self.block_writer.release()
    def real_lock(self):
        self.db_lock.acquire()
        work()
        self.db_lock.release()
    """
    fs = lint(src, rule="lock-discipline")
    assert len(fs) == 1 and "db_lock" in fs[0].message


def test_unawaited_coro_positive():
    src = """
    async def g():
        pass
    def caller():
        g()
    """
    fs = lint(src, rule="unawaited-coro")
    assert [f.rule for f in fs] == ["unawaited-coro"]


def test_unawaited_coro_negative_awaited_scheduled_returned():
    src = """
    import asyncio
    async def g():
        pass
    async def ok():
        await g()
    def ok2():
        return asyncio.create_task(g())
    async def ok3(aws):
        await asyncio.gather(g(), g(), return_exceptions=True)
    def ok4():
        coro = g()
        return coro
    """
    assert not lint(src, rule="unawaited-coro")


# ---------------------------------------------------------------------------
# call graph unit tests: resolution + fixpoint mechanics
# ---------------------------------------------------------------------------


def _project_of(src: str, path: str = "lodestar_tpu/mod.py"):
    summary = callgraph.summary_for_source(textwrap.dedent(src), path)
    assert summary is not None
    return callgraph.build_project([summary])


def test_callgraph_cycle_terminates_and_propagates():
    # a <-> b recursion: the fixpoint must terminate and both functions
    # inherit the blocking effect of the primitive below the cycle
    src = """
    import time
    def a(n):
        b(n)
    def b(n):
        a(n - 1)
        leaf()
    def leaf():
        time.sleep(1)
    """
    p = _project_of(src)
    assert "blocks" in p.inherited["lodestar_tpu.mod:a"]
    assert "blocks" in p.inherited["lodestar_tpu.mod:b"]
    # chain reconstruction is cycle-guarded too
    chain = effects.chain_for(p, "lodestar_tpu.mod:a", "blocks")
    assert "time.sleep" in chain[-1]


def test_callgraph_method_dispatch_via_self():
    src = """
    import time
    class Svc:
        def outer(self):
            self.inner()
        def inner(self):
            time.sleep(1)
    """
    p = _project_of(src)
    edges = {e.callee for e in p.funcs["lodestar_tpu.mod:Svc.outer"].edges}
    assert "lodestar_tpu.mod:Svc.inner" in edges
    assert "blocks" in p.inherited["lodestar_tpu.mod:Svc.outer"]


def test_callgraph_method_dispatch_via_base_class():
    src = """
    import time
    class Base:
        def slow(self):
            time.sleep(1)
    class Child(Base):
        def run(self):
            self.slow()
    """
    p = _project_of(src)
    edges = {e.callee for e in p.funcs["lodestar_tpu.mod:Child.run"].edges}
    assert "lodestar_tpu.mod:Base.slow" in edges


def test_callgraph_alias_import_cross_module():
    a = callgraph.summary_for_source(
        textwrap.dedent(
            """
            from lodestar_tpu.other_fixture import slow as quick
            async def f():
                quick()
            """
        ),
        "lodestar_tpu/caller_fixture.py",
    )
    b = callgraph.summary_for_source(
        textwrap.dedent(
            """
            import time
            def slow():
                time.sleep(1)
            """
        ),
        "lodestar_tpu/other_fixture.py",
    )
    p = callgraph.build_project([a, b])
    edges = {
        e.callee for e in p.funcs["lodestar_tpu.caller_fixture:f"].edges
    }
    assert "lodestar_tpu.other_fixture:slow" in edges
    assert "blocks" in p.inherited["lodestar_tpu.caller_fixture:f"]


def test_callgraph_protocol_dispatch():
    # a call through a Protocol-typed attribute fans out to concrete
    # implementations (the Repository -> KvController -> Sqlite shape)
    src = """
    import threading
    from typing import Protocol
    class Kv(Protocol):
        def put(self, k, v): ...
    class Mem:
        def put(self, k, v):
            pass
    class Sql:
        def __init__(self):
            self._lock = threading.Lock()
        def put(self, k, v):
            with self._lock:
                pass
    class Repo:
        def __init__(self, db: Kv):
            self.db = db
        def put(self, k, v):
            self.db.put(k, v)
    """
    p = _project_of(src)
    edges = {e.callee for e in p.funcs["lodestar_tpu.mod:Repo.put"].edges}
    assert "lodestar_tpu.mod:Mem.put" in edges
    assert "lodestar_tpu.mod:Sql.put" in edges
    assert "blocks" in p.inherited["lodestar_tpu.mod:Repo.put"]


def test_callgraph_nested_def_is_its_own_node():
    # a nested def handed to run_in_executor must NOT leak its blocking
    # effect into the enclosing async def (the chain.py run_stf shape)
    src = """
    import asyncio, time
    async def f():
        def work():
            time.sleep(1)
        await asyncio.get_running_loop().run_in_executor(None, work)
    """
    p = _project_of(src)
    assert "blocks" in p.funcs["lodestar_tpu.mod:f.work"].effects
    assert "blocks" not in p.inherited["lodestar_tpu.mod:f"]
    assert "blocks" not in p.funcs["lodestar_tpu.mod:f"].effects


def test_effects_direct_inference_vocabulary():
    src = """
    import threading
    class S:
        def __init__(self):
            self._lock = threading.Lock()
        async def f(self):
            await g()
            self.state = compute()
        def h(self):
            with self._lock:
                pass
    """
    p = _project_of(src)
    f = p.funcs["lodestar_tpu.mod:S.f"]
    assert "awaits" in f.effects and "mutates-shared" in f.effects
    h = p.funcs["lodestar_tpu.mod:S.h"]
    assert "blocks" in h.effects and "acquires-lock" in h.effects


# ---------------------------------------------------------------------------
# CLI: --json schema (effects/chain) and --graph
# ---------------------------------------------------------------------------


def test_json_schema_has_effects_and_chain(tmp_path, capsys):
    import json as _json

    from tools.lint.__main__ import main

    mod = tmp_path / "lodestar_fixture.py"
    mod.write_text(
        textwrap.dedent(
            """
            import time
            async def f():
                helper()
            def helper():
                time.sleep(1)
            async def direct():
                time.sleep(1)
            """
        )
    )
    rc = main(["--json", "--no-cache", "--no-baseline", str(mod)])
    out = _json.loads(capsys.readouterr().out)
    assert rc == 1
    tb = [f for f in out["findings"] if f["rule"] == "transitive-blocking"]
    assert tb, out
    # schema: interprocedural findings carry effects + the proving chain
    assert tb[0]["effects"] == ["blocks"]
    assert len(tb[0]["chain"]) == 2 and "time.sleep" in tb[0]["chain"][-1]
    # per-file findings carry the same keys (empty lists)
    ba = [f for f in out["findings"] if f["rule"] == "blocking-async"]
    assert ba and ba[0]["effects"] == [] and ba[0]["chain"] == []


def test_graph_cli_dumps_functions_and_effects(tmp_path, capsys):
    import json as _json

    from tools.lint.__main__ import main

    mod = tmp_path / "graph_fixture.py"
    mod.write_text(
        textwrap.dedent(
            """
            import time
            async def f():
                helper()
            def helper():
                time.sleep(1)
            """
        )
    )
    rc = main(["--graph", "--json", "--no-cache", str(mod)])
    assert rc == 0
    out = _json.loads(capsys.readouterr().out)
    by_name = {e["function"].split(":")[-1]: e for e in out["functions"]}
    assert by_name["helper"]["effects"] == ["blocks"]
    assert by_name["f"]["inherited_effects"] == ["blocks"]
    assert any(c.endswith(":helper") for c in by_name["f"]["calls"])
    # human-readable variant prints one line per function
    rc = main(["--graph", "--no-cache", str(mod)])
    text = capsys.readouterr().out
    assert rc == 0 and "[blocks]" in text


def test_summary_cache_roundtrip_and_invalidation(tmp_path):
    import os

    cache_file = tmp_path / "cache.json"
    cache = effects.SummaryCache(str(cache_file), root=str(tmp_path))
    mod = tmp_path / "m.py"
    mod.write_text("def f():\n    pass\n")
    st = os.stat(mod)
    cache.put("m.py", st, {"module": "m"}, [])
    cache.save()
    # fresh load with same mtime/size hits
    c2 = effects.SummaryCache(str(cache_file), root=str(tmp_path))
    assert c2.get("m.py", st) is not None
    # touching the file invalidates the entry
    mod.write_text("def f():\n    return 1\n")
    assert c2.get("m.py", os.stat(mod)) is None


def test_summary_cache_prunes_only_vanished_files(tmp_path):
    import os

    cache_file = tmp_path / "cache.json"
    kept = tmp_path / "kept.py"
    kept.write_text("x = 1\n")
    gone = tmp_path / "gone.py"
    gone.write_text("y = 2\n")
    cache = effects.SummaryCache(str(cache_file), root=str(tmp_path))
    cache.put("kept.py", os.stat(kept), {"module": "kept"}, [])
    cache.put("gone.py", os.stat(gone), {"module": "gone"}, [])
    cache.save()
    gone.unlink()
    # a save after the file vanished drops only that entry; a scoped run
    # (which never re-put "kept.py") keeps the rest of the repo warm
    c2 = effects.SummaryCache(str(cache_file), root=str(tmp_path))
    c2.save()
    c3 = effects.SummaryCache(str(cache_file), root=str(tmp_path))
    assert c3.get("kept.py", os.stat(kept)) is not None
    assert "gone.py" not in c3.entries


def test_repo_graph_builds_and_is_nontrivial():
    # whole-repo build: the graph must actually link across modules
    project = core.build_graph(core.DEFAULT_PATHS)
    assert len(project.funcs) > 500
    edges = sum(len(f.edges) for f in project.funcs.values())
    assert edges > 500
    # the satellite-1 chain is resolved: Repository.put dispatches into
    # the sqlite controller through the KvController protocol
    repo_put = project.funcs["lodestar_tpu.db.repository:Repository.put"]
    callees = {e.callee for e in repo_put.edges}
    assert "lodestar_tpu.db.controller:SqliteController.put" in callees
    assert "blocks" in project.funcs[
        "lodestar_tpu.db.controller:SqliteController.put"
    ].effects


# ---------------------------------------------------------------------------
# silent-except (ISSUE 7 satellite)
# ---------------------------------------------------------------------------


def test_silent_except_positive_merge_tracker_pre_fix():
    # the exact pre-fix pattern: a poll loop eating every EL failure
    src = """
    async def loop(self):
        while True:
            try:
                await self.poll_once()
            except Exception:
                pass
            await asyncio.sleep(12)
    """
    assert [f.rule for f in lint(src, rule="silent-except")] == ["silent-except"]


def test_silent_except_positive_return_fallback():
    src = """
    def probe():
        try:
            return compute()
        except Exception:
            return None
    """
    assert lint(src, rule="silent-except")


def test_silent_except_negative_logged():
    src = """
    async def loop(self):
        try:
            await self.poll_once()
        except Exception as e:
            self._log.warn(f"poll failed: {e}")
    """
    assert not lint(src, rule="silent-except")


def test_silent_except_positive_event_set_is_not_a_metric():
    # .set() on a non-metric receiver (threading.Event) still swallows
    src = """
    def handle(self):
        try:
            work()
        except Exception:
            self._done_event.set()
    """
    assert lint(src, rule="silent-except")


def test_silent_except_negative_metric_touch():
    src = """
    def handle(self):
        try:
            decode()
        except Exception:
            self.stats.invalid += 1
            return
    """
    assert not lint(src, rule="silent-except")


def test_silent_except_negative_reraise_and_bound_use():
    src = """
    def a():
        try:
            x()
        except Exception:
            raise RuntimeError("wrapped")

    def b(fut):
        try:
            x()
        except Exception as e:
            fut.set_exception(e)
    """
    assert not lint(src, rule="silent-except")


def test_silent_except_negative_narrowed_type():
    # narrowing to the expected error type is a valid fix
    src = """
    def probe():
        try:
            import jax
        except ImportError:
            return None
    """
    assert not lint(src, rule="silent-except")


def test_silent_except_scope_is_lodestar_tpu_only():
    src = """
    def probe():
        try:
            x()
        except Exception:
            return None
    """
    assert not lint(src, path="tests/test_mod.py", rule="silent-except")
    assert not lint(src, path="tools/lint/mod.py", rule="silent-except")
    assert lint(src, path="lodestar_tpu/mod.py", rule="silent-except")


# ---------------------------------------------------------------------------
# v3 whole-program rules (ISSUE 13): retrace-hazard, pool-ownership,
# metric-label-drift — plus the native sanitizer gate
# ---------------------------------------------------------------------------


def test_retrace_hazard_positive_raw_len_width():
    # the defect unregistered-jit cannot see: the wrapper is registered,
    # but the call site pads to len(sets) — one XLA program per distinct
    # input size at runtime, none of them in the warm manifest
    src = """
    from lodestar_tpu.aot import registry
    _jit_k = registry.jitted("k")
    def dispatch(sets):
        size = len(sets)
        for s in sets:
            _jit_k(s, size)
    """
    fs = lint(src, rule="retrace-hazard")
    assert [f.rule for f in fs] == ["retrace-hazard"]
    assert "len(sets)" in fs[0].message
    assert fs[0].effects == ("retrace",)
    # the chain names the dispatch site, including the loop
    assert any("loop" in c for c in fs[0].chain)


def test_retrace_hazard_negative_quantized_and_rung_const():
    src = """
    from lodestar_tpu.ops.bls12_381 import buckets as bk
    from lodestar_tpu.aot import registry
    _jit_k = registry.jitted("k")
    def dispatch(sets):
        size = bk.bucket_size(len(sets))
        _jit_k(sets, size)
    def dispatch_const(sets):
        bucket = 512
        _jit_k(sets, bucket)
    """
    assert not lint(src, rule="retrace-hazard")


def test_retrace_hazard_positive_nonrung_constant():
    src = """
    from lodestar_tpu.aot import registry
    _jit_k = registry.jitted("k")
    def dispatch(sets):
        bucket = 300
        _jit_k(sets, bucket)
    """
    fs = lint(src, rule="retrace-hazard")
    assert fs and "constant 300" in fs[0].message


def test_retrace_hazard_caller_witness_through_width_param():
    # the whole-program half: encode() itself is careful (None default
    # falls back to bucket_size) but ONE caller feeds it a raw length —
    # the finding anchors at that caller with the provenance chain
    src = """
    from lodestar_tpu.ops.bls12_381 import buckets as bk
    from lodestar_tpu.aot import registry
    _jit_k = registry.jitted("k")
    def encode(sets, bucket=None):
        size = bucket if bucket is not None else bk.bucket_size(len(sets))
        return size
    def good_caller(sets):
        encode(sets)
    def bad_caller(sets):
        encode(sets, bucket=len(sets))
    """
    fs = lint(src, rule="retrace-hazard")
    assert len(fs) == 1
    assert fs[0].line == 11  # the bad_caller call site, not encode()
    assert "width parameter 'bucket'" in fs[0].message
    assert fs[0].chain  # provenance chain present


def test_retrace_hazard_scope_requires_jit_connection():
    # the DB layer's keyspace Bucket enum reuses the word `bucket` with
    # an entirely different meaning: modules that neither mint jitted()
    # wrappers nor import the rung module are out of scope
    src = """
    def put(self, bucket, key):
        return encode_key(bucket, key)
    def caller(db):
        put(db, Bucket.blobs, b"k")
    """
    assert not lint(src, path="lodestar_tpu/db/mod.py", rule="retrace-hazard")


def test_retrace_hazard_suppression():
    src = """
    from lodestar_tpu.aot import registry
    _jit_k = registry.jitted("k")
    def dispatch(sets):
        size = len(sets)  # lodelint: disable=retrace-hazard
        _jit_k(sets, size)
    """
    assert not lint(src, rule="retrace-hazard")


def test_pool_ownership_positive_executor_mutation():
    # loop-owned state written from an executor thread, two hops deep —
    # asyncio.Lock would not help, and no threading lock is held
    src = """
    import asyncio
    class Pool:
        def _work(self):
            self._helper()
        def _helper(self):
            self.state = compute()
        async def go(self):
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, self._work)
    """
    fs = lint(src, rule="pool-ownership")
    assert [f.rule for f in fs] == ["pool-ownership"]
    assert fs[0].effects == ("mutates-unlocked",)
    assert "executor" in fs[0].message
    # chain walks dispatch -> _work -> _helper's write
    assert "writes self.state" in fs[0].chain[-1]


def test_pool_ownership_negative_locked_or_readonly():
    # a threading.Lock around the write is the sanctioned cross-thread
    # form; a read-only encode helper has nothing to flag.  The
    # getloop-call receiver form must resolve too.
    src = """
    import asyncio, threading
    class Pool:
        def __init__(self):
            self._lock = threading.Lock()
        def _locked_work(self):
            with self._lock:
                self.state = compute()
        def _pure(self, sets):
            return encode(sets)
        async def go(self):
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, self._locked_work)
            await asyncio.get_running_loop().run_in_executor(None, self._pure, [1])
    """
    assert not lint(src, rule="pool-ownership")


def test_pool_ownership_positive_unguarded_release():
    # the encode-stage token discipline: a bare release call cannot
    # prove it still owns the stage — a second caller double-releases
    src = """
    class Pool:
        def _release_encode(self):
            self._encoding = False
        async def run(self, owns):
            self._release_encode()
    """
    fs = lint(src, rule="pool-ownership")
    assert fs and "testing-and-clearing" in fs[0].message


def test_pool_ownership_negative_guarded_release():
    # the device_pool idiom: test the token, clear it, then release
    src = """
    class Pool:
        def _release_encode(self):
            self._encoding = False
        async def run(self, owns):
            if owns["encode"]:
                owns["encode"] = False
                self._release_encode()
    """
    assert not lint(src, rule="pool-ownership")


def test_pool_ownership_positive_await_in_release_guard():
    src = """
    class Pool:
        def _release_encode(self):
            self._encoding = False
        async def run(self, owns):
            if owns["encode"]:
                owns["encode"] = False
                await flush()
                self._release_encode()
    """
    fs = lint(src, rule="pool-ownership")
    assert fs and "critical section" in fs[0].message


def test_metric_label_drift_positive_wrong_and_missing_labels():
    src = """
    from prometheus_client import Counter
    class M:
        def __init__(self, registry):
            self.jobs = Counter("x_jobs_total", "d", ["tier"], registry=registry)
    class S:
        def use(self):
            self.m.jobs.labels(kind="host").inc()
            self.m.jobs.inc()
    """
    fs = lint(src, path="lodestar_tpu/mod.py", rule="metric-label-drift")
    msgs = " | ".join(f.message for f in fs)
    assert len(fs) == 2
    assert "does not match the declared label set" in msgs
    assert "directly on labeled metric" in msgs
    assert all(f.effects == ("metrics",) for f in fs)


def test_metric_label_drift_negative_matching_sites():
    src = """
    from prometheus_client import Counter, Gauge
    class M:
        def __init__(self, registry):
            ns = "x"
            self.jobs = Counter(f"{ns}_jobs_total", "d", ["tier"], registry=registry)
            self.depth = Gauge(f"{ns}_depth", "d", registry=registry)
    class S:
        def use(self):
            self.m.jobs.labels(tier="host").inc()
            self.m.depth.set(3)
    """
    assert not lint(src, path="lodestar_tpu/mod.py", rule="metric-label-drift")


def test_metric_label_drift_positive_duplicate_registration():
    # same resolved metric name constructed twice (f-string prefixes
    # resolved statically): the second registration is the finding
    src = """
    from prometheus_client import Counter
    class A:
        def __init__(self, registry):
            ns = "dup"
            self.jobs = Counter(f"{ns}_total", "d", registry=registry)
    class B:
        def __init__(self, registry):
            self.jobs2 = Counter("dup_total", "d", registry=registry)
    """
    fs = lint(src, path="lodestar_tpu/mod.py", rule="metric-label-drift")
    assert len(fs) == 1 and "registered more than once" in fs[0].message
    assert fs[0].chain  # points at the first registration


def test_metric_label_drift_positive_labels_on_unlabeled():
    src = """
    from prometheus_client import Gauge
    class M:
        def __init__(self, registry):
            self.depth = Gauge("x_depth", "d", registry=registry)
    class S:
        def use(self):
            self.m.depth.labels(topic="a").set(1)
    """
    fs = lint(src, path="lodestar_tpu/mod.py", rule="metric-label-drift")
    assert fs and "registered without" in fs[0].message


def test_v3_rules_report_effects_and_chain_in_json():
    # the --json schema: v3 findings carry their effect + proving chain
    # through the same as_json() the CLI serializes
    src = """
    from lodestar_tpu.aot import registry
    _jit_k = registry.jitted("k")
    def dispatch(sets):
        size = len(sets)
        _jit_k(sets, size)
    """
    fs = lint(src, rule="retrace-hazard")
    assert fs
    j = fs[0].as_json()
    assert j["effects"] == ["retrace"] and j["chain"]
    assert j["rule"] == "retrace-hazard" and j["line"] == fs[0].line


def test_callgraph_resolves_own_nested_def():
    # run_in_executor(None, nested) must resolve for pool-ownership:
    # a function's own nested defs are visible as bare names inside it
    src = """
    import asyncio
    class Svc:
        async def work(self):
            def inner():
                self.state = compute()
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, inner)
    """
    fs = lint(src, rule="pool-ownership")
    assert fs and "inner" in fs[0].message


# ---------------------------------------------------------------------------
# lint cache: the analyzer-source stamp must cover every rule module
# ---------------------------------------------------------------------------


def test_lint_stamp_covers_every_analyzer_module():
    # the (mtime,size) stamp is what invalidates cached findings when
    # the ANALYZER changes; every engine/rule module must be in it —
    # including the v3 additions — or an edited rule serves stale results
    import os

    stamp = effects._lint_stamp()
    lint_dir = os.path.dirname(os.path.abspath(effects.__file__))
    on_disk = sorted(f for f in os.listdir(lint_dir) if f.endswith(".py"))
    for required in (
        "core.py", "callgraph.py", "effects.py", "rules_async.py",
        "rules_jax.py", "rules_repo.py", "rules_interproc.py",
        "rules_program.py", "rules_bounds.py", "rules_shard.py",
    ):
        assert required in on_disk
    for fn in on_disk:
        assert f"{fn}:" in stamp, f"lint cache stamp misses {fn}"


def test_lint_cache_invalidated_by_rule_edit(tmp_path, monkeypatch):
    # regression: editing any rule file (a new stamp) must drop EVERY
    # cached summary and finding, not serve pre-edit results
    import os

    cache_file = tmp_path / "cache.json"
    mod = tmp_path / "m.py"
    mod.write_text("x = 1\n")
    monkeypatch.setattr(effects, "_lint_stamp", lambda: "rules-v1")
    c1 = effects.SummaryCache(str(cache_file), root=str(tmp_path))
    c1.put("m.py", os.stat(mod), {"module": "m"}, [{"cached": True}])
    c1.save()
    # same stamp: warm
    c2 = effects.SummaryCache(str(cache_file), root=str(tmp_path))
    assert c2.get("m.py", os.stat(mod)) is not None
    # the analyzer changed (any tools/lint/*.py edit): cold
    monkeypatch.setattr(effects, "_lint_stamp", lambda: "rules-v2-edited")
    c3 = effects.SummaryCache(str(cache_file), root=str(tmp_path))
    assert c3.get("m.py", os.stat(mod)) is None


# ---------------------------------------------------------------------------
# native sanitizer gate (python -m tools.sanitize): ASAN/UBSAN
# differential replay of csrc/*.c — the tier-1 wiring lives HERE,
# alongside test_repo_is_clean
# ---------------------------------------------------------------------------

from tools import sanitize  # noqa: E402


def test_native_sanitizer_gate():
    """THE standing gate: builds csrc/*.c under ASAN+UBSAN and replays
    the h2c differential vectors (+ sha256/merkle/snappy KATs).  Exit 0
    means clean OR an explicit compiler-unavailable notice — exit 1 is
    a real memory-safety/UB finding and fails tier-1."""
    import io

    out, err = io.StringIO(), io.StringIO()
    rc = sanitize.run_gate(out=out, err=err)
    assert rc == 0, (
        "native sanitizer gate found problems:\n"
        + out.getvalue() + err.getvalue()
    )
    text = out.getvalue()
    # never a silent no-op: either vectors replayed or a visible notice
    assert "replayed" in text or "notice:" in text


def test_sanitizer_driver_catches_vector_mismatch(tmp_path):
    # the driver is a real comparator, not a smoke test: corrupt one
    # expected digest and the replay must exit 1 naming the line
    import io

    cc = sanitize.find_compiler()
    if cc is None:
        import pytest as _pytest

        _pytest.skip("no sanitizer-capable compiler on this host")
    ok, exe = sanitize.build(cc)
    assert ok, exe
    vectors = sanitize.generate_vectors(h2c_msgs=[b"abc"]).splitlines()
    for i, line in enumerate(vectors):
        if line.startswith("sha256 "):
            parts = line.split()
            parts[2] = "00" * 32
            vectors[i] = " ".join(parts)
            break
    bad = tmp_path / "vectors.txt"
    bad.write_text("\n".join(vectors) + "\n")
    out, err = io.StringIO(), io.StringIO()
    assert sanitize.replay(exe, str(bad), out=out, err=err) == 1
    assert "sha256" in err.getvalue()


def test_sanitizer_skips_with_notice_when_no_compiler(monkeypatch):
    # the clang-absent contract: exit 0 BUT a visible notice — CI logs
    # show the gate was skipped, never silently green
    import io

    monkeypatch.setattr(sanitize, "find_compiler", lambda: None)
    out = io.StringIO()
    rc = sanitize.run_gate(out=out, err=out)
    assert rc == 0
    assert "notice:" in out.getvalue() and "SKIPPED" in out.getvalue()


def test_sanitizer_compiler_probe_rejects_bogus_cc():
    assert sanitize.find_compiler(candidates=["not-a-real-compiler-xyz"]) is None


def test_sanitizer_vectors_are_deterministic_and_complete():
    # replayable failures need byte-identical vectors across runs; the
    # file must cover every exported native entry point family
    v1 = sanitize.generate_vectors(h2c_msgs=[b"abc"])
    v2 = sanitize.generate_vectors(h2c_msgs=[b"abc"])
    assert v1 == v2
    for op in ("h2c ", "h2c_err ", "sha256 ", "pairs ", "layer ", "snappy "):
        assert any(l.startswith(op) for l in v1.splitlines()), op


def test_retrace_hazard_positive_inline_len_at_dispatch():
    # review hardening: the width need not live in a width-NAMED binding
    # — inline len() and an arbitrarily-named local both count
    src = """
    from lodestar_tpu.aot import registry
    _jit_k = registry.jitted("k")
    def inline(sets):
        _jit_k(sets, len(sets))
    def via_local(sets):
        n = len(sets)
        _jit_k(sets, n)
    """
    fs = lint(src, rule="retrace-hazard")
    assert len(fs) == 2
    assert all("len()-derived width" in f.message for f in fs)


def test_retrace_hazard_served_dispatch_through_registry_call():
    # served calls dispatch through registry.call(kernel, *args), not a
    # jitted wrapper: a len()-derived width there is the same hazard, and
    # a quantized one is not
    src = """
    from lodestar_tpu.aot import registry as _reg
    from lodestar_tpu.ops.bls12_381 import buckets as bk
    def raw(sets):
        n = len(sets)
        return _reg.call("k", sets, n)
    def quantized(sets):
        size = bk.bucket_size(len(sets))
        return _reg.call("k", sets, size)
    """
    fs = lint(src, rule="retrace-hazard")
    assert len(fs) == 1
    assert fs[0].line == 6
    assert "len()-derived width" in fs[0].message
    assert fs[0].chain == ("lodestar_tpu/mod.py:6 raw [dispatches jitted program]",)


def test_retrace_hazard_negative_tensor_args_at_dispatch():
    # tensor/encoded positional args at a dispatch site are NOT widths;
    # only len-provenance is judged there
    src = """
    from lodestar_tpu.ops.bls12_381 import buckets as bk
    from lodestar_tpu.aot import registry
    _jit_k = registry.jitted("k")
    def dispatch(sets):
        size = bk.bucket_size(len(sets))
        pk, sig = encode(sets, size)
        _jit_k(pk, sig, size)
    """
    assert not lint(src, rule="retrace-hazard")


def test_retrace_hazard_witness_through_non_width_param_into_bucket_kwarg():
    # review hardening: the raw value rides a plain param named `n`, and
    # only the RECEIVING kwarg is width-named — the witness must anchor
    # at the caller that feeds the len(), not vanish
    src = """
    from lodestar_tpu.ops.bls12_381 import buckets as bk
    from lodestar_tpu.aot import registry
    _jit_k = registry.jitted("k")
    def mid(dv, n):
        dv.run(bucket=n)
    def caller(dv, sets):
        mid(dv, len(sets))
    """
    fs = lint(src, rule="retrace-hazard")
    assert len(fs) == 1
    assert fs[0].line == 8  # the caller's mid(dv, len(sets)) site
    assert "'bucket'" in fs[0].message and fs[0].chain


def test_metric_label_drift_positive_module_level_name_receiver():
    # review hardening: a module-global labeled metric used bare drifts
    # exactly like the self.m.jobs.inc() form
    src = """
    from prometheus_client import Counter
    JOBS = Counter("x_jobs_total", "d", ["tier"])
    def use():
        JOBS.inc()
    """
    fs = lint(src, path="lodestar_tpu/mod.py", rule="metric-label-drift")
    assert fs and "directly on labeled metric" in fs[0].message


def test_retrace_hazard_one_finding_per_len_root_and_root_suppression():
    # review hardening round 2: a single len() feeding both a width
    # binding and a bucket= kwarg is ONE defect — one finding, at the
    # binding; and suppressing at the len() binding quiets every
    # downstream site (kwarg pass included)
    src = """
    from lodestar_tpu.aot import registry
    _jit_k = registry.jitted("k")
    def dispatch(dv, sets):
        size = len(sets)
        dv.run(sets, bucket=size)
        _jit_k(sets, size)
    """
    fs = lint(src, rule="retrace-hazard")
    assert len(fs) == 1 and fs[0].line == 5  # the binding, once
    suppressed = """
    from lodestar_tpu.aot import registry
    _jit_k = registry.jitted("k")
    def dispatch(dv, sets):
        size = len(sets)  # lodelint: disable=retrace-hazard
        dv.run(sets, bucket=size)
        _jit_k(sets, size)
    """
    assert not lint(suppressed, rule="retrace-hazard")


def test_retrace_hazard_negative_unrelated_width_local():
    # review hardening round 2: a byte-count local that merely MATCHES
    # the width vocabulary but never flows into any call is not a
    # program width — no spurious suppression needed in SSZ-ish code
    src = """
    from lodestar_tpu.ops.bls12_381 import buckets as bk
    from lodestar_tpu.aot import registry
    _jit_k = registry.jitted("k")
    def dispatch(sets, blob):
        chunk_size = len(blob)
        bucket = bk.pool_bucket(len(sets))
        _jit_k(sets, bucket)
        return chunk_size
    """
    assert not lint(src, rule="retrace-hazard")


def test_metric_label_drift_unresolvable_labels_skip_checks():
    # review hardening round 2: a labelnames argument that is a
    # VARIABLE is statically unresolvable — the metric must not be
    # treated as unlabeled (which flagged every legitimate .labels use)
    src = """
    from prometheus_client import Counter
    class M:
        def __init__(self, registry, LABELS):
            self.jobs = Counter("x_jobs_total", "d", LABELS, registry=registry)
    class S:
        def use(self):
            self.m.jobs.labels(tier="host").inc()
    """
    assert not lint(src, path="lodestar_tpu/mod.py", rule="metric-label-drift")


def test_pool_ownership_negative_guard_with_nested_condition():
    # review hardening round 3: the test-and-clear guard may wrap the
    # release in a FURTHER nested condition — still guarded
    src = """
    class Pool:
        def _release_encode(self):
            self._encoding = False
        async def run(self, owns):
            if owns["encode"]:
                owns["encode"] = False
                if self.dirty:
                    self._release_encode()
                else:
                    self._release_encode()
    """
    assert not lint(src, rule="pool-ownership")


def test_metric_label_drift_negative_event_set_name_collision():
    # review hardening round 3: `.set()` is also an Event verb — an
    # attr-name collision with a labeled gauge on a non-metric receiver
    # is not drift (metric-ish receivers still check)
    src = """
    from prometheus_client import Gauge
    class M:
        def __init__(self, registry):
            self.ready = Gauge("x_ready", "d", ["mod"], registry=registry)
    class S:
        def ok(self):
            self.event.ready.set()
        def still_flagged(self):
            self.metrics.ready.set(1)
    """
    fs = lint(src, path="lodestar_tpu/mod.py", rule="metric-label-drift")
    assert len(fs) == 1 and fs[0].line == 10  # only the metrics.* receiver


def test_sanitizer_build_reports_missing_source_cleanly(monkeypatch, tmp_path):
    # review hardening round 3: a vanished csrc source is a gate
    # failure message, not an uncaught OSError traceback
    missing = str(tmp_path / "gone.c")
    monkeypatch.setattr(sanitize, "_DEPS", sanitize._DEPS + [missing])
    ok, msg = sanitize.build("cc", out=str(tmp_path / "drv"))
    assert not ok and "cannot stat" in msg


# ---------------------------------------------------------------------------
# lodelint v4: limb-bounds (the limbcheck abstract interpreter)
#
# Fixtures opt into the interpreter's scope by carrying an ``@bounds:``
# token (callgraph.bounds_in_scope); the real kernel modules are in
# scope by path.  LIMB_BITS/NLIMBS module consts reseed the canonical
# interval, so the doubled-limb-count mutation demo is a pure fixture.
# ---------------------------------------------------------------------------


def test_limb_bounds_negative_canonical_add_within_annotation():
    src = """
    LIMB_BITS = 13
    NLIMBS = 30
    def add(a, b):
        '''@bounds: a [0, 2^13-1], b [0, 2^13-1] -> [0, 2^14-1]'''
        return a + b
    """
    assert not lint(src, rule="limb-bounds")


def test_limb_bounds_positive_deliberate_wrap_reports_at_wrap_site():
    # mod-2^32 wraparound is SILENT at the wrapping add; the finding
    # fires at the taint-incompatible >> use, anchored at the wrap site,
    # carrying the full interval derivation chain
    src = """
    # fixture opts in via @bounds: marker
    LIMB_BITS = 13
    NLIMBS = 30
    def column(a, b):
        prods = a * b
        col = 2 * NLIMBS * prods
        doubled = col + col
        return doubled >> LIMB_BITS
    """
    fs = lint(src, rule="limb-bounds")
    assert [f.rule for f in fs] == ["limb-bounds"]
    f = fs[0]
    assert f.line == 8  # the wrapping `col + col`, not the shift
    assert "exceeds 2^32 - 1" in f.message and "RShift" in f.message
    # the chain reconstructs the derivation down to the limb products
    assert any("a * b -> [0, 67092481]" in fr for fr in f.chain)
    assert "[0, 8051097720]" in f.chain[-1]


def test_limb_bounds_negative_mask_forgives_deliberate_wrap():
    # & (2^k - 1) is a ring homomorphism mod 2^k: the same wrapped value
    # masked back to canonical is NOT a finding
    src = """
    # fixture opts in via @bounds: marker
    LIMB_BITS = 13
    NLIMBS = 30
    MASK = (1 << LIMB_BITS) - 1
    def column(a, b):
        prods = a * b
        col = 2 * NLIMBS * prods
        doubled = col + col
        return doubled & MASK
    """
    assert not lint(src, rule="limb-bounds")


def test_limb_bounds_positive_interval_widening_through_for_loop():
    # a bounded loop whose body grows the interval each trip: the joined
    # fixpoint crosses 2^32 and the shift use reports with the widening
    # steps visible in the chain
    src = """
    # fixture opts in via @bounds: marker
    LIMB_BITS = 13
    NLIMBS = 30
    def runaway(a):
        acc = a
        for _ in range(NLIMBS):
            acc = acc * 2 + a
        return acc >> 1
    """
    fs = lint(src, rule="limb-bounds")
    assert [f.rule for f in fs] == ["limb-bounds"]
    assert "exceeds 2^32 - 1" in fs[0].message
    assert len(fs[0].chain) >= 2  # successive widening frames survive


def test_limb_bounds_positive_unknown_trip_count_loop_demands_bounds():
    # an unbounded while joins toward top: the canonical operand meeting
    # the widened accumulator is exactly the unprovable case
    src = """
    # fixture opts in via @bounds: marker
    LIMB_BITS = 13
    NLIMBS = 30
    def runaway(a, flags):
        acc = a
        while flags:
            acc = acc + a
        return acc >> 1
    """
    fs = lint(src, rule="limb-bounds")
    assert [f.rule for f in fs] == ["limb-bounds"]
    assert "cannot bound" in fs[0].message


def test_limb_bounds_mutation_demo_doubled_nlimbs_overflows_cios_column():
    # THE acceptance mutation: the real fp.py CIOS column bound
    # 2*NLIMBS*(2^13-1)^2 + carry < 2^32 holds at NLIMBS=30 and breaks
    # at 60 — the gate must go red on the doubled-limb-count kernel
    tmpl = """
    # fixture opts in via @bounds: marker
    LIMB_BITS = 13
    NLIMBS = {n}
    def cios_col(a, b, m, p):
        col = NLIMBS * (a * b) + NLIMBS * (m * p)
        return col >> LIMB_BITS
    """
    assert not lint(tmpl.format(n=30), rule="limb-bounds")
    fs = lint(tmpl.format(n=60), rule="limb-bounds")
    assert [f.rule for f in fs] == ["limb-bounds"]
    assert "8051097720" in fs[0].message  # 2*60*8191^2, computed not guessed


def test_limb_bounds_positive_implicit_dtype_promotion():
    src = """
    # fixture opts in via @bounds: marker
    import jax.numpy as jnp
    def f(a):
        scale = a.astype(jnp.float32)
        return a + scale
    """
    fs = lint(src, rule="limb-bounds")
    assert [f.rule for f in fs] == ["limb-bounds"]
    assert "implicit dtype promotion: u32 op f32" in fs[0].message


def test_limb_bounds_positive_untracked_operand_is_unprovable():
    src = """
    # fixture opts in via @bounds: marker
    import os
    def f(a):
        x = os.environ.whatever()
        return a + x
    """
    fs = lint(src, rule="limb-bounds")
    assert [f.rule for f in fs] == ["limb-bounds"]
    assert "untracked operand" in fs[0].message
    assert "@bounds:" in fs[0].message  # the fix the message demands


def test_limb_bounds_suppression_is_honored_at_the_finding_line():
    src = """
    # fixture opts in via @bounds: marker
    import os
    def f(a):
        x = os.environ.whatever()
        return a + x  # lodelint: disable=limb-bounds
    """
    assert not lint(src, rule="limb-bounds")


def test_limb_bounds_annotation_violated_by_body_return():
    # @bounds: is a verified contract, not a trusted comment: a body
    # returning wider than it declares is a finding at the return site
    src = """
    LIMB_BITS = 13
    def mul(a, b):
        '''@bounds: a [0, 2^13-1], b [0, 2^13-1] -> [0, 2^13-1]'''
        return a * b
    """
    fs = lint(src, rule="limb-bounds")
    assert [f.rule for f in fs] == ["limb-bounds"]
    assert "exceeding its declared @bounds return" in fs[0].message


def test_limb_bounds_annotation_checked_against_call_site_args():
    # the caller side of the contract: a value proven wider than the
    # callee's declared param interval is a finding at the call
    src = """
    LIMB_BITS = 13
    def widen2(a):
        '''@bounds: a [0, 2^13-1] -> [0, 2^14-1]'''
        return a + a
    def narrow(x):
        '''@bounds: x [0, 2^13-1] -> [0, 2^13-1]'''
        return x
    def caller(a):
        w = widen2(a)
        return narrow(w)
    """
    fs = lint(src, rule="limb-bounds")
    assert [f.rule for f in fs] == ["limb-bounds"]
    assert "outside its declared @bounds [0, 8191]" in fs[0].message


def test_limb_bounds_json_payload_carries_interval_chain():
    # satellite: --json consumers (editor integrations) get the interval
    # derivation as structured data, pinned here as schema
    src = """
    # fixture opts in via @bounds: marker
    LIMB_BITS = 13
    NLIMBS = 30
    def column(a, b):
        prods = a * b
        col = 2 * NLIMBS * prods
        doubled = col + col
        return doubled >> LIMB_BITS
    """
    d = lint(src, rule="limb-bounds")[0].as_json()
    assert set(d) == {"path", "line", "col", "rule", "message", "effects",
                      "chain"}
    assert d["rule"] == "limb-bounds"
    assert d["effects"] == ["overflow"]
    # chain frames are `path:line expr -> [lo, hi] (dtype)` strings
    assert d["chain"] and all(" -> [" in fr and "(u32)" in fr
                              for fr in d["chain"])


# ---------------------------------------------------------------------------
# lodelint v4: fault-coverage
# ---------------------------------------------------------------------------


def _fault_project(fire_src: str, test_src: str):
    mod = callgraph.summary_for_source(
        textwrap.dedent(fire_src), "lodestar_tpu/fixture_mod.py"
    )
    tests = callgraph.summary_for_source(
        textwrap.dedent(test_src), "tests/test_fixture_chaos.py"
    )
    return callgraph.build_project([mod, tests])


def test_fault_coverage_positive_undocumented_checkpoint():
    src = """
    from lodestar_tpu.testing import faults
    def f():
        faults.fire("fixture.bogus.point")
    """
    fs = lint(src, rule="fault-coverage")
    assert [f.rule for f in fs] == ["fault-coverage"]
    assert "no row in docs/FAULTS.md" in fs[0].message


def test_fault_coverage_fstring_checkpoint_name_resolves_statically():
    # the name is an f-string over a module str constant: coverage
    # checking sees the RESOLVED name, not an opaque expression
    src = """
    from lodestar_tpu.testing import faults
    _POINT = "bogus"
    def f():
        faults.fire(f"fixture.{_POINT}.point")
    """
    fs = lint(src, rule="fault-coverage")
    assert [f.rule for f in fs] == ["fault-coverage"]
    assert "'fixture.bogus.point'" in fs[0].message


def test_fault_coverage_positive_unresolvable_checkpoint_name():
    src = """
    from lodestar_tpu.testing import faults
    def f(name):
        faults.fire(name)
    """
    fs = lint(src, rule="fault-coverage")
    assert [f.rule for f in fs] == ["fault-coverage"]
    assert "not statically resolvable" in fs[0].message


def test_fault_coverage_mutation_demo_documented_but_untested():
    # THE acceptance mutation: net.transport.write has its FAULTS.md row,
    # but the project's only chaos test injects a different point —
    # exactly what deleting the write-fault chaos test would leave behind
    p = _fault_project(
        """
        from lodestar_tpu.testing import faults
        def send():
            faults.fire("net.transport.write")
        """,
        """
        from lodestar_tpu.testing import faults
        def test_chaos():
            with faults.inject("net.transport.read"):
                pass
        """,
    )
    fs = RULES["fault-coverage"].check_project(p)
    assert [f.rule for f in fs] == ["fault-coverage"]
    assert "no test ever injects it" in fs[0].message
    assert fs[0].path == "lodestar_tpu/fixture_mod.py"


def test_fault_coverage_negative_documented_and_injected():
    p = _fault_project(
        """
        from lodestar_tpu.testing import faults
        def send():
            faults.fire("net.transport.write")
        """,
        """
        from lodestar_tpu.testing import faults
        def test_chaos():
            with faults.inject("net.transport.write"):
                pass
        """,
    )
    assert not RULES["fault-coverage"].check_project(p)


# ---------------------------------------------------------------------------
# lodelint v4: task-lifecycle
# ---------------------------------------------------------------------------


def test_task_lifecycle_mutation_demo_attr_task_never_cancelled():
    # THE acceptance mutation: a tracked task whose owner HAS a close()
    # that simply forgets to cancel it — the PR-15 heartbeat leak shape
    src = """
    import asyncio
    class Svc:
        def start(self):
            self._hb = asyncio.create_task(self._beat())
        async def _beat(self):
            pass
        async def close(self):
            pass
    """
    fs = lint(src, rule="task-lifecycle")
    assert [f.rule for f in fs] == ["task-lifecycle"]
    assert "'_hb'" in fs[0].message
    assert "never cancelled or awaited" in fs[0].message


def test_task_lifecycle_negative_cancelled_on_close():
    src = """
    import asyncio
    class Svc:
        def start(self):
            self._hb = asyncio.create_task(self._beat())
        async def _beat(self):
            pass
        async def close(self):
            self._hb.cancel()
    """
    assert not lint(src, rule="task-lifecycle")


def test_task_lifecycle_negative_cancel_reached_through_helper():
    # close() -> _teardown() -> cancel: settlement is call-graph
    # reachability from lifecycle roots, not a same-body string match
    src = """
    import asyncio
    class Svc:
        def start(self):
            self._hb = asyncio.create_task(self._beat())
        async def _beat(self):
            pass
        def _teardown(self):
            self._hb.cancel()
        async def close(self):
            self._teardown()
    """
    assert not lint(src, rule="task-lifecycle")


def test_task_lifecycle_positive_owner_has_no_lifecycle_method():
    src = """
    import asyncio
    class Svc:
        def start(self):
            self._hb = asyncio.create_task(self._beat())
        async def _beat(self):
            pass
    """
    fs = lint(src, rule="task-lifecycle")
    assert [f.rule for f in fs] == ["task-lifecycle"]
    assert "no close()/stop() lifecycle method" in fs[0].message


def test_task_lifecycle_positive_local_task_leaks():
    src = """
    import asyncio
    async def leak():
        t = asyncio.create_task(g())
        print("spawned")
    async def g():
        pass
    """
    fs = lint(src, rule="task-lifecycle")
    assert [f.rule for f in fs] == ["task-lifecycle"]
    assert "outlives its owner" in fs[0].message


def test_task_lifecycle_negative_local_task_awaited():
    src = """
    import asyncio
    async def ok():
        t = asyncio.create_task(g())
        await t
    async def g():
        pass
    """
    assert not lint(src, rule="task-lifecycle")


def test_task_lifecycle_negative_collection_cancelled_via_alias():
    # stop() snapshots the set into a local before cancelling — the
    # UdpEndpoint/JobItemQueue idiom; alias expansion must see through it
    src = """
    import asyncio
    class Pool:
        def start(self):
            self._tasks.add(asyncio.create_task(w()))
        def stop(self):
            tasks = list(self._tasks)
            for t in tasks:
                t.cancel()
    """
    assert not lint(src, rule="task-lifecycle")


# ---------------------------------------------------------------------------
# v5 shardcheck rules (ISSUE 19): collective-axis, replicated-escape,
# shard-divisibility — static SPMD/collective safety over the call graph
# ---------------------------------------------------------------------------


def test_collective_axis_positive_unbound_psum():
    # mutation demo: a psum whose axis no enclosing shard_map/pmap binds
    # — the exact defect class the rule was built for
    src = """
    import jax
    def helper(x):
        return jax.lax.psum(x, "sp")
    """
    fs = lint(src, rule="collective-axis")
    assert [f.rule for f in fs] == ["collective-axis"]
    assert "'sp'" in fs[0].message and "not bound" in fs[0].message
    assert fs[0].effects == ("collective:psum", "axis:sp")


def test_collective_axis_negative_bound_by_local_mesh():
    # the decorator's mesh= kwarg resolves to a local Mesh(...) whose
    # axis_names bind the collective's axis
    src = """
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    def build():
        mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
        @lambda f: shard_map(f, mesh=mesh, in_specs=P("sp"), out_specs=P("sp"))
        def body(x):
            return jax.lax.psum(x, "sp")
        return body
    """
    assert not lint(src, rule="collective-axis")


def test_collective_axis_negative_helper_inherits_caller_axes():
    # interprocedural closure: a helper called from inside a shard_map
    # body inherits the body's bound axes
    src = """
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    def reduce_helper(x):
        return jax.lax.psum(x, "sp")
    def build():
        mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
        @lambda f: shard_map(f, mesh=mesh, in_specs=P("sp"), out_specs=P("sp"))
        def body(x):
            return reduce_helper(x)
        return body
    """
    assert not lint(src, rule="collective-axis")


def test_collective_axis_positive_unsharded_caller_witness_chain():
    # a collective helper reachable ONLY from an unsharded caller is
    # flagged WITH the witness chain proving the unbound reachability
    src = """
    import jax
    def gather_helper(x):
        return jax.lax.all_gather(x, "sp")
    def plain_caller(x):
        return gather_helper(x)
    """
    fs = lint(src, rule="collective-axis")
    assert len(fs) == 1
    assert fs[0].chain, "expected a witness chain through the unsharded caller"
    assert "plain_caller" in "".join(fs[0].chain)


def test_collective_axis_negative_mesh_docstring_contract():
    # the `@mesh:` docstring contract declares the axis bound without a
    # decorator in view (the sharded.py builder idiom)
    src = '''
    import jax
    def helper(x):
        """Cross-shard total.

        @mesh: sp
        """
        return jax.lax.psum(x, "sp")
    '''
    assert not lint(src, rule="collective-axis")


def test_collective_axis_negative_nonliteral_axis_underapproximates():
    # an axis that is not a string literal contributes nothing — the
    # rule under-approximates instead of guessing
    src = """
    import jax
    def helper(x, axis):
        return jax.lax.psum(x, axis)
    """
    assert not lint(src, rule="collective-axis")


def test_collective_axis_negative_pmap_axis_name():
    # pmap's axis_name= kwarg binds the axis for its function
    src = """
    import jax
    def build():
        @lambda f: jax.pmap(f, axis_name="dp")
        def step(x):
            return jax.lax.pmean(x, "dp")
        return step
    """
    assert not lint(src, rule="collective-axis")


def test_collective_axis_suppression():
    src = """
    import jax
    def helper(x):
        return jax.lax.psum(x, "sp")  # lodelint: disable=collective-axis
    """
    assert not lint(src, rule="collective-axis")


def test_replicated_escape_positive_unreduced_output():
    # mutation demo: out_specs=P() but the return value never passed
    # through a cross-axis collective — each device returns its local
    # shard and one copy silently wins
    src = """
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    def build():
        mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
        @lambda f: shard_map(f, mesh=mesh, in_specs=P("sp"), out_specs=P())
        def body(x):
            local = x * 2
            return local
        return body
    """
    fs = lint(src, rule="replicated-escape")
    assert [f.rule for f in fs] == ["replicated-escape"]
    assert "out_specs=P()" in fs[0].message
    assert fs[0].effects == ("out_specs:P()",)


def test_replicated_escape_negative_reduced_output():
    # the return value derives (transitively, through locals) from a
    # cross-axis collective: replication is real
    src = """
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    def build():
        mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
        @lambda f: shard_map(f, mesh=mesh, in_specs=P("sp"), out_specs=P())
        def body(x):
            parts = jax.lax.all_gather(x, "sp")
            total = parts.sum()
            return total
        return body
    """
    assert not lint(src, rule="replicated-escape")


def test_replicated_escape_positive_check_vma_false_unreviewed():
    src = """
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    def build():
        mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
        @lambda f: shard_map(f, mesh=mesh, in_specs=P("sp"), out_specs=P("sp"), check_vma=False)
        def body(x):
            return jax.lax.psum(x, "sp")
        return body
    """
    fs = lint(src, rule="replicated-escape")
    assert len(fs) == 1 and "check_vma=False" in fs[0].message
    assert "check_vma:False" in fs[0].effects


def test_replicated_escape_negative_check_vma_false_reviewed():
    # a reviewed root suppression (with its reason) on the check_vma
    # line is the sanctioned escape hatch — sharded.py's idiom
    src = """
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    def build():
        mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
        @lambda f: shard_map(f, mesh=mesh, in_specs=P("sp"), out_specs=P("sp"), check_vma=False)  # lodelint: disable=replicated-escape — gather+reduce not inferrable
        def body(x):
            return jax.lax.psum(x, "sp")
        return body
    """
    assert not lint(src, rule="replicated-escape")


def test_replicated_escape_check_vma_true_clean_dynamic_flagged():
    head = """
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    def build(flag):
        mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
        @lambda f: shard_map(f, mesh=mesh, in_specs=P("sp"), out_specs=P("sp"), check_vma={})
        def body(x):
            return jax.lax.psum(x, "sp")
        return body
    """
    assert not lint(head.format("True"), rule="replicated-escape")
    fs = lint(head.format("flag"), rule="replicated-escape")
    assert len(fs) == 1 and "non-literal" in fs[0].message


def test_shard_divisibility_positive_96_rung_on_4_mesh():
    # mutation demo: 96 divides 4 evenly but shards to per-device width
    # 24 — not a registered AOT rung, so every device cold-compiles an
    # unwarmed program shape at first dispatch
    src = """
    SUPPORTED_MESH_SIZES = (4,)
    SHARDED_BUCKETS = (96,)
    """
    fs = lint(src, rule="shard-divisibility")
    assert len(fs) == 1
    assert "per-device width 24" in fs[0].message
    assert fs[0].effects == ("rung:96", "mesh:4")


def test_shard_divisibility_positive_indivisible_rung():
    src = """
    SUPPORTED_MESH_SIZES = (8,)
    SHARDED_BUCKETS = (100,)
    """
    fs = lint(src, rule="shard-divisibility")
    assert len(fs) == 1
    assert "not divisible" in fs[0].message
    assert fs[0].effects == ("rung:100", "mesh:8")


def test_shard_divisibility_negative_clean_table():
    # every rung divides every mesh size AND every quotient is itself a
    # registered rung (the production sharded.py invariant)
    src = """
    SUPPORTED_MESH_SIZES = (2, 4, 8)
    SHARDED_BUCKETS = (128, 512, 1024, 2048)
    """
    assert not lint(src, rule="shard-divisibility")


def test_shard_divisibility_pool_buckets_feed_sharded_default_meshes():
    # POOL_BUCKETS are sharded-reachable dispatch widths; with no
    # SUPPORTED_MESH_SIZES in view the default 2/4/8 geometry applies
    src = """
    POOL_BUCKETS = (24,)
    """
    fs = lint(src, rule="shard-divisibility")
    assert fs and all(f.rule == "shard-divisibility" for f in fs)
    assert any("mesh:8" in f.effects[1] for f in fs)


def test_shard_divisibility_suppression_on_table_line():
    src = """
    SUPPORTED_MESH_SIZES = (4,)
    SHARDED_BUCKETS = (96,)  # lodelint: disable=shard-divisibility — host-only table
    """
    assert not lint(src, rule="shard-divisibility")


def test_v5_rules_report_axis_and_spec_payload_in_json():
    # the --json schema: shardcheck findings carry the axis/spec payload
    # in effects through the same as_json() the CLI serializes
    src = """
    import jax
    def helper(x):
        return jax.lax.psum(x, "nope")
    def caller(x):
        return helper(x)
    """
    fs = lint(src, rule="collective-axis")
    assert fs
    j = fs[0].as_json()
    assert j["effects"] == ["collective:psum", "axis:nope"]
    assert j["rule"] == "collective-axis" and j["chain"]

    src2 = """
    SUPPORTED_MESH_SIZES = (4,)
    SHARDED_BUCKETS = (96,)
    """
    j2 = lint(src2, rule="shard-divisibility")[0].as_json()
    assert j2["effects"] == ["rung:96", "mesh:4"]
