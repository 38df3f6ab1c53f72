"""Persistent compilation-cache config + observability spy.

``configure()`` is the single cache-setup path for every entry point.
Four divergent copies of this logic (bench.py, tests/conftest.py,
__graft_entry__.py, tools/diagnose_cache.py) previously disagreed on
defaults while the production node path never enabled the cache at all
— so first verification on a node paid the full multi-minute compile
every process start.

``install_cache_spy()`` wraps jax's internal persistent-cache get/put
(jax._src.compilation_cache.get_executable_and_time /
put_executable_and_time — both called through module-attribute lookup,
so wrapping the attributes is effective) to count hits/misses and
observe real compile times.  The warm tool uses the captured keys to
learn each program's cache filename; chain/bls/metrics.py feeds the
events into the Prometheus family.
"""
from __future__ import annotations

import logging
import os
import threading
from typing import Callable, Dict, List, Optional

_log = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

# Matches what bench.py historically used: only multi-second compiles
# are worth a cache entry; tests override to 0.0 for tiny programs.
DEFAULT_MIN_COMPILE_SECS = 1.0


# jax reads this variable itself when it starts; where it is set, the
# process's cache is placed from outside and nothing here moves it
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def repo_cache_dir() -> str:
    """The persistent-cache dir in effect: ``$JAX_COMPILATION_CACHE_DIR``
    as given, else the fixed ``<checkout>/.jax_cache`` (the directory
    is part of what a cached entry is found by, so it never moves)."""
    return os.environ.get(CACHE_DIR_ENV) or DEFAULT_CACHE_DIR


def configure(
    cache_dir: Optional[str] = None,
    *,
    min_compile_time_secs: float = DEFAULT_MIN_COMPILE_SECS,
) -> str:
    """Point jax at the persistent compilation cache.  Idempotent; safe
    before or after backend init (changing the directory mid-process
    resets jax's internal cache handle, which otherwise keeps serving
    the OLD directory).  With no ``cache_dir`` and
    ``$JAX_COMPILATION_CACHE_DIR`` set, jax's own setting from that
    variable is left alone.  Returns the cache dir in effect."""
    import jax

    if os.environ.get("XLA_FLAGS"):
        # compile options are part of the persistent-cache KEY: a
        # process running under XLA_FLAGS computes different keys than
        # the warm tool (which pins its env via pin_cache_key_env), so
        # warmed entries are invisible and first dispatch compiles
        # cold.  Warn — don't silently strip: XLA_FLAGS can be a
        # deliberate operator choice (e.g. the multichip dryrun's
        # host_platform_device_count).
        _log.warning(
            "XLA_FLAGS is set: persistent compilation-cache keys will "
            "not match `python -m lodestar_tpu.aot warm` (which clears "
            "it) — warmed programs may recompile cold"
        )
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_time_secs
    )
    if cache_dir is None and os.environ.get(CACHE_DIR_ENV):
        return os.environ[CACHE_DIR_ENV]
    cache_dir = cache_dir or DEFAULT_CACHE_DIR
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    if prev is not None and prev != cache_dir:
        from jax._src import compilation_cache as _cc

        _cc.reset_cache()
    return cache_dir


def pin_cache_key_env(environ: Optional[Dict[str, str]] = None) -> None:
    """Make the persistent-cache KEY deterministic across invokers by
    clearing XLA_FLAGS (compile options are part of the key: a cache
    warmed under a builder shell's stray flags is invisible to the
    driver's bare ``python bench.py`` — the round-4 failure mode).
    Call BEFORE the first jax backend use.  Mutates ``environ``
    (default: os.environ)."""
    env = environ if environ is not None else os.environ
    env.pop("XLA_FLAGS", None)


# ---------------------------------------------------------------------------
# persistent-cache spy
# ---------------------------------------------------------------------------

_spy_lock = threading.Lock()
_SPY: Dict[str, object] = {"installed": False}
_CALLBACKS: List[Callable[[str, str, float], None]] = []
_STATS = {"hits": 0, "misses": 0, "puts": 0, "load_errors": 0}
_KEYS: Dict[str, str] = {}  # cache_key -> last event kind


def install_cache_spy(
    callback: Optional[Callable[[str, str, float], None]] = None,
) -> None:
    """Wrap the persistent-cache read/write path.  ``callback`` (if
    given) receives (kind, cache_key, seconds) with kind in
    {"hit", "miss", "put", "load_error"}; seconds is the stored/observed
    compile time (0.0 on miss).  The executable store
    (``aot/exec_store.py``) reports through the same callbacks with the
    ``exec_*`` kinds.  Idempotent: the wrappers install once per
    process, callbacks accumulate."""
    with _spy_lock:
        if callback is not None:
            _CALLBACKS.append(callback)
        if _SPY["installed"]:
            return
        from jax._src import compilation_cache as cc

        orig_get = cc.get_executable_and_time
        orig_put = cc.put_executable_and_time

        def spy_get(cache_key, *args, **kwargs):
            from lodestar_tpu.testing import faults

            try:
                try:
                    faults.fire("aot.cache.get", cache_key=cache_key)
                    executable, compile_time = orig_get(
                        cache_key, *args, **kwargs
                    )
                except Exception as first_err:
                    # retry ONCE before declaring the entry poisoned: a
                    # transient I/O hiccup (flaky disk/NFS read) must
                    # not evict a healthy entry and force a multi-
                    # minute recompile — genuine deserialization
                    # failures are deterministic and fail again
                    _log.debug(
                        "persistent-cache load of %s failed once "
                        "(%s: %s); retrying before quarantine",
                        cache_key, type(first_err).__name__, first_err,
                    )
                    faults.fire("aot.cache.get", cache_key=cache_key)
                    executable, compile_time = orig_get(
                        cache_key, *args, **kwargs
                    )
            except Exception as e:
                # Self-heal (tentpole b): the entry EXISTS but cannot
                # deserialize — the one known production fault (a
                # poisoned 111 MB pairing entry kept full-pairing
                # multichip red for five rounds, because jax never
                # rewrites a failed-load key).  Quarantine the corrupt
                # bytes aside and report a MISS: jax recompiles and the
                # following put writes a fresh entry under the same key.
                try:
                    quarantined = quarantine_entry(
                        _current_cache_dir(), cache_key
                    )
                except OSError as qe:
                    # a read-only/permission-locked cache dir: the
                    # quarantine is best-effort — still degrade to a
                    # miss so the compile proceeds (the poisoned file
                    # stays, but this process gets its executable)
                    _log.warning(
                        "could not quarantine poisoned entry %s (%s: "
                        "%s)", cache_key, type(qe).__name__, qe,
                    )
                    quarantined = None
                _log.warning(
                    "persistent-cache entry %s failed to load (%s: %s); "
                    "quarantined to %s — recompiling",
                    cache_key,
                    type(e).__name__,
                    e,
                    quarantined or "<no file found>",
                )
                emit("load_error", cache_key, 0.0)
                return None, None
            if executable is not None:
                emit("hit", cache_key, float(compile_time or 0))
            else:
                emit("miss", cache_key, 0.0)
            return executable, compile_time

        def spy_put(cache_key, *args, **kwargs):
            from lodestar_tpu.testing import faults

            faults.fire("aot.cache.put", cache_key=cache_key)
            # signature: (cache_key, module_name, executable, backend,
            # compile_time:int seconds)
            seconds = 0.0
            if args:
                try:
                    seconds = float(args[-1])
                except (TypeError, ValueError):
                    seconds = 0.0
            # is this put the rewrite half of a self-heal?  (load_error
            # was this key's last event before the recompile)
            healed = _KEYS.get(cache_key) == "load_error"
            emit("put", cache_key, seconds)
            result = orig_put(cache_key, *args, **kwargs)
            if healed:
                # re-stamp the warm manifest's entry hash: the healed
                # bytes need not match the warm-time fingerprint, and a
                # stale hash would make the next `warm --check` call
                # this freshly-healed entry "corrupt"
                try:
                    from lodestar_tpu.aot import warm as _warm

                    _warm.refresh_entry_hash(_current_cache_dir(), cache_key)
                except Exception as e:
                    _log.debug(
                        "manifest hash refresh after self-heal failed: "
                        "%s: %s", type(e).__name__, e,
                    )
            return result

        cc.get_executable_and_time = spy_get
        cc.put_executable_and_time = spy_put
        _SPY["installed"] = True


def remove_cache_spy_callback(
    callback: Callable[[str, str, float], None],
) -> None:
    """Unregister a callback added by ``install_cache_spy``.  The spy
    wrappers stay installed (they are process-global and idempotent),
    but the callback — and whatever it strongly references, e.g. a
    closed verifier pool — is released."""
    # Reviewed exception: the lock guards a bare list.remove —
    # microseconds, never held across I/O or a compile — and the async
    # caller (DeviceBlsVerifier.close) runs it once at teardown.
    with _spy_lock:  # lodelint: disable=transitive-blocking
        try:
            _CALLBACKS.remove(callback)
        except ValueError:
            pass


_STAT_KEY = {
    "hit": "hits",
    "miss": "misses",
    "put": "puts",
    "load_error": "load_errors",
}


def emit(kind: str, cache_key: str, seconds: float) -> None:
    """Count one cache event and hand it to every callback."""
    stat = _STAT_KEY.get(kind, kind)
    _STATS[stat] = _STATS.get(stat, 0) + 1
    _KEYS[cache_key] = kind
    for cb in list(_CALLBACKS):
        try:
            cb(kind, cache_key, seconds)
        except Exception as e:
            # a metrics sink must never break a compile — but a broken
            # sink must not be invisible either
            _log.debug(
                "cache-spy callback failed: %s: %s", type(e).__name__, e
            )


def cache_stats() -> Dict[str, int]:
    """Snapshot of persistent-cache traffic since the spy installed."""
    return dict(_STATS)


def observed_keys() -> Dict[str, str]:
    """cache_key -> last event kind ("hit"/"miss"/"put")."""
    return dict(_KEYS)


def reset_stats() -> None:
    for k in list(_STATS):
        _STATS[k] = 0
    _KEYS.clear()


def entry_exists(cache_dir: str, cache_key: str) -> bool:
    """True if a persistent-cache entry for ``cache_key`` is on disk
    (jax's LRU file cache stores ``<key>-cache``; the plain layout
    stores ``<key>``)."""
    return os.path.isfile(os.path.join(cache_dir, cache_key + "-cache")) or (
        os.path.isfile(os.path.join(cache_dir, cache_key))
    )


def entry_paths(cache_dir: str, cache_key: str) -> List[str]:
    """On-disk file(s) holding ``cache_key``'s entry (either layout)."""
    out = []
    for suffix in ("-cache", ""):
        p = os.path.join(cache_dir, cache_key + suffix)
        if os.path.isfile(p):
            out.append(p)
    return out


# ---------------------------------------------------------------------------
# corrupt-entry quarantine (self-healing cache — tentpole b)
# ---------------------------------------------------------------------------

QUARANTINE_DIR = "quarantine"


def quarantine_dir(cache_dir: str) -> str:
    return os.path.join(cache_dir, QUARANTINE_DIR)


def quarantine_entry(cache_dir: str, cache_key: str) -> Optional[str]:
    """Move a corrupt entry's file(s) into ``<cache>/quarantine/``,
    preserving the bytes for post-mortem — NEVER delete, and never
    touch any other entry.  Returns the first quarantined path (None if
    no file was on disk).  Name collisions from repeated poisonings get
    a numeric suffix instead of overwriting earlier evidence."""
    moved: Optional[str] = None
    qdir = quarantine_dir(cache_dir)
    for src in entry_paths(cache_dir, cache_key):
        os.makedirs(qdir, exist_ok=True)
        dst = os.path.join(qdir, os.path.basename(src))
        n = 1
        while os.path.exists(dst):
            dst = os.path.join(qdir, f"{os.path.basename(src)}.{n}")
            n += 1
        os.replace(src, dst)
        moved = moved or dst
    return moved


def quarantined_files(cache_dir: str) -> List[str]:
    qdir = quarantine_dir(cache_dir)
    if not os.path.isdir(qdir):
        return []
    return sorted(
        os.path.join(qdir, f) for f in os.listdir(qdir)
        if os.path.isfile(os.path.join(qdir, f))
    )


def _current_cache_dir() -> str:
    """The dir jax is ACTUALLY using right now (falls back to the
    configured repo dir when jax has none set)."""
    try:
        import jax

        d = jax.config.jax_compilation_cache_dir
        if d:
            return d
    except ImportError:  # no jax in this process: the configured default
        pass
    return repo_cache_dir()
