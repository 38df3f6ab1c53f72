"""Store of serialized compiled executables: a warm start loads a
program with nothing traced or lowered.

On a warm start JAX re-traces and re-lowers a program only to compute
the key of the persistent-cache entry it already holds.  For the verify
programs that is ~125 s of host work in every process (PERF.md §5).
This store keys a compiled executable by what decides its bytes and
loads it directly:

- the program (``jit_<fn name>``), its argument pytree, and each leaf's
  shape, dtype and weak type;
- ``warm.environment_key()``: backend, JAX version, and the fingerprint
  of the kernel sources (``warm.source_files()``);
- the jaxlib version, the device kind and the backend's platform
  version;
- the fp engine's options that ``opcache`` folds into its trace key,
  ``XLA_FLAGS``, ``LIBTPU_INIT_ARGS`` and ``jax_enable_x64``.

Entries live in ``<compilation cache dir>/executables/``, named
``<program>-<environment tag>-<digest>``, compressed as JAX compresses
its own entries (zstd where installed, else zlib).  Writing an entry
removes the program's entries whose environment tag differs: after a
source or jax upgrade the stale ones go with the first new write.  The
store is on exactly where JAX's compilation cache directory is
configured, and nothing in the key is set by a user.  JAX's own cache
leaves the subdirectory alone: its LRU eviction globs the top level
only.

Reading an entry never raises: an entry that is unreadable, truncated,
fails its digest or fails to deserialize is counted, removed and
handled as a miss, so the program is compiled and written afresh.
Events reach the ``aot.cache`` spy callbacks keyed by the entry's name:
``exec_hit`` (seconds: load time), ``exec_miss``, ``exec_put`` (seconds:
serialize and write time) and ``exec_load_error``.
"""
from __future__ import annotations

import hashlib
import logging
import os
import pickle
import tempfile
import time
import zlib
from typing import Dict, Optional, Sequence

from . import cache as aot_cache

try:
    import zstandard
except ImportError:
    zstandard = None

_log = logging.getLogger(__name__)

STORE_DIR = "executables"


def store_dir() -> Optional[str]:
    """``<compilation cache dir>/executables``, or None where JAX has no
    compilation cache configured (the store is then off)."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    cache_dir = jax.config.jax_compilation_cache_dir
    return os.path.join(cache_dir, STORE_DIR) if cache_dir else None


def environment() -> Dict[str, str]:
    """Everything besides the program and its avals that decides the
    executable's bytes."""
    import jax
    import jaxlib

    from lodestar_tpu.ops.bls12_381 import opcache

    from . import warm

    device = jax.devices()[0]
    return {
        **warm.environment_key(),
        "jaxlib": jaxlib.__version__,
        "device_kind": device.device_kind,
        "platform_version": device.client.platform_version,
        "fp_engine": repr(opcache.env_key()),
        "XLA_FLAGS": os.environ.get("XLA_FLAGS", ""),
        "LIBTPU_INIT_ARGS": os.environ.get("LIBTPU_INIT_ARGS", ""),
        "x64": str(jax.config.jax_enable_x64),
    }


def _sha256(material) -> str:
    return hashlib.sha256(repr(material).encode()).hexdigest()


def environment_tag(env: Optional[list] = None) -> str:
    """The part of an entry's name that changes with ``environment()``."""
    return _sha256(env or sorted(environment().items()))[:16]


def entry_key(name: str, treedef, avals: Sequence[tuple]) -> str:
    """``<name>-<environment tag>-<sha256>``: the entry's file name and
    its event key.  ``avals``: each argument leaf's (shape, dtype, weak
    type)."""
    env = sorted(environment().items())
    digest = _sha256((name, str(treedef), list(avals), env))
    return f"{name}-{environment_tag(env)}-{digest}"


def signature(args: tuple):
    """(treedef, avals) of ``args``: what selects a compiled program,
    whether the arguments are host or device arrays."""
    import jax

    leaves, treedef = jax.tree.flatten(args)
    avals = tuple(
        (tuple(a.shape), str(a.dtype), bool(a.weak_type))
        for a in map(jax.typeof, leaves)
    )
    return treedef, avals


def load_or_compile(name: str, jitted, args: tuple, treedef, avals: Sequence[tuple]):
    """``jitted`` compiled for ``args``: loaded from the store where it
    holds an entry, else compiled (JAX's persistent cache still serves an
    unchanged program) and written.  With the store off, ``jitted``
    itself."""
    directory = store_dir()
    if directory is None:
        return jitted
    key = entry_key(name, treedef, avals)
    path = os.path.join(directory, key)
    compiled = _load(path, key)
    if compiled is None:
        # A miss dispatches ``jitted`` itself, exactly as a start without
        # the store does: one trace, one lowering, one compile.
        # ``lower().compile()`` then finds all three in JAX's in-memory
        # caches (JAX still reports a trace event for the cache hit, of
        # well under a millisecond).
        jitted(*args)
        compiled = jitted.lower(*args).compile()
        _put(path, key, compiled)
    return compiled


def save(name: str, compiled, args: tuple) -> Optional[str]:
    """Write ``compiled`` (``name`` compiled for ``args``) as the entry a
    served call will load; its key, or None where the store is off or the
    write failed.  For ``aot warm``, which compiles through ``lower()``."""
    directory = store_dir()
    if directory is None:
        return None
    key = entry_key(name, *signature(args))
    return key if _put(os.path.join(directory, key), key, compiled) else None


def holds(cache_dir: str, key: Optional[str]) -> bool:
    """Whether ``<cache_dir>/executables`` holds entry ``key``, written in
    this environment."""
    parts = (key or "").rsplit("-", 2)
    if len(parts) != 3 or parts[1] != environment_tag():
        return False
    return os.path.isfile(os.path.join(cache_dir, STORE_DIR, key))


def _load(path: str, key: str):
    import jax
    from jax.experimental import serialize_executable

    t0 = time.monotonic()
    if not os.path.exists(path):
        aot_cache.emit("exec_miss", key, 0.0)
        return None
    try:
        with open(path, "rb") as fh:
            entry = pickle.load(fh)
        if hashlib.sha256(entry["executable"]).hexdigest() != entry["sha256"]:
            raise ValueError("executable bytes do not match their digest")
        by_id = {d.id: d for d in jax.devices()}
        compiled = serialize_executable.deserialize_and_load(
            _decompress(entry["codec"], entry["executable"]),
            entry["in_tree"],
            entry["out_tree"],
            execution_devices=[by_id[i] for i in entry["devices"]],
        )
    except Exception as e:
        _log.warning(
            "executable store entry %s failed to load (%s: %s); removed, "
            "compiling afresh", key, type(e).__name__, e,
        )
        try:
            os.remove(path)
        except OSError:
            pass
        aot_cache.emit("exec_load_error", key, 0.0)
        return None
    aot_cache.emit("exec_hit", key, time.monotonic() - t0)
    return compiled


def _compress(payload: bytes):
    """(codec, compressed payload)."""
    if zstandard is not None:
        return "zstd", zstandard.ZstdCompressor().compress(payload)
    return "zlib", zlib.compress(payload)


def _decompress(codec: str, data: bytes) -> bytes:
    if codec == "zstd":
        return zstandard.ZstdDecompressor().decompress(data)
    if codec == "zlib":
        return zlib.decompress(data)
    raise ValueError(f"unknown codec {codec!r}")


def _put(path: str, key: str, compiled) -> bool:
    """Write ``compiled`` under ``path`` atomically (a temp file in the
    same directory, then a rename), then remove the program's entries of
    other environments.  A failure is logged, never raised: the compiled
    program serves this process either way."""
    import jax
    from jax.experimental import serialize_executable

    t0 = time.monotonic()
    tmp = None
    try:
        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        codec, data = _compress(payload)
        shardings = jax.tree.leaves((compiled.input_shardings, compiled.output_shardings))
        entry = {
            "codec": codec,
            "executable": data,
            "sha256": hashlib.sha256(data).hexdigest(),
            "in_tree": in_tree,
            "out_tree": out_tree,
            "devices": sorted({d.id for s in shardings for d in s.device_set}),
        }
        directory = os.path.dirname(path)
        os.makedirs(directory, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=directory, prefix=f"{key}.", suffix=".tmp")
        with os.fdopen(fd, "wb") as fh:
            pickle.dump(entry, fh, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, path)
    except Exception as e:
        _log.warning(
            "could not write executable store entry %s (%s: %s)",
            key, type(e).__name__, e,
        )
        if tmp is not None:
            try:
                os.remove(tmp)
            except OSError:  # renamed into place already, or never made
                pass
        return False
    aot_cache.emit("exec_put", key, time.monotonic() - t0)
    _prune(os.path.dirname(path), key)
    return True


def _prune(directory: str, key: str) -> None:
    """Remove the entries of ``key``'s program whose environment tag is
    not ``key``'s: no process in this environment reads them again."""
    name, tag, _ = key.rsplit("-", 2)
    for fname in os.listdir(directory):
        parts = fname.rsplit("-", 2)
        if (len(parts) == 3 and parts[0] == name and parts[1] != tag
                and not fname.endswith(".tmp")):
            try:
                os.remove(os.path.join(directory, fname))
            except OSError:  # another process removed it first
                pass
