"""Resumable AOT warmer + freshness manifest.

``warm_programs()`` lowers + compiles each registered program into the
persistent cache and records it in ``<cache>/warm_manifest.json``, one
entry per program, SAVED AFTER EVERY PROGRAM: on the 2-core driver host
a single pairing compile costs ~15-40 minutes, so a warm run killed by
an external timeout must bank every finished program — the next
invocation skips them (manifest fresh + cache entry on disk) and picks
up where it left off.

Manifest freshness is keyed by (backend, jax version, source
fingerprint): the fingerprint hashes the kernel-relevant sources
(ops/bls12_381, crypto/bls, aot), so editing a kernel invalidates
exactly the manifest — never the cache files themselves.  Nothing here
ever deletes ``.jax_cache`` entries; stale entries are merely
recompiled under their new keys.

Each warmed program is additionally serialized to
``<cache>/export/<kernel>_b<bucket>.bin`` (portable StableHLO, usable
for cross-process AOT loading); failures are recorded, not fatal.
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

from . import cache as aot_cache

MANIFEST_NAME = "warm_manifest.json"
SCHEMA = 2

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
# sources whose edits can change a compiled kernel (or what gets warmed)
SOURCE_DIRS = (
    "lodestar_tpu/ops/bls12_381",
    "lodestar_tpu/crypto/bls",
    "lodestar_tpu/aot",
)


def source_fingerprint() -> str:
    """sha256 over the kernel-relevant source tree (path + content)."""
    h = hashlib.sha256()
    for d in SOURCE_DIRS:
        root = os.path.join(_REPO_ROOT, d)
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = sorted(x for x in dirnames if x != "__pycache__")
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, fn), _REPO_ROOT)
                h.update(rel.encode())
                with open(os.path.join(dirpath, fn), "rb") as fh:
                    h.update(hashlib.sha256(fh.read()).digest())
    return h.hexdigest()


def environment_key() -> Dict[str, str]:
    import jax

    return {
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "source": source_fingerprint(),
    }


# ---------------------------------------------------------------------------
# manifest
# ---------------------------------------------------------------------------


def manifest_path(cache_dir: Optional[str] = None) -> str:
    return os.path.join(cache_dir or aot_cache.repo_cache_dir(), MANIFEST_NAME)


def load_manifest(cache_dir: Optional[str] = None) -> Dict:
    path = manifest_path(cache_dir)
    try:
        with open(path, "r", encoding="utf-8") as fh:
            data = json.load(fh)
    except (OSError, json.JSONDecodeError):
        data = {}
    if data.get("schema") != SCHEMA:
        data = {"schema": SCHEMA, "entries": {}}
    data.setdefault("entries", {})
    return data


def save_manifest(manifest: Dict, cache_dir: Optional[str] = None) -> None:
    """Atomic write (tmp + rename): a killed warm run must never leave
    a half-written manifest that voids earlier banked programs."""
    path = manifest_path(cache_dir)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(manifest, fh, indent=2, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)


def _file_sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as fh:
        for chunk in iter(lambda: fh.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def mismatched_entry_keys(entry: Dict, cache_dir: str) -> List[str]:
    """Cache keys of ``entry`` whose on-disk bytes no longer match the
    sha256 recorded at warm time (reads + hashes each entry file)."""
    out = []
    hashes = entry.get("entry_sha256") or {}
    for k in entry.get("cache_keys") or []:
        recorded = hashes.get(k)
        if not recorded:
            continue  # warmed before hashes were recorded: trusted
        paths = aot_cache.entry_paths(cache_dir, k)
        if paths and _file_sha256(paths[0]) != recorded:
            out.append(k)
    return out


def program_state(
    prog, manifest: Dict, cache_dir: str, envk: Dict[str, str],
    check_hashes: bool = True,
) -> str:
    """"warm" | "stale" | "missing" | "corrupt" for one registered
    program.  "corrupt" means the entry file EXISTS but its bytes no
    longer match the sha256 recorded at warm time — the
    poisoned-cache-entry class ``--check`` previously could not see
    (an entry that exists but cannot deserialize looked "warm").

    ``check_hashes=False`` skips the content hashing and reports such
    entries as "warm": existence/freshness checks are stat-cheap, but
    hashing reads every entry file (hundreds of MB for the pairing
    programs) — callers that only need a freshness gauge (the pool's
    startup probe) must not pay that on a 2-core host."""
    entry = manifest.get("entries", {}).get(prog.key)
    if entry is None:
        return "missing"
    for k in ("backend", "jax", "source"):
        if entry.get(k) != envk[k]:
            return "stale"
    keys = entry.get("cache_keys") or []
    # entries warmed before the spy captured a key are trusted on
    # manifest freshness alone; captured keys are verified on disk
    if keys and not all(aot_cache.entry_exists(cache_dir, k) for k in keys):
        return "missing"
    if check_hashes and mismatched_entry_keys(entry, cache_dir):
        return "corrupt"
    return "warm"


# ---------------------------------------------------------------------------
# warming
# ---------------------------------------------------------------------------


def _try_export(prog, cache_dir: str) -> Tuple[Optional[str], Optional[str]]:
    """Serialize via jax.export; (path, error)."""
    from jax import export as jexport

    try:
        exported = jexport.export(prog.fn())(*prog.example_args())
        blob = exported.serialize()
        out_dir = os.path.join(cache_dir, "export")
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{prog.kernel}_b{prog.bucket}.bin")
        tmp = path + ".tmp"
        with open(tmp, "wb") as fh:
            fh.write(blob)
        os.replace(tmp, path)
        return path, None
    except Exception as e:  # serialization is best-effort by design
        return None, f"{type(e).__name__}: {e}"


def warm_program(prog, cache_dir: str, do_export: bool = True) -> Dict:
    """Lower + compile ONE program (hitting the persistent cache when
    the entry already exists) and return its manifest entry."""
    prefix = f"jit_{prog.fn_name()}-"
    # scoped event capture: a per-call callback (not a global observed-
    # keys delta, which is empty when the same program was already
    # touched earlier in this process — e.g. warm followed by heal)
    events: Dict[str, str] = {}

    def _capture(kind: str, key: str, seconds: float) -> None:
        if key.startswith(prefix):
            events[key] = kind

    aot_cache.install_cache_spy(_capture)
    try:
        t0 = time.monotonic()
        lowered = prog.fn().lower(*prog.example_args())
        lower_s = time.monotonic() - t0
        t1 = time.monotonic()
        lowered.compile()
        compile_s = time.monotonic() - t1
    finally:
        aot_cache.remove_cache_spy_callback(_capture)
    hit = any(kind == "hit" for kind in events.values())
    # content fingerprint of each entry file: ``--check`` compares these
    # so an entry that later rots on disk reports "corrupt", not "warm"
    entry_sha = {}
    for k in events:
        paths = aot_cache.entry_paths(cache_dir, k)
        if paths:
            entry_sha[k] = _file_sha256(paths[0])
    entry = {
        "kernel": prog.kernel,
        "bucket": prog.bucket,
        "cache_keys": sorted(events),
        "cache_hit": hit,
        "entry_sha256": entry_sha,
        "lower_s": round(lower_s, 3),
        "compile_s": round(compile_s, 3),
        "warmed_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if do_export:
        path, err = _try_export(prog, cache_dir)
        if path:
            entry["export"] = os.path.basename(path)
        if err:
            entry["export_error"] = err
    return entry


def warm_programs(
    programs: Sequence,
    cache_dir: Optional[str] = None,
    *,
    budget_s: Optional[float] = None,
    min_compile_time_secs: float = aot_cache.DEFAULT_MIN_COMPILE_SECS,
    do_export: bool = True,
    log=print,
) -> Dict:
    """Warm every program not already fresh, in priority order, saving
    the manifest after EACH program.  ``budget_s`` stops before
    STARTING a program that no longer fits (a started compile runs to
    completion — killing it would bank nothing); the FIRST pending
    program always starts, so even an undersized budget makes forward
    progress across repeated invocations."""
    cache_dir = aot_cache.configure(
        cache_dir, min_compile_time_secs=min_compile_time_secs
    )
    envk = environment_key()
    manifest = load_manifest(cache_dir)
    t0 = time.monotonic()
    report = {"compiled": [], "skipped": [], "deferred": [], "cache_dir": cache_dir}
    for prog in programs:
        state = program_state(prog, manifest, cache_dir, envk)
        if state == "warm":
            report["skipped"].append(prog.key)
            log(f"aot warm: {prog.key} already warm — skipped")
            continue
        if (
            budget_s is not None
            and report["compiled"]
            and time.monotonic() - t0 > budget_s
        ):
            report["deferred"].append(prog.key)
            continue
        log(f"aot warm: compiling {prog.key} ({state}) ...")
        entry = warm_program(prog, cache_dir, do_export=do_export)
        entry.update(envk)
        manifest["entries"][prog.key] = entry
        save_manifest(manifest, cache_dir)  # bank immediately
        report["compiled"].append(prog.key)
        log(
            f"aot warm: {prog.key} done in {entry['compile_s']:.1f}s compile "
            f"(+{entry['lower_s']:.1f}s lower, persistent-cache "
            f"{'HIT' if entry['cache_hit'] else 'miss'})"
        )
    if report["deferred"]:
        log(
            "aot warm: budget exhausted — deferred "
            + ", ".join(report["deferred"])
            + " (re-run to continue; finished programs are banked)"
        )
    return report


def check_programs(
    programs: Sequence,
    cache_dir: Optional[str] = None,
    *,
    check_hashes: bool = True,
) -> Tuple[bool, List[Tuple[str, str]]]:
    """(all_warm, [(program key, state)]).  Read-only: no compiles, no
    lowering — manifest freshness + on-disk cache entries (existence
    and, unless ``check_hashes=False``, content hash)."""
    cache_dir = cache_dir or aot_cache.repo_cache_dir()
    envk = environment_key()
    manifest = load_manifest(cache_dir)
    rows = [
        (p.key, program_state(p, manifest, cache_dir, envk, check_hashes))
        for p in programs
    ]
    return all(state == "warm" for _, state in rows), rows


def refresh_entry_hash(cache_dir: str, cache_key: str) -> bool:
    """Re-stamp the manifest's ``entry_sha256`` for every program whose
    entry was just rewritten under ``cache_key``.

    Called by the cache spy after an in-process self-heal (load failure
    → quarantine → recompile → put): the fresh bytes are NOT guaranteed
    to match the hash recorded at warm time, and without this re-stamp
    the next ``warm --check`` would cry "corrupt" over a healthy entry
    — and ``--heal`` would re-pay the multi-minute compile for nothing.
    Returns True if any manifest entry was updated.

    Takes the warm tool's ``.aot.lock`` (non-blocking): a concurrent
    resumable warm run banks manifest entries program-by-program, and a
    lockless read-modify-write here could overwrite an entry it just
    banked (voiding a 40 min-2 h compile).  If the lock is busy, skip —
    the re-stamp is best-effort and ``warm --heal`` repairs a stale
    hash later anyway."""
    import fcntl

    paths = aot_cache.entry_paths(cache_dir, cache_key)
    if not paths:
        return False
    try:
        lock_fh = open(os.path.join(cache_dir, ".aot.lock"), "w")
    except OSError:
        return False
    try:
        try:
            fcntl.flock(lock_fh, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError:
            return False  # a warm run owns the manifest right now
        manifest = load_manifest(cache_dir)
        new_hash = _file_sha256(paths[0])
        changed = False
        for entry in manifest.get("entries", {}).values():
            hashes = entry.get("entry_sha256")
            if hashes and cache_key in hashes and hashes[cache_key] != new_hash:
                hashes[cache_key] = new_hash
                changed = True
        if changed:
            save_manifest(manifest, cache_dir)
        return changed
    finally:
        lock_fh.close()


# ---------------------------------------------------------------------------
# healing (``warm --heal``)
# ---------------------------------------------------------------------------


def heal_programs(
    programs: Sequence,
    cache_dir: Optional[str] = None,
    *,
    budget_s: Optional[float] = None,
    min_compile_time_secs: float = aot_cache.DEFAULT_MIN_COMPILE_SECS,
    do_export: bool = True,
    log=print,
) -> Dict:
    """Load-round-trip every registered program; quarantine entries
    that are corrupt on disk or fail deserialization; recompile what
    was quarantined or missing.  Healthy entries are NOT rewritten (the
    round-trip is a persistent-cache HIT, which never touches the
    file).

    Two corruption detectors compose here:

    * the manifest's ``entry_sha256`` catches byte rot / truncation
      against the fingerprint recorded at warm time (also what makes
      ``--check`` honest), and
    * the spy's load-error path catches entries whose bytes LOOK intact
      but still fail jax deserialization — those are quarantined by the
      spy mid-compile and rewritten by the put that follows.

    ``budget_s`` mirrors warm_programs: stop before STARTING a
    round-trip that no longer fits (the first program always runs, the
    manifest banks after each, and deferred programs are listed so a
    re-invocation continues).

    Report keys: ``healthy`` (round-tripped clean), ``healed``
    (quarantined + recompiled), ``stale_rewarmed`` (manifest stale or
    entry missing — recompiled), ``quarantined`` (files moved aside),
    ``deferred`` (budget ran out first).
    """
    cache_dir = aot_cache.configure(
        cache_dir, min_compile_time_secs=min_compile_time_secs
    )
    aot_cache.install_cache_spy()
    envk = environment_key()
    manifest = load_manifest(cache_dir)
    t0 = time.monotonic()
    report = {
        "healthy": [],
        "healed": [],
        "stale_rewarmed": [],
        "quarantined": [],
        "deferred": [],
        "cache_dir": cache_dir,
    }
    started = 0
    for prog in programs:
        if (
            budget_s is not None
            and started
            and time.monotonic() - t0 > budget_s
        ):
            report["deferred"].append(prog.key)
            continue
        started += 1
        # one hash pass, not two: classify WITHOUT hashing, then hash
        # each file exactly once to find what needs quarantining
        state = program_state(
            prog, manifest, cache_dir, envk, check_hashes=False
        )
        entry = manifest.get("entries", {}).get(prog.key) or {}
        if state == "warm":
            bad_keys = mismatched_entry_keys(entry, cache_dir)
            if bad_keys:
                state = "corrupt"
                # quarantine BEFORE the round-trip so jax can't load
                # the bad bytes; recompile then rewrites a fresh entry
                for k in bad_keys:
                    moved = aot_cache.quarantine_entry(cache_dir, k)
                    if moved:
                        report["quarantined"].append(moved)
                        log(f"aot heal: quarantined corrupt entry {k} -> {moved}")
        errors_before = aot_cache.cache_stats().get("load_errors", 0)
        q_before = set(aot_cache.quarantined_files(cache_dir))
        log(f"aot heal: round-tripping {prog.key} ({state}) ...")
        new_entry = warm_program(prog, cache_dir, do_export=do_export)
        new_entry.update(envk)
        manifest["entries"][prog.key] = new_entry
        save_manifest(manifest, cache_dir)  # bank immediately
        load_errors = aot_cache.cache_stats().get("load_errors", 0) - errors_before
        # the spy quarantines undeserializable bytes mid-round-trip;
        # report whatever newly landed in the quarantine dir
        report["quarantined"].extend(
            sorted(set(aot_cache.quarantined_files(cache_dir)) - q_before)
        )
        if state == "corrupt" or load_errors:
            report["healed"].append(prog.key)
            log(f"aot heal: {prog.key} healed (recompiled)")
        elif state == "warm" and new_entry.get("cache_hit"):
            report["healthy"].append(prog.key)
        else:
            report["stale_rewarmed"].append(prog.key)
            log(f"aot heal: {prog.key} was {state} — re-warmed")
    if report["deferred"]:
        log(
            "aot heal: budget exhausted — deferred "
            + ", ".join(report["deferred"])
            + " (re-run to continue)"
        )
    return report
