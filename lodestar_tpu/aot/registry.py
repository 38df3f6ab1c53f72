"""AOT program registry — every jit program the node can dispatch.

The BLS verify kernels used to be jitted by ad-hoc module-level
closures in ops/bls12_381/verify.py; nothing enumerated which
(kernel, bucket) shapes a node would actually run, so the warm tooling
had to guess and the latency governor could mint program shapes nobody
ever compiled.  This registry is now the single source of truth:

- ``jitted(kernel)`` hands out THE memoized ``jax.jit`` wrapper per
  kernel (verify.py's ``_jit_*`` attributes are these objects, and the
  lodelint ``unregistered-jit`` rule keeps any other module-scope
  ``jax.jit`` out of ``lodestar_tpu/``);
- ``call(kernel, *args)`` is how every served call dispatches: through
  the executable store, so a warm start loads each program without
  tracing it;
- ``registered_programs()`` enumerates the concrete (kernel, bucket)
  entries — with example avals — that ``python -m lodestar_tpu.aot
  warm`` compiles into the persistent cache.

Scopes: the default ``core`` scope is the set a production node + the
bench actually dispatch (bench stages, the pool's quantized widths, the
sync-committee fast-aggregate bucket) — deliberately small because one
cold compile costs ~15-40 min on a 2-core host.  ``full`` adds every
direct-call bucket for belt-and-braces coverage.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

from lodestar_tpu.ops.bls12_381 import buckets as bk

from . import exec_store

RAND_BITS = 64  # production random-coefficient width (bits)

_KERNELS: Dict[str, Callable] = {}


def register_kernels(**kernels: Callable) -> None:
    """Called by ops/bls12_381/verify.py at import with its kernel
    functions (batch/hashed/each/fast_agg)."""
    _KERNELS.update(kernels)


def ensure_kernels() -> Dict[str, Callable]:
    if not _KERNELS:
        # verify.py registers its kernels at import time
        import lodestar_tpu.ops.bls12_381.verify  # noqa: F401
    return _KERNELS


_JITTED: Dict[str, object] = {}


def jitted(kernel: str):
    """THE jit wrapper for a kernel — one object per process, so every
    call site shares one trace cache and the persistent-cache filename
    is stable (``jit_<fn name>-<key>``).

    Memoized with an explicit dict, NOT lru_cache: resolving the kernel
    table can import ops/bls12_381/verify.py, whose module body calls
    jitted() reentrantly — under lru_cache the outer frame would mint a
    SECOND wrapper and overwrite the reentrant one, silently splitting
    the trace cache by import order.  Resolving kernels BEFORE the
    memo check makes the reentrant wrapper the one everybody gets."""
    fns = ensure_kernels()
    if kernel in _JITTED:
        return _JITTED[kernel]
    if kernel not in fns:
        raise KeyError(
            f"unknown kernel {kernel!r} (registered: {sorted(fns)})"
        )
    import jax

    # Reviewed exception: this IS the memoized factory jit-in-func
    # points everyone at — the dict above guarantees one wrapper per
    # kernel per process (lru_cache would double-mint on the reentrant
    # verify.py import; see docstring).
    wrapper = _JITTED[kernel] = jax.jit(  # lodelint: disable=jit-in-func
        fns[kernel]
    )
    return wrapper


# (kernel, treedef, avals) -> what serves that signature in this process
_SERVED: Dict[tuple, Callable] = {}
_first_dispatch = threading.Lock()


def call(kernel: str, *args):
    """Dispatch ``kernel`` on ``args``: the path of every served call.

    The first call at an argument signature takes the program from the
    executable store (``aot/exec_store.py``): loaded where the store
    holds it, else compiled and written; one thread does so while the
    others wait for it.  A later call adds a lookup on the leaves' avals
    and never traces."""
    treedef, avals = exec_store.signature(args)
    signature = (kernel, treedef, avals)
    fn = _SERVED.get(signature)
    if fn is None:
        with _first_dispatch:
            fn = _SERVED.get(signature)
            if fn is None:
                name = f"jit_{ensure_kernels()[kernel].__name__}"
                fn = _SERVED[signature] = exec_store.load_or_compile(
                    name, jitted(kernel), args, treedef, avals
                )
    return fn(*args)


@dataclass(frozen=True)
class Program:
    """One compilable program: a kernel at a concrete batch bucket,
    optionally sharded over a ``mesh_size``-device (sp,) mesh
    (``mesh_size=0`` means the ordinary single-device program)."""

    kernel: str  # "batch" | "hashed" | "each" | "fast_agg" | "sharded"
    bucket: int
    priority: int = 100  # warm order: lower first
    note: str = ""
    mesh_size: int = 0  # 0 = unsharded; else devices on the (sp,) mesh

    @property
    def key(self) -> str:
        base = f"{self.kernel}/b{self.bucket}"
        return f"{base}@m{self.mesh_size}" if self.mesh_size else base

    def fn(self):
        if self.mesh_size:
            from lodestar_tpu.ops.bls12_381 import sharded

            return sharded.jitted_sharded(self.mesh_size)
        return jitted(self.kernel)

    def fn_name(self) -> str:
        """Underlying function name — the persistent-cache filename
        prefix is ``jit_<fn_name>-``."""
        if self.mesh_size:
            return "sharded_verify"
        return ensure_kernels()[self.kernel].__name__

    def example_args(self) -> tuple:
        """Concrete zero/padding inputs with the exact avals the host
        wrappers produce at this bucket (values never matter for the
        cache key — only shapes/dtypes do)."""
        return _example_args(self.kernel, self.bucket)


def _example_args(kernel: str, B: int) -> tuple:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lodestar_tpu.ops.bls12_381 import curve as cv

    pk_aff, pk_inf = cv.encode_g1_affine([None] * B)
    sig_aff, sig_inf = cv.encode_g2_affine([None] * B)
    active = jnp.asarray(np.zeros(B, dtype=bool))
    bits = cv.scalars_to_bits([1] * B, RAND_BITS)
    if kernel == "hashed":
        from lodestar_tpu.ops.bls12_381 import h2c

        u0, u1 = h2c.encode_field_draws([], B)
        return (pk_aff, pk_inf, u0, u1, sig_aff, sig_inf, bits, active)
    msg_aff, msg_inf = cv.encode_g2_affine([None] * B)
    if kernel == "batch":
        return (pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, bits, active)
    if kernel == "sharded":
        # ops/bls12_381/sharded.py arg order (active before bits,
        # matching __graft_entry__'s dryrun signature)
        return (pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, active, bits)
    if kernel == "each":
        return (pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, active)
    if kernel == "fast_agg":
        squeeze = lambda t: jax.tree.map(lambda x: x[0], t)
        return (
            pk_aff,
            pk_inf,
            squeeze(msg_aff),
            msg_inf[0],
            squeeze(sig_aff),
            sig_inf[0],
            active,
        )
    raise KeyError(f"unknown kernel {kernel!r}")


# ---------------------------------------------------------------------------
# the registered set
# ---------------------------------------------------------------------------


def _device_h2c() -> Optional[bool]:
    from lodestar_tpu.ops.bls12_381 import verify as dv

    return dv.use_device_h2c()


def bench_buckets() -> List[int]:
    """The widths bench.py stages dispatch (flagship + fallback)."""
    batch_max = int(os.environ.get("BENCH_BATCH_MAX", "4096"))
    return list(dict.fromkeys((min(1024, batch_max), batch_max)))


def registered_programs(
    scope: str = "core", device_h2c: Optional[bool] = None
) -> List[Program]:
    """The programs ``warm`` compiles and ``warm --check`` requires.

    Priority order matters operationally: warming is resumable but each
    cold program costs tens of minutes on the 2-core host, so the bench
    fallback stage comes first — the first completed warm invocation is
    enough for bench to bank a real number.
    """
    if scope not in ("core", "full"):
        raise ValueError(f"unknown scope {scope!r} (core|full)")
    if device_h2c is None:
        device_h2c = _device_h2c()
    from lodestar_tpu.chain.bls import device_pool as dp
    from lodestar_tpu.params import SYNC_COMMITTEE_SIZE

    progs: List[Program] = []
    # 1. bench stages (bench uses the device-h2c kernel explicitly:
    #    end-to-end message-bytes -> bool is the headline metric)
    for i, b in enumerate(bench_buckets()):
        progs.append(
            Program("hashed", b, priority=i, note="bench stage")
        )
    # 2. the pool's quantized dispatch widths for the node's verify
    #    kernel (h2c mode decides which kernel that is).  EVERY rung up
    #    to the overload drain width is reachable (partial packs
    #    quantize to the smallest rung that holds them), so every rung
    #    is registered.  The per-set fallback kernel ("each") is FULL
    #    scope only: it dispatches exclusively after a failed batch — a
    #    misbehaving-peer event, not the steady path — and each core
    #    program costs tens of minutes of warm time on a 2-core host
    #    (docs/AOT.md discusses the tradeoff).
    vk = "hashed" if device_h2c else "batch"
    drain = bk.align_down(dp.MAX_SIGNATURE_SETS_PER_JOB)
    pool_widths = sorted(b for b in bk.POOL_BUCKETS if b <= drain)
    for b in pool_widths:
        progs.append(Program(vk, b, priority=10, note="pool dispatch"))
    # 3. sync-committee fast aggregate (fastAggregateVerify path)
    progs.append(
        Program(
            "fast_agg",
            bk.bucket_size(SYNC_COMMITTEE_SIZE),
            priority=30,
            note="sync committee",
        )
    )
    if scope == "full":
        for b in pool_widths:
            progs.append(Program("each", b, priority=40, note="pool fallback"))
        widths = set(bk.BUCKETS) | set(bk.POOL_BUCKETS)
        widths |= set(
            range(bk.BUCKETS[-1], dp.MAX_SIGNATURE_SETS_PER_JOB + 1, 512)
        )
        for b in sorted(widths):
            for k in (vk, "each"):
                progs.append(Program(k, b, priority=50, note="full sweep"))
        for b in bk.BUCKETS:
            progs.append(Program("fast_agg", b, priority=60, note="full sweep"))
        # mesh-parameterized sharded verify (ops/bls12_381/sharded.py):
        # one entry per (bucket, mesh geometry) this host can actually
        # build — warming a sharded program on a host with too few
        # devices would abort the whole warm run, so the gate is on
        # live device count.  Full scope only: a cold sharded pairing
        # compile costs hours on XLA:CPU (docs/AOT.md).
        from lodestar_tpu.ops.bls12_381 import sharded as sh

        import jax

        n_dev = len(jax.devices())
        for m in sh.SUPPORTED_MESH_SIZES:
            if m > n_dev:
                continue
            for b in sh.SHARDED_BUCKETS:
                progs.append(
                    Program(
                        "sharded", b, priority=70, note="sharded verify",
                        mesh_size=m,
                    )
                )
    # dedupe by key, keeping the highest-priority (lowest number) entry
    seen: Dict[str, Program] = {}
    for p in sorted(progs, key=lambda p: p.priority):
        seen.setdefault(p.key, p)
    return sorted(seen.values(), key=lambda p: (p.priority, p.bucket))


def registered_keys(scope: str = "core", device_h2c: Optional[bool] = None) -> List[str]:
    return [p.key for p in registered_programs(scope, device_h2c)]
