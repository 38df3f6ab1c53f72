"""Trace-once op cache — the program-build-time fix for the BLS engine.

Measured on the 1-CPU bench host (tools/probe_dedupe.py): every
``pl.pallas_call`` re-traces its kernel body at EVERY call site (~0.5 s
trace + ~0.25 s lowering per ``f2_mul`` site), while XLA itself dedupes
identical kernels fine (256 chained sites: 136 s trace + 61 s lower vs
22 s compile).  The same re-trace tax applies to the CPU path's
``lax.scan`` bodies (CIOS loop, ``tower.outlined`` wrappers).  Program
BUILD time — not XLA optimization — was the dominant term in the cold
420 s+ bench stages.

``cached(fn)`` traces ``fn`` once per (argument shapes/dtypes, static
args, platform) into a ClosedJaxpr and replays it with ``eval_jaxpr`` at
every subsequent call site: one primitive bind per inner primitive, no
kernel/scan re-tracing, and — because all sites now share one jaxpr
object — the param-identity-keyed lowering caches hit as well.

Replay inlines the jaxpr into the caller's trace, so jit/vmap/shard_map
semantics are exactly those of calling ``fn`` directly.  The cache key
includes the fp platform dispatch state (LODESTAR_TPU_FP_PLATFORM /
PALLAS toggles) because those select different traced code paths.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax._src.core import eval_jaxpr as _eval_jaxpr

_CACHE: dict = {}


def env_key():
    import os

    from . import fp

    return (
        fp._target_platform(),
        fp._use_pallas(),
        os.environ.get("LODESTAR_TPU_CPU_PARALLEL_FP"),
    )


def _leaf_aval(leaf) -> tuple | None:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return None
    return (tuple(shape), str(dtype))


def cached(fn, static_argnums: tuple = ()):
    """Wrap an op so its jaxpr is traced once per shape and replayed."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if kwargs:  # keyword calls bypass the cache (key would be ambiguous)
            return fn(*args, **kwargs)
        statics = tuple(args[i] for i in static_argnums if i < len(args))
        dyn = tuple(a for i, a in enumerate(args) if i not in static_argnums)
        leaves, treedef = jax.tree.flatten(dyn)
        avals = []
        for leaf in leaves:
            av = _leaf_aval(leaf)
            if av is None:  # non-array leaf (None, python scalar): bypass
                return fn(*args)
            avals.append(av)
        if any(jax.typeof(leaf).vma for leaf in leaves):
            # inside a checked shard_map: the replayed jaxpr was traced
            # without varying axes, so its scans would mistype the
            # carries; trace the op itself instead
            return fn(*args)
        try:
            key = (fn, statics, treedef, tuple(avals), env_key())
            hash(key)
        except TypeError:
            return fn(*args)
        entry = _CACHE.get(key)
        if entry is None:
            structs = [
                jax.ShapeDtypeStruct(s, d) for (s, d) in avals
            ]

            def flat_fn(*flat):
                dyn_t = iter(jax.tree.unflatten(treedef, flat))
                full = [
                    a if i in static_argnums else next(dyn_t)
                    for i, a in enumerate(args)
                ]
                return fn(*full)

            closed, out_shape = jax.make_jaxpr(flat_fn, return_shape=True)(
                *structs
            )
            _, out_tree = jax.tree.flatten(out_shape)
            entry = (closed, out_tree)
            _CACHE[key] = entry
        closed, out_tree = entry
        out = _eval_jaxpr(closed.jaxpr, closed.consts, *leaves)
        return jax.tree.unflatten(out_tree, out)

    wrapper.__wrapped_uncached__ = fn
    return wrapper


def clear() -> None:
    _CACHE.clear()
