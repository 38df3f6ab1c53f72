"""Multi-chip sharded BLS verification — the manual-collectives
formulation, promoted out of ``__graft_entry__`` into a production
module the lodelint v5 shardcheck rules can see (ISSUE 19; ROADMAP
item 3's architecture step).

The SURVEY's §2.5/§7 ICI mapping: the signature-set batch axis is
sharded over the mesh's ``sp`` axis (data parallelism over signature
sets), each device computes its local r_i·sig_i partial sum and its
local Miller-loop product, the partials ride the ICI in one ``psum``
each (a gather into per-device slots), and one shared final
exponentiation finishes the pairing check.  The GSPMD formulation
(annotate shardings, let XLA insert the collectives) lives in
``__graft_entry__.dryrun_multichip``; THIS module is the explicit-axes
twin kept for real-hardware bringup, where reading the collectives off
the program text matters more than compile time.

Geometry contract (checked statically by lodelint's ``shard-divisibility``
and dynamically by ``tests/test_mesh_smoke.py``): every bucket in
``SHARDED_BUCKETS`` divides evenly over every ``SUPPORTED_MESH_SIZES``
entry, and every per-device quotient is itself a registered AOT rung, so
a mesh dispatch never truncates, pads, or cold-compiles an unwarmed
program shape.

@mesh: sp
"""
from __future__ import annotations

from functools import lru_cache, partial

# the single mesh axis every collective in this module names: data
# parallelism over signature sets (SURVEY §2.5 row 1)
SHARD_AXIS = "sp"

# mesh geometries the node supports (v4e-8 slice and its halvings);
# lodelint's shard-divisibility reads this table live
SUPPORTED_MESH_SIZES = (2, 4, 8)

# dispatch widths the sharded programs accept: each divides every
# supported mesh size AND shards to a per-device width that is itself a
# registered AOT rung (128/8=16 ... 2048/2=1024 are all in
# buckets.BUCKETS), so `aot warm` coverage extends to the shards
SHARDED_BUCKETS = (128, 512, 1024, 2048)


def _slot_stack(tree, n_dev: int):
    """Each leaf placed in this device's slot of an ``(n_dev, ...)``
    zero stack.  A ``psum`` of the stacks is an all_gather whose result
    jax types as replicated (every other slot adds zero, so it is
    exact), which is what lets ``out_specs=P()`` pass ``check_vma``:
    a plain ``all_gather`` output stays typed as varying.

    @mesh: sp
    """
    import jax
    import jax.numpy as jnp

    i = jax.lax.axis_index(SHARD_AXIS)
    return jax.tree.map(
        lambda x: jnp.zeros((n_dev, *x.shape), x.dtype).at[i].set(x), tree
    )


def build_sharded_verify(mesh):
    """Manual-collectives batched signature-set verification over
    ``mesh``: local scalar muls + Miller products per shard, a gather
    (``psum`` of slot stacks) + GT-product reduction over "sp", one
    replicated final exponentiation.  Arg order matches
    ``__graft_entry__``'s dryrun:
    ``(pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, active,
    bits)``.

    @mesh: sp
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from lodestar_tpu.ops.bls12_381 import curve as cv, pairing as pr, tower as tw
    from lodestar_tpu.ops.bls12_381 import verify as dv

    n_dev = mesh.shape[SHARD_AXIS]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS),) * 8,
        out_specs=P(),
    )
    def sharded_verify(pk_aff, pk_inf, msg_aff, msg_inf, sig_aff, sig_inf, active, bits):
        pk_jac = cv.from_affine(cv.F1, pk_aff, pk_inf | ~active)
        sig_jac = cv.from_affine(cv.F2, sig_aff, sig_inf | ~active)
        rpk = cv.scalar_mul_bits(cv.F1, pk_jac, bits)
        rsig = cv.scalar_mul_bits(cv.F2, sig_jac, bits)
        local_sig_sum = dv.jac_reduce_add(cv.F2, rsig)

        rpk_aff, rpk_inf = dv.batch_to_affine(cv.F1, rpk)
        mask = active & ~rpk_inf & ~msg_inf
        local_f = dv.multi_miller_product(msg_aff, rpk_aff, mask)

        sums = jax.lax.psum(_slot_stack(local_sig_sum, n_dev), SHARD_AXIS)
        fs = jax.lax.psum(_slot_stack(local_f, n_dev), SHARD_AXIS)
        sig_sum = dv.jac_reduce_add(cv.F2, sums)
        f_msgs = dv.f12_reduce_mul(fs)

        ss_aff, ss_inf = cv.to_affine(cv.F2, sig_sum, tw.f2_inv)
        f_sig = pr.miller_loop(ss_aff, (dv._NEG_G1_X, dv._NEG_G1_Y))
        f_sig = tw.f12_select(ss_inf, tw.f12_one(shape=()), f_sig)
        f = tw.f12_mul(f_msgs, f_sig)
        return tw.f12_is_one(pr.final_exponentiation(f))

    return sharded_verify


def build_reduced_step(mesh):
    """Reduced sharded step over ``mesh``: the production curve kernels
    (mixed Jacobian arithmetic, branch-free double-and-add scalar mul)
    with the cross-shard Jacobian reduction made explicit — the pairing
    (Miller + final exp) is omitted so a cold compile fits a test
    budget.  Returns the affine sum ``((x, y), is_inf)`` so bit-equality
    against the unsharded execution compares canonical coordinates.

    @mesh: sp
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from lodestar_tpu.ops.bls12_381 import curve as cv, fp
    from lodestar_tpu.ops.bls12_381 import verify as dv

    n_dev = mesh.shape[SHARD_AXIS]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS),) * 4,
        out_specs=P(),
    )
    def reduced_step(pk_aff, pk_inf, bits, active):
        pk_jac = cv.from_affine(cv.F1, pk_aff, pk_inf | ~active)
        rpk = cv.scalar_mul_bits(cv.F1, pk_jac, bits)
        local = dv.jac_reduce_add(cv.F1, rpk)
        parts = jax.lax.psum(_slot_stack(local, n_dev), SHARD_AXIS)
        total = dv.jac_reduce_add(cv.F1, parts)
        return cv.to_affine(cv.F1, total, fp.inv)

    return reduced_step


def default_mesh(mesh_size: int):
    """The canonical ``(sp,)`` mesh over the first ``mesh_size`` local
    devices (the registry's enumeration gate guarantees enough exist)."""
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()[:mesh_size]
    if len(devices) < mesh_size:
        raise ValueError(
            f"sharded program needs {mesh_size} devices, have {len(devices)}"
        )
    return Mesh(devices, (SHARD_AXIS,))


@lru_cache(maxsize=None)
def jitted_for_mesh(mesh):
    """THE memoized jitted sharded-verify program for a concrete mesh
    (``Mesh`` is hashable) — one wrapper per geometry per process, so
    every call site shares one trace cache and the persistent-cache
    filename (``jit_sharded_verify-``) is stable."""
    import jax

    return jax.jit(build_sharded_verify(mesh))


@lru_cache(maxsize=None)
def jitted_sharded(mesh_size: int):
    """``jitted_for_mesh`` over the canonical ``mesh_size``-device mesh
    — the registry's ``Program.fn()`` for ``sharded/b*@m{mesh_size}``
    entries, so warm/--check cover the sharded geometries."""
    return jitted_for_mesh(default_mesh(mesh_size))
