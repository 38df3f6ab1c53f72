"""Deviceless compiles of the Pallas field kernels for a TPU v5e.

The TPU compiler is installed here, so it compiles for a chip that is
described and not attached (on-chip-measurement guide, section 2.3).
This catches what the Pallas interpreter cannot: tiling, fast-memory
and lowering refusals of the chip's compiler.  Nothing runs, so it
says nothing about results or times.

The topology is described inside a module fixture, never while a
module is imported: only one process at a time may load the TPU
library, and under xdist every worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from lodestar_tpu.ops.bls12_381 import pallas_fp
from lodestar_tpu.ops.bls12_381.limbs import NLIMBS

DEVICE_MEMORY_BYTES = 16 * 2**30  # one v5e chip's HBM

# 512 is one grid block; 27,648 is the stacked-f12 working width the
# pairing's products run at
LANES = (512, 27_648)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A deviceless compile is written to the persistent cache but can
    never be read back here; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _limbs(n, sharding):
    return jax.ShapeDtypeStruct((n, NLIMBS), jnp.uint32, sharding=sharding)


KERNELS = {
    "mont_mul": (pallas_fp.mont_mul, 2),
    "f2_mul": (lambda a0, a1, b0, b1: pallas_fp.f2_mul((a0, a1), (b0, b1)), 4),
    "f2_sqr": (lambda a0, a1: pallas_fp.f2_sqr((a0, a1)), 2),
}


@pytest.mark.parametrize("lanes", LANES)
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_pallas_kernel_compiles_for_v5e(one_chip, kernel, lanes):
    fn, n_args = KERNELS[kernel]
    args = [_limbs(lanes, one_chip)] * n_args
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert 0 < used < DEVICE_MEMORY_BYTES
