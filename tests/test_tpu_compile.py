"""Compiles for a described TPU v5e chip: the main path's kernels at
real widths, refused here before any chip time is spent.

Nothing runs: JAX stays on the CPU, and the TPU compiler builds each
program for one chip of a described ``v5e:2x2`` topology.  That finds
what interpret mode cannot — a slice not aligned to the tiling, a
kernel over its fast-memory budget.  The whole pairing programs take
minutes each to compile, so they are rehearsed by hand, not here.

The topology is described inside a fixture of this file only: loading
the TPU library at import would make xdist workers collect different
tests (see the on-chip-measurement guide, section 2).
"""

import os

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


@pytest.mark.parametrize("rows", [4096, 1000])
def test_mont_mul_pallas_compiles_for_v5e(one_chip, rows):
    """1000 is not a multiple of the 128 lanes: the padding path."""
    from harmony_tpu.ops import fp_pallas as FP

    spec = _spec((rows, 32), one_chip)
    compiled = FP.mont_mul_pallas.lower(spec, spec).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mont_mul_scan_compiles_for_v5e(one_chip):
    from harmony_tpu.ops import fp as F

    spec = _spec((4096, 32), one_chip)
    compiled = jax.jit(F.mont_mul).lower(spec, spec).compile()
    assert compiled.memory_analysis().output_size_in_bytes == 4096 * 32 * 4


def test_fp12_mul_compiles_for_v5e(one_chip):
    """One Fp12 multiply at the replay batch of 256 pairings."""
    from harmony_tpu.ops import towers as T

    spec = _spec((256, 2, 3, 2, 32), one_chip)
    compiled = jax.jit(T.fp12_mul).lower(spec, spec).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 256 * 2 * 3 * 2 * 32 * 4
    assert mem.temp_size_in_bytes < 16 << 30  # fits one v5e's HBM
