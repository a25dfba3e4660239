"""Compile the merge kernels for a TPU v5e chip, without the chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached.  These compiles refuse what interpret mode
cannot see (tiles not aligned to the layout, too much fast memory, a
program too large for the device) at the sizes ``chip_smoke.py`` runs:
windows of 32 blocks of 65,536 bf16 elements, upcast to float32.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  Keep these tests in this one file.
"""
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.kernels import ops  # noqa: E402

NB = 32          # PipelineConfig.window_blocks
W = 65536        # 128 KiB blocks of bf16
V5E_HBM = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM
    return compiled.as_text()


@pytest.mark.parametrize("k", [4, 8])
def test_linear_kernel_compiles_for_v5e(one_chip, k):
    hlo = _compile(ops._linear_pallas, _f32(one_chip, NB, W),
                   _f32(one_chip, NB, k, W), 1.0 / (k + 1), False)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("k", [4, 8])
def test_ties_kernel_compiles_for_v5e(one_chip, k):
    hlo = _compile(ops._ties_pallas, _f32(one_chip, NB, W),
                   _f32(one_chip, NB, k, W), _f32(one_chip, NB, k), 1.0, False)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("k", [4, 8])
def test_ties_thresholds_compile_for_v5e(one_chip, k):
    hlo = _compile(ops._ties_thresh_jit, _f32(one_chip, NB, k, W), 0.3)
    # a sort of 65,536-wide rows costs ~20 s of compile for each shape
    assert "sort" not in hlo


def test_dare_kernel_compiles_for_v5e(one_chip):
    k = 4
    masks = jax.ShapeDtypeStruct((NB, k, W), np.bool_, sharding=one_chip)
    hlo = _compile(ops._dare_pallas, _f32(one_chip, NB, W),
                   _f32(one_chip, NB, k, W), masks, 0.5, 1.0, False)
    assert "tpu_custom_call" in hlo
