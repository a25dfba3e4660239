"""Compile the merge kernels for a TPU v5e chip, without the chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached.  These compiles refuse what interpret mode
cannot see (tiles not aligned to the layout, too much fast memory, a
program too large for the device) at the sizes the chip benchmark runs:
windows of 32 blocks of 65,536 bf16 elements, shipped in bf16 and
widened inside the program.

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


def _arg(sharding, dtype, *shape):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM
    return compiled.as_text()


def _merge(sharding, op, params, k, dtype=jnp.bfloat16, full=(),
           masks=False):
    """Compile the whole program of one ``merge_blocks`` call on a
    window: stored-dtype operands and the full-weights flags."""
    return _compile(
        ops._merge, _arg(sharding, dtype, NB, W), _arg(sharding, dtype, NB, k, W),
        _arg(sharding, np.bool_, *full),
        _arg(sharding, np.bool_, NB, k, W) if masks else None,
        op, params, True, False)


@pytest.mark.parametrize("k", [4, 8])
def test_linear_kernel_compiles_for_v5e(one_chip, k):
    hlo = _merge(one_chip, "ta", (0.3,), k)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("k", [4, 8])
def test_ties_kernel_compiles_for_v5e(one_chip, k):
    hlo = _merge(one_chip, "ties", (0.2, 1.0), k)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("k", [4, 8])
def test_ties_thresholds_compile_for_v5e(one_chip, k):
    hlo = _merge(one_chip, "ties", (0.3, 1.0), k, full=(NB, k))
    # a sort of 65,536-wide rows costs ~20 s of compile for each shape
    assert "sort" not in hlo


def test_dare_kernel_compiles_for_v5e(one_chip):
    hlo = _merge(one_chip, "dare", (0.5, 1.0), 4, masks=True)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float16])
def test_other_stored_dtypes_compile_for_v5e(one_chip, dtype):
    # float32: the batched path's operands; per-row flags of a mixed window
    hlo = _merge(one_chip, "avg", (1 / 5,), 4, dtype=dtype, full=(NB, 4))
    assert "tpu_custom_call" in hlo
