"""Compile-only checks of the dataframe kernels for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a chip that is described,
not attached. Each test compiles one kernel at the paper's size (25M rows
per worker, ``configs/paper_cylon.py``) with the engine's default block
sizes, and checks that the compiler accepted it as a Mosaic kernel
(``tpu_custom_call``) that fits the chip's memory. Interpret-mode tests
cannot catch what these do: tiles the hardware cannot hold, ops the
compiler cannot lower, and layouts that pad past HBM.

The topology is described in a fixture, never at import: only one process
at a time may load the TPU library.
"""

import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_cylon import CONFIG
from repro.core import cost_model

_hp = importlib.import_module("repro.kernels.hash_partition")
_sr = importlib.import_module("repro.kernels.segment_reduce")

# HBM a v5e chip's compiler reports available to one program (16 GiB
# nominal, less what the runtime reserves)
_V5E_PROGRAM_HBM_BYTES = 15.75e9


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip; the persistent compile cache is off while
    compiling for it (its entries cannot be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _rows(kernel: str) -> tuple[int, int]:
    """(rows, block): the paper's rows per worker, padded to whole blocks
    as the dispatching wrapper pads them."""
    block = cost_model.kernel_params("tpu").block[kernel]
    return -(-CONFIG.rows_per_worker // block) * block, block


def _compile_checked(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < _V5E_PROGRAM_HBM_BYTES, used
    return compiled


@pytest.mark.parametrize("with_hist", [False, True],
                         ids=["dest_only", "with_hist"])
def test_hash_partition_compiles_for_v5e(v5e, with_hist):
    n, block = _rows("hash_partition")
    key = jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=v5e)
    _compile_checked(
        lambda k0, k1: _hp.hash_partition([k0, k1], 4, block=block,
                                          with_hist=with_hist),
        key, key)


@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize(
    "dtype", cost_model.kernel_params("tpu").supported_dtypes["segment_reduce"])
def test_segment_reduce_compiles_for_v5e(v5e, dtype, op):
    n, block = _rows("segment_reduce")
    vals = jax.ShapeDtypeStruct((n,), jnp.dtype(dtype), sharding=v5e)
    segs = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=v5e)
    _compile_checked(
        lambda v, s: _sr.segment_reduce_partials(v, s, block=block, op=op),
        vals, segs)
