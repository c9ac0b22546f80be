"""Compile-only checks of the dataframe kernels for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a chip that is described,
not attached. Each test compiles one kernel at the paper's size (25M rows
per worker, ``configs/paper_cylon.py``) with the engine's default block
sizes, and checks that the compiler accepted it as a Mosaic kernel
(``tpu_custom_call``) that fits the chip's memory. Interpret-mode tests
cannot catch what these do: tiles the hardware cannot hold, ops the
compiler cannot lower, and layouts that pad past HBM.

One more compiles the shuffle's all-to-all of an int64 column over a
described 2x2 host at the four-worker cell's quota: how the compiler
sends 64-bit words is what the benchmark's exchange readers count.

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
def v5e_2x2():
    """A described v5e 2x2 host; the persistent compile cache is off while
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e(v5e_2x2):
    """One described v5e chip."""
    return SingleDeviceSharding(v5e_2x2.devices[0])


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


def test_int64_all_to_all_compiles_for_v5e_2x2(v5e_2x2):
    """The shuffle's exchange of one int64 column at the four-worker
    cell's quota (``default_quota(25M, 4)`` slots per destination): the
    compiler accepts it over the 2x2 and sends the column as its two u32
    words, all four chips in one group."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.compat import shard_map
    from repro.core.comm.collectives import _all_to_all
    from repro.core.partition import default_quota
    from repro.launch.mesh import make_mesh

    quota = default_quota(CONFIG.rows_per_worker, 4)
    with jax.enable_x64(True):
        mesh = make_mesh((4,), ("data",), devices=v5e_2x2.devices)
        exchange = shard_map(lambda x: _all_to_all(x.reshape(4, quota), "data").reshape(-1),
                             mesh=mesh, in_specs=PartitionSpec("data"),
                             out_specs=PartitionSpec("data"), check_vma=False)
        col = jax.ShapeDtypeStruct((16 * quota,), jnp.int64,
                                   sharding=NamedSharding(mesh, PartitionSpec("data")))
        text = jax.jit(exchange).lower(col).compile().as_text()
    ops = [line for line in text.splitlines() if " all-to-all(" in line]
    assert len(ops) == 2, ops
    for line in ops:
        assert f"u32[4,1,{quota}]" in line.split(" all-to-all(")[0], line
        assert "replica_groups={{0,1,2,3}}" in line, line
