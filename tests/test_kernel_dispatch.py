"""Kernel dispatch registry + Pallas/jnp parity (ISSUE 5).

Three layers of coverage:

1. **registry/cost-model units** — ``set_backend`` validation and restore,
   ``resolve`` honoring the ``kernel_params`` thresholds / dtype gates /
   native flag, cache keys separating backends;
2. **kernel parity properties** — pallas(interpret) == jnp bit-exactness
   for ``hash_partition`` and ``segment_reduce`` across dtypes
   (int32/int64-folded/float32), uneven segment runs, empty and
   all-invalid tables. Float test values are integer-valued so sums are
   exact under any association (the kernel's partials tree reassociates
   float addition; see docs/KERNELS.md) — min/max and all integer ops are
   exact for arbitrary values;
3. **end-to-end equivalence** — groupby/join/shuffle results bit-identical
   between ``set_backend("pallas")`` (interpret on CPU) and
   ``set_backend("jnp")`` across the eager and lazy layers (the streaming
   layer is covered on 8 devices by the CI kernel smoke leg).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro import kernels
from repro.core import DDF, DDFContext, cost_model
from repro.core.dataframe import Table, from_numpy as table_from_numpy
from repro.core.local_ops import local_groupby
from repro.core.partition import hash_partition_ids, u32_normalize
from repro.expr import col
from repro.kernels import ops, ref, registry


@pytest.fixture(autouse=True)
def _restore_backend():
    prev = registry.get_backend()
    yield
    registry.set_backend(prev)


@pytest.fixture(scope="module")
def ctx():
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    return DDFContext(mesh=mesh, axes=("data",))


# -- registry / cost model units ------------------------------------------------

def test_set_backend_validates_and_restores():
    prev = registry.set_backend("pallas")
    assert registry.get_backend() == "pallas"
    with pytest.raises(ValueError):
        registry.set_backend("cuda")
    assert registry.get_backend() == "pallas"
    with registry.use_backend("jnp"):
        assert registry.get_backend() == "jnp"
    assert registry.get_backend() == "pallas"
    registry.set_backend(prev)


def test_resolve_modes_per_backend():
    params = registry.current_params()
    registry.set_backend("jnp")
    assert registry.resolve("hash_partition", 1 << 20) == "jnp"
    registry.set_backend("pallas")
    expected = "pallas" if params.native else "interpret"
    assert registry.resolve("hash_partition", 4) == expected
    # forced pallas still falls back to jnp for unsupported dtypes (the jnp
    # path IS the kernel semantics there, so parity holds trivially)
    assert registry.resolve("segment_reduce", 1 << 20, "float64") == "jnp"
    registry.set_backend("auto")
    decision = registry.resolve("hash_partition", 1 << 20)
    if params.native:
        assert decision == "pallas"
    else:
        assert decision == "jnp"  # interpret never profitable off-TPU


def test_kernel_params_thresholds_and_dtypes():
    kp = cost_model.kernel_params("tpu")
    assert kp.native
    assert kp.profitable("hash_partition", kp.min_rows["hash_partition"])
    assert not kp.profitable("hash_partition",
                             kp.min_rows["hash_partition"] - 1)
    assert not kp.profitable("segment_reduce", 1 << 30, "float64")
    assert kp.dtype_supported("segment_reduce", jnp.int32)
    assert kp.dtype_supported("hash_partition", "float64")  # unrestricted
    host = cost_model.kernel_params("cpu")
    assert not host.native
    assert not host.profitable("hash_partition", 1 << 30)


def test_explain_matches_resolve():
    registry.set_backend("auto")
    e = registry.explain("segment_reduce", 1024, jnp.int32)
    assert e["decision"] == registry.resolve("segment_reduce", 1024, jnp.int32)
    assert e["min_rows"] == registry.current_params().min_rows["segment_reduce"]


def test_backend_flip_retraces_not_aliases(ctx):
    """Flipping set_backend must compile a distinct cache entry — the
    dispatch signature is part of the key, so a program traced under one
    backend never serves the other. Asserted via the cache's miss counter
    (entry count is not monotone: a full LRU evicts on insert)."""
    from repro.core.api import _OP_CACHE

    rng = np.random.default_rng(0)
    d = DDF.from_numpy({"k": rng.integers(0, 9, 64).astype(np.int32),
                        "v": rng.integers(0, 99, 64).astype(np.int32)}, ctx)
    registry.set_backend("jnp")
    d.groupby(("k",), {"v": ("sum",)}, pre_combine=True)
    n_miss = _OP_CACHE.stats()["misses"]
    registry.set_backend("pallas")
    d.groupby(("k",), {"v": ("sum",)}, pre_combine=True)
    assert _OP_CACHE.stats()["misses"] > n_miss  # retraced, not aliased
    n_miss = _OP_CACHE.stats()["misses"]
    d.groupby(("k",), {"v": ("sum",)}, pre_combine=True)
    assert _OP_CACHE.stats()["misses"] == n_miss  # same backend: cache hit


# -- kernel parity: hash_partition ---------------------------------------------

def _hash_parity(keys_np, P):
    keys = jnp.asarray(keys_np)
    if keys.ndim == 1:
        keys = keys[:, None]
    ku = jnp.stack([u32_normalize(keys[:, c]) for c in range(keys.shape[1])],
                   axis=1)
    dest_i, hist_i = ops.hash_partition(ku, P, force="interpret")
    dest_j, hist_j = ref.hash_partition_ref(ku, P)
    assert jnp.array_equal(dest_i, dest_j)
    assert jnp.array_equal(hist_i, hist_j)


@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.float32])
@pytest.mark.parametrize("n", [1, 7, 1024, 1500])
@pytest.mark.parametrize("P", [2, 8, 64])
def test_hash_partition_parity_sweep(n, P, dtype):
    rng = np.random.default_rng(42)
    if dtype == np.float32:
        keys = rng.normal(size=(n, 2)).astype(np.float32)
    else:
        keys = rng.integers(0, 1 << 31, size=(n, 2)).astype(dtype)
    _hash_parity(keys, P)


def test_hash_partition_dest_only_variant():
    """with_hist=False (the hash_partition_ids shape) returns identical
    destinations and no histogram."""
    rng = np.random.default_rng(9)
    keys = jnp.asarray(rng.integers(0, 1 << 31, size=(1300, 2)).astype(np.uint32))
    d_full, h_full = ops.hash_partition(keys, 16, force="interpret")
    d_only, h_none = ops.hash_partition(keys, 16, force="interpret",
                                        with_hist=False)
    assert h_none is None
    assert jnp.array_equal(d_full, d_only)
    assert int(h_full.sum()) == 1300


def test_hash_partition_parity_int64_folding():
    """64-bit keys fold hi^lo in u32_normalize before either path."""
    with jax.enable_x64(True):
        rng = np.random.default_rng(7)
        keys = rng.integers(-(1 << 62), 1 << 62, size=(512,)).astype(np.int64)
        _hash_parity(keys, 16)


def test_hash_partition_ids_backend_parity():
    """The engine entry point (invalid rows -> drop bucket) is identical
    under both backends, including the forced-interpret one."""
    rng = np.random.default_rng(3)
    t = table_from_numpy({"a": rng.integers(0, 1 << 30, 700).astype(np.int32),
                          "b": rng.normal(size=700).astype(np.float32)},
                         capacity=1000)
    registry.set_backend("jnp")
    dj = hash_partition_ids(t, ["a", "b"], 8)
    registry.set_backend("pallas")
    dp = hash_partition_ids(t, ["a", "b"], 8)
    assert jnp.array_equal(dj, dp)
    assert int(jnp.sum(dp == 8)) == 300  # invalid tail in the drop bucket


# -- kernel parity: segment_reduce ----------------------------------------------

def _seg_parity(vals_np, seg_np, nseg, op):
    vals = jnp.asarray(vals_np)
    seg = jnp.asarray(seg_np, dtype=jnp.int32)
    got = ops.segment_reduce(vals, seg, nseg, op=op, force="interpret")
    exp = ref.segment_reduce_ref(vals, seg, nseg, op=op)
    assert got.dtype == exp.dtype
    # compare only segments that contain rows: empty-segment defaults are
    # backend identities (never observed by local_groupby, which compacts
    # to the live group count)
    present = np.zeros(nseg, bool)
    present[np.asarray(seg_np)[np.asarray(seg_np) < nseg]] = True
    assert np.array_equal(np.asarray(got)[present], np.asarray(exp)[present])


@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
@pytest.mark.parametrize("n,nseg", [(1, 1), (255, 3), (1024, 100), (1300, 7)])
def test_segment_reduce_parity_sweep(n, nseg, op, dtype):
    rng = np.random.default_rng(11)
    # integer-valued floats: exact under any summation order
    vals = rng.integers(-1000, 1000, size=(n, 2)).astype(dtype)
    seg = np.sort(rng.integers(0, nseg, n)).astype(np.int32)
    _seg_parity(vals, seg, nseg, op)


@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_segment_reduce_parity_int_overflow_wraps_identically(op):
    """int32 sums that overflow wrap the same way on both paths."""
    rng = np.random.default_rng(13)
    vals = rng.integers(1 << 30, (1 << 31) - 1, size=(512, 1)).astype(np.int32)
    seg = np.sort(rng.integers(0, 4, 512)).astype(np.int32)
    _seg_parity(vals, seg, 4, op)


def test_local_groupby_parity_empty_and_all_invalid():
    """Empty tables and tables whose rows are all invalid produce identical
    groupby output under both backends."""
    for nvalid in (0, 5):
        cols = {"k": jnp.zeros((600,), jnp.int32).at[:5].set(
                    jnp.arange(5, dtype=jnp.int32)),
                "v": jnp.ones((600,), jnp.int32)}
        t = Table(cols, jnp.asarray(nvalid, jnp.int32))
        outs = {}
        for b in ("jnp", "pallas"):
            registry.set_backend(b)
            g = local_groupby(t, ["k"], {"v": ("sum", "min", "max", "count")})
            n = int(g.nvalid)
            outs[b] = {k: np.asarray(v)[:n] for k, v in g.columns.items()}
        assert int(outs["jnp"]["k"].shape[0]) == nvalid
        for k in outs["jnp"]:
            assert np.array_equal(outs["jnp"][k], outs["pallas"][k]), k


def _groupby_parity_case(keys, vals):
    n = len(keys)
    t = table_from_numpy({"k": keys, "v": vals}, capacity=max(n, 1))
    outs = {}
    for b in ("jnp", "pallas"):
        registry.set_backend(b)
        g = local_groupby(t, ["k"], {"v": ("sum", "min", "max", "count")})
        nv = int(g.nvalid)
        outs[b] = {k: np.asarray(v)[:nv] for k, v in g.columns.items()}
    for k in outs["jnp"]:
        assert outs["jnp"][k].dtype == outs["pallas"][k].dtype
        assert np.array_equal(outs["jnp"][k], outs["pallas"][k]), k


def test_local_groupby_parity_seeded():
    rng = np.random.default_rng(17)
    for n in (1, 3, 257, 1024, 2000):
        for card in (1, 2, max(n // 3, 1)):
            keys = rng.integers(0, card, n).astype(np.int32)
            vals = rng.integers(-(1 << 20), 1 << 20, n).astype(np.int32)
            _groupby_parity_case(keys, vals)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(
        n=st.integers(min_value=1, max_value=700),
        card=st.integers(min_value=1, max_value=64),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
        as_float=st.booleans(),
    )
    def test_local_groupby_parity_property(n, card, seed, as_float):
        rng = np.random.default_rng(seed)
        keys = rng.integers(0, card, n).astype(np.int32)
        vals = rng.integers(-(1 << 16), 1 << 16, n)
        vals = vals.astype(np.float32 if as_float else np.int32)
        _groupby_parity_case(keys, vals)

    @settings(max_examples=25, deadline=None)
    @given(
        n=st.integers(min_value=1, max_value=900),
        P=st.sampled_from([2, 5, 8, 32]),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
    )
    def test_hash_partition_parity_property(n, P, seed):
        rng = np.random.default_rng(seed)
        keys = rng.integers(0, 1 << 31, size=(n, 1)).astype(np.int32)
        _hash_parity(keys, P)


# -- end-to-end equivalence across layers ---------------------------------------

def _pipeline_outputs(ctx):
    rng = np.random.default_rng(23)
    n = 3000
    d1 = DDF.from_numpy({"k": rng.integers(0, 40, n).astype(np.int32),
                         "v": rng.integers(-500, 500, n).astype(np.int32)},
                        ctx)
    d2 = DDF.from_numpy({"k": np.arange(40, dtype=np.int32),
                         "w": rng.integers(0, 50, 40).astype(np.int32)}, ctx)
    g, _ = d1.groupby(("k",), {"v": ("sum", "min", "max", "count")})
    j, _ = d1.join(d2, on=("k",), strategy="shuffle")
    u, _ = d1.unique(("k",))
    lz = (d1.lazy().select(col("v") > -400)
          .join(d2.lazy(), on=("k",), strategy="shuffle")
          .groupby(("k",), {"v": ("sum", "count"), "w": ("max",)}))
    return {"groupby": g.to_numpy(), "join": j.to_numpy(),
            "unique": u.to_numpy(), "lazy": lz.collect().to_numpy()}


def test_end_to_end_pallas_vs_jnp_bit_identical(ctx):
    registry.set_backend("jnp")
    base = _pipeline_outputs(ctx)
    registry.set_backend("pallas")
    forced = _pipeline_outputs(ctx)
    for op in base:
        for k in base[op]:
            assert base[op][k].dtype == forced[op][k].dtype
            assert np.array_equal(base[op][k], forced[op][k]), (op, k)
