"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(task spec deliverable (c))."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,H,KV,hd", [(256, 4, 2, 64), (128, 2, 2, 128), (256, 8, 1, 64)])
def test_flash_attention_sweep(S, H, KV, hd, dtype):
    rng = np.random.default_rng(0)
    B = 2
    q = _rand(rng, (B, S, H, hd), dtype)
    k = _rand(rng, (B, S, KV, hd), dtype)
    v = _rand(rng, (B, S, KV, hd), dtype)
    got = ops.flash_attention(q, k, v, force="interpret", causal=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("kwargs", [
    dict(causal=True, window=64),
    dict(causal=True, softcap=50.0),
    dict(causal=False),
    dict(causal=True, window=32, softcap=30.0),
])
def test_flash_attention_variants(kwargs):
    rng = np.random.default_rng(1)
    q = _rand(rng, (1, 256, 4, 64), jnp.float32)
    k = _rand(rng, (1, 256, 2, 64), jnp.float32)
    v = _rand(rng, (1, 256, 2, 64), jnp.float32)
    got = ops.flash_attention(q, k, v, force="interpret", **kwargs)
    exp = ref.flash_attention_ref(q, k, v, **kwargs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("H,dh,G,ds", [(4, 32, 2, 16), (2, 64, 1, 32)])
def test_ssd_scan_sweep(chunk, H, dh, G, ds):
    rng = np.random.default_rng(2)
    b, L = 2, 128
    x = _rand(rng, (b, L, H, dh), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, L, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    B = _rand(rng, (b, L, G, ds), jnp.float32)
    C = _rand(rng, (b, L, G, ds), jnp.float32)
    D = _rand(rng, (H,), jnp.float32)
    got = ops.ssd_scan(x, dt, A, B, C, D, chunk=chunk, force="interpret")
    exp = ref.ssd_scan_ref(x, dt, A, B, C, D, chunk=chunk)
    scale = float(jnp.max(jnp.abs(exp))) + 1e-6
    np.testing.assert_allclose(np.asarray(got) / scale, np.asarray(exp) / scale,
                               atol=3e-5)


def test_ssd_kernel_matches_model_layer():
    """The kernel must agree with the model's SSD reference (same math used
    in training), including the D skip term."""
    from repro.models.ssm import ssd_scan_ref as model_ssd
    rng = np.random.default_rng(3)
    b, L, H, dh, ds = 1, 64, 2, 32, 16
    x = _rand(rng, (b, L, H, dh), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.3, (b, L, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 1.5, (H,)), jnp.float32)
    B = _rand(rng, (b, L, 1, ds), jnp.float32)
    C = _rand(rng, (b, L, 1, ds), jnp.float32)
    D = _rand(rng, (H,), jnp.float32)
    y_model, _ = model_ssd(x, dt, A, B, C, chunk=32)
    y_model = y_model + x * D[None, None, :, None]
    y_kernel = ops.ssd_scan(x, dt, A, B, C, D, chunk=32, force="interpret")
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model), atol=3e-5)


@pytest.mark.parametrize("dtype", [np.int32, np.uint32])
@pytest.mark.parametrize("P", [4, 16, 64])
@pytest.mark.parametrize("ncols", [1, 2])
def test_hash_partition_sweep(P, ncols, dtype):
    rng = np.random.default_rng(4)
    keys = jnp.asarray(rng.integers(0, 1 << 31, size=(2048, ncols)).astype(dtype))
    dest, hist = ops.hash_partition(keys, P, block=512, force="interpret")
    dref, href = ref.hash_partition_ref(keys, P)
    assert jnp.array_equal(dest, dref)
    assert jnp.array_equal(hist, href)
    assert int(hist.sum()) == 2048


def test_hash_partition_matches_engine_hash():
    """Kernel hash must equal core.partition.hash_columns (the DDF engine's
    partitioner) bit-for-bit."""
    from repro.core.dataframe import from_arrays
    from repro.core.partition import hash_columns
    rng = np.random.default_rng(5)
    k0 = rng.integers(0, 1 << 31, 1024).astype(np.int32)
    k1 = rng.integers(0, 1 << 31, 1024).astype(np.int32)
    t = from_arrays({"a": jnp.asarray(k0), "b": jnp.asarray(k1)})
    h_engine = hash_columns(t, ["a", "b"])
    dest, _ = ops.hash_partition(jnp.stack([jnp.asarray(k0), jnp.asarray(k1)], 1),
                                 1 << 16, block=512, force="interpret")
    assert jnp.array_equal(dest, (h_engine % jnp.uint32(1 << 16)).astype(jnp.int32))


@pytest.mark.parametrize("op", ["sum", "max", "min"])
@pytest.mark.parametrize("nseg,block", [(100, 512), (13, 256)])
def test_segment_reduce_sweep(op, nseg, block):
    rng = np.random.default_rng(6)
    N, W = 2048, 4
    seg = np.sort(rng.integers(0, nseg, N)).astype(np.int32)
    vals = jnp.asarray(rng.normal(size=(N, W)), jnp.float32)
    got = ops.segment_reduce(vals, jnp.asarray(seg), nseg, op=op,
                             block=block, force="interpret")
    exp = ref.segment_reduce_ref(vals, jnp.asarray(seg), nseg, op=op)
    mask = np.isfinite(np.asarray(exp))
    np.testing.assert_allclose(np.asarray(got)[mask], np.asarray(exp)[mask], atol=1e-4)
