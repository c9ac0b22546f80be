"""Pure-jnp oracles for every Pallas kernel.

The model-layer kernels (flash attention, SSD scan) are compared against
these with ``allclose``; the dataframe kernels (``hash_partition_ref``,
``segment_reduce_ref``) are also the *dispatch fallbacks* — the registry's
"jnp" mode — so they must be (and are property-tested to be) bit-identical
to the Pallas path wherever the operation is associative (integer hashing
and sums, min/max in every dtype)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["flash_attention_ref", "ssd_scan_ref", "hash_partition_ref",
           "segment_reduce_ref"]


def flash_attention_ref(q, k, v, *, causal=True, window=None, softcap=None,
                        scale=None):
    """q: (B,S,H,hd); k/v: (B,S,KV,hd) -> (B,S,H,hd). Dense masked softmax."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    qg = q.reshape(B, S, KV, G, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqhgc,bthc->bhgqt", qg, kf) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qi = jnp.arange(S)[:, None]
    ki = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqt,bthc->bqhgc", p, vf)
    return o.reshape(B, S, H, hd).astype(q.dtype)


def ssd_scan_ref(x, dt, A, B, C, D, *, chunk=128):
    """Identical semantics to kernels.ssd_scan (sequential recurrence)."""
    from ..models.ssm import ssd_scan_ref as _model_ref
    y, _ = _model_ref(x.astype(jnp.float32), dt.astype(jnp.float32), A,
                      B.astype(jnp.float32), C.astype(jnp.float32), chunk)
    y = y + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype)


def hash_partition_ref(keys, num_partitions):
    """Destination ids + histogram from the lowbias32 hash chain.

    Must match ``partition.hash32``/``hash_columns`` bit-for-bit: callers
    pass pre-normalized uint32 key columns (``partition.u32_normalize``
    handles 64-bit folding / bool / float bitcasting)."""
    if keys.ndim == 1:
        keys = keys[:, None]
    h = jnp.zeros((keys.shape[0],), jnp.uint32)
    for c in range(keys.shape[1]):
        x = keys[:, c].astype(jnp.uint32)
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x7FEB352D)
        x = x ^ (x >> 15)
        x = x * jnp.uint32(0x846CA68B)
        x = x ^ (x >> 16)
        h = h ^ (x + jnp.uint32(0x9E3779B9) + (h << 6) + (h >> 2))
    dest = (h % jnp.uint32(num_partitions)).astype(jnp.int32)
    hist = jnp.zeros((num_partitions,), jnp.int32).at[dest].add(1)
    return dest, hist


def segment_reduce_ref(values, seg_ids, num_segments, op="sum"):
    """Dtype-preserving direct segment reduction (scatter-add/min/max).

    This is the "jnp" dispatch path of ``ops.segment_reduce`` and the
    semantics the kernel path must reproduce: integer ops are exact (wrap
    like the kernel's scan), min/max exact in every dtype."""
    if op == "sum":
        return jax.ops.segment_sum(values, seg_ids, num_segments=num_segments)
    if op == "max":
        return jax.ops.segment_max(values, seg_ids, num_segments=num_segments)
    if op == "min":
        return jax.ops.segment_min(values, seg_ids, num_segments=num_segments)
    raise ValueError(op)
