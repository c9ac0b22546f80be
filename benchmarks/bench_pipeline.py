"""Pipelined chunked shuffle vs monolithic all-to-all (ISSUE 1 tentpole).

Times the hash-partition + shuffle + local-merge hot path over 8 host devices
across table sizes and chunk counts K, then compares the cost model's chosen
K (``cost_model.choose_chunk_count``) against the empirically best K. The
acceptance bar: the model-chosen K's wall time is within 20% of the best
measured K.

Like bench_comm's Hockney fit, the model constants are calibrated from the
measurements (the baked-in HOST profile describes a real NIC, not XLA's
emulated host all-to-all): we least-squares fit the pipelined cost shape
``t(K) = K*alpha' + n*beta' + core/K`` over the measured chunk counts, map
the fit back onto ``CostParams``, and then let ``choose_chunk_count`` pick K.

Emits the standard ``name,us_per_call,derived`` CSV and writes
``BENCH_PIPELINE.json`` next to this file for the README results table.
"""

import json
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from benchmarks._util import emit, time_fn
from repro.compat import shard_map
from repro.core.comm import collectives
from repro.core.comm.communicator import HOST, FabricProfile
from repro.core.cost_model import CostParams, choose_chunk_count, t_shuffle_pipelined
from repro.core.dataframe import Table
from repro.core.local_ops import local_unique
from repro.core.partition import hash_partition_ids

ROW_BYTES = 8.0  # two int32 columns
CHUNK_COUNTS = (1, 2, 4, 8, 16)


def build_shuffle_fn(mesh, nw, quota, num_chunks):
    """jitted shard_map: hash partition -> (pipelined) shuffle -> local dedup.

    The dedup leg stands in for the pattern's core op so the pipeline has
    compute to overlap, mirroring dist_unique's structure.
    """

    def run(cols, counts):
        t = Table(dict(cols), counts.reshape(()))
        dest = hash_partition_ids(t, ("k",), nw)
        if num_chunks == 1:
            shuf, ov = collectives.shuffle_table(t, dest, "data", quota)
        else:
            shuf, ov = collectives.shuffle_table_pipelined(
                t, dest, "data", quota, num_chunks)
        out = local_unique(shuf, ("k",), capacity=t.capacity)
        return out.nvalid.reshape(1), ov.reshape(1)

    sm = shard_map(run, mesh=mesh,
                   in_specs=({"k": P("data"), "v": P("data")}, P("data")),
                   out_specs=P("data"), check_vma=False)
    return jax.jit(sm)


def calibrate_params(timings: dict, n_bytes_w: float, P: int):
    """Fit the pipelined cost shape to measured (K -> seconds).

    ``t_shuffle_pipelined`` with comm-bound chunks reduces to
    ``t(K) = K*startup + transfer + core/K``; least-squares those three
    constants and express them as a ``CostParams`` (+ core_s) so
    ``choose_chunk_count`` reproduces the fit. Mirrors bench_comm's
    alpha/beta Hockney fit.
    """
    ks = np.array(sorted(timings), float)
    ts = np.array([timings[int(k)] for k in ks])
    A = np.vstack([ks, np.ones_like(ks), 1.0 / ks]).T
    (startup, transfer, core), *_ = np.linalg.lstsq(A, ts, rcond=None)
    startup = max(float(startup), 1e-9)
    transfer = max(float(transfer), 0.0)
    core = max(float(core), 0.0)
    # t_shuffle("isend-irecv"): startup = (P-1)*alpha, transfer = (P-1)/P*n*beta
    alpha = startup / (P - 1)
    beta = transfer / ((P - 1) / P * n_bytes_w)
    fabric = FabricProfile("host-fitted", alpha_s=alpha, beta_s_per_byte=beta)
    return CostParams(fabric=fabric), core


def main():
    nd = len(jax.devices())
    mesh = jax.make_mesh((nd,), ("data",))
    nw = nd
    params = CostParams(fabric=HOST)
    record = {"P": nw, "sizes": {}}

    for n in (40_000, 160_000, 640_000):
        cap = 2 * (n // nw + 1)
        quota = cap  # generous: zero overflow by construction
        rng = np.random.default_rng(0)
        cols = {
            "k": jnp.asarray(rng.integers(0, int(0.9 * n), size=(nw * cap,)).astype(np.int32)),
            "v": jnp.asarray(rng.integers(0, 1000, size=(nw * cap,)).astype(np.int32)),
        }
        counts = jnp.asarray(np.full((nw,), n // nw, np.int32))

        timings = {}
        for k in CHUNK_COUNTS:
            fn = build_shuffle_fn(mesh, nw, quota, k)
            nvalid, ov = fn(cols, counts)
            assert int(np.asarray(ov).sum()) == 0, f"overflow at K={k}"
            t = time_fn(lambda fn=fn: fn(cols, counts)[0])
            timings[k] = t
            emit(f"pipeline/shuffle_n{n}_K{k}", t, f"P={nw}")

        n_bytes_w = (n / nw) * ROW_BYTES
        fit_params, fit_core = calibrate_params(timings, n_bytes_w, nw)
        k_model = choose_chunk_count(nw, n_bytes_w, fit_params, core_s=fit_core,
                                     max_chunks=max(CHUNK_COUNTS),
                                     min_chunk_bytes=1.0)
        k_model = min(timings, key=lambda c: abs(c - k_model))  # snap to measured grid
        # uncalibrated choice from the default HOST profile, for comparison
        k_default = choose_chunk_count(nw, n_bytes_w, params,
                                       core_s=params.gamma_s_per_row * (n / nw),
                                       max_chunks=max(CHUNK_COUNTS))
        k_best = min(timings, key=timings.get)
        ratio = timings[k_model] / timings[k_best]
        emit(f"pipeline/model_choice_n{n}", timings[k_model],
             f"K_model={k_model},K_best={k_best},t_ratio={ratio:.3f},K_default={k_default}")
        pred = {k: t_shuffle_pipelined(nw, n_bytes_w, k, fit_params, core_s=fit_core)
                for k in CHUNK_COUNTS}
        record["sizes"][n] = {
            "timings_s": {str(k): v for k, v in timings.items()},
            "predicted_s": {str(k): v for k, v in pred.items()},
            "K_model": k_model, "K_default": k_default, "K_best": k_best,
            "model_vs_best_ratio": ratio,
            "pipelined_speedup_best": timings[1] / timings[k_best],
        }

    out_path = os.path.join(os.path.dirname(__file__), "BENCH_PIPELINE.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    worst = max(v["model_vs_best_ratio"] for v in record["sizes"].values())
    emit("pipeline/model_vs_best_worst_ratio", 0.0, f"ratio={worst:.3f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
