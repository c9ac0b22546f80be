"""Out-of-core streaming engine benchmark (ISSUE 3 tentpole).

Runs the 4-op pipeline ``select -> project -> join -> groupby`` over a
chunked on-disk dataset ~8x one batch's per-device footprint, three ways:

- **monolithic**: the whole dataset materialized on device first, then the
  lazy-optimized pipeline (the "when-it-fits" baseline — the thing that
  stops existing once the data outgrows device memory);
- **stream (no overlap)**: morsel-driven batches with serial host decode
  (``prefetch=False``) — out-of-core, but decode and device execution
  alternate;
- **stream (overlap)**: double-buffered decode — host-side chunk decode of
  batch *k+1* overlaps device execution of batch *k*.

Asserts streamed results match the monolithic run bit-for-bit and that
decode/compute overlap beats non-overlapped streaming; writes
``BENCH_STREAM.json`` next to this file.
"""

import json
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from benchmarks._util import emit, time_fn
from repro import stream
from repro.core import DDF, DDFContext
from repro.data.dataset import write_dataset

N = 320_000          # on-disk rows
N_RIGHT = 60_000     # in-memory build side
KEYS = 20_000
N_BATCHES = 8        # dataset is 8x one batch


def make_data():
    rng = np.random.default_rng(0)
    left = {"k": rng.integers(0, KEYS, N).astype(np.int32),
            "v": rng.integers(0, 1000, N).astype(np.int32),
            "junk_a": rng.integers(0, 5, N).astype(np.int32),
            "junk_b": rng.integers(0, 5, N).astype(np.int32)}
    right = {"k": rng.integers(0, KEYS, N_RIGHT).astype(np.int32),
             "w": rng.integers(0, 50, N_RIGHT).astype(np.int32)}
    return left, right


def _pred(c):
    return c["v"] % 2 == 0


def pipeline(lz, dr):
    return (lz.select(_pred, name="even")
            .project(["k", "v"])
            .join(dr.lazy(), on=("k",), strategy="shuffle")
            .groupby(("k",), {"v": ("sum", "count")}))


def main():
    nd = len(jax.devices())
    mesh = jax.make_mesh((nd,), ("data",))
    ctx = DDFContext(mesh=mesh, axes=("data",))
    left, right = make_data()
    batch_rows = N // N_BATCHES

    tmp = tempfile.mkdtemp(prefix="repro-bench-stream-")
    man = write_dataset(left, tmp, chunk_rows=batch_rows // 2)
    dr = DDF.from_numpy(right, ctx, capacity=2 * (-(-N_RIGHT // nd)))
    dl = DDF.from_numpy(left, ctx, capacity=2 * (-(-N // nd)))

    def mono():
        return pipeline(dl.lazy(), dr).collect()

    def stream_run(prefetch):
        lz = pipeline(stream.scan_dataset(man, ctx, batch_rows=batch_rows), dr)
        return lz.collect_stream(prefetch=prefetch)

    # correctness: streamed == monolithic, bit for bit
    ref = mono().to_numpy()
    got = stream_run(True).to_numpy()
    for k in ref:
        assert np.array_equal(ref[k], got[k]), k

    t_mono = time_fn(lambda: mono().counts, repeat=3)
    t_serial = time_fn(lambda: stream_run(False).counts, repeat=3)
    t_overlap = time_fn(lambda: stream_run(True).counts, repeat=3)

    overlap_gain = t_serial / t_overlap
    emit("stream/monolithic_4op", t_mono, f"P={nd},rows={N}")
    emit("stream/serial_decode_4op", t_serial,
         f"P={nd},batches={N_BATCHES},vs_mono={t_mono / t_serial:.3f}")
    emit("stream/overlap_decode_4op", t_overlap,
         f"P={nd},batches={N_BATCHES},overlap_gain={overlap_gain:.3f}")

    record = {
        "P": nd,
        "rows_on_disk": N,
        "rows_right_in_memory": N_RIGHT,
        "batch_rows": batch_rows,
        "n_batches": N_BATCHES,
        "pipeline": "select -> project -> join -> groupby",
        "t_monolithic_s": t_mono,
        "t_stream_serial_s": t_serial,
        "t_stream_overlap_s": t_overlap,
        "overlap_gain_over_serial": overlap_gain,
        "stream_overhead_vs_monolithic": t_overlap / t_mono,
        "bit_identical_to_monolithic": True,
    }
    out_path = os.path.join(os.path.dirname(__file__), "BENCH_STREAM.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    assert overlap_gain > 1.0, (
        f"decode/compute overlap gain {overlap_gain:.3f}x did not beat "
        "serial streaming")
    print(f"overlap gain over serial streaming: {overlap_gain:.2f}x; "
          f"streamed vs monolithic-when-it-fits: "
          f"{t_overlap / t_mono:.2f}x wall", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
