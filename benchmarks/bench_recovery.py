"""Fault-tolerance overhead + recovery latency benchmark (ISSUE 6).

Measures the cost of making streaming queries restartable, on the same
4-op pipeline as ``bench_stream`` (select -> project -> join -> groupby)
over an 8-morsel on-disk dataset:

- **fault-free** vs **checkpointed** wall time at the default cadence
  (``checkpoint_every=4``) — the acceptance bound is <= 10% overhead;
- **recovery latency**: kill the query mid-stream with a deterministic
  injected fault (``kill_after`` on ``device_op``), then time the
  ``resume=True`` run back to a verified bit-identical result, reporting
  resume wall vs a full fresh re-run (work saved by the snapshot).

Writes ``BENCH_RECOVERY.json`` next to this file.
"""

import json
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import shutil
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time

import jax
import numpy as np

from benchmarks._util import emit
from repro import stream
from repro.core import DDF, DDFContext
from repro.data.dataset import write_dataset
from repro.testing import FaultPlan, fault_scope

N = 320_000          # on-disk rows
N_RIGHT = 60_000     # in-memory build side
KEYS = 20_000
N_BATCHES = 8        # dataset is 8 morsels
KILL_AT = 5          # device_op invocation ordinal that turns persistent-fatal
CHECKPOINT_EVERY = 4


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


def main():
    nd = len(jax.devices())
    mesh = jax.make_mesh((nd,), ("data",))
    ctx = DDFContext(mesh=mesh, axes=("data",))
    left, right = make_data()
    batch_rows = N // N_BATCHES

    tmp = tempfile.mkdtemp(prefix="repro-bench-recovery-")
    man = write_dataset(left, os.path.join(tmp, "data"),
                        chunk_rows=batch_rows // 2)
    dr = DDF.from_numpy(right, ctx, capacity=2 * (-(-N_RIGHT // nd)))

    def pipeline():
        return (stream.scan_dataset(man, ctx, batch_rows=batch_rows)
                .select(_pred, name="even")
                .project(["k", "v"])
                .join(dr.lazy(), on=("k",), strategy="shuffle")
                .groupby(("k",), {"v": ("sum", "count")}))

    def run(**opts):
        return pipeline().collect_stream(**opts)

    ckpt = os.path.join(tmp, "ckpt")

    def checkpointed():
        shutil.rmtree(ckpt, ignore_errors=True)
        return run(checkpoint_dir=ckpt, checkpoint_every=CHECKPOINT_EVERY)

    # correctness first: checkpointed == fault-free, bit for bit
    ref = run().to_numpy()
    got = checkpointed().to_numpy()
    for k in ref:
        assert np.array_equal(ref[k], got[k]), k

    # Runs last seconds, so wall noise between back-to-back blocks would
    # swamp a small per-snapshot cost; interleave the two configurations
    # and take per-config minima instead of block medians.
    t_plain, t_ckpt = [], []
    for _ in range(4):
        t0 = time.perf_counter()
        jax.block_until_ready(run().counts)
        t_plain.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(checkpointed().counts)
        t_ckpt.append(time.perf_counter() - t0)
    t_plain, t_ckpt = min(t_plain), min(t_ckpt)
    overhead = t_ckpt / t_plain - 1.0

    emit("recovery/fault_free_4op", t_plain, f"P={nd},batches={N_BATCHES}")
    emit("recovery/checkpoint_every_4", t_ckpt,
         f"P={nd},overhead={overhead * 100:.1f}%")

    # recovery latency: kill mid-stream, resume from the snapshot.
    def killed_then_resumed():
        shutil.rmtree(ckpt, ignore_errors=True)
        plan = FaultPlan(seed=0, kill_after={"device_op": KILL_AT})
        try:
            with fault_scope(plan):
                run(checkpoint_dir=ckpt, checkpoint_every=CHECKPOINT_EVERY,
                    max_retries=1, retry_backoff_s=0.0)
            raise AssertionError("injected kill did not fire")
        except Exception:
            pass
        t0 = time.perf_counter()
        out = run(checkpoint_dir=ckpt, resume=True)
        jax.block_until_ready(out.counts)
        return time.perf_counter() - t0, out

    t_resume, out = killed_then_resumed()
    got = out.to_numpy()
    for k in ref:
        assert np.array_equal(ref[k], got[k]), k

    work_saved = 1.0 - t_resume / t_plain
    emit("recovery/resume_after_kill", t_resume,
         f"P={nd},kill_at={KILL_AT},vs_fresh={t_resume / t_plain:.3f}")

    record = {
        "P": nd,
        "rows_on_disk": N,
        "batch_rows": batch_rows,
        "n_batches": N_BATCHES,
        "checkpoint_every": CHECKPOINT_EVERY,
        "pipeline": "select -> project -> join -> groupby",
        "t_fault_free_s": t_plain,
        "t_checkpointed_s": t_ckpt,
        "checkpoint_overhead": overhead,
        "kill_site": "device_op",
        "kill_at_ordinal": KILL_AT,
        "t_resume_s": t_resume,
        "resume_vs_fresh": t_resume / t_plain,
        "resume_bit_identical": True,
    }
    out_path = os.path.join(os.path.dirname(__file__), "BENCH_RECOVERY.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    shutil.rmtree(tmp, ignore_errors=True)
    assert overhead <= 0.10, (
        f"checkpoint overhead {overhead * 100:.1f}% exceeds the 10% budget "
        f"at checkpoint_every={CHECKPOINT_EVERY}")
    print(f"checkpoint overhead at every-{CHECKPOINT_EVERY}: "
          f"{overhead * 100:.1f}%; resume after kill@{KILL_AT}: "
          f"{t_resume / t_plain:.2f}x of a fresh run "
          f"({work_saved * 100:.0f}% of work saved), bit-identical",
          flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
