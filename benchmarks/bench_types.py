"""Dict-encoded string column benchmark (ISSUE 10).

Two claims:

- **Codes are (nearly) free**: a string-keyed join -> groupby pipeline over
  dict-encoded columns runs the *same* device program as a pre-coded
  ``int32`` baseline — the only extra work is host-side vocab metadata,
  one ``Recode`` gather at the join boundary, and decode-on-collect. The
  dict/int wall-time ratio is reported and the two results are asserted
  equal (codes decoded through the merged vocabulary).
- **Recode overhead is one gather**: the isolated cost of vocab
  unification (host sorted-merge + ``recode_map`` + device ``take`` over
  the large side's code column) is measured on its own.

Writes ``BENCH_TYPES.json`` next to this file.
"""

import json
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._util import emit, time_fn
from repro.core import DDF, DDFContext
from repro.core.vocab import DictVocab

N_LEFT = 32_768
N_WORDS = 1_000
RIGHT_POOL = 800          # right holds words[:800]; left draws from words[200:]
REPEAT = 3
CAP = 2 * N_LEFT


def _canon(host):
    order = np.lexsort(tuple(np.asarray(host[k]) for k in sorted(host)))
    return {k: np.asarray(v)[order] for k, v in host.items()}


def _make_data():
    words = np.asarray([f"key{i:04d}" for i in range(N_WORDS)])
    rng = np.random.default_rng(0)
    # Left draws from the *upper* 800 words so its vocab is NOT a prefix of
    # the merged vocab -> the big side is the one that needs the recode.
    left_idx = rng.integers(200, N_WORDS, N_LEFT)
    left = {"k": words[left_idx],
            "v": rng.integers(0, 100, N_LEFT).astype(np.int32)}
    right = {"k": words[:RIGHT_POOL],
             "w": np.arange(RIGHT_POOL, dtype=np.int32)}
    return words, left, right


def _pipeline(left, right):
    return (left.lazy()
            .join(right.lazy(), on=("k",))
            .groupby(("k",), {"v": ("sum", "count")}))


def _run_timed(left, right):
    ts, out = [], None
    for _ in range(REPEAT):
        t0 = time.perf_counter()
        out = _pipeline(left, right).collect().to_numpy()
        ts.append(time.perf_counter() - t0)
    return _canon(out), float(np.median(ts))


def bench_join_groupby(ctx):
    words, left, right = _make_data()
    d_left = DDF.from_numpy(left, ctx, capacity=CAP)
    d_right = DDF.from_numpy(right, ctx, capacity=CAP)

    # Pre-coded baseline: both sides encoded up front into the merged vocab,
    # so the pipeline is pure int32 with no Recode and no decode-on-collect.
    merged = DictVocab.from_values(left["k"]).merge(
        DictVocab.from_values(right["k"]))
    i_left = DDF.from_numpy(
        {"k": merged.encode(left["k"]), "v": left["v"]}, ctx, capacity=CAP)
    i_right = DDF.from_numpy(
        {"k": merged.encode(right["k"]), "w": right["w"]}, ctx, capacity=CAP)

    _pipeline(d_left, d_right).collect()   # warm compile caches
    _pipeline(i_left, i_right).collect()
    out_dict, t_dict = _run_timed(d_left, d_right)
    out_int, t_int = _run_timed(i_left, i_right)

    # Same answer: the int baseline's key codes decode to the dict run's keys.
    out_int = _canon({**out_int, "k": merged.decode(out_int["k"])})
    assert set(out_dict) == set(out_int)
    for c in out_dict:
        assert np.array_equal(out_dict[c], out_int[c]), c

    ratio = t_dict / max(t_int, 1e-9)
    emit("types_join_groupby_dict", t_dict,
         f"{len(out_dict['k'])} groups; recode on {N_LEFT}-row side")
    emit("types_join_groupby_int", t_int, "pre-coded int32 baseline")
    emit("types_dict_over_int", t_dict - t_int, f"x{ratio:.3f}")
    return {
        "rows_left": N_LEFT,
        "vocab_words": N_WORDS,
        "seconds_dict": t_dict,
        "seconds_int_baseline": t_int,
        "dict_over_int_ratio": ratio,
        "bit_identical": True,
    }


def bench_recode_overhead(ctx):
    words, left, right = _make_data()
    lv = DictVocab.from_values(left["k"])
    rv = DictVocab.from_values(right["k"])

    def host_merge():
        merged = lv.merge(rv)
        return lv.recode_map(merged)

    rmap = host_merge()
    t_host = time_fn(lambda: jnp.zeros(()) + host_merge()[0],
                     warmup=1, repeat=REPEAT)
    codes = jnp.asarray(lv.encode(left["k"]))
    rmap_dev = jnp.asarray(rmap)
    t_gather = time_fn(lambda: jnp.take(rmap_dev, codes),
                       warmup=1, repeat=REPEAT)
    emit("types_recode_host_merge", t_host,
         f"merge+map over {len(lv.words)}+{len(rv.words)} words")
    emit("types_recode_gather", t_gather, f"{N_LEFT}-row int32 take")
    return {
        "seconds_host_merge": t_host,
        "seconds_device_gather": t_gather,
        "map_width": int(len(rmap)),
    }


def main():
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    ctx = DDFContext(mesh=mesh, axes=("data",))
    results = {
        "join_groupby": bench_join_groupby(ctx),
        "recode": bench_recode_overhead(ctx),
    }
    out_path = os.path.join(os.path.dirname(__file__), "BENCH_TYPES.json")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    emit("types_total", 0.0, f"wrote {os.path.basename(out_path)}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
