"""DDF engine smoke: runs on N host devices (set by env) and checks results
against numpy oracles. Usable directly and via subprocess from tests."""
import os
import sys

if "--devices" in sys.argv:
    n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import DDF, DDFContext
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


def main():
    devs = jax.devices()
    print(f"devices: {len(devs)}")
    mesh = make_host_mesh()
    ctx = DDFContext(mesh=mesh, axes=("data",))

    rng = np.random.default_rng(0)
    n = 1000
    # ~90% cardinality like the paper's experiments
    lkey = rng.integers(0, 900, size=n).astype(np.int32)
    lval = rng.integers(0, 1000, size=n).astype(np.int32)
    rkey = rng.integers(0, 900, size=n).astype(np.int32)
    rval = rng.integers(0, 1000, size=n).astype(np.int32)

    L = DDF.from_numpy({"k": lkey, "v": lval}, ctx, capacity=2 * n)
    R = DDF.from_numpy({"k": rkey, "w": rval}, ctx, capacity=2 * n)

    # --- join (shuffle-compute) ---
    J, info = L.join(R, on=("k",), strategy="shuffle", capacity=16 * n)
    got = J.to_numpy()
    # numpy oracle
    import collections
    ridx = collections.defaultdict(list)
    for i, k in enumerate(rkey):
        ridx[int(k)].append(i)
    exp = []
    for i, k in enumerate(lkey):
        for j in ridx.get(int(k), []):
            exp.append((int(k), int(lval[i]), int(rval[j])))
    got_set = sorted(zip(got["k"].tolist(), got["v"].tolist(), got["w"].tolist()))
    assert int(np.asarray(info["overflow_left"]).sum()) == 0, "left shuffle overflow"
    assert int(np.asarray(info["overflow_right"]).sum()) == 0
    assert int(np.asarray(info["overflow_join"]).sum()) == 0
    assert got_set == sorted(exp), f"join mismatch: {len(got_set)} vs {len(exp)}"
    print(f"join OK: {len(got_set)} rows")

    # --- broadcast join ---
    J2, _ = L.join(R, on=("k",), strategy="broadcast", capacity=16 * n)
    got2 = J2.to_numpy()
    got2_set = sorted(zip(got2["k"].tolist(), got2["v"].tolist(), got2["w"].tolist()))
    assert got2_set == sorted(exp), "broadcast join mismatch"
    print("broadcast join OK")

    # --- groupby (combine-shuffle-reduce) ---
    G, ginfo = L.groupby(("k",), {"v": ("sum", "count", "mean", "min", "max")}, pre_combine=True)
    gg = G.to_numpy()
    order = np.argsort(gg["k"])
    exp_sum = {}
    exp_cnt = collections.Counter()
    exp_min = {}
    exp_max = {}
    for k, v in zip(lkey, lval):
        k = int(k)
        exp_sum[k] = exp_sum.get(k, 0) + int(v)
        exp_cnt[k] += 1
        exp_min[k] = min(exp_min.get(k, 1 << 30), int(v))
        exp_max[k] = max(exp_max.get(k, -1), int(v))
    ks = sorted(exp_sum)
    assert sorted(gg["k"].tolist()) == ks, "groupby keys mismatch"
    m = dict(zip(gg["k"].tolist(), gg["v_sum"].tolist()))
    assert all(m[k] == exp_sum[k] for k in ks), "groupby sum mismatch"
    m = dict(zip(gg["k"].tolist(), gg["v_count"].tolist()))
    assert all(m[k] == exp_cnt[k] for k in ks)
    m = dict(zip(gg["k"].tolist(), gg["v_min"].tolist()))
    assert all(m[k] == exp_min[k] for k in ks)
    m = dict(zip(gg["k"].tolist(), gg["v_mean"].tolist()))
    assert all(abs(m[k] - exp_sum[k] / exp_cnt[k]) < 1e-4 for k in ks)
    print(f"groupby OK: {len(ks)} groups")

    # also the no-combine variant
    G2, _ = L.groupby(("k",), {"v": ("sum",)}, pre_combine=False)
    gg2 = G2.to_numpy()
    m = dict(zip(gg2["k"].tolist(), gg2["v_sum"].tolist()))
    assert all(m[k] == exp_sum[k] for k in ks)
    print("groupby (shuffle-compute variant) OK")

    # --- sort (sample-shuffle-compute) ---
    S, sinfo = L.sort_values("v")
    ss = S.to_numpy()
    assert int(np.asarray(sinfo["overflow_shuffle"]).sum()) == 0, "sort shuffle overflow"
    assert np.array_equal(np.sort(lval), ss["v"]), "global sort mismatch"
    print("sort OK")

    # --- unique / union / difference ---
    U, _ = L.unique(("k",))
    assert sorted(U.to_numpy()["k"].tolist()) == sorted(set(lkey.tolist()))
    print("unique OK")

    UN, _ = L.project(["k"]).union(R.project(["k"]), on=("k",))
    assert sorted(UN.to_numpy()["k"].tolist()) == sorted(set(lkey.tolist()) | set(rkey.tolist()))
    print("union OK")

    DF, _ = L.project(["k"]).difference(R.project(["k"]), on=("k",))
    assert sorted(DF.to_numpy()["k"].tolist()) == sorted(set(lkey.tolist()) - set(rkey.tolist()))
    print("difference OK")

    # --- column agg (globally reduce) ---
    assert int(L.agg("v", "sum")) == int(lval.sum())
    assert abs(float(L.agg("v", "mean")) - float(lval.mean())) < 1e-3
    assert int(L.agg("v", "min")) == int(lval.min())
    assert L.length() == n
    print("column agg OK")

    # --- rolling window (halo exchange) ---
    W, winfo = L.rolling_sum("v", window=5)
    ww = W.to_numpy()
    ref = np.convolve(lval.astype(np.float64), np.ones(5), mode="full")[4:len(lval)]
    wvalid = ww["window_valid"]
    vals = ww["v_rollsum"][wvalid]
    assert not np.asarray(winfo["halo_short"]).any(), "partition shorter than window"
    assert np.allclose(vals, ref), "rolling sum mismatch"
    print("rolling window OK")

    # --- select / map (embarrassingly parallel) ---
    SEL = L.select(lambda c: c["v"] > 500)
    assert sorted(SEL.to_numpy()["v"].tolist()) == sorted(lval[lval > 500].tolist())
    print("select OK")

    # --- rebalance / head ---
    RB, _ = SEL.rebalance()
    cnts = np.asarray(RB.counts)
    assert cnts.max() - cnts.min() <= 1, f"unbalanced: {cnts}"
    assert sorted(RB.to_numpy()["v"].tolist()) == sorted(lval[lval > 500].tolist())
    print("rebalance OK")

    H = S.head(10)
    assert np.array_equal(H.to_numpy()["v"], np.sort(lval)[:10])
    print("head OK")

    # --- Bruck shuffle == native shuffle (paper Table 3 algorithm) ---
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.dataframe import Table
    from repro.core.partition import hash_partition_ids

    nw = ctx.nworkers
    cap = L.capacity

    def _shuf(alg="native", num_chunks=1):
        def run(cols, counts):
            t = Table(dict(cols), counts.reshape(()))
            dest = hash_partition_ids(t, ("k",), nw)
            out, ov = ctx.comm().shuffle(t, dest, quota=cap, algorithm=alg,
                                         num_chunks=num_chunks)
            return dict(out.columns), out.nvalid.reshape(1), ov.reshape(1)
        from repro.compat import shard_map
        sm = shard_map(run, mesh=mesh,
                           in_specs=({"k": P("data"), "v": P("data")}, P("data")),
                           out_specs=P("data"), check_vma=False)
        return jax.jit(sm)(L.columns, L.counts)

    cn, nn, _ = _shuf("native")
    cb, nb, _ = _shuf("bruck")
    assert np.array_equal(np.asarray(nn), np.asarray(nb)), "bruck counts mismatch"
    # same multiset of rows per partition (order may differ across sources);
    # shuffle output capacity per shard is P*quota
    P_ = nw
    capg = nw * cap
    for w in range(P_):
        n1 = int(np.asarray(nn)[w])
        a = sorted(zip(np.asarray(cn["k"]).reshape(P_, capg)[w][:n1].tolist(),
                       np.asarray(cn["v"]).reshape(P_, capg)[w][:n1].tolist()))
        b = sorted(zip(np.asarray(cb["k"]).reshape(P_, capg)[w][:n1].tolist(),
                       np.asarray(cb["v"]).reshape(P_, capg)[w][:n1].tolist()))
        assert a == b, f"bruck rows mismatch on worker {w}"
    print("bruck shuffle OK (matches native all-to-all)")

    # --- pipelined chunked shuffle == monolithic shuffle (bit-exact) ---
    for K in (2, 3, 4):
        cp, np_, ovp = _shuf(num_chunks=K)
        assert np.array_equal(np.asarray(nn), np.asarray(np_)), f"K={K} counts mismatch"
        assert int(np.asarray(ovp).sum()) == 0, f"K={K} unexpected overflow"
        for name in ("k", "v"):
            assert np.array_equal(np.asarray(cn[name]), np.asarray(cp[name])), (
                f"K={K} pipelined shuffle not bit-exact on column {name}")
    print("pipelined shuffle OK (bit-exact vs monolithic, K=2..4)")

    # pipelined path through the operators: join/groupby/sort with K=3
    Jp, infop = L.join(R, on=("k",), strategy="shuffle", capacity=16 * n, num_chunks=3)
    gp = Jp.to_numpy()
    gp_set = sorted(zip(gp["k"].tolist(), gp["v"].tolist(), gp["w"].tolist()))
    assert gp_set == sorted(exp), "pipelined join mismatch"
    assert int(np.asarray(infop["overflow_left"]).sum()) == 0
    Gp, _ = L.groupby(("k",), {"v": ("sum",)}, pre_combine=True, num_chunks=3)
    ggp = Gp.to_numpy()
    mp = dict(zip(ggp["k"].tolist(), ggp["v_sum"].tolist()))
    assert all(mp[k] == exp_sum[k] for k in ks), "pipelined groupby mismatch"
    Sp, _ = L.sort_values("v", num_chunks=3)
    assert np.array_equal(Sp.to_numpy()["v"], np.sort(lval)), "pipelined sort mismatch"
    print("pipelined operators OK (join/groupby/sort, K=3)")

    # --- lazy plan layer (ISSUE 2): whole-pipeline compile, bit-exact ---
    lz = (L.lazy().select(lambda c: c["v"] > 500, name="vbig")
          .join(R.lazy(), on=("k",), strategy="shuffle", capacity=16 * n)
          .groupby(("k",), {"v": ("sum", "count")}))
    ex = lz.explain()
    assert "elide_shuffle" in ex and ex.strip().endswith("shuffles: 1"), ex
    lzout = lz.to_numpy()
    ESel = L.select(lambda c: c["v"] > 500, name="vbig")
    EJ, _ = ESel.join(R, on=("k",), strategy="shuffle", capacity=16 * n)
    EG, _ = EJ.groupby(("k",), {"v": ("sum", "count")})
    eout = EG.to_numpy()
    for name in eout:
        assert np.array_equal(eout[name], lzout[name]), f"lazy mismatch: {name}"
    assert all(int(np.asarray(v).sum()) == 0 for v in lz.last_info.values())
    print("lazy plan OK (pushdown+elision, bit-exact vs eager)")

    # --- expression API (ISSUE 4): expr forms == callable forms, bit-exact ---
    from repro.expr import col
    XSel = L.select(col("v") > 500)
    assert np.array_equal(XSel.to_numpy()["v"], SEL.to_numpy()["v"])
    XW = L.with_column("d", col("v") * 2 + col("k"))
    host = L.to_numpy()
    assert np.array_equal(XW.to_numpy()["d"], host["v"] * 2 + host["k"])
    xlz = (L.lazy().select(col("v") > 500, name="vbig")
           .join(R.lazy(), on=("k",), strategy="shuffle", capacity=16 * n)
           .groupby(("k",), [col("v").sum(), col("v").count()]))
    xex = xlz.explain()
    assert "SELECT" in xex or "select[(v > 500)]" in xex, xex
    xout = xlz.to_numpy()
    for name in eout:
        assert np.array_equal(eout[name], xout[name]), f"expr mismatch: {name}"
    print("expression API OK (select/with_column/agg specs, bit-exact)")

    print("ALL DDF SMOKE TESTS PASSED")


if __name__ == "__main__":
    enable_compile_cache()
    main()
