"""The readers of the shuffle's exchange (``comm.all_to_all_frac``,
``comm.all_to_all_roofline``) on small hand-built traces: the byte count,
the roofline's scale, the wait at the barrier and a trace with no
exchange."""

import bench_cases  # noqa: F401  (puts the checkout on sys.path)
import pytest

from bench import harness, trace
from bench.trace import Event, Trace

MS = 1e6  # ns
QUOTA = 12_500_008  # default_quota(25M rows, 4 workers)
PEAK = 200e9        # B/s, a v5e's inter-chip interconnect
KIND = "TPU v5 lite"

frac = harness.load_module(harness.BENCH / "metrics" / "comm.all_to_all_frac.py")
roof = harness.load_module(harness.BENCH / "metrics" / "comm.all_to_all_roofline.py")

# one int64 column of the four-worker join, exchanged whole
A2A_S64 = (f"%all-to-all.3 = s64[4,{QUOTA}]{{1,0}} all-to-all(s64[4,{QUOTA}]{{1,0}} "
           "%bitcast.5), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}")
# the same column as its two u32 words, as the chip's trace names them
A2A_U32 = [(f"%all_to_all.{i} = u32[4,1,{QUOTA}]{{2,1,0:T(1,128)}} all-to-all("
            f"u32[4,1,{QUOTA}]{{2,1,0:T(1,128)}} %all_to_all.{i - 1}), channel_id=1, "
            "replica_groups={{0,1,2,3}}, dimensions={0}") for i in (90, 87)]
A2A_IOTA = A2A_S64.replace("replica_groups={{0,1,2,3}}", "replica_groups=[1,4]<=[4]")
SORT = ("%sort.17 = (u32[50000000]{0:T(1024)}, s32[50000000]{0:T(1024)}) "
        "sort(u32[50000000]{0:T(1024)} %a, s32[50000000]{0:T(1024)} %b), dimensions={0}")

LEAVING = 4 * QUOTA * 8 * 3 / 4  # block j goes to peer j; the own block stays
AT_PEAK = LEAVING / PEAK * 1e9   # ns


def _trace(ops: dict) -> Trace:
    return Trace(ops, [Event("bench.window", 0, 1000 * MS)])


def test_bytes_leaving_is_three_quarters_of_the_operands():
    for name in (A2A_S64, A2A_IOTA):
        assert roof.bytes_leaving(trace.hlo(Event(name, 0, 1))) == LEAVING
    words = sum(roof.bytes_leaving(trace.hlo(Event(n, 0, 1))) for n in A2A_U32)
    assert words == LEAVING


def test_an_exchange_at_the_peak_reads_100_percent():
    t = AT_PEAK
    tr = _trace({0: [Event(A2A_S64, 10 * MS, 10 * MS + t), Event(SORT, 20 * MS, 30 * MS)]})
    assert roof.roofline(tr, KIND) == pytest.approx(100.0, rel=1e-12)
    words = [Event(A2A_U32[0], 0, t / 2), Event(A2A_U32[1], t / 2, t)]
    assert roof.roofline(_trace({0: words}), KIND) == pytest.approx(100.0, rel=1e-12)
    slow = _trace({0: [Event(A2A_S64, 0, 4 * t)]})
    assert roof.roofline(slow, KIND) == pytest.approx(25.0, rel=1e-12)


def test_the_wait_at_the_barrier_is_not_transfer_time():
    """Chip 0 reaches each exchange early and waits in it for chip 1; the
    transfer takes what the last to arrive spends there. The share of busy
    time counts the wait."""
    t = AT_PEAK
    w0 = [Event(A2A_U32[0], 0, 10 * t), Event(A2A_U32[1], 10 * t, 10.5 * t)]
    w1 = [Event(SORT, 0, 9.5 * t), Event(A2A_U32[0], 9.5 * t, 10 * t),
          Event(A2A_U32[1], 10 * t, 10.5 * t)]
    tr = _trace({0: w0, 1: w1})
    assert [d for d, _ in roof.transfers(tr)] == pytest.approx([t / 2, t / 2])
    assert roof.roofline(tr, KIND) == pytest.approx(100.0, rel=1e-12)
    assert frac.read(harness.Reading(tr, None)) == pytest.approx((10.5 + 1) / 21)


def test_repeated_exchanges_match_in_order():
    t = AT_PEAK
    q = 100 * MS  # a second query
    tr = _trace({0: [Event(A2A_S64, 0, t), Event(A2A_S64, q, q + 3 * t)],
                 1: [Event(A2A_S64, 0, 2 * t), Event(A2A_S64, q, q + t)]})
    assert sorted(d for d, _ in roof.transfers(tr)) == pytest.approx([t, t])


def test_no_exchange_reads_none():
    tr = _trace({0: [Event(SORT, 0, 10 * MS)], 1: []})
    assert frac.read(harness.Reading(tr, None)) is None
    assert roof.roofline(tr, KIND) is None
    assert roof.roofline(_trace({}), KIND) is None


def test_an_unknown_chip_is_an_error():
    tr = _trace({0: [Event(A2A_S64, 0, 1 * MS)]})
    with pytest.raises(KeyError):
        roof.roofline(tr, "cpu")
