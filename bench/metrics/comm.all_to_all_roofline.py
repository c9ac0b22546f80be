"""The shuffle's all-to-all against the chip's interconnect, in percent: the
bytes that leave a chip in its exchanges over the time the transfers take,
times the chip's peak inter-chip bandwidth.

Of each exchange's operand bytes (8 B an element for ``s64``/``u64``, 4 B
for 32-bit types, from its HLO) a share (P - 1) / P leaves the chip: block
j goes to peer j, and the chip's own block stays. P is the size of the
op's replica group. Every chip sends the same bytes.

An all-to-all is a barrier: a chip that reaches it early waits inside the
op for the last one. So a transfer's time is the op's shortest duration
over the chips (the last to arrive waits for none), summed over the ops,
each op matched across the chips by its HLO text and its order. On a v5e
2x2 the busiest chip's exchange time was mostly such waiting (its share
would have read 1.99%), while a column's second word, sent when the chips
had just met at the first, took 2.3 ms for the 150 MB that left the chip
(traced run, seed 2900001604).
"""

from __future__ import annotations

import math
import pathlib
import re

from bench import harness, trace

# Google Cloud documentation, "TPU v5e": 1,600 Gbit/s of inter-chip
# interconnect bandwidth per chip
PEAK_ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}

_frac = harness.load_module(pathlib.Path(__file__).with_name("comm.all_to_all_frac.py"))
_GROUPS = re.compile(r"replica_groups=\{\{([0-9,]*)\}")
_IOTA = re.compile(r"replica_groups=\[[0-9]+,([0-9]+)\]")


def _group_size(h) -> int:
    if m := _GROUPS.search(h.attrs):
        return len(m.group(1).split(","))
    if m := _IOTA.search(h.attrs):
        return int(m.group(1))
    raise ValueError(f"an all-to-all without replica groups: {h.attrs[:200]}")


def _itemsize(dtype: str) -> int:
    bits = re.search(r"[0-9]+$", dtype)
    if bits is None:
        raise ValueError(f"no width for HLO type {dtype}")
    return int(bits.group()) // 8


def bytes_leaving(h) -> float:
    """Bytes one exchange sends off its chip."""
    p = _group_size(h)
    # the op's result has its operands' types, where those are not printed
    sent = sum(math.prod(a.dims) * _itemsize(a.dtype) for a in h.operands or h.result)
    return sent * (p - 1) / p


def transfers(tr) -> list:
    """``[(ns, Hlo)]``: each exchange in the window, with its shortest
    duration over the chips."""
    lo, hi = tr.window
    runs: dict[str, list[list[float]]] = {}  # op -> per chip, its durations in order
    for evs in trace.ops_where(tr, _frac.is_exchange).values():
        mine: dict[str, list[float]] = {}
        for e in evs:
            if lo <= e.start and e.end <= hi:
                mine.setdefault(e.name, []).append(e.dur)
        for name, durs in mine.items():
            runs.setdefault(name, []).append(durs)
    out = []
    for name, chips in runs.items():
        h = trace.hlo(trace.Event(name, 0, 0))
        for k in range(max(map(len, chips))):
            out.append((min(d[k] for d in chips if k < len(d)), h))
    return out


def roofline(tr, device_kind: str) -> float | None:
    """Percent of the peak the exchanges' transfers reach; ``None`` where
    no exchange ran."""
    found = transfers(tr)
    ns = sum(d for d, _ in found)
    if ns <= 0:
        return None
    sent = sum(bytes_leaving(h) for _, h in found)
    return 100.0 * sent / (ns / 1e9 * PEAK_ICI_BYTES_PER_S[device_kind])


def read(r):
    import jax

    return roofline(r.trace, jax.devices()[0].device_kind)
