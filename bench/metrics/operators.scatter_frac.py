"""Share of the devices' busy time spent scattering rows: on a TPU, XLA's
custom fusions that write values at an s32 index array over a fill
(compaction and the shuffle's placement). A 32-bit column comes as
(values, index, scalar fill); a 64-bit one as its two 32-bit words: (fill
words, index, value words), writing a tuple of both words."""

from bench import trace


def is_scatter(h) -> bool:
    if h.opcode != "fusion" or "kind=kCustom" not in h.attrs:
        return False
    ops = h.operands
    if len(ops) == 3 and ops[0].dtype == "s32" and ops[2].dims == ():
        return True
    k = len(ops) // 2
    return (len(ops) == 2 * k + 1 and k >= 2 and len(h.result) == k
            and ops[k].dtype == "s32" and len(ops[k].dims) == 1
            and all(a.dims == b.dims for a, b in zip(ops[:k], h.result)))


def read(r):
    return trace.share_of_busy(r.trace, is_scatter)
