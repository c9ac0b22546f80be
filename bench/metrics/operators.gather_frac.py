"""Share of the devices' busy time spent gathering rows: on a TPU, XLA's
custom fusions that read one array at an s32 index array (the row
permutations after a sort, the join's expansion)."""

from bench import trace


def is_gather(h) -> bool:
    return (h.opcode == "fusion" and "kind=kCustom" in h.attrs
            and len(h.operands) == 2 and h.operands[1].dtype == "s32"
            and len(h.operands[1].dims) == 1)


def read(r):
    return trace.share_of_busy(r.trace, is_gather)
