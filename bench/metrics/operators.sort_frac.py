"""Share of the devices' busy time spent in XLA sort ops."""

from bench import trace


def read(r):
    return trace.share_of_busy(r.trace, lambda h: h.opcode == "sort")
