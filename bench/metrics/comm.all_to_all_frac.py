"""Share of the devices' busy time spent in the shuffle's all-to-all
exchange (``collectives._all_to_all``), summed over the chips.

On a v5e 2x2 the exchange shows on the ``XLA Ops`` line as sync
``all-to-all`` ops, ten per query of the four-worker join: the TPU compiler
sends each int64 column as its two u32 words (``u32[4,1,12500008]``) and
each table's counts as one ``s32[4,1,1]``. An op's time on a chip includes
its wait at the barrier for the slowest chip: in a traced run (seed
2900001604) the first word of a column took 0.13-1.12 s on the chips that
arrived early, the second word 2.3 ms.
"""

from bench import trace


def is_exchange(h) -> bool:
    return h.opcode == "all-to-all"


def read(r):
    return trace.share_of_busy(r.trace, is_exchange)
