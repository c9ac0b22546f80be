"""The ``join`` query: ``DDF.join`` of two device-resident tables through the
eager API, and its comparison with the configuration's reference.

Every query in the window is checked for its row count and the engine's
overflow counters; the last one, kept on the device past the window, is
checked row by row through the reference's fingerprints of keys, payloads
and pairs, and for columns in the configuration's ``dtype``, one shard per
chip. Every limit is 0: a join is exact.
"""

from __future__ import annotations

import jax
import numpy as np


class Query:
    def __init__(self, ctx, data: dict, config: dict, traffic: dict, reference):
        from repro.core import DDF

        self.ctx = ctx
        self.data = data
        self.reference = reference
        self.on = tuple(traffic["on"])
        self.strategy = traffic["strategy"]
        self.dtype = np.dtype(config["dtype"])
        self.left = DDF.from_numpy(data["left"], ctx, mode="eager")
        self.right = DDF.from_numpy(data["right"], ctx, mode="eager")
        jax.block_until_ready((self.left, self.right))
        self.rows = len(data["left"]["c0"]) + len(data["right"]["c0"])

    def run(self):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            out, info = self.left.join(self.right, on=self.on, strategy=self.strategy)
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready(out)
        return out, info

    @staticmethod
    def record(result):
        """What is kept of every query: its per-worker row counts and
        overflow counters, a few bytes on the device."""
        out, info = result
        return out.counts, info

    def check(self, records: list, last) -> tuple[dict, int]:
        """``({name: (value, limit)}, queries that failed)``."""
        ref = self.reference.join_reference(self.data["left"], self.data["right"],
                                            self.data["n_keys"])
        bad = [int(np.sum(np.asarray(counts))) != ref["rows"]
               or sum(int(np.sum(np.asarray(v))) for v in info.values()) != 0
               for counts, info in records]
        out, info = last
        overflow = sum(int(np.sum(np.asarray(v))) for v in info.values())
        rows = out.to_numpy()
        numbers = self.reference.compare(self.reference.join_digest(rows), ref)
        numbers["overflow"] = overflow
        numbers["queries_rows_diff"] = sum(bad)
        numbers["columns_not_" + self.dtype.name] = sum(
            v.dtype != self.dtype for v in rows.values())
        numbers["columns_off_chip"] = sum(
            len({s.device for s in arr.addressable_shards}) != self.ctx.nworkers
            for arr in out.columns.values())
        checks = {name: (value, 0) for name, value in numbers.items()}
        last_bad = any(v > 0 for v in numbers.values())
        return checks, max(sum(bad), int(last_bad))
