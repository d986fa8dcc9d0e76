"""Table I's bucket as the benchmark times it (arXiv:2108.06322).

The durations of one GET, of a bulk GET over a pool of connections and of
a listing, written out here so that the traffic a cell offers does not move
with the program's own bucket model.  The data plane takes this wherever it
takes its ``BucketModel``: the fields have the same names, and the bucket
store asks only for these durations.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence


@dataclasses.dataclass(frozen=True)
class TableIBucket:
    request_latency_s: float  # the fixed cost of one GET
    per_connection_bw: float  # bytes/s of one connection once a GET streams
    parallel_alpha: float  # n connections read n ** alpha times as fast as one
    max_connections: int
    listing_latency_s: float  # one page of a listing (a Class A request)
    page_size: int  # objects a listing page names

    def get_seconds(self, size_bytes: int) -> float:
        """One GET on one connection."""
        return self.request_latency_s + size_bytes / self.per_connection_bw

    def parallel_efficiency(self, n_connections: int) -> float:
        n = max(1, min(n_connections, self.max_connections))
        return float(n) ** self.parallel_alpha

    def bulk_get_seconds(self, sizes: Sequence[int], n_connections: int = 16) -> float:
        """GETs of ``sizes`` over a pool of ``n_connections``: their sequential
        time over the pool's efficiency, summed left to right."""
        seq = 0.0
        for size in sizes:
            seq += self.get_seconds(size)
        return seq / self.parallel_efficiency(n_connections) if sizes else 0.0

    def list_seconds(self, n_objects: int) -> float:
        return max(1, math.ceil(n_objects / self.page_size)) * self.listing_latency_s
