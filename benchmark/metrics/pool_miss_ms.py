"""Layer: row pool and Gram repair.  Median of the window's ``pool.miss`` spans: one
paging step under the pool's lock - the LRU's victims leave, the missing rows'
blocks are built on the host chunk by chunk (``pool.miss.fetch``), their
uploads and scatters into a copy of the pool enqueued (``pool.miss.scatter``).  Source: program_span.  Moves
``read_p95_ms``."""

import statistics

from lib import spantree


def read(ctx):
    ms = spantree.all_spans_ms(ctx, "pool.miss")
    return statistics.median(ms) if ms else None
