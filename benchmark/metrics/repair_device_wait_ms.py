"""Layer: row pool and Gram repair.  Median of the window's ``pool.gram``
spans: the part of a repair in which the host blocks on the device - the
rank-k count dispatches over the written planes, old and new, until the
repaired Gram is back in host memory.  Source: program_span.  Moves
``write_to_read_p95_ms``."""

import statistics

from lib import spantree


def read(ctx):
    ms = spantree.all_spans_ms(ctx, "pool.gram")
    return statistics.median(ms) if ms else None
