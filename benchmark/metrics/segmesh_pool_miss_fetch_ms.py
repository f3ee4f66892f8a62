"""Layer: row pool and Gram repair.  Median, over the window's ``pool.miss`` spans,
of the time the host spent reading the missed rows from the fragments'
containers (the span's ``pool.miss.fetch`` children together: one a chunk of
8 rows, a walk of 256 fragments each), in the cell on four chips.  The reader
is ``pool_miss_fetch_ms``'s.  Source: program_span.  Moves ``read_p50_ms``."""

from lib import byname


def read(ctx):
    return byname.load("metrics", "pool_miss_fetch_ms").read(ctx)
