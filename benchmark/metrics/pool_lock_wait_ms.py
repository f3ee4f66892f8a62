"""Layer: row pool and Gram repair.  Nearest-rank 95th percentile, over
the window's read requests, of the time each spent queueing for the row
pool's lock (its ``pool.lock_wait`` spans together; 0 for a read that never
went to the pool).  Source: program_span.  Moves ``read_p95_ms``.  Nothing
to read in a window where no read went to the pool."""

from lib import spantree


def read(ctx):
    found = [spantree.ms_of(t, "pool.lock_wait") for t in spantree.trees(ctx, writes=False)]
    if not any(n for n, _ in found):
        return None
    return spantree.percentile([ms for _, ms in found], 0.95)
