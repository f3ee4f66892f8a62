"""Layer: row pool and Gram repair.  Nearest-rank 95th percentile, over the window's
read requests, of the time each spent queueing for the row pool's lock, in a
cell whose reads page (the lock is held through every miss: the victims, the
host's block, the upload and the scatter's dispatch).  The reader is
``pool_lock_wait_ms``'s.  Source: program_span.  Moves ``read_p95_ms``."""

from lib import byname


def read(ctx):
    return byname.load("metrics", "pool_lock_wait_ms").read(ctx)
