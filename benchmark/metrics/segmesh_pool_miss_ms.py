"""Layer: row pool and Gram repair.  Median of the window's ``pool.miss`` spans in
the cell whose pool is sharded over four chips: one paging step under the
pool's lock - the LRU's victims leave, the missing rows' words are read from
256 fragments a chunk on the host (``pool.miss.fetch``), their upload to every
device and the ``shard_map`` scatter into a copy of each shard enqueued
(``pool.miss.scatter``).  The reader is ``pool_miss_ms``'s.  Source:
program_span.  Moves ``read_p95_ms``."""

from lib import byname


def read(ctx):
    return byname.load("metrics", "pool_miss_ms").read(ctx)
