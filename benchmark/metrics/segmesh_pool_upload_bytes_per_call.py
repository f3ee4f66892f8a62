"""Layer: row pool and Gram repair.  Bytes the sharded pool's misses handed to
the devices (the ``upload_bytes`` tags of the window's ``pool.miss`` spans:
cells and values of a sparse chunk's bucket, counted once though every device
receives them, or a dense chunk's block) over the PQL calls the window's reads
answered.  The reader is ``pool_upload_bytes_per_call``'s.  Source:
program_span.  Moves ``calls_per_s``."""

from lib import byname


def read(ctx):
    return byname.load("metrics", "pool_upload_bytes_per_call").read(ctx)
