"""Layer: row pool and Gram repair.  Median of the window's ``pool.gram`` spans in a
cell on the four-device slice mesh: the part of a repair in which the host
blocks on the mesh - the dispatch of the per-device counts, the psum, and
the read of the reduced result (its ``mesh.fetch`` child).  The reader is
``repair_device_wait_ms``'s.  Source: program_span.  Moves
``write_to_read_p95_ms``."""

from lib import byname


def read(ctx):
    return byname.load("metrics", "repair_device_wait_ms").read(ctx)
