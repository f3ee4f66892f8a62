"""Layer: row pool and Gram repair.  Median of the window's ``pool.repair`` spans in a
cell on the four-device slice mesh: one repair under the pool's lock, in the
mesh engine's composed form (host densify, a functional scatter over the
sharded pool, the written rows counted on every device and psummed).  The
reader is ``repair_ms``'s; the name is the mesh cell's own, so that the two
cells' repairs are never read as one series.  Source: program_span.  Moves
``write_to_read_p95_ms``."""

from lib import byname


def read(ctx):
    return byname.load("metrics", "repair_ms").read(ctx)
