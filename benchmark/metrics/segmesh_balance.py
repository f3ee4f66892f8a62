"""Layer: device.  The busiest device's busy time over the least busy one's
inside the traced span, in the cell whose reads work the mesh: 1 where the four
shards share a read's work evenly.  The reader is ``mesh_balance``'s
(``lib/mesh_trace.py``).  Source: device_trace.  Moves ``calls_per_s``."""

from lib import byname


def read(ctx):
    return byname.load("metrics", "mesh_balance").read(ctx)
