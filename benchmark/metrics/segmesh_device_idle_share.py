"""Layer: device.  The idle share of the busiest of the four devices inside
the traced span, in percent, in the cell whose reads work the mesh (each
shard's pool copy, scatters, gather kernels, the psum).  The reader is
``mesh_device_idle_share``'s (``lib/mesh_trace.py``).  Source: device_trace.
Moves ``calls_per_s``.  Nothing to read from a trace with fewer than two
device planes."""

from lib import byname


def read(ctx):
    return byname.load("metrics", "mesh_device_idle_share").read(ctx)
