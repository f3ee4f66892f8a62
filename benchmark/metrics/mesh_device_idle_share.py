"""Layer: device.  The idle share of the BUSIEST device of the mesh: 1 - (that
device's union of op intervals / the traced span between the door's
markers), in percent (``lib/mesh_trace.py``; ``device_idle_share`` averages
the devices).  The busiest device is the one the others wait for at every
collective.  Source: device_trace.  Moves ``calls_per_s``.  Nothing to read
from a trace with fewer than two device planes."""

from lib import mesh_trace


def read(ctx):
    t = mesh_trace.of_run(ctx)
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - max(t["busy_s"]) / t["window_s"])
