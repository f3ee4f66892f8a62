"""Layer: device.  The busiest device's busy time over the least busy
one's, inside the traced span (``lib/mesh_trace.py``): 1 where the mesh's
devices share the work evenly, more where some wait for others.  Source:
device_trace.  Moves ``calls_per_s``.  Nothing to read from a trace with
fewer than two device planes, or where a device ran no op at all (the
``mesh_trace`` line of the run shows each device's seconds)."""

from lib import mesh_trace


def read(ctx):
    t = mesh_trace.of_run(ctx)
    if not t or min(t["busy_s"]) <= 0:
        return None
    return max(t["busy_s"]) / min(t["busy_s"])
