"""Layer: mesh collectives.  Device time per repair in collective ops
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all, by
the op's category) on the busiest device, inside the traced span, over the
``pool.repair`` spans that began in it (``lib/mesh_trace.py``).  A collective's
time on one device includes its wait for the slowest of the others.  Source:
device_trace.  Moves ``write_to_read_p95_ms``.  Nothing to read from a trace
with fewer than two device planes, or from a span without a repair."""

from lib import mesh_trace


def read(ctx):
    t = mesh_trace.of_run(ctx)
    if not t or not t["repairs"]:
        return None
    return 1e3 * t["collective_s"] / t["repairs"]
