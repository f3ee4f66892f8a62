"""Layer: mesh collectives.  Device time per gather dispatch in collective ops
(all-reduce and the others, by the op's category: the psum of every
``shard_map``'d count) on the busiest device inside the traced span
(``lib/mesh_trace.py``), over the gather dispatches whose ``device`` span
(lane ``gather``) began inside it - a span's start on the client's clock is
its request's send time plus the span's ``start_ms``.  A collective's time on
one device includes its wait for the slowest of the others.
``mesh_collective_ms`` divides the same time by repairs; this cell has none.
Source: device_trace.  Moves ``read_p50_ms``.  Nothing to read from a trace
with fewer than two device planes, or from a span in which nothing gathered."""

from lib import mesh_trace, spantree


def read(ctx):
    t = mesh_trace.of_run(ctx)
    if not t:
        return None
    start, stop = ctx["traced"]
    dispatches = sum(
        1 for r in ctx["records"] if r.spans
        for node in spantree.named(r.spans, ("device",))
        if (node.get("tags") or {}).get("lane") == "gather"
        and start <= r.t_send + float(node.get("start_ms", 0.0)) / 1e3 <= stop)
    return 1e3 * t["collective_s"] / dispatches if dispatches else None
