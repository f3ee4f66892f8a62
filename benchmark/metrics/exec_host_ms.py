"""Layer: server host path (door, executor, row pool, native lanes).
Mean, per read request of the window, of the request's root span (span
trees from ``X-Pilosa-Trace``): everything the server does on the host
for a read between taking the request and writing the answer - admission,
parse, lane choice, the row pool's look-up and repair, the native lanes.
The spans do not split those yet (the served lane records only the native
crossing below its root), so the layer is the whole path and is named so.
Source: program_span.  Moves ``read_p50_ms``."""

from lib import spans


def read(ctx):
    ms = [spans.root_ms(r.spans) for r in ctx["records"] if r.req.kind != "write" and r.spans]
    return sum(ms) / len(ms) if ms else None
