"""Layer: server host path.  Mean, per TopN call of the window's read
requests, of its ``topn.candidates`` spans (a fragment's rank-cache
candidates as arrays, and their form on the device, built anew where the
fragment's generation or its cache's rankings moved) and ``topn.select``
spans (the selection over the counts) together: the host's share of a TopN
that is not the device's.  Source: program_span.  Moves ``read_p50_ms``.
A program whose TopN has no such span gives nothing to read."""

from lib import spantree


def read(ctx):
    found = ms = calls = 0
    for r in ctx["records"]:
        if not r.spans or r.req.kind == "write":
            continue
        n, t = spantree.ms_of(r.spans, "topn.candidates", "topn.select")
        found += n
        ms += t
        calls += sum(1 for c in r.req.calls if c and c[0] == "TopN")
    return ms / calls if found and calls else None
