"""Layer: device.  Mean, per TopN call of the window's read requests, of
its ``topn.score`` spans: the src row's upload, the one dispatch that
scores a fragment's candidates and the host's wait for their counts
(``device.fetch`` inside).  Source: program_span.  Moves ``read_p50_ms``.
A program whose TopN takes no such span gives nothing to read."""

from lib import spantree


def read(ctx):
    found = ms = calls = 0
    for r in ctx["records"]:
        if not r.spans or r.req.kind == "write":
            continue
        n, t = spantree.ms_of(r.spans, "topn.score")
        found += n
        ms += t
        calls += sum(1 for c in r.req.calls if c and c[0] == "TopN")
    return ms / calls if found and calls else None
