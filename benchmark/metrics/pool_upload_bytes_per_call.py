"""Layer: row pool and Gram repair.  Bytes the row pool's misses uploaded (the
``upload_bytes`` tags of the window's ``pool.miss`` spans: whole buckets, their
zero planes included) over the PQL calls the window's reads answered (the run
is traced throughout, so the window is the traced window).  Source:
program_span.  Moves ``calls_per_s``.  Nothing to read where no read paged."""

from lib import spantree


def read(ctx):
    reads = [r for r in ctx["records"] if r.req.kind != "write" and r.results is not None]
    calls = sum(len(r.req.calls) for r in reads)
    found = [n for r in reads if r.spans for n in spantree.named(r.spans, ("pool.miss",))]
    if not found or not calls:
        return None
    return sum(float((n.get("tags") or {}).get("upload_bytes", 0)) for n in found) / calls
