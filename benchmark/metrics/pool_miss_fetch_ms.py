"""Layer: row pool and Gram repair.  Median, over the window's ``pool.miss`` spans,
of the time the host spent building the missed rows' blocks from the fragments'
containers: the span's ``pool.miss.fetch`` children together (one a chunk of
rows; every slice's planes of every missed row).  Source: program_span.  Moves
``read_p50_ms``.  A program without the span gives nothing to read."""

import statistics

from lib import spantree


def read(ctx):
    found = [spantree.ms_of(node.get("children"), "pool.miss.fetch")
             for r in ctx["records"] if r.spans
             for node in spantree.named(r.spans, ("pool.miss",))]
    ms = [total for n, total in found if n]
    return statistics.median(ms) if ms else None
