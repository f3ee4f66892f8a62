"""Layer: row pool and Gram repair.  The wait for both frames' pools a read
request: the ``xframe.acquire`` span of a pass that counted across frames
(the holds on every frame's pool, their misses paged in; one span a pass
part), summed a pass and averaged over the read requests those passes
answered (the fused queue gives the pass's spans to its first sampled
request, whose root says how many it answered: ``coalesced``).  Source:
program_span.  Moves ``read_p50_ms``.  A program without the cross-frame lane
holds no such span: nothing to read."""

from lib import spantree


def read(ctx):
    waited = answered = found = 0
    for tree in spantree.trees(ctx, writes=False):
        n, ms = spantree.ms_of(tree, "xframe.acquire")
        if not n:
            continue
        weight = int(spantree.root_tag(tree, "coalesced") or 1)
        found += n
        waited += weight * ms
        answered += weight
    return waited / answered if found else None
