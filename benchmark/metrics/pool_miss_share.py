"""Layer: row pool and Gram repair.  The share, in percent, of the window's read
requests that were answered by an evaluation pass in which the row pool
missed: a request's tree holds a ``pool.miss`` span, or the request was
coalesced into the pass of one that does (the read coalescer gives the pass's
pool and device spans to its first request, whose root says how many requests
the pass answered: ``coalesced``).  Source: program_span.  Moves
``read_p50_ms``.  Nothing to read where no read carries spans."""

from lib import spantree


def read(ctx):
    trees = spantree.trees(ctx, writes=False)
    if not trees:
        return None
    missed = sum(int(spantree.root_tag(t, "coalesced") or 1)
                 for t in trees if spantree.ms_of(t, "pool.miss")[0])
    return 100.0 * missed / len(trees)
