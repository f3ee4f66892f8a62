"""Layer: server host path.  Mean, per read request of the window, of its wait
in the read coalescer: the ``serve.queue`` spans of its tree (from the item's
append to the start of the batch that took it; ``serve.pass`` is that batch
from start to done, and the two together are what a follower's root holds
beside the door and the cache).  A read the armed serve lane answered never
reaches the queue and counts 0; so does a whole window of them (the write
cells): 0.0, as long as the trees are those of a program that stamps its queue
(its roots carry ``t0_s``, which came with the stamps).  Source: program_span.
Moves ``read_p50_ms``.  Nothing to read from a program that takes no such
stamp: no ``serve.queue`` and no ``t0_s`` anywhere."""

from lib import spantree


def read(ctx):
    stamps, waited = False, []
    for tree in spantree.trees(ctx, writes=False):
        n, ms = spantree.ms_of(tree, "serve.queue")
        stamps = stamps or n > 0 or spantree.root_tag(tree, "t0_s") is not None
        waited.append(ms)
    return spantree.mean(waited) if stamps else None
