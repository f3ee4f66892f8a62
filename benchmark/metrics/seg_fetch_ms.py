"""Layer: device.  The host's wait for the device a read request, on one chip:
``segmesh_fetch_ms``'s rule over the ``device.fetch`` spans - those under a
pass that gathered (a tree that holds a ``device`` span of lane ``gather``),
summed a pass (one span for each op group's counts, the first of them the wait
itself once every dispatch of the pass has gone out) and averaged over the
read requests those passes answered (weight: the root's ``coalesced``).
Source: program_span.  Moves ``read_p50_ms``.  A program whose one-chip fetch
takes no span gives nothing to read."""

from lib import spantree


def read(ctx):
    waited = answered = found = 0
    for tree in spantree.trees(ctx, writes=False):
        if not any((n.get("tags") or {}).get("lane") == "gather"
                   for n in spantree.named(tree, ("device",))):
            continue
        n, ms = spantree.ms_of(tree, "device.fetch")
        weight = int(spantree.root_tag(tree, "coalesced") or 1)
        found += n
        waited += weight * ms
        answered += weight
    return waited / answered if found else None
