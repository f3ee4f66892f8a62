"""Layer: server host path.  Mean, per read request of the window, of what of
the request no span names: the root's ``ms`` less the union of its direct
children's intervals (``start_ms``, ``ms``; cut to the root's own, children
that overlap - the coalescer's ``serve.pass`` and the leader's pool and device
spans inside it - counted once).  Source: program_span.  Moves
``read_p50_ms``.  Nothing to read where no read carries spans."""

from lib import spans, spantree


def unnamed(root) -> float:
    """One root span's milliseconds outside every direct child."""
    end = float(root.get("ms", 0.0))
    covered = reach = 0.0
    for a, b in sorted((float(c.get("start_ms", 0.0)),
                        float(c.get("start_ms", 0.0)) + float(c.get("ms", 0.0)))
                       for c in spans.roots(root.get("children"))):
        a, b = max(a, reach), min(b, end)
        if b > a:
            covered += b - a
            reach = b
    return end - covered


def read(ctx):
    return spantree.mean([sum(unnamed(root) for root in spans.roots(tree))
                          for tree in spantree.trees(ctx, writes=False)])
