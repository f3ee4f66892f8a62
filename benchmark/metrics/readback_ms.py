"""Layer: row pool and Gram repair.  Median, over the window's read-backs
(the read a client sends as soon as its ``SetBit`` is acknowledged, which
is the read that finds the serve state stale and waits for its repair),
of send to answer on the client's clock, less the median of the window's
other reads: what the repair adds to a read.  Source: host_clock.  Moves
``write_to_read_p95_ms``.  Nothing to read in a window without both."""

import statistics


def read(ctx):
    ms = {"read": [], "readback": []}
    for r in ctx["records"]:
        if r.req.kind in ms and r.results is not None:
            ms[r.req.kind].append((r.t_recv - r.t_send) * 1e3)
    if not ms["read"] or not ms["readback"]:
        return None
    return statistics.median(ms["readback"]) - statistics.median(ms["read"])
