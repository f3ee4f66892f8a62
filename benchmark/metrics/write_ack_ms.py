"""Layer: write lane and log.  Median, over the window's ``SetBit``
requests, of send to acknowledgement on the client's clock.  Source:
host_clock.  Moves ``write_to_read_p95_ms``."""

import statistics


def read(ctx):
    ms = [(r.t_recv - r.t_send) * 1e3 for r in ctx["records"]
          if r.req.kind == "write" and r.results is not None]
    return statistics.median(ms) if ms else None
