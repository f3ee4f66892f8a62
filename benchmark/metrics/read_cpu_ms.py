"""Layer: server host path.  Mean, per read request of the window, of the
root span's ``cpu_ms`` tag: the CPU time of the thread that served the
request, from the request line's arrival to the payload.  Times the
requests a second, it is the share of one core (of one interpreter) that
the reads take.  Source: program_span.  Moves ``calls_per_s``."""

from lib import spantree


def read(ctx):
    cpu = [spantree.root_tag(t, "cpu_ms") for t in spantree.trees(ctx, writes=False)]
    return spantree.mean([float(c) for c in cpu if c is not None])
