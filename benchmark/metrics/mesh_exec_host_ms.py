"""Layer: server host path.  Mean, per read request of the window, of the request's
root span in a cell on the four-device slice mesh: everything above the
engine is the code the one-chip cell runs.  The reader is ``exec_host_ms``'s.  Source: program_span.  Moves
``read_p50_ms``."""

from lib import byname


def read(ctx):
    return byname.load("metrics", "exec_host_ms").read(ctx)
