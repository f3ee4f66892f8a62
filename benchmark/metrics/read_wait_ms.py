"""Layer: server host path.  Mean, per read request of the window, of the
root span's ``ms`` less its ``cpu_ms`` tag: the time the request's thread
held no core between the request line's arrival and the payload - waiting
for the interpreter's lock, for the pool's lock, for the device or for the
body.  Source: program_span.  Moves ``read_p50_ms``."""

from lib import spans, spantree


def read(ctx):
    wait = [spans.root_ms(t) - float(cpu) for t in spantree.trees(ctx, writes=False)
            for cpu in [spantree.root_tag(t, "cpu_ms")] if cpu is not None]
    return spantree.mean(wait)
