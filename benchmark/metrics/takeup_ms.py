"""Layer: door and admission.  Mean, per read request of the window, of the
time from the client's send to the root span's start: the client's send, the
loopback, the worker's wake and its wait for the interpreter after ``recv``.
The root says when it began (tag ``t0_s``: ``time.perf_counter()``, the
machine's monotonic clock, which this process stamps ``t_send`` and ``t_recv``
with too), so take-up = ``t0_s - t_send``, and what ``reply_ms`` reads is
``t_recv - (t0_s + ms)``: the two split client latency less root between them.
Source: program_span.  Moves ``read_p50_ms``.  Nothing to read from a program
whose roots carry no ``t0_s``, nor where a root began more than ``SKEW_MS``
before its request was sent: the two processes' clocks are then not one."""

from lib import spans, spantree

SKEW_MS = 0.05


def parts(ctx):
    """[(take-up ms, reply ms)] of the window's read requests whose root says
    when it began; None where one of them shows two clocks."""
    out = []
    for r in ctx["records"]:
        if r.req.kind == "write" or not r.spans:
            continue
        t0_s = spantree.root_tag(r.spans, "t0_s")
        if t0_s is None:
            continue
        takeup = (float(t0_s) - r.t_send) * 1e3
        reply = (r.t_recv - float(t0_s)) * 1e3 - spans.root_ms(r.spans)
        if takeup < -SKEW_MS:
            return None
        out.append((takeup, reply))
    return out


def read(ctx):
    found = parts(ctx)
    return spantree.mean([p[0] for p in found]) if found else None
