"""Layer: server host path.  The share, in percent, of the window's traced
Count calls that no fused lane answered - they ran on the sequential path, a
call and a slice at a time: the roots' ``unfused`` tags (the executor tags
every request whose calls reached its fused lanes with how many Count calls
fell back, 0 where none did) over the calls of every traced read request.
Source: program_span.  Moves ``calls_per_s``.  0.0 where traced reads came
and none fell back; nothing to read where no root carries the tag (a program
that does not say)."""

from lib import spantree


def read(ctx):
    fell = calls = tagged = 0
    for r in ctx["records"]:
        if not r.spans or r.req.kind == "write":
            continue
        calls += len(r.req.calls)
        n = spantree.root_tag(r.spans, "unfused")
        if n is not None:
            tagged += 1
            fell += int(n)
    return 100.0 * fell / calls if tagged and calls else None
