"""Layer: server host path.  Milliseconds a second for which the interpreter's
full collections stopped the server: the window's distinct ``interp.gc`` spans
(a collection stops every thread, so every request it held shows it: one pause
is known by its ``t0_s`` tag, its start on the machine's clock), their
milliseconds together over the seconds from the first traced request's send
to the last one's answer.  Source: program_span.  Moves ``read_p95_ms``.  0.0
where trees came and none holds a pause; nothing to read from a program whose
roots carry no ``t0_s`` (it does not watch its collector)."""

from lib import spantree


def read(ctx):
    traced = [r for r in ctx["records"] if r.spans]
    if not any(spantree.root_tag(r.spans, "t0_s") is not None for r in traced):
        return None
    pauses = {}
    for r in traced:
        for node in spantree.named(r.spans, ("interp.gc",)):
            pauses[(node.get("tags") or {}).get("t0_s")] = float(node.get("ms", 0.0))
    seconds = max(r.t_recv for r in traced) - min(r.t_send for r in traced)
    return sum(pauses.values()) / seconds if seconds > 0 else None
