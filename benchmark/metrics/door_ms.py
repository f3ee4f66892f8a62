"""Layer: door and admission.  Mean, per read request of the window, of
its ``door.read`` (request line to dispatch: header parse, body read),
``qos.admit`` (wait at the admission gate) and ``encode`` (the answer's
serialisation) spans together.  Source: program_span.  Moves
``read_p50_ms``.  Nothing to read from a program whose door has no span
(``qos.admit`` alone is not the layer)."""

from lib import spantree


def read(ctx):
    reads = spantree.trees(ctx, writes=False)
    if not any(spantree.ms_of(t, "door.read")[0] for t in reads):
        return None
    return spantree.mean([spantree.ms_of(t, "door.read", "qos.admit", "encode")[1] for t in reads])
