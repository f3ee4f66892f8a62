"""Layer: door and admission.  Mean, per read request of the window, of the
time from the root span's end to the client's last byte: ``t_recv - (t0_s +
ms)``, with ``t0_s`` the root's own start stamp on the machine's monotonic
clock (``takeup_ms`` has the rule and the check that the clocks are one).  It
holds what the server does after the root has ended - the tracer's
``finish_request`` with the tree's ``json.dumps``, the status line and
headers, the two socket writes (annotation ``door.reply`` in a profile) - and
the client's read.  Source: program_span.  Moves ``read_p50_ms``."""

from lib import byname, spantree


def read(ctx):
    found = byname.load("metrics", "takeup_ms").parts(ctx)
    return spantree.mean([p[1] for p in found]) if found else None
