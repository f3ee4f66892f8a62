"""Layer: kernels.  The gather-count kernels' share of the HBM roofline, in
percent: the bytes their dispatches had to read at least once
(``lib/work.gather_min_bytes`` over each dispatch's own ``unique_rows`` tag:
the ``device`` spans of lane ``gather`` in the trees of the requests sent and
answered inside the traced span - never over the requests: one answered from
a Gram did no kernel work), over the device time of the ops under the
``gather.count`` scope in the same span (``lib/scope_trace.py``), over the
chip's published HBM bandwidth (``lib/peaks.py``).  Requests that straddle an
edge of the span give their ops and not their bytes: the share errs low.
Source: device_trace.  Moves ``calls_per_s``.  No such op in the trace, or no
dispatch that says what it gathered: nothing to read (the run's ``lanes``
line says which lane answered)."""

import json

from lib import peaks, scope_trace, spantree, work
from lib.records import SLICE_WIDTH


def read(ctx):
    path = scope_trace.newest_xplane(ctx)
    kernels = scope_trace.scope_seconds(path, "gather.count") if path else None
    start, stop = ctx["traced"]
    cfg = ctx["config"]
    n_bytes = dispatches = pairs = 0
    for r in ctx["records"]:
        if not r.spans or r.t_send < start or r.t_recv > stop:
            continue
        for node in spantree.named(r.spans, ("device",)):
            tags = node.get("tags") or {}
            if tags.get("lane") == "gather" and "unique_rows" in tags:
                n_bytes += work.gather_min_bytes(tags["unique_rows"], cfg["slices"], SLICE_WIDTH // 32)
                dispatches += 1
                pairs += int(tags.get("pairs", 0))
    if not kernels or not n_bytes:
        return None
    peak = peaks.hbm_bytes_per_s(ctx["device"]["kind"])
    share = 100.0 * n_bytes / kernels["seconds"] / peak
    print(json.dumps({"phase": "gather_trace", "kernel_s": kernels["seconds"], "kernel_ops": kernels["ops"],
                      "by_op": kernels["by_op"], "dispatches": dispatches, "pairs": pairs,
                      "min_bytes": n_bytes, "hbm_bytes_per_s": peak, "gather_hbm_share": share}), flush=True)
    return share
