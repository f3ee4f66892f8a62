"""Layer: kernels.  The TopN scoring kernel's share of the HBM roofline, in
percent: the bytes its dispatches had to read at least once
(``lib/topn_work.topn_min_bytes`` over each ``topn.score`` span's ``words``
tag, in the trees of the requests sent and answered inside the traced
span), over the device time of the ops under the ``topn.count`` scope in
the same span (``lib/scope_trace.py``), over the chip's published HBM
bandwidth (``lib/peaks.py``).  Requests that straddle an edge of the span
give their ops and not their bytes: the share errs low.  Source:
device_trace.  Moves ``calls_per_s``.  No such op in the trace, or no
scoring that says how many words it read: nothing to read."""

import json

from lib import peaks, scope_trace, spantree, topn_work
from lib.records import SLICE_WIDTH


def read(ctx):
    path = scope_trace.newest_xplane(ctx)
    kernels = scope_trace.scope_seconds(path, "topn.count") if path else None
    start, stop = ctx["traced"]
    n_bytes = scorings = words = 0
    for r in ctx["records"]:
        if not r.spans or r.t_send < start or r.t_recv > stop:
            continue
        for node in spantree.named(r.spans, ("topn.score",)):
            tags = node.get("tags") or {}
            if "words" in tags:
                n_bytes += topn_work.topn_min_bytes(tags["words"], SLICE_WIDTH // 32)
                scorings += 1
                words += int(tags["words"])
    if not kernels or not n_bytes:
        return None
    peak = peaks.hbm_bytes_per_s(ctx["device"]["kind"])
    share = 100.0 * n_bytes / kernels["seconds"] / peak
    print(json.dumps({"phase": "topn_trace", "kernel_s": kernels["seconds"], "kernel_ops": kernels["ops"],
                      "by_op": kernels["by_op"], "scorings": scorings, "words": words,
                      "min_bytes": n_bytes, "hbm_bytes_per_s": peak, "topn_hbm_share": share}), flush=True)
    return share
