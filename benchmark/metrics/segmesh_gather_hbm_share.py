"""Layer: kernels.  The gather-count kernels' share of the HBM roofline under
``shard_map``, in percent.  The reader is ``gather_hbm_share``'s: it sums the
``gather.count`` scope's device time over all device planes and the least
bytes over all of the configuration's 256 slices (``lib/work.gather_min_bytes``),
so what it reads here is the mean share of ONE chip's published bandwidth
(``lib/peaks.py``) over the four chips, each of which reads its own 64 slices;
it cannot pass 100%.  Source: device_trace.  Moves ``calls_per_s``."""

from lib import byname


def read(ctx):
    return byname.load("metrics", "gather_hbm_share").read(ctx)
