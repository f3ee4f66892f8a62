"""Layer: device.  The device's idle share of the traced span, in percent, in a
cell whose reads work the device (pool copies, scatters, gather kernels).  The
reader is ``device_idle_share``'s (``lib/trace_reduce.py``).  Source:
device_trace.  Moves ``calls_per_s``."""

from lib import byname


def read(ctx):
    return byname.load("metrics", "device_idle_share").read(ctx)
