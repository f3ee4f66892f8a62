"""Layer: device.  The device's idle share of the traced span, in percent,
in the cell whose reads are TopNs scored on the device.  The reader is
``device_idle_share``'s (``lib/trace_reduce.py``).  Source: device_trace.
Moves ``calls_per_s``."""

from lib import byname


def read(ctx):
    return byname.load("metrics", "device_idle_share").read(ctx)
