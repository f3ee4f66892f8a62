"""Layer: row pool and Gram repair.  Median of the window's ``pool.repair``
spans: one patch of the written planes and the Gram, under the pool's lock
(host densify, upload and scatter, the wait for the device's counts).
Source: program_span.  Moves ``write_to_read_p95_ms``."""

import statistics

from lib import spantree


def read(ctx):
    ms = spantree.all_spans_ms(ctx, "pool.repair")
    return statistics.median(ms) if ms else None
