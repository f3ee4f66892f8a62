"""Layer: write lane and log.  Median, over the window's ``SetBit``
requests, of their ``write.apply`` span: container insert, op-log append
and a snapshot when one is due - the write lane itself, without the door
and the wait for the interpreter that ``write_ack_ms`` includes.  Source:
program_span.  Moves ``write_to_read_p95_ms``."""

import statistics

from lib import spantree


def read(ctx):
    found = [spantree.ms_of(t, "write.apply") for t in spantree.trees(ctx, writes=True)]
    ms = [ms for n, ms in found if n]
    return statistics.median(ms) if ms else None
