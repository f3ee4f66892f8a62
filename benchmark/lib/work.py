"""The least work a kernel's call has to do, from its shapes alone: what
the roofline shares divide by the device's time.  Kept with the benchmark,
so no PR that claims a gain in a kernel can change what it is held to."""

from __future__ import annotations


def gather_min_bytes(unique_rows: int, slices: int, words: int) -> int:
    """Bytes a gather-count dispatch must read from HBM at least once:
    every distinct operand row, over every slice, ``words`` uint32 words a
    (row, slice) plane.  Whatever kernel, layout or reuse the program
    picks, it cannot read less (a kernel that reads a row once per pair
    reads more, and its share of the roofline is the lower for it), so a
    share computed from this cannot pass 100%.  The counts it writes are
    4 bytes a pair: left out."""
    return int(unique_rows) * int(slices) * int(words) * 4
