"""What a generator makes and a loop records: shared by every generator,
loop, reader and the comparison, so that each of those can be a file of
its own."""

from __future__ import annotations

from dataclasses import dataclass, field

SLICE_WIDTH = 1 << 20  # bits per (row, slice); Pilosa's, never changed


@dataclass
class Request:
    kind: str                 # read | write | readback (a generator may add kinds)
    body: str                 # the PQL text of one POST /index/<i>/query
    calls: list = field(default_factory=list)   # the body's calls, in the generator's own terms
    row: int = -1             # write: the row ...
    col: int = -1             # ... and the column set


@dataclass
class Record:
    client: int
    req: Request
    t_send: float          # perf_counter seconds
    t_recv: float
    results: object        # list as served, or None where no answer came
    error: str = ""
    spans: object = None   # span tree of a traced request
    ok: bool = True        # set by compare.judge: every result equals the reference
