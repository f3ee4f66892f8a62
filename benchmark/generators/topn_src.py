"""Generator ``topn_src``: bodies of one ``TopN`` with an input bitmap -
upstream's "the users who starred most of what u starred" on a Star Trace
frame, ``TopN(Bitmap(frame=f, rowID=u), frame=f, n=n)`` - read-only.  A
traffic mix is a data file (``benchmark/traffic/<traffic>.json``) of this
generator's parameters: ``clients``, ``n``; ``frame`` (the configuration's
first frame where absent).  A stream is a pure function of (parameters,
the frame's name and rows, seed, client, phase).

``u`` is drawn uniformly over all the frame's rows from the client's
seeded stream, so most srcs are users of the long tail, of a bit or two,
and a src of one row makes the n-th best intersection 0 or 1: the rank
cache's pruning never fires and every candidate is scored.  A client never
sends one body twice; two clients may, rarely.

The file gives the harness what ``pairs_rw`` gives it: ``Stream``,
``fill_requests``, ``stress_mixes``, ``expected`` (from a reference that
answers ``topn(frame, src_row, n)``, such as ``references/topn.py``) and
``n_calls``.

**A warm-up with a deadline.**  A program that scores every candidate by
paging dense rows through the pool answers a TopN of this cell in tens of
seconds, so eight closed loops would keep a warm-up phase open for minutes
and the run would outlast its limit of 360 s.  The fill and the
warm-up therefore have ``WARM_BUDGET_S`` from the fill's start.  A stream
of a warm-up phase made when the phase's eight first requests, at the
fill's pace a request, would end past it - or made past it - raises
``TimeoutError``, which ends the run as a failure with no result; a stream
of a phase under way stops sending past it (``next`` raises in the
client's thread).  The window itself has no deadline.
"""

from __future__ import annotations

import time

import numpy as np

from lib.records import Request

FILL_SENDS = 2   # TopNs sent once before the warm-up: the first builds what the second reuses
WARM_BUDGET_S = 150.0   # fill and warm-up; with load, window and comparison inside 360 s

# perf_counter seconds by which the warm-up has to end (None: no deadline),
# when the fill began, and the fill's seconds a request once it is over.
_deadline = [None]
_fill = {"t0": 0.0, "per": None}


def _check_deadline(what: str, ahead_s: float = 0.0) -> None:
    if _deadline[0] is not None and time.perf_counter() + ahead_s > _deadline[0]:
        raise TimeoutError(f"{what}: the fill and warm-up would take more than {WARM_BUDGET_S:.0f} s; "
                           "this program cannot end a run of this cell inside 360 s")


def _frame(p: dict, frames: list):
    return next(f for f in frames if f.name == p["frame"]) if "frame" in p else frames[0]


def topn_body(frame: str, src_row: int, n: int) -> str:
    return f'TopN(Bitmap(frame="{frame}", rowID={src_row}), frame="{frame}", n={n})'


def n_calls(req: Request) -> int:
    """PQL calls in the request: what ``calls_per_s`` counts (one TopN)."""
    return len(req.calls)


def expected(oracle, req: Request) -> list:
    """The right results of ``req``: a TopN's list of {"id", "count"}."""
    return [oracle.topn(frame, src_row, n) for _, frame, src_row, n in req.calls]


def stress_mixes(p: dict) -> list:
    """Read-only and every request alike: nothing rarer to drive."""
    return []


def _request(frame: str, src_row: int, n: int) -> Request:
    return Request("read", topn_body(frame, src_row, n), [("TopN", frame, src_row, n)])


def fill_requests(p: dict, frames: list, n_cols: int, seed: int) -> list:
    """TopNs from a stream of their own, sent one after another before the
    warm-up: the first of them finds each fragment's candidates with no
    device form yet and builds it.  Starts the warm-up's deadline."""
    _fill["t0"], _fill["per"] = time.perf_counter(), None
    _deadline[0] = _fill["t0"] + WARM_BUDGET_S
    f = _frame(p, frames)
    rng = np.random.default_rng([seed, 0x70F])
    return [_request(f.name, int(u), p["n"]) for u in rng.integers(0, f.rows, size=FILL_SENDS)]


class Stream:
    def __init__(self, p: dict, frames: list, n_cols: int,
                 seed: int, client: int, phase: int = 0):
        if phase == 0:   # the window's: the warm-up is over
            _check_deadline("the window's stream")
            _deadline[0] = None
        elif _deadline[0] is not None:
            if _fill["per"] is None:
                _fill["per"] = (time.perf_counter() - _fill["t0"]) / FILL_SENDS
            _check_deadline(f"a warm-up phase at {_fill['per']:.1f} s a request",
                            p["clients"] * _fill["per"])
        f = _frame(p, frames)
        self.frame, self.rows, self.n = f.name, f.rows, p["n"]
        self.rng = np.random.default_rng([seed, client, phase, 0x70F])
        self._seen: set = set()

    def next(self) -> Request:
        _check_deadline("a warm-up phase")
        if len(self._seen) >= self.rows:   # every row asked: start over
            self._seen.clear()
        while True:
            u = int(self.rng.integers(0, self.rows))
            if u not in self._seen:    # a client's bodies never repeat
                self._seen.add(u)
                return _request(self.frame, u, self.n)
