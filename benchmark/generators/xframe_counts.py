"""Generator ``xframe_counts``: bodies of counts whose operands come from two
frames - upstream's Star Trace query "which repositories did users a and b
both star that are written in language l",
``Count(Intersect(Bitmap(frame=users, rowID=a), Bitmap(frame=users, rowID=b),
Bitmap(frame=labels, rowID=l)))``, and its two-operand form "how many did a
star in l" - read-only.  A traffic mix is a data file
(``benchmark/traffic/<traffic>.json``) of this generator's parameters:
``clients``, ``read_calls`` (a body's counts, half of each form, in turn),
``frames`` (the users' frame, then the labels'), ``hot_rows``, ``hot_share``
and ``zipf_s`` (the users' skew, ``pairs_rw``'s rule and ranking: a share
``hot_share`` of draws Zipf(``zipf_s``) over the first ``hot_rows`` ranks,
the rest uniform over the others), ``label_zipf_s`` (a label of rank k is
drawn with weight 1 / k ** label_zipf_s, ranks as the data generator
``startrace`` dealt them from the labels' frame seed: popular languages are
asked more).  A stream is a pure function of (parameters, the frames, seed,
client, phase); a client never sends one body twice.

The file gives the harness what ``pairs_rw`` gives it: ``Stream``,
``fill_requests``, ``stress_mixes``, ``expected`` (from the ``sets``
reference's columns: a count is the smallest operand's columns tested for
membership in the others', the set arithmetic of ``Sets.count`` without
sorting a label's millions of columns a call) and ``n_calls``.

**A warm-up with a deadline**, as ``topn_src``'s: a program that has no
fused lane for these calls answers them a call and a slice at a time, a body
in seconds or minutes, and the run would outlast its limit of 360 s.  The
fill and the warm-up therefore have ``WARM_BUDGET_S`` from the fill's start.
The fill's first request is two calls; each later fill request, and each
warm-up phase's streams (one body a client), is refused with ``TimeoutError``
where, at the pace a call of the last fill request, it would end past the
deadline; a stream of a phase under way stops sending past it.  That ends
the run as a failure with no result.  The window has no deadline.
"""

from __future__ import annotations

import hashlib
import time

import numpy as np

from lib import byname, schema
from lib.records import Request

WARM_BUDGET_S = 150.0   # fill and warm-up; with load, window and comparison inside 360 s
FILL_PASSES = 2         # the fill names every hot user and every label this many times
COLD_FILL = 2           # ... and this many times as many cold users as hot ones

# perf_counter seconds by which the warm-up has to end (None: no deadline),
# and the fill's seconds a call, from its latest request.
_deadline = [None]
_pace = {"per_call": None}


def _check_deadline(what: str, ahead_s: float = 0.0) -> None:
    if _deadline[0] is not None and time.perf_counter() + ahead_s > _deadline[0]:
        raise TimeoutError(f"{what}: the fill and warm-up would take more than {WARM_BUDGET_S:.0f} s; "
                           "this program cannot end a run of this cell inside 360 s")


def _frames(p: dict, frames: list):
    by = {f.name: f for f in frames}
    return by[p["frames"][0]], by[p["frames"][1]]


def label_ranking(p: dict, frames: list, seed: int) -> np.ndarray:
    """rank - 1 -> label row id, as the data generator dealt the ranks from
    the labels' frame seed."""
    users, labels = _frames(p, frames)
    at = [f.name for f in frames].index(labels.name)
    return byname.load("datagens", "startrace").label_ranks(schema.frame_seed(seed, at), labels.rows)


def body_of(calls: list) -> str:
    return " ".join(
        "Count(Intersect(" + ", ".join(f'Bitmap(frame="{f}", rowID={r})' for f, r in operands) + "))"
        for _op, *operands in calls)


def n_calls(req: Request) -> int:
    """PQL calls in the request: what ``calls_per_s`` counts."""
    return len(req.calls)


def _intersect_count(oracle, operands) -> int:
    cols = sorted((oracle.cols(f, r) for f, r in operands), key=len)
    acc = cols[0]
    for more in cols[1:]:
        if not len(acc) or not len(more):
            return 0
        at = np.minimum(np.searchsorted(more, acc), len(more) - 1)
        acc = acc[more[at] == acc]
    return int(len(acc))


def expected(oracle, req: Request) -> list:
    """The right results of ``req``: each Intersect's count over the loaded
    bits (the reference's columns of each (frame, row))."""
    return [_intersect_count(oracle, operands) for _op, *operands in req.calls]


def stress_mixes(p: dict) -> list:
    """Read-only and every body alike: nothing rarer to drive."""
    return []


def _request(calls: list) -> Request:
    return Request("read", body_of(calls), calls)


def _calls(users: str, labels: str, a, b, ls) -> list:
    """Upstream's three-operand query and the two-operand form in turn."""
    return [("Intersect", (users, int(x)), (users, int(y)), (labels, int(lg))) if i % 2 == 0
            else ("Intersect", (users, int(x)), (labels, int(lg)))
            for i, (x, y, lg) in enumerate(zip(a, b, ls))]


def fill_requests(p: dict, frames: list, n_cols: int, seed: int):
    """The requests that bring the server to the state the window finds,
    sent one after another before the warm-up: a probe of two calls, then
    every hot user and every label named ``FILL_PASSES`` times in bodies of
    ``read_calls``, then ``COLD_FILL`` times as many cold users as hot ones
    (the next ranks), so that the users' pool has filled and evicted - and
    compiled what a miss meets - before the window, which evicts.  Starts
    the warm-up's deadline; a request that would end past it at the pace of
    the one before raises ``TimeoutError``."""
    t0 = time.perf_counter()
    _deadline[0], _pace["per_call"] = t0 + WARM_BUDGET_S, None
    users, labels = _frames(p, frames)
    rank_to_row = byname.load("generators", "pairs_rw").ranking(seed, users.rows)
    hot = rank_to_row[: int(p["hot_rows"])]
    lab = label_ranking(p, frames, seed)
    half = len(hot) // 2
    reqs = [_request(_calls(users.name, labels.name, hot[:2], hot[half:half + 2], lab[:2]))]
    for k in range(FILL_PASSES):
        ls = lab[(np.arange(len(hot)) + k) % len(lab)]
        calls = _calls(users.name, labels.name, hot, np.roll(hot, half + k), ls)
        reqs += [_request(calls[at:at + p["read_calls"]]) for at in range(0, len(calls), p["read_calls"])]
    cold = rank_to_row[len(hot): len(hot) * (1 + COLD_FILL)]
    calls = _calls(users.name, labels.name, cold, np.roll(cold, 1), lab[np.arange(len(cold)) % len(lab)])
    reqs += [_request(calls[at:at + p["read_calls"]]) for at in range(0, len(calls), p["read_calls"])]
    for req in reqs:
        if _pace["per_call"] is not None:
            _check_deadline("the fill", _pace["per_call"] * len(req.calls))
        t = time.perf_counter()
        yield req
        _pace["per_call"] = (time.perf_counter() - t) / len(req.calls)


class Stream:
    def __init__(self, p: dict, frames: list, n_cols: int,
                 seed: int, client: int, phase: int = 0):
        if phase == 0:   # the window's: the warm-up is over
            _check_deadline("the window's stream")
            _deadline[0] = None
        elif _deadline[0] is not None:
            _check_deadline(f"a warm-up phase at {_pace['per_call'] or 0.0:.3f} s a call",
                            p["clients"] * p["read_calls"] * (_pace["per_call"] or 0.0))
        users, labels = _frames(p, frames)
        self.p, self.users, self.labels = p, users.name, labels.name
        self.rng = np.random.default_rng([seed, client, phase, 0x3F7])
        self.rank_to_row = byname.load("generators", "pairs_rw").ranking(seed, users.rows)
        hot_n = int(p["hot_rows"])
        w = 1.0 / np.arange(1, hot_n + 1, dtype=np.float64) ** p["zipf_s"]
        self.hot_cdf = np.cumsum(w) / w.sum()
        self.n_rows = users.rows
        self.lab = label_ranking(p, frames, seed)
        w = 1.0 / np.arange(1, labels.rows + 1, dtype=np.float64) ** p["label_zipf_s"]
        self.lab_cdf = np.cumsum(w) / w.sum()
        self._seen: set = set()

    def _users(self, n: int) -> np.ndarray:
        p, hot_n = self.p, len(self.hot_cdf)
        ranks = np.minimum(np.searchsorted(self.hot_cdf, self.rng.random(n)), hot_n - 1)
        cold = self.rng.random(n) >= p["hot_share"]
        ranks[cold] = self.rng.integers(hot_n, self.n_rows, size=int(cold.sum()))
        return self.rank_to_row[ranks]

    def next(self) -> Request:
        _check_deadline("a warm-up phase")
        n = self.p["read_calls"]
        while True:
            a, b = self._users(n), self._users(n)
            while (same := a == b).any():
                b[same] = self._users(int(same.sum()))
            ls = self.lab[np.minimum(np.searchsorted(self.lab_cdf, self.rng.random(n)), len(self.lab) - 1)]
            calls = _calls(self.users, self.labels, a, b, ls)
            req = _request(calls)
            key = hashlib.blake2b(req.body.encode(), digest_size=8).digest()
            if key not in self._seen:       # bodies never repeat: qcache cannot answer
                self._seen.add(key)
                return req
