"""Generator ``pairs_rw``: bodies of pair counts, with or without writes.
A traffic mix is a data file (``benchmark/traffic/<traffic>.json``) of
this generator's parameters; a stream of requests is a pure function of
(parameters, number of rows, seed, client, phase).

Every request is one PQL body for ``POST /index/<index>/query``:

* ``read``: ``read_calls`` counts ``Count(op(Bitmap(r1), Bitmap(r2)))``,
  ``op`` cycling through ``ops``, ``r1 != r2``.
* ``write``: one ``SetBit`` on a row this client owns, at a uniform column;
  the stream's next request is its ``readback``: ``readback_calls`` counts
  that pair the written row with other rows.

Rows are ranked by a permutation drawn from the seed alone.  A share
``hot_share`` of draws is Zipf(``zipf_s``) over the first ``hot_rows``
ranks (a number, or ``"all"``), the rest uniform over the other rows,
each draw decided on its own.  Where the mix writes, ranks
``owned_rank_start .. + clients * owned_rows_per_client`` are dealt to the
clients in turn and a client never reads a row another client owns, so
every answer has exactly one right value whatever the clients' timing.

What a generator file gives the harness: ``Stream`` (``next()`` ->
``Request``), ``fill_requests`` (the requests that bring the server to the
state the window finds, sent once before the warm-up), ``stress_mixes``
(variants of the mix that drive its rarest requests at the highest
concurrency the loop can reach, for the warm-up), ``expected`` (the right
results of one request, from the plain reference) and ``n_calls``.
"""

from __future__ import annotations

import hashlib

import numpy as np

from lib.records import SLICE_WIDTH, Request


def ranking(seed: int, n_rows: int) -> np.ndarray:
    """rank -> row id; the same for every client of a seed."""
    return np.random.default_rng([seed, 0x7A7]).permutation(n_rows)


def owned_ranks(p: dict, client: int) -> np.ndarray:
    per = p.get("owned_rows_per_client", 0)
    start = p.get("owned_rank_start", 0)
    return start + client + p["clients"] * np.arange(per)


def pair_body(frame: str, calls: list) -> str:
    return " ".join(
        f'Count({op}(Bitmap(rowID={a}, frame="{frame}"), Bitmap(rowID={b}, frame="{frame}")))'
        for op, a, b in calls)


def n_calls(req: Request) -> int:
    """PQL calls in the request: what ``calls_per_s`` counts."""
    return 1 if req.kind == "write" else len(req.calls)


def expected(oracle, req: Request) -> list:
    """The right results of ``req``; a write is applied to the reference."""
    if req.kind == "write":
        return [oracle.set_bit(req.row, req.col)]
    return [oracle.count(op, a, b) for op, a, b in req.calls]


def stress_mixes(p: dict) -> list:
    """The mix with every request a write pair: all clients write at once,
    so the warm-up meets every write concurrency the closed loop can reach
    far more often than the window will."""
    return [dict(p, write_share=1.0)] if p.get("write_share", 0) > 0 else []


def fill_requests(p: dict, frame: str, n_rows: int, n_cols: int, seed: int) -> list:
    """The requests that bring the server to the state the window finds,
    sent once, one after another, before the warm-up's phases: every hot
    row named in three pairings; then, where the mix writes, one burst for
    every pair (writers, slices) that the loop's clients can have
    outstanding between two reads - ``k`` SetBits on ``k`` owned rows over
    ``s <= k`` distinct slices (a slice, 2^20 columns, is Pilosa's unit of
    storage: what a write costs depends on how many it touches), then one
    read of the ``k`` rows - for ``k`` up to ``clients``.  The ladder has
    no knob: it follows from ``clients`` alone."""
    rank_to_row = ranking(seed, n_rows)
    ops = p["ops"]
    hot = rank_to_row if p["hot_rows"] == "all" else rank_to_row[: int(p["hot_rows"])]
    out, half = [], len(hot) // 2
    for k in range(3):
        calls = [(ops[i % len(ops)], int(hot[i]), int(hot[half + (i + k) % half]))
                 for i in range(half)]
        for at in range(0, len(calls), p["read_calls"]):
            part = calls[at:at + p["read_calls"]]
            out.append(Request("read", pair_body(frame, part), part))
    if p.get("write_share", 0) > 0:
        rng = np.random.default_rng([seed, 0x3A3])
        owned = [int(rank_to_row[r]) for c in range(p["clients"]) for r in owned_ranks(p, c)]
        n_slices = n_cols // SLICE_WIDTH
        for k in range(1, min(p["clients"], len(owned)) + 1):
            for s in range(1, min(k, n_slices) + 1):
                slices = rng.permutation(n_slices)[:s]
                rows = [owned[int(i)] for i in rng.permutation(len(owned))[:k]]
                for i, row in enumerate(rows):
                    col = int(slices[i % s]) * SLICE_WIDTH + int(rng.integers(0, SLICE_WIDTH))
                    out.append(Request("write", f'SetBit(rowID={row}, frame="{frame}", columnID={col})',
                                       row=row, col=col))
                calls = [(ops[i % len(ops)], row, int(next(h for h in hot if h != row)))
                         for i, row in enumerate(rows)]
                out.append(Request("readback", pair_body(frame, calls), calls))
    return out


class Stream:
    def __init__(self, p: dict, frame: str, n_rows: int, n_cols: int,
                 seed: int, client: int, phase: int = 0):
        self.p, self.frame, self.n_cols = p, frame, n_cols
        self.rng = np.random.default_rng([seed, client, phase])
        self.rank_to_row = ranking(seed, n_rows)
        hot_n = n_rows if p["hot_rows"] == "all" else int(p["hot_rows"])
        allowed = np.ones(n_rows, dtype=bool)          # by rank
        for other in range(p["clients"]):
            if other != client and p.get("write_share", 0) > 0:
                allowed[owned_ranks(p, other)] = False
        hot = np.flatnonzero(allowed[:hot_n])
        w = 1.0 / (hot + 1.0) ** p["zipf_s"]
        self.hot_ranks, self.hot_cdf = hot, np.cumsum(w) / w.sum()
        self.cold_ranks = hot_n + np.flatnonzero(allowed[hot_n:])
        self.hot_share = p["hot_share"] if len(self.cold_ranks) else 1.0
        self.owned = self.rank_to_row[owned_ranks(p, client)] if p.get("write_share", 0) > 0 else None
        self._seen: set = set()
        self._pending = None

    def _rows(self, n: int) -> np.ndarray:
        ranks = self.hot_ranks[np.minimum(np.searchsorted(self.hot_cdf, self.rng.random(n)),
                                          len(self.hot_ranks) - 1)]
        if self.hot_share < 1.0:
            cold = self.rng.random(n) >= self.hot_share
            ranks[cold] = self.cold_ranks[self.rng.integers(0, len(self.cold_ranks), size=int(cold.sum()))]
        return self.rank_to_row[ranks]

    def _counts(self, n: int, first_row: int = -1) -> Request:
        ops = self.p["ops"]
        while True:
            r1 = self._rows(n) if first_row < 0 else np.full(n, first_row)
            r2 = self._rows(n)
            while (same := r1 == r2).any():
                r2[same] = self._rows(int(same.sum()))
            calls = [(ops[i % len(ops)], int(a), int(b)) for i, (a, b) in enumerate(zip(r1, r2))]
            body = pair_body(self.frame, calls)
            key = hashlib.blake2b(body.encode(), digest_size=8).digest()
            if key not in self._seen:       # bodies never repeat: qcache cannot answer
                self._seen.add(key)
                return Request("read" if first_row < 0 else "readback", body, calls)

    def next(self) -> Request:
        if self._pending is not None:
            req, self._pending = self._pending, None
            return req
        if self.owned is not None and self.rng.random() < self.p["write_share"]:
            row = int(self.owned[self.rng.integers(0, len(self.owned))])
            col = int(self.rng.integers(0, self.n_cols))
            self._pending = self._counts(self.p["readback_calls"], first_row=row)
            return Request("write", f'SetBit(rowID={row}, frame="{self.frame}", columnID={col})',
                           row=row, col=col)
        return self._counts(self.p["read_calls"])
