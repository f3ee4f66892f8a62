"""The plain reference: sets of columns per row, in numpy.

``SetOracle`` is ``chip_smoke.py``'s ``Oracle`` for one frame (sorted
column arrays, edits per row): the plainest form, a millisecond or more
an answer.  ``GramOracle`` holds the same sets as the sizes of every
pair's intersection, counted once per column from the loaded bits (a
column that holds rows a and b adds one to the pair (a, b)) and kept up
per write, so that every answer of a window (10^5 counts over 10^7 bits)
is compared in about a second.  ``benchmark/tests`` holds the two equal.
Neither imports anything of the program.
"""

from __future__ import annotations

import numpy as np

OPS = ("Intersect", "Union", "Difference", "Xor")


class SetOracle:
    def __init__(self, rows: np.ndarray, cols: np.ndarray, n_rows: int = 0):
        order = np.argsort(rows, kind="stable")
        self.rows, self.colv = rows[order], cols[order]
        self.edits: dict = {}

    def cols(self, row: int) -> np.ndarray:
        if row in self.edits:
            return self.edits[row]
        lo, hi = np.searchsorted(self.rows, [row, row + 1])
        return np.unique(self.colv[lo:hi])

    def set_bit(self, row: int, col: int) -> bool:
        cur = self.cols(row)
        new = np.union1d(cur, np.array([col], dtype=cur.dtype))
        self.edits[row] = new
        return len(new) != len(cur)

    def count(self, op: str, r1: int, r2: int) -> int:
        fn = {"Intersect": np.intersect1d, "Union": np.union1d,
              "Difference": np.setdiff1d, "Xor": np.setxor1d}[op]
        return int(len(fn(self.cols(r1), self.cols(r2))))


class GramOracle:
    def __init__(self, rows: np.ndarray, cols: np.ndarray, n_rows: int):
        n = n_rows
        key = np.unique(cols.astype(np.int64) * n + rows.astype(np.int64))   # by (column, row), each bit once
        self.col_sorted, self.row_by_col = c, r = key // n, key % n
        starts = np.flatnonzero(np.concatenate(([True], c[1:] != c[:-1]))) if len(c) else np.zeros(0, np.int64)
        held = np.diff(np.append(starts, len(c)))              # rows that each column holds
        both = np.zeros(n * n, dtype=np.int64)
        for k in np.unique(held):
            block = r[starts[held == k][:, None] + np.arange(k)]   # [columns holding k rows, k]
            for i in range(k):
                for j in range(k):
                    both += np.bincount(block[:, i] * n + block[:, j], minlength=n * n)
        self.both = both.reshape(n, n)       # both[a, b] = |a & b|; both[a, a] = |a|
        self.written: dict = {}              # column -> rows set in it since the load

    def _holders(self, col: int) -> set:
        lo, hi = np.searchsorted(self.col_sorted, [col, col + 1])
        return set(self.row_by_col[lo:hi].tolist()) | self.written.get(col, set())

    def set_bit(self, row: int, col: int) -> bool:
        holders = self._holders(col)
        if row in holders:
            return False
        for other in holders:
            self.both[row, other] += 1
            self.both[other, row] += 1
        self.both[row, row] += 1
        self.written.setdefault(col, set()).add(row)
        return True

    def count(self, op: str, r1: int, r2: int) -> int:
        both = int(self.both[r1, r2])
        if op == "Intersect":
            return both
        a, b = int(self.both[r1, r1]), int(self.both[r2, r2])
        return {"Union": a + b - both, "Difference": a - both, "Xor": a + b - 2 * both}[op]


class StaleOracle(GramOracle):
    """A control: every write is acknowledged and never applied."""

    def set_bit(self, row: int, col: int) -> bool:
        return True
