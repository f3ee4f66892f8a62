"""Data generator ``powerlaw``: rows whose sizes fall off as 1/rank, the
shape that makes a ranked cache worth having.  Row sizes are the
configuration's rule, not the source's (it gives none): the row of rank
``r`` (1-based, ranks dealt to the rows by a permutation drawn from the
seed) holds ``max(1, top_bits // r)`` bits, at columns drawn uniformly
over the frame's columns and distinct within the row.  With ``top_bits``
twice a rank cache's size the cache's last rank holds 2 bits and the next
rank 1, so the cache is exactly the rows of 2 bits or more: no tie at its
edge.  Returns (rows, cols) uint64, sorted by (row, column).
"""

from __future__ import annotations

import numpy as np

from lib.records import SLICE_WIDTH


def row_bits(frame: dict) -> np.ndarray:
    """Bits of the row of each rank, rank 1 first."""
    r = np.arange(1, frame["rows"] + 1, dtype=np.int64)
    return np.maximum(1, frame["top_bits"] // r)


def rank_rows(seed: int, n_rows: int) -> np.ndarray:
    """rank - 1 -> row id."""
    return np.random.default_rng([seed, 0x5A7]).permutation(n_rows)


def make_frame(seed: int, slices: int, frame: dict):
    rng = np.random.default_rng(seed)
    n_cols = slices * SLICE_WIDTH
    bits = row_bits(frame)
    rows = np.repeat(rank_rows(seed, frame["rows"]).astype(np.int64), bits)
    cols = rng.integers(0, n_cols, size=len(rows), dtype=np.int64)
    # Distinct within a row: redraw the repeats until none is left.
    while True:
        key = rows * n_cols + cols
        order = np.argsort(key, kind="stable")
        key = key[order]
        dup = np.zeros(len(key), dtype=bool)
        dup[1:] = key[1:] == key[:-1]
        rows, cols = rows[order], cols[order]
        if not dup.any():
            return rows.astype(np.uint64), cols.astype(np.uint64)
        cols[dup] = rng.integers(0, n_cols, size=int(dup.sum()), dtype=np.int64)
