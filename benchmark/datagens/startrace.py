"""Data generator ``startrace``: the two frames of the Star Trace index over
one pool of repositories a slice, so that every repository a user starred
has exactly one language.

Both frames' entries name this generator with the same ``columns_seed`` and
``pool``: slice s's repositories in use are drawn from the stream
``[columns_seed, s]``, which each frame reproduces whatever seed the
harness hands it (``lib/schema.frame_seed`` gives each later frame a stream
of its own).  An entry's ``rule`` says which frame it is:

* ``windows``: the users.  ``windows.py``'s row rule (imported): row r holds
  ``bits_per_row_slice`` consecutive entries of the slice's shuffled pool
  from an offset drawn from the frame's own seed.
* ``one_label``: the languages.  Every pool column of every slice gets one
  row, the row of rank k (ranks dealt to the rows by a permutation drawn
  from the frame's seed: ``label_ranks``) with probability proportional to
  ``1 / k ** zipf_s``.

Returns (rows, cols) uint64 sorted by (slice, row, col), as ``windows``.
"""

from __future__ import annotations

import numpy as np

from lib import byname
from lib.records import SLICE_WIDTH


def slice_pool(frame: dict, s: int) -> np.ndarray:
    """Slice s's repositories in use, local columns in a shuffled order:
    the same for every frame that names the same ``columns_seed``."""
    return np.random.default_rng([frame["columns_seed"], s]).choice(
        SLICE_WIDTH, size=frame["pool"], replace=False)


def label_ranks(seed: int, n_rows: int) -> np.ndarray:
    """rank - 1 -> label row id (the most common label first)."""
    return np.random.default_rng([seed, 0x1A6]).permutation(n_rows)


def label_shares(frame: dict) -> np.ndarray:
    """Each rank's share of the pool's columns, rank 1 first."""
    w = 1.0 / np.arange(1, frame["rows"] + 1, dtype=np.float64) ** frame["zipf_s"]
    return w / w.sum()


def make_frame(seed: int, slices: int, frame: dict):
    rng = np.random.default_rng(seed)
    rows_out, cols_out = [], []
    if frame["rule"] == "windows":
        n_rows, pool = frame["rows"], frame["pool"]
        bits = byname.load("datagens", "windows").bits_of_rows(frame)
        j = np.arange(int(bits.max()))[None, :]
        take = j < bits[:, None]
        r = np.broadcast_to(np.arange(n_rows, dtype=np.int64)[:, None], take.shape)[take]
        for s in range(slices):
            local = slice_pool(frame, s)
            off = rng.integers(0, pool, size=n_rows)
            picked = local[(off[:, None] + j) % pool][take]
            key = np.sort(r * SLICE_WIDTH + picked)        # by (row, local column)
            rows_out.append(key // SLICE_WIDTH)
            cols_out.append(key % SLICE_WIDTH + s * SLICE_WIDTH)
    elif frame["rule"] == "one_label":
        rank_row = label_ranks(seed, frame["rows"]).astype(np.int64)
        cdf = np.cumsum(label_shares(frame))
        for s in range(slices):
            local = slice_pool(frame, s).astype(np.int64)
            ranks = np.minimum(np.searchsorted(cdf, rng.random(len(local))), frame["rows"] - 1)
            key = np.sort(rank_row[ranks] * SLICE_WIDTH + local)
            rows_out.append(key // SLICE_WIDTH)
            cols_out.append(key % SLICE_WIDTH + s * SLICE_WIDTH)
    else:
        raise KeyError(f"startrace: unknown rule {frame['rule']!r}")
    return (np.concatenate(rows_out).astype(np.uint64),
            np.concatenate(cols_out).astype(np.uint64))
