"""Data generator ``windows``: seeded bits of one frame, as (rows, cols)
uint64 columns.  The rule is ``chip_smoke.py``'s (``_windows``), which ran
on the chip in PR 21; the numbers (rows, bits per (row, slice), the share
of columns in use) are the configuration's, from its source.  A data
generator file gives the harness ``make_frame(seed, slices, frame)``.
"""

from __future__ import annotations

import numpy as np

from lib.records import SLICE_WIDTH


def bits_of_rows(frame: dict) -> np.ndarray:
    """Bits per (row, slice) for each row, from the configuration's
    ``bits_per_row_slice`` rule ``base + (row * step) % mod``."""
    rule = frame["bits_per_row_slice"]
    r = np.arange(frame["rows"], dtype=np.int64)
    return rule["base"] + (r * rule.get("step", 0)) % rule.get("mod", 1)


def make_frame(seed: int, slices: int, frame: dict):
    """Sparse rows that still intersect: per slice, a shuffled pool of
    ``pool`` local columns; row r takes a window of its bit count of
    consecutive pool entries from a random offset, so two rows share bits
    where their windows overlap.  Returns (rows, cols) sorted by (slice,
    row, col), as ``pilosa-tpu sort`` would leave an import file."""
    rng = np.random.default_rng(seed)
    n_rows, pool = frame["rows"], frame["pool"]
    bits = bits_of_rows(frame)
    j = np.arange(int(bits.max()))[None, :]
    take = j < bits[:, None]
    rows_out, cols_out = [], []
    for s in range(slices):
        local = rng.choice(SLICE_WIDTH, size=pool, replace=False)
        off = rng.integers(0, pool, size=n_rows)
        picked = local[(off[:, None] + j) % pool]
        r = np.broadcast_to(np.arange(n_rows, dtype=np.int64)[:, None], picked.shape)[take]
        key = np.sort(r * SLICE_WIDTH + picked[take])       # by (row, local column)
        rows_out.append((key // SLICE_WIDTH).astype(np.uint64))
        cols_out.append((key % SLICE_WIDTH + s * SLICE_WIDTH).astype(np.uint64))
    return np.concatenate(rows_out), np.concatenate(cols_out)
