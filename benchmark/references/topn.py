"""Reference ``topn``: the reference ``sets`` (imported, not changed) that
also answers ``TopN(Bitmap(frame=f, rowID=u), frame=f, n)`` in upstream's
two phases (executor.go:281-404, fragment.go:493-625), in plain numpy.
Imports nothing of the program.

* Per slice, the rank cache's candidates: the top ``cacheSize`` rows of
  the slice (the frame's option; 50,000 where it names none) by their
  bits in that slice, count descending then id ascending.
* Phase 1, per slice: each candidate's |row & src| in that slice, the
  candidates with a count above 0, the top ``n`` of them by count
  descending, id ascending; the slices' lists summed by id.
* Phase 2: for those ids, |row & src| over every slice (a slice counts an
  id whether or not it is one of that slice's candidates); the ids with a
  count above 0, by count descending, id ascending, the first ``n``.

Answers are lists of {"id", "count"}, as the program serves them.  A
write applied to the reference moves the candidates and the counts of the
next TopN, as it does the program's.
"""

from __future__ import annotations

import numpy as np

from lib import byname
from lib.records import SLICE_WIDTH

sets = byname.load("references", "sets")

DEFAULT_CACHE_SIZE = 50000   # upstream frame.go DefaultCacheSize


def _top(ids: np.ndarray, counts: np.ndarray, n: int) -> tuple[np.ndarray, np.ndarray]:
    """The ids with a count above 0, by count descending, id ascending; the
    first ``n`` (all with n 0)."""
    keep = counts > 0
    ids, counts = ids[keep], counts[keep]
    order = np.lexsort((ids, -counts))
    if n:
        order = order[:n]
    return ids[order], counts[order]


class TopN(sets.Sets):
    def __init__(self, frames: list):
        super().__init__(frames)
        self.cache_size = {b.frame.name: int(b.frame.options.get("cacheSize", DEFAULT_CACHE_SIZE))
                           for b in frames}
        self._cands: dict = {}   # frame -> (writes seen, {slice: candidate mask over row ids})

    def _candidates(self, frame: str) -> dict:
        """{slice: bool[rows] - the slice's rank-cache candidates}."""
        f = self.frames[frame]
        got = self._cands.get(frame)
        if got is not None and got[0] == len(f.added):
            return got[1]
        rows, cols = f.row_r, f.row_c
        if f.added:
            a = np.array(f.added, dtype=np.int64)
            rows, cols = np.concatenate((rows, a[:, 0])), np.concatenate((cols, a[:, 1]))
        key, bits = np.unique(np.stack((cols // SLICE_WIDTH, rows)), axis=1, return_counts=True)
        n_rows = int(rows.max()) + 1 if len(rows) else 0
        out = {}
        for s in np.unique(key[0]).tolist():
            at = key[0] == s
            ids, _ = _top(key[1][at], bits[at], self.cache_size[frame])
            mask = np.zeros(n_rows, dtype=bool)
            mask[ids] = True
            out[s] = mask
        self._cands[frame] = (len(f.added), out)
        return out

    def topn(self, frame: str, src_row: int, n: int) -> list:
        src = self.cols(frame, src_row)
        slices, rows, counts = self.row_counts(src, frame)
        cands = self._candidates(frame)
        # Phase 1: each slice's top n among its own candidates.
        merged: dict = {}
        for s in np.unique(slices).tolist():
            at = slices == s
            r, c = rows[at], counts[at]
            mask = cands.get(s)
            ok = np.zeros(len(r), dtype=bool) if mask is None else (r < len(mask))
            ok[ok] = mask[r[ok]]
            for i, cnt in zip(*(x.tolist() for x in _top(r[ok], c[ok], n))):
                merged[i] = merged.get(i, 0) + cnt
        if not merged:
            return []
        # Phase 2: the merged ids' counts over every slice.
        ids = np.array(sorted(merged), dtype=np.int64)
        total = np.zeros(len(ids), dtype=np.int64)
        at = np.searchsorted(ids, rows)
        hit = (at < len(ids)) & (ids[np.minimum(at, len(ids) - 1)] == rows)
        np.add.at(total, at[hit], counts[hit])
        got_ids, got_counts = _top(ids, total, n)
        return [{"id": int(i), "count": int(c)} for i, c in zip(got_ids.tolist(), got_counts.tolist())]


def build(frames: list) -> TopN:
    return TopN(frames)
