"""A view's array containers as columns: what a block's walk reads
instead of every fragment's dict.

A pool miss walks a chunk of 8 rows over every fragment of a view, and
the rows of a tall frame hold a few bits a slice: ``Fragment.walk_rows``
finds one or two tiny arrays a row, and its cost is the Python a
fragment (a lock, a set intersection, a loop), times 64 or 256
fragments.  ``ViewColumns`` keeps a copy of the view's array containers
in flat arrays ordered by (container key, slice), so that a row's
containers of all slices lie together: a chunk's lookup is two probes a
row and a fixed handful of numpy calls whatever the slice count, and it
fills the same ``RowPieces`` that ``walk_rows`` fills.

A fragment's part (``FragmentColumns``: ``Fragment.array_columns``, a
copy under the fragment's lock) is stamped with the fragment's
generation and used only while the generation read during the walk is
that stamp; it is built when a walk over all the view's fragments meets
the fragment a second time at one generation, never on the first walk
after a write.  What the columns
cannot serve takes ``walk_rows`` as before, fragment by fragment: no
part or a stale one, a pending bulk overlay, a bitmap container among
the block's keys.  The process's write epoch
(``core.fragment.write_epoch``) makes "is every part still valid" one
comparison: an epoch that has not moved since every part was verified
proves that no fragment's generation was assigned since.
"""

from __future__ import annotations

import copy
from typing import Optional, Sequence

import numpy as np

from pilosa_tpu.analysis import lockcheck
from pilosa_tpu.core.fragment import Fragment, FragmentColumns, RowPieces, write_epoch
from pilosa_tpu.pilosa import SLICE_WIDTH

_WORDS = SLICE_WIDTH // 32
_PER_ROW = SLICE_WIDTH >> 16  # containers a row spans


def _ranges(lo: np.ndarray, n: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(the indices ``lo[i] .. lo[i] + n[i]`` of every range in turn, the
    range each index came from)."""
    which = np.arange(len(n)).repeat(n)
    return np.arange(len(which)) + (lo - (n.cumsum() - n))[which], which


class _Stitched:
    """The parts of a view's fragments stitched into one immutable set of
    columns.  Slot ``i`` is slice ``slices[i]``'s, its part taken at
    generation ``gens[i]``; an array container is an entry ``ck = key *
    len(slices) + slot`` (ascending), its values ``vals[start : start +
    lens]``, a slot's values lying together from ``base[slot]`` on;
    ``bitmap_ck``: the bitmap containers' entries, which are not served.
    ``epoch``: the write epoch read before every fragment of the view
    was found to have a part at its generation (None: some had not).
    4 bytes a bit and 16 a container."""

    __slots__ = ("slices", "slot_of", "served", "gens", "base", "ck", "start", "lens", "vals",
                 "bitmap_ck", "epoch", "nbytes", "_slots")

    def __init__(self, old: "Optional[_Stitched]" = None, new: "Optional[dict[int, FragmentColumns]]" = None):
        """``old``'s parts, those of ``new``'s slices replaced and added."""
        new = new or {}
        o_slices = old.slices if old is not None else ()
        self.slices = tuple(sorted({*o_slices, *new}))
        self.slot_of = slot_of = {s: i for i, s in enumerate(self.slices)}
        n = len(self.slices)
        kept = [s for s in o_slices if s not in new]
        self.gens = [new[s].gen if s in new else old.gens[old.slot_of[s]] for s in self.slices]
        self.served = {s: slot_of[s] for s in kept if s in old.served}
        self.served.update((s, slot_of[s]) for s, p in new.items() if not p.overlay)
        # The values, slot after slot: an old part's segment or a new part's array.
        segs = [new[s].vals if s in new else old.vals[old.base[old.slot_of[s]] : old.base[old.slot_of[s] + 1]]
                for s in self.slices]
        self.base = base = np.cumsum([0] + [len(v) for v in segs])
        self.vals = np.concatenate(segs) if segs else np.zeros(0, dtype=np.uint32)
        ck = [p.keys * n + slot_of[s] for s, p in new.items()]
        start = [p.start + base[slot_of[s]] for s, p in new.items()]
        lens = [p.lens for p in new.values()]
        bitmap_ck = [p.bitmap_keys * n + slot_of[s] for s, p in new.items()]
        if kept:
            # old slot -> new slot (-1: replaced)
            to = np.array([-1 if s in new else slot_of[s] for s in o_slices])

            def renumbered(entries):
                key, slot = np.divmod(entries, len(o_slices))
                stay = np.flatnonzero(to[slot] >= 0)
                return key[stay] * n + to[slot[stay]], stay, slot[stay]

            entries, stay, slot = renumbered(old.ck)
            ck.append(entries)
            start.append(old.start[stay] + (base[to] - old.base[:-1])[slot])  # its values moved with its slot
            lens.append(old.lens[stay])
            bitmap_ck.append(renumbered(old.bitmap_ck)[0])
        ck = np.concatenate(ck) if ck else np.zeros(0, dtype=np.int64)
        # Every part's keys ascend: sorted runs, which a stable sort merges.
        order = np.argsort(ck, kind="stable")
        self.ck = ck[order]
        self.start = (np.concatenate(start) if start else np.zeros(0, dtype=np.int64))[order].astype(
            np.int32 if base[-1] < 2 ** 31 else np.int64)
        self.lens = (np.concatenate(lens) if lens else np.zeros(0, dtype=np.int32))[order]
        self.bitmap_ck = np.sort(np.concatenate(bitmap_ck)) if bitmap_ck else np.zeros(0, dtype=np.int64)
        self.epoch: Optional[int] = None
        self.nbytes = sum(a.nbytes for a in (self.ck, self.start, self.lens, self.vals, self.bitmap_ck))
        self._slots: dict = {}

    def slots(self, chunk_slices: Sequence[int]) -> np.ndarray:
        """The slot of each of the chunk's slices; -1 where the columns
        have no part for it, or one taken under a bulk overlay."""
        key = tuple(chunk_slices)
        got = self._slots.get(key)
        if got is None:
            served = self.served
            got = np.array([served.get(s, -1) for s in key], dtype=np.int64)
            if len(key) > 8:  # a pool's whole slice list, asked for again and again
                if len(self._slots) >= 8:
                    self._slots.clear()
                self._slots[key] = got
        return got

    def lookup(self, pieces: RowPieces, plane0: np.ndarray) -> np.ndarray:
        """Note in ``pieces`` the array containers that the block's rows
        have in every slot ``i`` with ``plane0[i] >= 0`` (the first plane
        of that slice's part of the block).  Returns the slots among
        them that hold a bitmap container of one of the rows: nothing of
        theirs is noted, they are ``walk_rows``'s."""
        n = len(self.slices)
        w0 = np.array([w for w, _ in pieces.rows], dtype=np.int64)
        rid = np.array([r for _, r in pieces.rows], dtype=np.int64)
        first = rid * (_PER_ROW * n)  # a row's entries: [first, first + 16 n)
        bounds = np.concatenate((first, first + _PER_ROW * n))
        denied = np.zeros(0, dtype=np.int64)
        if len(self.bitmap_ck):
            b = np.searchsorted(self.bitmap_ck, bounds)
            hit, _ = _ranges(b[: len(rid)], b[len(rid):] - b[: len(rid)])
            denied = np.unique(self.bitmap_ck[hit] % n)
            denied = denied[plane0[denied] >= 0]
            if len(denied):
                plane0 = plane0.copy()
                plane0[denied] = -1
        b = np.searchsorted(self.ck, bounds)
        e, row = _ranges(b[: len(rid)], b[len(rid):] - b[: len(rid)])
        key, slot = np.divmod(self.ck[e], n)
        p0 = plane0[slot]
        if len(p0) and p0.min() < 0:
            keep = np.flatnonzero(p0 >= 0)
            e, row, key, p0 = e[keep], row[keep], key[keep], p0[keep]
        if len(e):
            lens = self.lens[e]
            v, _ = _ranges(self.start[e], lens)
            pieces.cols = (
                p0 * _WORDS + w0[row] + (key - rid[row] * _PER_ROW) * 2048, lens, self.vals[v])
        return denied


@lockcheck.guarded_class
class ViewColumns:
    """One view's columns and the rule that builds them.  A walk reads
    ``_state`` (immutable, replaced whole) with no lock; one that finds
    the write epoch moved, or the parts not yet all there, looks at its
    fragments one by one under ``_mu`` and builds the parts that are due.
    ``stats`` is the walker's (the executor's): counters
    ``walk.fragments_snapshot`` / ``walk.fragments_dict`` (fragments of
    a block served each way), ``walk.snapshot_builds`` (parts built)."""

    _guarded_by_ = {
        "_state": "core.columns._mu",
        "_walked": "core.columns._mu",
    }

    def __init__(self, fragments: "dict[int, Fragment]"):
        self.fragments = fragments  # the view's own dict
        self._mu = lockcheck.named_lock("core.columns._mu")
        self._state = _Stitched()
        # slice -> the generation at which it was last walked with no
        # part to serve it: a second walk at that generation builds one.
        self._walked: dict[int, int] = {}

    @property
    def nbytes(self) -> int:
        """Host bytes the columns hold."""
        return self._state.nbytes

    def drop(self) -> None:
        with self._mu:
            self._state = _Stitched()
            self._walked = {}

    def _check(self, chunk_slices: Sequence[int], stats) -> tuple[_Stitched, set]:
        """(the state, the chunk's slices whose part in it is at another
        generation than their fragment) after every fragment of the chunk
        was looked at.  A fragment without a part at its generation gets
        one built if it was walked before at that generation and this
        walk crosses every fragment of the view (a repair's fetch of the
        slices just written does not: two readers that took different
        generations can fetch one twice, and a walk of a few fragments
        has nothing to gain); otherwise it is noted as walked."""
        with self._mu:
            epoch = write_epoch()  # before any generation is read
            state, walked = self._state, self._walked
            found = {s: f for s in chunk_slices if (f := self.fragments.get(s)) is not None}
            whole = len(found) == len(self.fragments)
            due, stale, current = {}, set(), 0
            for s, f in found.items():
                gen = f.generation
                slot = state.slot_of.get(s)
                if slot is not None and state.gens[slot] == gen:
                    current += 1
                elif whole and walked.get(s) == gen:
                    due[s] = f
                else:
                    walked[s] = gen
                    if slot is not None:
                        stale.add(s)
            if due:
                for s in due:
                    del walked[s]
                self._state = state = _Stitched(state, {s: f.array_columns() for s, f in due.items()})
                stats.count("walk.snapshot_builds", len(due))
                current += len(due)
            if whole and current == len(found) == len(state.slices):
                # Every fragment of the view has a part at its generation.
                self._state = state = copy.copy(state)
                state.epoch = epoch
            return state, stale

    def walk(self, pieces: RowPieces, chunk_slices: Sequence[int], plane0: np.ndarray, stats) -> None:
        """Fill ``pieces`` with the block's rows over ``chunk_slices``,
        slice ``chunk_slices[bi]``'s part of the block beginning at plane
        ``plane0[bi]``: from the columns for every fragment they can
        serve (``pieces.served`` says how many), by
        ``Fragment.walk_rows`` for the others."""
        state, stale = self._state, None
        if state.epoch is None or state.epoch != write_epoch():
            state, stale = self._check(chunk_slices, stats)
        slots = state.slots(chunk_slices)
        if stale:
            slots = np.where([s in stale for s in chunk_slices], -1, slots)
        by_dict = slots < 0
        if not by_dict.all():
            at = np.full(len(state.slices), -1, dtype=np.int64)
            at[slots[~by_dict]] = plane0[~by_dict]
            denied = state.lookup(pieces, at)
            if len(denied):
                by_dict |= np.isin(slots, denied)
        left = by_dict.nonzero()[0].tolist()
        pieces.served = len(slots) - len(left)
        n_dict = 0
        for bi in left:
            f = self.fragments.get(chunk_slices[bi])
            if f is not None:
                f.walk_rows(pieces, int(plane0[bi]))
                n_dict += 1
        if pieces.served:
            stats.count("walk.fragments_snapshot", pieces.served)
        if n_dict:
            stats.count("walk.fragments_dict", n_dict)
