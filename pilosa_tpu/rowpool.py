"""Paged device-resident row pool: the HBM working set without a row cap.

Round-1's fused query lanes kept ONE device matrix per (frame, view,
slice-batch) holding exactly the rows ever referenced, hard-capped at
``PILOSA_TPU_MATRIX_ROWS_MAX`` rows — past the cap every request fell back
to host numpy.  The reference has no such ceiling: its rank cache tracks
``DefaultCacheSize=50000`` rows per fragment (frame.go:33-40,
cache.go:126-275) and rows page between mmap and memory on demand
(fragment.go:338-367).

This module is the TPU-native replacement: a fixed-capacity slot pool
``uint32[n_slices, capacity, W]`` in device memory.  Rows page in on
demand - a miss goes to the device, chunk by chunk, as its rows' words
that are not zero (host roaring -> a word list -> one scatter a chunk:
the planes are built in HBM), or as a dense host block where a row has
a bitmap container, a pending bulk overlay, or too many words
(``_page_in``) - LRU rows page out when the pool is full, and the
capacity itself grows by power-of-two doubling up to an HBM budget.  Query kernels index rows by
SLOT id — the same gather kernels as before, they never cared whether
slot assignment was dense or paged.

Consistency model: a reader that acquired ``(positions, matrix)`` for
rows it wanted holds an immutable snapshot — a concurrent eviction or
write repair can only affect later acquires, never a result in flight.
Paging, growth and the blind refresh produce a NEW engine array
(functional ``.at[].set``); the write repair (``_repair_dirty``) may
update the pool's array in place, and does so only while that array has
never been handed to a reader.  Write invalidation is generation-based
exactly like the old cache: stale slices get their planes re-fetched
(bounded), or the pool resets when a refresh would cost more than
repopulating on demand.
"""

from __future__ import annotations

import os
import threading

from pilosa_tpu.analysis import lockcheck
from pilosa_tpu.engine import _pow2
from pilosa_tpu.stats import NOP_STATS
from collections import OrderedDict
from typing import Callable, Optional, Sequence

import numpy as np

POOL_BYTES_PER_DEVICE = 2 * 1024 * 1024 * 1024
# A miss pages its rows in chunks of this many: the host reads chunk k+1
# from storage while chunk k's upload and scatter are in flight; a sparse
# chunk's program takes this many slots, whatever its rows; a dense
# chunk's block (64 MiB at 64 slices) is padded to at most 3 rows more
# than it holds, and its programs are the buckets up to here (1, 2, 4,
# 8).  Copying and donating forms of each.
MISS_CHUNK_ROWS = 8
# The most words a chunk may ship sparse (the largest word bucket, a power
# of four: ``engine._pow4``); a chunk with more goes dense.  Where the two
# forms cost the device and PCIe the same on a TPU v5e (PERF.md 7): the
# sparse scatter into a 2 GiB pool takes 0.3 ms and 0.097 us a word (6.4 ms
# at 65,536), a dense chunk of 8 rows at 64 slices 6.4 ms to upload and
# write (0.096 ns a byte); the host's side only widens the gap (no 64 MiB
# of zeros to make and fill).  Below one row of a frame as dense as
# CENSUS1881's (78,700 set words over 64 slices): such rows page dense.
MISS_WORDS_MAX = 65536


def pool_bytes(engine=None, n_slices: int = 0) -> tuple[int, int]:
    """(HBM budget for ONE pool's matrix, devices that hold it): 2 GiB PER
    DEVICE that shares the pool's slice axis - one device on the numpy and
    jax engines, the mesh's devices on the mesh engine where it shards
    ``n_slices`` (``engine.slice_axis_devices``), so each device holds
    the 2 GiB share it would hold alone.  ``PILOSA_TPU_POOL_BYTES``, where
    set, is one pool's whole budget whatever the engine (read per call:
    benches and tests tune it).  Total pool memory is bounded by this times
    the executor's matrix-cache entry count; transient peaks reach 2x one
    pool plus a miss's chunks in flight during a miss (the pool is copied
    once a miss, not donated: old + new array alive, and what the chunks
    being uploaded hold: 16 bytes a word of a sparse chunk, a block of
    ``MISS_CHUNK_ROWS`` rows of planes for a dense one)."""
    devices = engine.slice_axis_devices(n_slices) if engine is not None else 1
    # analysis-ok: lockstep-determinism: deployment config, launcher sets identical env on every rank
    env = os.environ.get("PILOSA_TPU_POOL_BYTES")
    return (int(env) if env else POOL_BYTES_PER_DEVICE * devices), devices


def _refresh_bytes_max() -> int:
    """A stale-slice plane refresh re-uploads every resident row for those
    slices; past this many bytes a reset-and-repopulate is cheaper than
    the blind refresh (writes invalidated most of what residency was
    worth)."""
    return int(os.environ.get("PILOSA_TPU_POOL_REFRESH_BYTES", str(512 * 1024 * 1024)))


def pool_capacity(n_slices: int, words: int, engine=None, budget_bytes: int = 0) -> int:
    """Slot capacity the budget allows for an ``[n_slices, cap, W]`` pool
    on ``engine``'s devices: the per-device budget times the devices that
    share the slice axis (``pool_bytes``), so a mesh of four holds four
    times the slots of one device at the same bytes per device."""
    budget = budget_bytes or pool_bytes(engine, n_slices)[0]
    return max(0, budget // max(1, n_slices * words * 4))


class DeviceRowPool:
    """One frame-view's paged row working set over a fixed slice batch.

    ``fetch(row_ids, slice_idxs) -> uint32[len(slice_idxs), len(row_ids), W]``
    pulls dense rows from host storage (fragment ``row_dense``): the
    repair's, the blind refresh's and a dense miss chunk's block.
    ``fetch_pieces``, where given, is the walk under it, from which a
    miss takes the rows' set words alone (``_page_in``).
    """

    def __init__(
        self,
        engine,
        n_slices: int,
        words: int,
        fetch: Callable[[Sequence[int], Sequence[int]], np.ndarray],
        cap_max: int = 0,
        row_major: bool = False,
        stats=None,
        fetch_pieces: Optional[Callable] = None,
    ):
        self.engine = engine
        self.stats = stats if stats is not None else NOP_STATS
        self.n_slices = n_slices
        self.words = words
        self.fetch = fetch
        # ``fetch_pieces(row_ids, slice_idxs)``: the walk under ``fetch``
        # without the block (words(), dense, fill(): what
        # ``core.fragment.RowPieces`` has), planes numbered as ``fetch``'s
        # block lays them out.  With it a miss pages sparse where the
        # chunk allows (``_page_in``); without it every chunk is dense.
        self.fetch_pieces = fetch_pieces
        # Row-major pools store [cap, n_slices, W] (tiled) so the gather
        # regime's kernels get one contiguous DMA descriptor per operand
        # row; ``fetch`` must then return [len(row_ids), len(slice_idxs),
        # W] blocks (the executor's densify fills either order directly).
        # Slice-major (default) matches mesh sharding and the Gram/TopN
        # lanes.
        self.row_major = row_major
        # 0 = budget-driven (re-read per access so a retuned
        # PILOSA_TPU_POOL_BYTES applies to cached pools, keeping this in
        # lockstep with callers that consult pool_capacity() directly).
        self._cap_override = cap_max
        self.mu = lockcheck.named_rlock("rowpool.mu")
        self.gens: Optional[tuple] = None
        self.matrix = None  # engine array [n_slices, cap, W]; clears _handed_out
        self.cap = 0
        self.slot_of: dict[int, int] = {}
        self.row_at: list[Optional[int]] = []
        self.lru: OrderedDict[int, None] = OrderedDict()
        self.box: dict = self._new_box()
        # Telemetry for benches/tests: paging behavior must be observable.
        # Each also goes out through ``stats`` where it is incremented
        # (rowpool.misses, .evictions, .resets, .repairs, .repairs_in_place,
        # .repairs_composed, .patch_planes).
        self.stat_misses = 0
        self.stat_evictions = 0
        self.stat_resets = 0
        self.stat_repairs = 0
        # Of those, the ones that updated the pool's array where it lay
        # (no copy of the pool: the jax or mesh engine's compiled step,
        # donated).
        self.stat_repairs_in_place = 0
        # ... and the ones that took the copying form (set_plane_cells and
        # gram_update_rows: every repair of the numpy engine, the wide
        # ones of the others).
        self.stat_repairs_composed = 0
        # (row, slice) planes actually fetched by the patch lane — the
        # per-(row, slice) granularity benches/tests assert on this.
        self.stat_patch_planes = 0
        # Block sizes that misses' chunks were padded to
        # (``rowpool.miss_buckets`` counts them as they appear): each is
        # one scatter program, copying and donating.
        self.miss_buckets: set[int] = set()

    @staticmethod
    def default_cap(n_slices: int, words: int, engine=None) -> int:
        """The budget-driven cap an un-overridden pool on ``engine`` would
        report (2 GiB per device that shares the slice axis) — shared with
        callers that must predict a pool's capacity WITHOUT instantiating
        it (executor lane probes)."""
        return max(1, pool_capacity(n_slices, words, engine))

    @property
    def matrix(self):
        return self._matrix

    @matrix.setter
    def matrix(self, m) -> None:
        # A new array object has not left the pool yet: ``acquire`` marks
        # it when it hands it to a caller that asked for rows, and a write
        # repair may donate it (update it in place) until then.
        self._matrix = m
        self._handed_out = False

    @property
    def cap_max(self) -> int:
        if self._cap_override:
            return self._cap_override
        return self.default_cap(self.n_slices, self.words, self.engine)

    @cap_max.setter
    def cap_max(self, v: int) -> None:
        self._cap_override = v

    def _new_box(self) -> dict:
        # Same contract as the old matrix-cache "box": holds the Gram and
        # its lut, dies on ANY content change.  id_pos is the full
        # row->slot snapshot (immutable; rebuilt per box) so steady-state
        # hits hand out positions without copying; n_used bounds the slot
        # range in use so Gram builds can ignore free capacity tail.
        return {
            "hits": 0,
            "mu": lockcheck.named_lock("rowpool.entry_mu"),
            "id_pos": dict(self.slot_of),
            "n_used": max(self.slot_of.values(), default=-1) + 1,
        }

    # -- internals (call with self.mu held) ------------------------------

    def _grow_to(self, need: int) -> None:
        new_cap = min(self.cap_max, _pow2(need))
        if new_cap <= self.cap:
            return
        if self.matrix is None or self.cap == 0:
            if self.row_major:
                host = np.zeros((new_cap, self.n_slices, self.words), dtype=np.uint32)
                self.matrix = self.engine.matrix_rows(host)
            else:
                host = np.zeros((self.n_slices, new_cap, self.words), dtype=np.uint32)
                self.matrix = self.engine.matrix(host)
        elif self.row_major:
            self.matrix = self.engine.grow_rows_rm(self.matrix, new_cap - self.cap)
        else:
            # Zero capacity appended device-side (no host transfer).
            self.matrix = self.engine.grow_rows(self.matrix, new_cap - self.cap)
        self.row_at.extend([None] * (new_cap - self.cap))
        self.cap = new_cap
        # What the pool that last took device memory was budgeted.
        budget, devices = pool_bytes(self.engine, self.n_slices)
        self.stats.gauge("rowpool.budget_bytes_per_device", budget // devices)
        self.stats.gauge("rowpool.capacity_slots", self.cap_max)

    def _reset(self) -> None:
        self.slot_of.clear()
        self.lru.clear()
        self.row_at = [None] * self.cap
        # Matrix contents are stale garbage but unreferenced: no slot maps
        # to them, and gathers only index mapped slots.
        self.stat_resets += 1
        self.stats.count("rowpool.resets")

    def _drop(self) -> None:
        """To the empty state: a repair that failed after the array was
        donated leaves none, so the next acquire rebuilds from storage."""
        self.matrix = None
        self.cap = 0
        self.gens = None
        self._reset()
        self.box = self._new_box()

    def _fetch_block(self, rows: list[int], slice_idxs: list[int]):
        """(the dense block of ``rows`` over ``slice_idxs``, laid out per
        ``self.row_major``; how many of its fragments the view's columns
        served: ``RowPieces.served``, 0 for a pool with ``fetch`` alone)."""
        if self.fetch_pieces is None:
            return self.fetch(rows, slice_idxs), 0
        pieces = self.fetch_pieces(rows, slice_idxs)
        return self._dense(pieces, len(rows), len(slice_idxs)), pieces.served

    def _dense(self, pieces, n_rows: int, n_slices: int) -> np.ndarray:
        """The dense block of a walk's pieces, laid out per ``self.row_major``."""
        shape = (n_rows, n_slices) if self.row_major else (n_slices, n_rows)
        block = np.zeros(shape + (self.words,), dtype=np.uint32)
        pieces.fill(block)
        return block

    def _refresh_stale(self, stale: list[int]) -> None:
        """Re-pull resident rows' planes for written slices, or reset.

        Only the RESIDENT slots are scattered (set_plane_rows) — a
        whole-plane replacement would transfer the full capacity width,
        mostly zeros, undercutting the byte budget this check enforces.
        """
        if not self.slot_of:
            return
        if len(self.slot_of) * len(stale) * self.words * 4 > _refresh_bytes_max():
            self._reset()
            return
        rows = sorted(self.slot_of, key=self.slot_of.get)
        slots = [self.slot_of[r] for r in rows]
        block = self.fetch(rows, stale)  # layout per self.row_major
        if self.row_major:  # block: [len(rows), len(stale), W]
            self.matrix = self.engine.set_plane_rows_rm(
                self.matrix, stale, slots, block
            )
        else:  # block: [len(stale), len(rows), W]
            self.matrix = self.engine.set_plane_rows(self.matrix, stale, slots, block)

    def _repair_dirty(self, stale: list[int], dirty_rows, span=None) -> bool:
        """Patch ONLY the written (row, slice) planes and rank-k-repair
        the box Gram, instead of the blind whole-plane refresh + box
        reset: the box (and with it the Gram, its glut, and the id_pos
        snapshot) SURVIVES the write, so a small write costs O(dirty
        planes) row fetches plus one pass over the written slices' rows
        — not an O(R^2) Gram rebuild.  ``dirty_rows`` is either
        a ``{slice_index: rows}`` mapping (per-(row, slice) granularity:
        each stale slice re-fetches only the rows written IN that slice)
        or a flat row iterable (legacy: every dirty row re-fetched
        across every stale slice).  The caller (executor) guarantees it
        covers every row whose storage changed across the stale slices
        (fragment dirty-row journals); rows not resident in the pool
        need no patch at all.  Returns False (nothing mutated) when the
        dirty slots fall outside the Gram's slot range — an invariant
        breach that the conservative full refresh handles.

        Planes and Gram go through ONE engine call a repair
        (``engine.repair_planes``).  While the pool's array has never
        been handed to a reader, the jax and mesh engines update it IN
        PLACE (the array is donated to one compiled step - on the mesh
        the same step on every device's own shard, under ``shard_map``,
        the deltas psummed: no copy of the pool or of a shard, and the
        old array object is gone); otherwise, for the repairs the step
        does not take (wide ones, over half the slices: the composed
        form) and on the numpy engine, the update is functional and a
        reader's snapshot stays whole.  A step that fails after taking
        the array leaves the pool empty (``_drop``) and raises.  Row-major
        pools carry no Gram and keep their functional scatter.

        ``span`` (the request's ``pool.repair``) gets the tag ``form``
        (``step`` or ``composed``: which form the engine ran) and a child
        per stage:
        ``pool.fetch`` (host densify; tag ``snapshot``: fragments that
        the view's columns served - none where only the slice just
        written is fetched), ``pool.scatter`` (index building,
        upload and the dispatch) and ``pool.gram``, where the host blocks
        on the device for the counts and folds them into its Gram (on the
        mesh engine with a ``mesh.fetch`` child for the wait itself)."""
        if isinstance(dirty_rows, dict):
            per_slice = {
                si: sorted(r for r in set(dirty_rows.get(si, ())) if r in self.slot_of)
                for si in stale
            }
        else:
            flat = sorted(r for r in set(dirty_rows) if r in self.slot_of)
            per_slice = {si: flat for si in stale}
        patched = [si for si in stale if per_slice[si]]
        if not patched:
            return True  # writes only touched rows the pool doesn't hold
        gram = self.box.get("gram")
        if gram is not None and any(
            self.slot_of[r] >= gram.shape[0] for si in patched for r in per_slice[si]
        ):
            return False  # defensive: slot outside the Gram bucket
        # One fetch per distinct row set: slices written with the same
        # rows batch into a single densify, and a slice whose dirty rows
        # aren't resident costs nothing at all.
        by_rows: dict[tuple, list[int]] = {}
        for si in patched:
            by_rows.setdefault(tuple(per_slice[si]), []).append(si)
        sp = span.child("pool.fetch") if span is not None else None
        served = 0
        groups = []
        for rows_t, group in by_rows.items():
            block, n = self._fetch_block(list(rows_t), group)
            groups.append((group, [self.slot_of[r] for r in rows_t], block))
            served += n
        if sp is not None:
            sp.finish().annotate(snapshot=served)
            sp = span.child("pool.scatter")
        planes = sum(len(group) * len(slots) for group, slots, _ in groups)
        self.stat_patch_planes += planes
        self.stats.count("rowpool.patch_planes", planes)
        if self.row_major:
            for group, slots, block in groups:
                self.matrix = self.engine.set_plane_rows_rm(
                    self.matrix, group, slots, block
                )
            if sp is not None:
                sp.finish()
            return True
        taken = self.matrix
        try:
            self.matrix, finish, in_place, form = self.engine.repair_planes(
                taken, gram, groups, donate=not self._handed_out
            )
            if sp is not None:
                sp.finish()
                span.annotate(form=form)
            if finish is not None:
                sp = span.child("pool.gram") if span is not None else None
                gram = finish(sp)
                if sp is not None:
                    sp.finish()
        except BaseException:
            # The array was taken and not given back, or the planes are
            # written and the Gram is not: nothing here can be trusted.
            if self.matrix is not taken or getattr(taken, "is_deleted", lambda: False)():
                self._drop()
            raise
        if in_place:
            self.stat_repairs_in_place += 1
            self.stats.count("rowpool.repairs_in_place")
        if form == "composed":
            self.stat_repairs_composed += 1
            self.stats.count("rowpool.repairs_composed")
        if finish is not None:
            self.box["gram"] = gram
            glut = self.box.get("gram_lut")
            if glut is not None:
                # rs/ps are membership-keyed and membership didn't change;
                # only the count table is new.
                self.box["gram_lut"] = (glut[0], np.ascontiguousarray(gram), glut[2])
        return True

    def _page_in(self, missing: list[int], slots: list[int], span=None):
        """The pool's array with ``missing`` in ``slots``, and what was
        uploaded for it (rows, padding included; chunks that went sparse;
        their words): chunk after chunk of ``MISS_CHUNK_ROWS``, each
        read from storage on the host (span ``pool.miss.fetch``) while
        the one before uploads, then enqueued (``pool.miss.scatter``).
        The first chunk is scattered into a COPY of the pool (a reader
        may hold the array), the others into that copy itself (donated:
        no other reference exists).

        A chunk is sparse or dense by what the walk of its rows found
        (``fetch_pieces``; a pool built with ``fetch`` alone pages dense).
        The walk (``executor._walk_block``) reads the view's columns - a
        copy of its array containers in flat arrays, two probes a row and
        a fixed handful of numpy calls whatever the slice count
        (``core.columns.ViewColumns``) - for every fragment whose part of
        them is at the fragment's generation, and walks the dict
        (``Fragment.walk_rows``) of the others; ``pool.miss.fetch``'s tag
        ``snapshot`` says how many fragments the columns served.
        Sparse, when no row of it has a bitmap container or a pending
        bulk overlay and its words that are not zero number at most
        ``MISS_WORDS_MAX``: those words go to the device as (slice, slot,
        word) cells and ``uint32`` values, and the engine zeroes the
        chunk's slots and writes the words into them
        (``engine.set_words_at``).  Dense otherwise: the same words
        scattered into a zeroed host block, bitmap containers and
        overlays copied in, uploaded whole (``engine.set_rows_at``).  An
        engine that compiles gets a dense block in the power-of-two
        bucket of its rows (row -1 a zero plane, slot -1 dropped) and a
        sparse chunk's slots padded to ``MISS_CHUNK_ROWS``: one scatter
        program a bucket and form."""
        static = getattr(self.engine, "wants_static_shapes", False)
        engine = self.engine
        set_rows = engine.set_rows_at_rm if self.row_major else engine.set_rows_at
        if self.fetch_pieces is not None:
            set_words = engine.set_words_at_rm if self.row_major else engine.set_words_at
        all_slices = list(range(self.n_slices))
        matrix, uploaded, sparse, n_words = self.matrix, 0, 0, 0
        for at in range(0, len(missing), MISS_CHUNK_ROWS):
            rows = missing[at : at + MISS_CHUNK_ROWS]
            into = slots[at : at + MISS_CHUNK_ROWS]
            tail = [-1] * (_pow2(len(rows)) - len(rows) if static else 0)
            bucket = len(rows) + len(tail)
            if bucket not in self.miss_buckets:
                self.miss_buckets.add(bucket)
                self.stats.count("rowpool.miss_buckets")
            sp = span.child("pool.miss.fetch") if span is not None else None
            cells = None
            if self.fetch_pieces is None:
                block = self.fetch(rows + tail, all_slices)  # layout per self.row_major
            else:
                pieces = self.fetch_pieces(rows + tail, all_slices)
                word, values = pieces.words()
                if pieces.dense or len(word) > MISS_WORDS_MAX:
                    block = self._dense(pieces, bucket, self.n_slices)
                else:
                    plane, w = np.divmod(word, self.words)
                    if self.row_major:
                        k, si = np.divmod(plane, self.n_slices)
                    else:
                        si, k = np.divmod(plane, bucket)
                    cells = np.stack(
                        [si, np.asarray(into, dtype=np.int64)[k], w], axis=1
                    ).astype(np.int32)
            if sp is not None:
                sp.finish()
                if self.fetch_pieces is not None:
                    sp.annotate(snapshot=pieces.served)
                sp = span.child("pool.miss.scatter")
            if cells is None:
                matrix = set_rows(matrix, into + tail, block, donate=at > 0)
                self.stats.count("rowpool.miss_chunks_dense")
            else:
                pad = [-1] * (MISS_CHUNK_ROWS - len(into) if static else 0)
                matrix = set_words(matrix, into + pad, cells, values, donate=at > 0)
                sparse += 1
                n_words += len(values)
                self.stats.count("rowpool.miss_chunks_sparse")
                self.stats.count("rowpool.miss_words", len(values))
            if sp is not None:
                sp.finish()
            uploaded += bucket
        return matrix, uploaded, sparse, n_words

    def _repair_spanned(self, stale: list[int], dirty_rows, span) -> bool:
        """``_repair_dirty`` under the request's ``pool.repair`` span."""
        if span is None:
            return self._repair_dirty(stale, dirty_rows)
        sp = span.child("pool.repair")
        planes0, up0 = self.stat_patch_planes, self.engine.stat_upload_bytes
        in_place0 = self.stat_repairs_in_place
        ok = self._repair_dirty(stale, dirty_rows, sp)
        sp.finish().annotate(
            planes=self.stat_patch_planes - planes0,
            upload_bytes=self.engine.stat_upload_bytes - up0,
            slices=len(stale),
            in_place=self.stat_repairs_in_place > in_place0,
            devices=self.engine.slice_axis_devices(self.n_slices),
        )
        return ok

    # -- API --------------------------------------------------------------

    def acquire(self, want: Sequence[int], gens: tuple, dirty_rows=None, span=None):
        """Ensure ``want`` rows are resident; returns (id_pos, matrix, box).

        ``id_pos`` maps every RESIDENT row id to its slot (a stable
        snapshot — safe to index concurrently); ``matrix`` is the engine
        array snapshot those slots refer to: the caller's to keep and to
        dispatch on outside the lock when it asked for rows (the array
        is then marked as handed out, and no later repair touches it); a
        caller with no ``want`` takes the box alone, for the array may be
        updated in place by the next write repair.  Raises ValueError when
        ``want`` alone exceeds the pool capacity — callers chunk their
        query batch by unique-row count first (``chunk_queries``).

        ``dirty_rows``: the complete delta written since this pool's
        recorded generations (from the fragment dirty-row journals) —
        either a ``{slice_index: rows}`` mapping (per-(row, slice)
        granularity) or a flat row set (every row dirty in every stale
        slice) — or None when unknown.  When provided, a generation
        mismatch takes the PATCH lane (_repair_dirty) and the cache box
        — including a warm Gram — survives the write.

        ``span``: the traced request's span (None = unsampled: one branch
        per site).  Children: ``pool.lock_wait`` (entry to ``self.mu``
        held), then whichever of ``pool.repair`` (the patch lane),
        ``pool.refresh`` (the blind refresh) and ``pool.miss`` (paging)
        this call ran under the lock.

        What a miss costs, under the lock: the LRU's victims leave
        (host bookkeeping), then the missing rows page in by chunks of
        ``MISS_CHUNK_ROWS`` (``_page_in``): one lookup in the view's
        columns for all the fragments they serve (a fragment that was
        written is walked dict by dict until it has been quiet for two
        walks: ``core.columns``) and one numpy pass give the chunk's words
        that are not zero - a span ``pool.miss.fetch`` a chunk, tag
        ``snapshot``: the fragments the columns served - and the engine
        enqueues their upload (16 bytes a word) and the program that
        zeroes the chunk's slots and writes the words into them
        (``pool.miss.scatter``); a chunk the word list cannot hold (a
        bitmap container, a bulk overlay, over ``MISS_WORDS_MAX`` words)
        goes up as a dense block instead, padded to the power-of-two
        bucket of its rows for an engine that compiles.  Either into a
        COPY of the pool for the first chunk (the pool is not donated,
        for a reader may hold it) and into that copy for the rest.
        ``pool.miss`` carries ``rows``, ``bucket`` (rows paged, a dense
        block's padding included), ``evicted``, ``sparse`` (chunks that
        went sparse), ``words`` (what they shipped), ``upload_bytes``
        (bytes handed to the device: cells and values, or blocks) and
        ``devices`` (how many share the pool's slice axis).  The
        first miss that evicts has the engine compile every program a
        miss can meet first (``warm_set_rows``): once a pool.
        """
        want = list(dict.fromkeys(want))  # de-dup, keep order
        if len(want) > self.cap_max:
            raise ValueError(
                f"want {len(want)} rows > pool capacity {self.cap_max}; chunk the batch"
            )
        sp = span.child("pool.lock_wait") if span is not None else None
        with self.mu:
            if sp is not None:
                sp.finish()
            changed = False
            if self.gens != gens:
                if self.gens is not None:
                    stale = [
                        si for si in range(self.n_slices) if self.gens[si] != gens[si]
                    ]
                    if stale:
                        if dirty_rows is not None and self._repair_spanned(
                            stale, dirty_rows, span
                        ):
                            self.stat_repairs += 1
                            self.stats.count("rowpool.repairs")
                        else:
                            sp = span.child("pool.refresh") if span is not None else None
                            up0 = self.engine.stat_upload_bytes
                            self._refresh_stale(stale)
                            changed = True
                            if sp is not None:
                                sp.finish().annotate(
                                    rows=len(self.slot_of),
                                    upload_bytes=self.engine.stat_upload_bytes - up0,
                                )
                self.gens = gens
            missing = [r for r in want if r not in self.slot_of]
            if missing:
                sp = span.child("pool.miss") if span is not None else None
                self.stat_misses += len(missing)
                self.stats.count("rowpool.misses", len(missing))
                changed = True
                need = len(self.slot_of) + len(missing)
                if need > self.cap:
                    self._grow_to(need)
                free = [s for s in range(self.cap) if self.row_at[s] is None]
                if len(free) < len(missing) and not self.stat_evictions:
                    # From here on this pool pages for as long as it
                    # lives: no later miss count may compile.
                    self.engine.warm_set_rows(
                        self.matrix, MISS_CHUNK_ROWS, self.row_major,
                        MISS_WORDS_MAX if self.fetch_pieces is not None else 0,
                    )
                up0, ev0 = self.engine.stat_upload_bytes, self.stat_evictions
                if len(free) < len(missing):
                    want_set = set(want)
                    for victim in list(self.lru):
                        if len(free) >= len(missing):
                            break
                        if victim in want_set:
                            continue
                        s = self.slot_of.pop(victim)
                        del self.lru[victim]
                        self.row_at[s] = None
                        free.append(s)
                        self.stat_evictions += 1
                slots = free[: len(missing)]
                self.matrix, bucket, sparse, words = self._page_in(missing, slots, sp)
                for r, s in zip(missing, slots):
                    self.slot_of[r] = s
                    self.row_at[s] = r
                evicted = self.stat_evictions - ev0
                if evicted:
                    self.stats.count("rowpool.evictions", evicted)
                if sp is not None:
                    sp.finish().annotate(
                        rows=len(missing), evicted=evicted, bucket=bucket,
                        sparse=sparse, words=words,
                        upload_bytes=self.engine.stat_upload_bytes - up0,
                        devices=self.engine.slice_axis_devices(self.n_slices),
                    )
            for r in want:
                self.lru[r] = None
                self.lru.move_to_end(r)
            if changed:
                self.box = self._new_box()
            # The generations THIS box's matrix content was validated
            # against: consumers deriving cached state from the box (the
            # executor's serve-state capture) must use these as validity
            # tokens, not generations re-read later — a write landing
            # between acquire and capture would otherwise stamp post-
            # write tokens onto pre-write data (permanent stale serves).
            self.box["gens"] = gens
            self.box["hits"] += 1
            if want:
                self._handed_out = True
            return self.box["id_pos"], self.matrix, self.box


def chunk_queries(
    queries: Sequence, rows_of: Callable, cap: int, oversize_ok: bool = False
) -> list[list]:
    """Partition a query batch so each chunk's UNIQUE row set fits ``cap``.

    Greedy in arrival order (preserves per-chunk dispatch order).  A
    single query whose own rows exceed cap has no valid chunking: with
    ``oversize_ok`` it becomes its own chunk (the caller's slice-streaming
    branch handles any row count); otherwise it raises.
    """
    chunks: list[list] = []
    cur: list = []
    cur_rows: set = set()
    for q in queries:
        rows = set(rows_of(q))
        if len(rows) > cap:
            if not oversize_ok:
                raise ValueError(
                    f"single query references {len(rows)} rows > capacity {cap}"
                )
            if cur:
                chunks.append(cur)
                cur, cur_rows = [], set()
            chunks.append([q])
            continue
        if cur and len(cur_rows | rows) > cap:
            chunks.append(cur)
            cur, cur_rows = [], set()
        cur.append(q)
        cur_rows |= rows
    if cur:
        chunks.append(cur)
    return chunks
