"""Compute engines for batched slice evaluation.

The executor evaluates a PQL bitmap-call tree over a *batch* of slices at
once: leaves gather dense rows into a ``uint32[n_slices, W]`` matrix and
set ops/counts apply to the whole stack in one call.  The engine decides
where that matrix lives:

- `JaxEngine` — jnp arrays on the default JAX backend; fused counts go
  through pilosa_tpu.ops.dispatch (Pallas on TPU).  This is the production
  path: one device dispatch per query stage for *all* local slices, the
  TPU-native replacement for the reference's goroutine-per-slice fan-out
  (executor.go:1209-1244).
- `NumpyEngine` — pure numpy; chosen by name only (``engine = "numpy"``:
  the CPU tests and host-only tools say so explicitly).

Both satisfy the same small protocol; results surface as numpy.
"""

from __future__ import annotations

import os

import numpy as np

from pilosa_tpu.roaring import _POPCNT8
from pilosa_tpu.stats import NOP_STATS

# Pair-op table for the numpy engine.  Deliberately NOT shared with
# ops.bitwise.apply_pair_op: importing ops.bitwise pulls in jax at module
# top, and the numpy engine must work on hosts where jax is absent/broken.
_NP_OPS = {
    "and": lambda a, b: a & b,
    "or": lambda a, b: a | b,
    "xor": lambda a, b: a ^ b,
    "andnot": lambda a, b: a & ~b,
}

# Tree-fold opcodes by id (ops.bitwise.gather_count_tree encoding);
# opcode 4 = PASS (take the left child — perfect-tree padding).
_TREE_NP_OPS = {
    0: _NP_OPS["and"],
    1: _NP_OPS["or"],
    2: _NP_OPS["xor"],
    3: _NP_OPS["andnot"],
    4: lambda a, b: a,
}


def nbytes(*arrays) -> int:
    """Total byte size of the given arrays (None entries skipped) — the
    dispatch meter's operand/transfer accounting.  Works for numpy and
    jax arrays alike (both expose .nbytes)."""
    total = 0
    for a in arrays:
        if a is None:
            continue
        n = getattr(a, "nbytes", None)
        if n is None:
            n = getattr(a, "size", 0) * getattr(a, "itemsize", 0)
        total += int(n)
    return total


def _pow2(n: int) -> int:
    """The power-of-two bucket of a count (jitted shapes stay few): the
    one rule for what is uploaded - a repair's cells, a pool miss's rows
    (8 MiB of zeros a padded row at 64 slices: the ladder is fine)."""
    return 1 << (n - 1).bit_length() if n > 1 else 1


def _pow4(n: int) -> int:
    """The power-of-four bucket of a gather dispatch's batch.  Coarser
    than ``_pow2`` because a program here is a Pallas kernel per (op,
    bucket) and a padded pair costs only its own two row reads: the
    served lanes then meet two buckets where they met four, and a
    warm-up that runs the traffic has met them all."""
    return 1 << (2 * (((n - 1).bit_length() + 1) // 2)) if n > 1 else 1


def _padded_batch(idx) -> np.ndarray:
    """A gather dispatch's index tuples int32[B, K] padded to their
    ``_pow4`` bucket with copies of the first tuple; the caller drops the
    tail of the counts."""
    idx = np.asarray(idx, dtype=np.int32)
    pad = _pow4(len(idx)) - len(idx) if len(idx) else 0
    if not pad:
        return idx
    return np.concatenate([idx, np.broadcast_to(idx[:1], (pad,) + idx.shape[1:])])


def _padded_words(cells, values) -> tuple[np.ndarray, np.ndarray]:
    """A sparse miss's words as one scatter's arguments: ``cells``
    int32[N, 3] of (slice, slot, word) and ``values`` uint32[N], N the
    ``_pow4`` bucket of their count (one program a bucket; a padded word
    costs its 16 bytes); the tail is (-1, -1, -1) over 0, which the
    scatter drops."""
    n = len(values)
    idx = np.full((_pow4(n), 3), -1, dtype=np.int32)
    idx[:n] = cells
    vals = np.zeros(len(idx), dtype=np.uint32)
    vals[:n] = values
    return idx, vals


def _padded_cells(groups):
    """A repair's written cells as one scatter's arguments: ``cells`` (the
    (slice, slot) pairs in group order), ``idx`` int32[C, 2] and ``planes``
    uint32[C, W], C the power-of-two bucket of ``len(cells)`` (compiled
    shapes stay few); a bucket's unused tail is (-1, -1) over zero planes,
    which every consumer drops."""
    cells = [
        (si, slot) for slice_idxs, slots, _ in groups
        for si in slice_idxs for slot in slots
    ]
    cb = _pow2(len(cells))
    idx = np.full((cb, 2), -1, dtype=np.int32)
    idx[: len(cells)] = cells
    planes = np.zeros((cb, np.asarray(groups[0][2]).shape[-1]), dtype=np.uint32)
    at = 0
    for slice_idxs, slots, block in groups:
        g = len(slice_idxs) * len(slots)
        planes[at : at + g] = np.asarray(block).reshape(g, -1)
        at += g
    return cells, idx, planes


def _repair_planes_composed(engine, matrix, gram, groups):
    """The copying form of ``repair_planes``, from the engine's own
    ``set_plane_cells`` (ONE functional scatter of every written cell: a
    new array, the caller's stays whole.  A scatter per group put as many
    copies of the pool in flight as a burst had groups - dispatch is
    asynchronous and every output is allocated at once: 14.1 GiB on a 16
    GiB chip for eight groups on a 2 GiB shard) and ``gram_update_rows``,
    which gets the pre-patch array for its restricted-slice delta.  What
    the numpy engine runs, and the jax and mesh engines for the repairs
    their compiled step does not take.  ``finish(span)`` hands the
    request's span (or None) on to ``gram_update_rows``."""
    old = matrix
    _, idx, planes = _padded_cells(groups)
    matrix = engine.set_plane_cells(matrix, idx, planes)
    if gram is None:
        return matrix, None, False, "composed"

    def finish(span=None):
        d = gram.shape[0]
        new, was = (matrix, old) if d == matrix.shape[1] else (matrix[:, :d], old[:, :d])
        return engine.gram_update_rows(
            new, gram, [s for _, slots, _ in groups for s in slots], old_matrix=was,
            slice_idxs=[si for slice_idxs, _, _ in groups for si in slice_idxs],
            span=span,
        )

    return matrix, finish, False, "composed"


class NumpyEngine:
    name = "numpy"
    # No jit: callers may use exact (ragged) dispatch shapes freely.
    wants_static_shapes = False
    # Host == device on numpy: nothing ever crosses a transfer boundary,
    # so the upload ledger stays at zero (class attr, never mutated).
    stat_upload_bytes = 0

    def stack(self, rows: list[np.ndarray]) -> np.ndarray:
        return np.stack(rows) if rows else np.zeros((0, 0), dtype=np.uint32)

    def stack_rows(self, rows: list) -> np.ndarray:
        """Stack engine-resident rows (same as stack on numpy)."""
        return self.stack(rows)

    def stack_slices(self, stacks: list) -> np.ndarray:
        """Stack along the SLICE axis (mesh engines shard this one)."""
        return self.stack(stacks)

    def asarray(self, x: np.ndarray):
        return np.asarray(x)

    def matrix(self, host_matrix: np.ndarray):
        """Move a fully-assembled host row matrix [n_slices, n_rows, W]
        into engine storage in ONE transfer (vs per-row uploads)."""
        return host_matrix

    def gather_count_and(self, row_matrix, pairs) -> np.ndarray:
        """Batched Count(Intersect) over [n_slices, n_rows, W] for int32[B,2]
        row-index pairs; returns int64[B]."""
        return self.gather_count("and", row_matrix, pairs)

    def gather_count(self, op: str, row_matrix, pairs) -> np.ndarray:
        """Batched Count(<op>(...)) — and/or/xor/andnot pair counts."""
        a = row_matrix[:, pairs[:, 0], :]
        b = row_matrix[:, pairs[:, 1], :]
        r = _NP_OPS[op](a, b)
        return self.count(r).sum(axis=0)

    def gather_bucket(self, n: int) -> int:
        """The batch a gather dispatch of ``n`` index tuples runs at:
        nothing compiles here, so ``n`` itself."""
        return n

    def gather_count_multi(self, op: str, row_matrix, idx) -> np.ndarray:
        """Batched Count over a left-fold of K gathered rows — N-operand
        Intersect/Union/Difference and the fused Range cover (op="or").
        idx: int32[B, K], padded with fold-idempotent ids.  Returns
        int64[B].

        Chunked over the batch so the gathered [S, chunk, K, W] stays a
        few MB — one shot over the whole batch would materialize
        S*B*K*W*4 bytes (easily hundreds of MB) for nothing.
        """
        from pilosa_tpu.pilosa import OR_MULTI_BUDGET_HOST, or_multi_chunk_size

        s, _, w = row_matrix.shape
        k = idx.shape[1]
        chunk = or_multi_chunk_size(s, k, w, OR_MULTI_BUDGET_HOST)
        out = np.empty(idx.shape[0], dtype=np.int64)
        for i in range(0, idx.shape[0], chunk):
            g = row_matrix[:, idx[i : i + chunk], :]
            if op == "or":
                acc = np.bitwise_or.reduce(g, axis=2)
            elif op == "and":
                acc = np.bitwise_and.reduce(g, axis=2)
            elif op == "andnot":
                acc = g[:, :, 0] & ~np.bitwise_or.reduce(g[:, :, 1:], axis=2)
            else:
                raise ValueError(f"unsupported multi-op {op!r}")
            out[i : i + chunk] = self.count(acc).sum(axis=0)
        return out

    def gather_count_or_multi(self, row_matrix, idx) -> np.ndarray:
        return self.gather_count_multi("or", row_matrix, idx)

    def gather_count_tree(self, row_matrix, leaves, opc) -> np.ndarray:
        """Batched Count over arbitrary nested expression trees (perfect-
        tree encoding, see ops.bitwise.gather_count_tree).  Chunked over
        the batch like gather_count_multi (same transient bound).

        Implemented inline (not via ops.bitwise) for two reasons: the
        numpy engine must work on jax-less hosts (bitwise imports jax at
        module top), and per-node opcode GROUPING does one bitwise pass
        per node — the where-select form evaluates all four ops per node,
        which XLA fuses away but a host loop pays for real.
        """
        from pilosa_tpu.pilosa import OR_MULTI_BUDGET_HOST, or_multi_chunk_size

        s, _, w = row_matrix.shape
        b, k = leaves.shape
        chunk = or_multi_chunk_size(s, k, w, OR_MULTI_BUDGET_HOST)
        out = np.empty(b, dtype=np.int64)
        for i in range(0, b, chunk):
            g = row_matrix[:, leaves[i : i + chunk], :]  # [S, c, K, W]
            oc = opc[i : i + chunk]
            off = 0
            n = k // 2
            while n >= 1:
                a = g[:, :, 0::2]
                bb = g[:, :, 1::2]
                nxt = np.empty_like(a)
                for t in range(n):
                    col = oc[:, off + t]
                    for o in np.unique(col):
                        m = col == o
                        nxt[:, m, t] = _TREE_NP_OPS[int(o)](a[:, m, t], bb[:, m, t])
                g = nxt
                off += n
                n //= 2
            out[i : i + chunk] = self.count(g[:, :, 0]).sum(axis=0)
        return out

    def gather_count_dev(self, op: str, row_matrix, pairs):
        """Like gather_count but returns an ENGINE array without forcing a
        host sync — slice-streaming accumulates these so the next chunk's
        upload overlaps the previous chunk's compute."""
        return self.gather_count(op, row_matrix, pairs)

    def gather_count_multi_dev(self, op: str, row_matrix, idx):
        return self.gather_count_multi(op, row_matrix, idx)

    def gather_count_tree_dev(self, row_matrix, leaves, opc):
        return self.gather_count_tree(row_matrix, leaves, opc)

    def bit_and(self, a, b):
        return a & b

    def bit_or(self, a, b):
        return a | b

    def bit_xor(self, a, b):
        return a ^ b

    def bit_andnot(self, a, b):
        return a & ~b

    def zeros_like(self, a):
        return np.zeros_like(a)

    def count(self, batch) -> np.ndarray:
        """Per-slice popcounts over the last axis (LUT-based, vectorized)."""
        if batch.size == 0:
            return np.zeros(batch.shape[:-1], dtype=np.int64)
        counts = _POPCNT8[np.ascontiguousarray(batch).view(np.uint8)]
        return counts.reshape(*batch.shape[:-1], -1).sum(axis=-1, dtype=np.int64)

    def batch_intersection_count(self, rows, src, tiled: bool = False) -> np.ndarray:
        if tiled:  # trailing [W/128, 128] word axes -> logical [..., W]
            rows = rows.reshape(*rows.shape[:-2], -1)
            src = src.reshape(*src.shape[:-2], -1)
        return self.count(rows & src)

    # Row-major gather lane: no benefit on host (numpy transposes are
    # views), so the executor keeps slice-major transients.
    supports_row_major_gather = False

    def update_slices(self, matrix, slice_idxs, planes):
        """Functionally replace whole slice planes of a row matrix
        (incremental refresh of a cached matrix after writes)."""
        out = matrix.copy()
        out[list(slice_idxs)] = planes
        return out

    def append_rows(self, matrix, block):
        """Append new rows (axis 1) to a row matrix: [S, R, W] + [S, R', W]."""
        return np.concatenate([matrix, block], axis=1)

    def set_rows(self, matrix, row_start: int, block):
        """Functionally write a block of rows at [.., row_start:, ..] —
        fills preallocated capacity without changing the matrix shape
        (shape changes would recompile jitted kernels downstream)."""
        out = matrix.copy()
        out[:, row_start : row_start + block.shape[1], :] = block
        return out

    def set_rows_at(self, matrix, slots, block, donate: bool = False):
        """Write rows into ARBITRARY slots (row-pool paging: a miss's
        dense chunk scatters into freed slots in one call; a sparse one
        goes through ``set_words_at``): into a copy, or
        with ``donate`` into ``matrix`` itself (the caller holds the only
        reference: the copy an earlier chunk of the same miss made).  The
        pool pads a chunk only for engines that compile
        (``wants_static_shapes``): never here."""
        out = matrix if donate else matrix.copy()
        out[:, list(slots), :] = block
        return out

    def set_words_at(self, matrix, slots, cells, values, donate: bool = False):
        """The sparse form of ``set_rows_at``: the rows in ``slots`` become
        zero but for ``values[c]`` at word ``cells[c, 2]`` of the (slice
        ``cells[c, 0]``, slot ``cells[c, 1]``) plane.  Unpadded, as this
        engine's blocks are."""
        out = matrix if donate else matrix.copy()
        out[:, list(slots), :] = 0
        out[cells[:, 0], cells[:, 1], cells[:, 2]] = values
        return out

    def warm_set_rows(
        self, matrix, max_rows: int, row_major: bool = False, max_words: int = 0
    ) -> None:
        """Nothing compiles here."""

    def grow_rows(self, matrix, n: int):
        """Append n zero rows of capacity (row-pool doubling)."""
        s, _, w = matrix.shape
        return np.concatenate(
            [matrix, np.zeros((s, n, w), dtype=matrix.dtype)], axis=1
        )

    def set_plane_rows(self, matrix, slice_idxs, slots, block):
        """Functionally write block[i, j] into (slice_idxs[i], slots[j]) —
        the stale-plane refresh touches only RESIDENT slots, transferring
        resident-rows x stale-slices bytes, not whole capacity planes."""
        out = matrix.copy()
        out[np.ix_(list(slice_idxs), list(slots))] = block
        return out

    def set_plane_cells(self, matrix, cells, planes):
        """Functionally write ``planes[c]`` into the (slice, slot) cell
        ``cells[c]``; a cell of (-1, -1) (a bucket's tail) is skipped."""
        out = matrix.copy()
        keep = cells[:, 0] >= 0
        out[cells[keep, 0], cells[keep, 1]] = planes[keep]
        return out

    def build_planes(self, rows, cols):
        """Bulk sort/segment/scatter build: (row, col) uint64 columns ->
        ``(slice_ids, row_ids, planes uint32[G, W])`` — the device-layout
        word planes the bulk ingest door commits into fragments.  Host
        twin (vectorized numpy); the jax engine runs the same contract on
        device."""
        from pilosa_tpu.bulk.build import build_planes_numpy

        return build_planes_numpy(rows, cols)

    def build_words(self, rows, cols):
        """Sparse form of :meth:`build_planes` (CSR over nonzero plane
        words) — the commit path prefers it on host, where scattering
        a chunk's few-hundred touched words per plane beats
        materializing full planes.  The jax engines deliberately do NOT
        implement this: their scatter output is born dense on device."""
        from pilosa_tpu.bulk.build import build_words_numpy

        return build_words_numpy(rows, cols)

    def pair_gram(self, matrix):
        """All-pairs AND-count Gram, or None when unsupported (host
        all-pairs popcount would dwarf the direct path)."""
        return None

    def gram_update_rows(self, matrix, gram, slots, old_matrix=None, slice_idxs=None,
                         span=None):
        """Rank-k repair of a host AND-count Gram after row rewrites
        (the Gram half of the copying ``repair_planes``): recompute ONLY
        the dirty rows/columns with one batched pair-count pass against
        the (already patched) resident matrix — O(K*R*W) instead of the
        O(R^2*W) full rebuild.  Returns a NEW array (copy-on-write:
        readers holding the old Gram keep a consistent pre-write
        snapshot; AND is symmetric, so one K x R count block fills both
        the rows and the columns).

        Per-(row, slice) delta mode: with ``old_matrix`` (the array as it
        was before the patch, which the copying repair still holds) and
        ``slice_idxs`` (the slice planes actually written),
        the dirty rows' counts are ADJUSTED by (new - old) restricted to
        those slices instead of recomputed over the whole span —
        unchanged slices cancel out of the difference, so the dispatch
        covers K x R x |dirty slices| instead of K x R x S.  Falls back
        to the full recompute when the restriction wouldn't pay
        (>= half the slices dirty).  ``span``: the request's span where
        one is sampled; only the mesh engine has a stage of its own to
        show under it."""
        slots = np.asarray(sorted({int(s) for s in slots}), dtype=np.int64)
        n = gram.shape[0]
        pairs = np.empty((len(slots) * n, 2), dtype=np.int32)
        pairs[:, 0] = np.repeat(slots.astype(np.int32), n)
        pairs[:, 1] = np.tile(np.arange(n, dtype=np.int32), len(slots))
        si = sorted({int(s) for s in slice_idxs}) if slice_idxs is not None else None
        if old_matrix is not None and si and 2 * len(si) < matrix.shape[0]:
            new_c = np.asarray(self.gather_count("and", matrix[si], pairs))
            old_c = np.asarray(self.gather_count("and", old_matrix[si], pairs))
            delta = (new_c.astype(np.int64) - old_c.astype(np.int64)).reshape(
                len(slots), n
            )
            block = (np.asarray(gram)[slots, :] + delta).astype(gram.dtype)
        else:
            block = (
                np.asarray(self.gather_count("and", matrix, pairs))
                .reshape(len(slots), n)
                .astype(gram.dtype)
            )
        out = np.array(gram, copy=True)
        out[slots, :] = block
        out[:, slots] = block.T
        return out

    def repair_planes(self, matrix, gram, groups, donate=False):
        """Patch the written planes of a pool matrix and repair its Gram:
        ``groups`` = [(slice_idxs, slots, block[len(slice_idxs),
        len(slots), W])], the written (slice, slot) cells with their new
        contents; ``gram`` the host Gram over the first ``gram.shape[0]``
        slots, or None (the planes alone).  Returns ``(matrix, finish,
        in_place, form)``: the patched matrix at once, ``finish(span)``
        the repaired Gram (a new array; it blocks on the device where
        there is one; None without a Gram), whether the caller's array was
        updated in place and is gone, and which form ran (``"step"``: the
        jax and mesh engines' compiled step; ``"composed"``: the copying
        form).
        ``donate`` says the caller holds the only reference to ``matrix``;
        this engine copies regardless."""
        return _repair_planes_composed(self, matrix, gram, groups)

    def slice_axis_devices(self, n_slices: int) -> int:
        """Devices that share the slice axis of an ``[n_slices, ...]``
        array of this engine (what the row pool's budget follows)."""
        return 1

    def to_numpy(self, x, span=None) -> np.ndarray:
        return np.asarray(x)

    def device_info(self) -> dict:
        """What this engine computes on (the ``device`` object of the
        server's startup line and ``GET /status``): the host."""
        return {"engine": self.name, "platform": "host", "device_kind": None,
                "count": 0, "devices": []}


class JaxEngine:
    name = "jax"
    # Jitted kernels recompile per distinct shape (seconds each on TPU):
    # callers should pad dispatch shapes to canonical buckets.
    wants_static_shapes = True

    def __init__(self):
        import jax.numpy as jnp  # deferred so numpy-only paths never init jax

        from pilosa_tpu.ops import dispatch

        self._jnp = jnp
        self._dispatch = dispatch
        # Running host->device transfer ledger (bytes), bumped at every
        # upload seam (matrix/block/src uploads).  A plain int under the
        # GIL; the executor's dispatch meter reads deltas around engine
        # calls to attribute transfer bytes per dispatch.
        self.stat_upload_bytes = 0
        # The executor hands its stats client over (``engine.upload_bytes``
        # at /debug/vars); directly-constructed engines count to nowhere.
        self.stats = NOP_STATS

    def _note_upload(self, n: int) -> None:
        self.stat_upload_bytes += n
        self.stats.count("engine.upload_bytes", n)

    def stack(self, rows: list[np.ndarray]):
        return self._jnp.asarray(np.stack(rows)) if rows else self._jnp.zeros((0, 0), dtype=self._jnp.uint32)

    def stack_rows(self, rows: list):
        """Stack device-resident rows WITHOUT a host round trip — rows from
        the fragment device cache stay in HBM (device-side concat)."""
        if not rows:
            return self._jnp.zeros((0, 0), dtype=self._jnp.uint32)
        return self._jnp.stack([self._jnp.asarray(r) for r in rows])

    def stack_slices(self, stacks: list):
        """Stack along the SLICE axis (mesh engines shard this one)."""
        return self.stack_rows(stacks)

    def asarray(self, x):
        return self._jnp.asarray(x)

    @staticmethod
    def _tile_host(block: np.ndarray) -> np.ndarray:
        """Host-side reshape [..., W] -> [..., W/128, 128] (free: a numpy
        view).  Jax engines store row matrices in this TILED form so the
        Pallas kernels never reshape them inside jit — an in-jit
        [S, R, W] -> [S, R, W/128, 128] reshape changes the physical
        (8, 128) tiling and XLA materializes a full HBM copy of the
        matrix (the round-2 1024-slice OOM; BASELINE.md round-3 note)."""
        if block.shape[-1] % 128:
            return block  # non-tileable widths stay logical (jnp fallback)
        return block.reshape(*block.shape[:-1], block.shape[-1] // 128, 128)

    def matrix(self, host_matrix: np.ndarray):
        """One host→device transfer for an assembled row matrix, stored in
        canonical tiled form uint32[S, R, W/128, 128]."""
        self._note_upload(host_matrix.nbytes)
        return self._jnp.asarray(self._tile_host(host_matrix))

    def gather_count_and(self, row_matrix, pairs) -> np.ndarray:
        """Batched Count(Intersect) in ONE device dispatch (Pallas on TPU)."""
        return self.gather_count("and", row_matrix, pairs)

    def gather_bucket(self, n: int) -> int:
        """The batch a pair dispatch of ``n`` pairs runs at: ``gather_count``
        and its ``_dev`` forms pad the pairs on the host to this bucket
        (``_padded_batch``: copies of the first pair), one program a
        bucket; ``gather_count`` drops the tail itself, the ``_dev``
        forms return the padded counts un-fetched and the caller does."""
        return _pow4(n)

    def gather_count(self, op: str, row_matrix, pairs) -> np.ndarray:
        # allow_gram=False: eager per-request dispatch can't amortize the
        # all-pairs matmul; the executor's generation-cached Gram
        # (pair_gram) is the product-path version of that strategy.
        out = self._dispatch.gather_count(
            op, self._jnp.asarray(row_matrix), self._jnp.asarray(_padded_batch(pairs)),
            allow_gram=False,
        )
        return self.to_numpy(out)[: len(pairs)].astype(np.int64)

    def gather_count_multi(self, op: str, row_matrix, idx) -> np.ndarray:
        out = self._dispatch.gather_count_multi(
            op, self._jnp.asarray(row_matrix), self._jnp.asarray(idx)
        )
        return self.to_numpy(out).astype(np.int64)

    def gather_count_or_multi(self, row_matrix, idx) -> np.ndarray:
        return self.gather_count_multi("or", row_matrix, idx)

    def gather_count_dev(self, op: str, row_matrix, pairs):
        """Async variant: the dispatch is enqueued and the device array
        returned un-fetched, so a streaming loop pipelines chunk k+1's
        host->device upload behind chunk k's kernel."""
        return self._dispatch.gather_count(
            op, self._jnp.asarray(row_matrix), self._jnp.asarray(_padded_batch(pairs)),
            allow_gram=False,
        )

    # -- row-major gather lane (streaming regime's tall row sets) --------

    @property
    def supports_row_major_gather(self) -> bool:
        # Only worth it where the Pallas kernel runs (TPU): elsewhere the
        # rowmajor dispatch just transposes back per chunk — a pure cost.
        return self._dispatch.use_pallas()

    def matrix_rows(self, host_matrix: np.ndarray):
        """Upload a ROW-MAJOR [R, S, W] host block in tiled form — the
        layout whose per-row bytes are one contiguous DMA descriptor
        (dispatch.gather_count_rowmajor)."""
        self._note_upload(host_matrix.nbytes)
        return self._jnp.asarray(self._tile_host(host_matrix))

    def rowmajor_ok(self, n_slices: int, words: int, k: int = 2) -> bool:
        return self._dispatch.rowmajor_ok(n_slices, words, k)

    def prefer_rowmajor(
        self, n_rows: int, n_slices: int, words: int, n_pairs: int, max_k: int
    ) -> bool:
        """Whether a resident working set of ``n_rows`` rows should live
        in a ROW-MAJOR pool: exactly when dispatch would pick the gather
        kernels for its pair groups (the resident kernel predicate says
        no) and the row-major kernels can buffer the widest group's
        operand rows.  Multi-fold groups always gather, so parts without
        pair groups prefer row-major whenever the buffer bound allows."""
        from pilosa_tpu.ops.pallas_kernels import resident_strategy

        return not resident_strategy(n_rows, words, n_pairs) and self.rowmajor_ok(
            n_slices, words, max_k
        )

    def gather_count_rowmajor_dev(self, op: str, row_major, pairs):
        return self._dispatch.gather_count_rowmajor(
            op, self._jnp.asarray(row_major), self._jnp.asarray(_padded_batch(pairs))
        )

    def gather_count_multi_rowmajor_dev(self, op: str, row_major, idx):
        return self._dispatch.gather_count_multi_rowmajor(
            op, self._jnp.asarray(row_major), self._jnp.asarray(idx)
        )

    def grow_rows_rm(self, matrix, n: int):
        """Append n zero SLOTS to a row-major [cap, S, ...] pool matrix."""
        z = self._jnp.zeros((n,) + matrix.shape[1:], dtype=matrix.dtype)
        return self._jnp.concatenate([matrix, z], axis=0)

    def set_rows_at_rm(self, matrix, slots, block, donate: bool = False):
        """Scatter a row-major miss chunk [k, S, W] into slots (axis 0):
        ``set_rows_at``'s program over the other axis."""
        return self._set_rows(matrix, slots, block, 0, donate)

    def set_plane_rows_rm(self, matrix, slice_idxs, slots, block):
        """Refresh (slot, stale-slice) cells of a row-major matrix;
        block: [len(slots), len(slice_idxs), W]."""
        sl = self._jnp.asarray(np.asarray(slots, dtype=np.int32))
        si = self._jnp.asarray(np.asarray(slice_idxs, dtype=np.int32))
        return matrix.at[sl[:, None], si[None, :]].set(
            self._match_block(matrix, block)
        )

    def gather_count_multi_dev(self, op: str, row_matrix, idx):
        return self._dispatch.gather_count_multi(
            op, self._jnp.asarray(row_matrix), self._jnp.asarray(idx)
        )

    # -- TopN all-slice candidate scorer (one dispatch per chunk set) ----

    @property
    def row_scorer_all_slices(self) -> bool:
        """Single-chip jax engines route through the memoizing scorer
        factory too (round 5): phase-1 candidate chunks dispatch their
        one slice eagerly, and a candidate set re-asked by a SECOND
        slice (phase 2's merged-id refetch) upgrades to one all-slice
        launch memoized for the rest."""
        return True

    @property
    def supports_single_slice_score(self) -> bool:
        """Whether ``matrix[si]`` indexing is process-addressable (true
        off-mesh; multi-process meshes must stay SPMD)."""
        return True

    def prepare_topn_src(self, src_stack: np.ndarray):
        """Upload a host [S, W] src stack once per TopN query (tiled)."""
        src = np.ascontiguousarray(src_stack)
        self._note_upload(src.nbytes)
        return self._jnp.asarray(self._tile_host(src))

    def topn_scorer_counts(self, matrix, pos, src_dev) -> np.ndarray:
        """int32[S, K] candidate counts in one dispatch (fused Pallas
        kernel on TPU; per-slice jnp fallback elsewhere)."""
        out = self._dispatch.topn_scorer_counts(
            self._jnp.asarray(matrix),
            self._jnp.asarray(np.asarray(pos, dtype=np.int32)),
            src_dev,
        )
        return self.to_numpy(out).astype(np.int64)

    def gather_count_tree(self, row_matrix, leaves, opc) -> np.ndarray:
        return self.to_numpy(
            self.gather_count_tree_dev(row_matrix, leaves, opc)
        ).astype(np.int64)

    def gather_count_tree_dev(self, row_matrix, leaves, opc):
        return self._dispatch.gather_count_tree(
            self._jnp.asarray(row_matrix),
            self._jnp.asarray(leaves),
            self._jnp.asarray(opc),
        )

    def bit_and(self, a, b):
        return self._jnp.bitwise_and(a, b)

    def bit_or(self, a, b):
        return self._jnp.bitwise_or(a, b)

    def bit_xor(self, a, b):
        return self._jnp.bitwise_xor(a, b)

    def bit_andnot(self, a, b):
        return self._jnp.bitwise_and(a, self._jnp.bitwise_not(b))

    def zeros_like(self, a):
        return self._jnp.zeros_like(a)

    def count(self, batch) -> np.ndarray:
        if batch.size == 0:
            return np.zeros(batch.shape[:-1], dtype=np.int64)
        return self.to_numpy(self._dispatch.count(batch)).astype(np.int64)

    def batch_intersection_count(self, rows, src, tiled: bool = False) -> np.ndarray:
        # ``tiled=True``: rows were sliced from a (4D tiled) engine matrix
        # and carry the word axis as trailing [W/128, 128] dims.  Explicit
        # — ndim alone cannot distinguish a tiled [K, W/128, 128] stack
        # from a logical [S, K, W] one.
        return self.to_numpy(
            self._dispatch.batch_intersection_count(rows, src, tiled=tiled)
        ).astype(np.int64)

    def tile_src(self, src_dense: np.ndarray):
        """Upload a dense [W] operand in the matrix-compatible tiled form
        (so kernels can pair it with rows sliced from a 4D matrix)."""
        src = np.asarray(src_dense)
        self._note_upload(src.nbytes)
        return self._jnp.asarray(self._tile_host(src))

    def _match_block(self, matrix, block):
        """Reshape a host [.., .., W] block to the matrix's storage form
        (tiled 4D matrices take [.., .., W/128, 128] blocks)."""
        block = np.asarray(block)
        self._note_upload(block.nbytes)
        if matrix.ndim == block.ndim + 1:
            block = self._tile_host(block)
        return self._jnp.asarray(block)

    def update_slices(self, matrix, slice_idxs, planes):
        """Replace stale slice planes on-device: uploads only the changed
        planes and patches HBM→HBM instead of re-transferring the matrix."""
        idx = self._jnp.asarray(np.asarray(slice_idxs, dtype=np.int32))
        return matrix.at[idx].set(self._match_block(matrix, planes))

    def append_rows(self, matrix, block):
        """Device-side concat of new rows: only the new block crosses PCIe."""
        return self._jnp.concatenate(
            [matrix, self._match_block(matrix, block)], axis=1
        )

    def set_rows(self, matrix, row_start: int, block):
        """Write rows into preallocated capacity device-side (shape
        preserved, so downstream jitted kernels never recompile)."""
        return matrix.at[:, row_start : row_start + block.shape[1]].set(
            self._match_block(matrix, block)
        )

    def set_rows_at(self, matrix, slots, block, donate: bool = False):
        """Scatter a miss's DENSE chunk (one with a bitmap container, a
        bulk overlay or too many words; every other goes through
        ``set_words_at``) into arbitrary pool slots: the chunk's planes
        cross host->device whole (the upload is enqueued, not waited for);
        the scatter itself is HBM->HBM, into a copy of the pool (a reader
        may hold ``matrix``) or, with ``donate``, into ``matrix`` itself
        (the copy an earlier chunk of the same miss made: the caller
        holds the only reference, and the array is gone afterwards).  One
        program a block size and form (``ops.bitwise.set_rows``): the pool
        pads a chunk to its power-of-two bucket, slot -1 over a zero
        plane, which the scatter drops."""
        return self._set_rows(matrix, slots, block, 1, donate)

    def _set_rows(self, matrix, slots, block, axis: int, donate: bool):
        if not hasattr(self, "_set_rows_jit"):
            import jax

            from pilosa_tpu.ops.bitwise import set_rows

            self._set_rows_jit = {
                d: jax.jit(set_rows, static_argnames="axis", donate_argnums=(0,) if d else ())
                for d in (False, True)
            }
        return self._set_rows_jit[donate](
            matrix, np.asarray(slots, dtype=np.int32),
            self._match_block(matrix, block), axis=axis,
        )

    def set_words_at(self, matrix, slots, cells, values, donate: bool = False):
        """The sparse form of ``set_rows_at``: a miss's chunk crosses
        host->device as its words that are not zero - ``cells`` int32[N,
        3] of (slice, slot, word) and ``values`` uint32[N], padded here to
        their ``_pow4`` bucket, 16 bytes a word - and one program
        (``ops.bitwise.set_row_words``, one a word bucket and form)
        zeroes the rows of ``slots`` (a fixed count, -1 dropped) and
        writes the words into them, HBM->HBM: into a copy of the pool,
        or with ``donate`` into ``matrix`` itself, as ``set_rows_at``."""
        return self._set_words(matrix, slots, cells, values, 1, donate)

    def set_words_at_rm(self, matrix, slots, cells, values, donate: bool = False):
        """``set_words_at`` on a row-major pool ``[cap, S, ...]``."""
        return self._set_words(matrix, slots, cells, values, 0, donate)

    def _set_words(self, matrix, slots, cells, values, axis: int, donate: bool):
        cells, values = _padded_words(cells, values)
        self._note_upload(cells.nbytes + values.nbytes)
        return self._scatter_words(
            matrix, np.asarray(slots, dtype=np.int32), cells, values, axis, donate
        )

    def _scatter_words(self, matrix, slots, cells, values, axis: int, donate: bool):
        if not hasattr(self, "_set_words_jit"):
            import jax

            from pilosa_tpu.ops.bitwise import set_row_words

            self._set_words_jit = {
                d: jax.jit(set_row_words, static_argnames="axis", donate_argnums=(0,) if d else ())
                for d in (False, True)
            }
        return self._set_words_jit[donate](matrix, slots, cells, values, axis=axis)

    def warm_set_rows(
        self, matrix, max_rows: int, row_major: bool = False, max_words: int = 0
    ) -> None:
        """Compile a miss's scatter programs for this pool, copying and
        donating, by running each once with every slot dropped (nothing
        is written): ``set_rows_at``'s for every bucket a chunk can pad
        to (1 .. ``max_rows``) and, where the pool pages sparse
        (``max_words``), ``set_words_at``'s for every word bucket (1 ..
        ``max_words``) at ``max_rows`` slots.  A pool calls this when it
        first evicts: from then on it pages for as long as it lives, and
        no later miss compiles."""
        axis, k = (0 if row_major else 1), 1
        shape = list(matrix.shape[: 2]) + [int(np.prod(matrix.shape[2:]))]
        while k <= min(max_rows, matrix.shape[axis]):
            shape[axis] = k
            drop = np.full(k, -1, dtype=np.int32)
            block = np.zeros(shape, dtype=np.uint32)
            copy = self._set_rows(matrix, drop, block, axis, False)
            # done before the block goes up again: one upload of it is alive
            # beside the pool and its copy, not two (the pool's peak memory)
            copy.block_until_ready()
            self._set_rows(copy, drop, block, axis, True).block_until_ready()
            k *= 2
        drop, n = np.full(max_rows, -1, dtype=np.int32), 1
        while n <= max_words:
            cells, values = np.full((n, 3), -1, dtype=np.int32), np.zeros(n, dtype=np.uint32)
            copy = self._set_words(matrix, drop, cells, values, axis, False)
            self._set_words(copy, drop, cells, values, axis, True).block_until_ready()
            n *= 4

    def grow_rows(self, matrix, n: int):
        """Append n zero capacity rows DEVICE-side (no host transfer)."""
        s = matrix.shape[0]
        z = self._jnp.zeros((s, n) + matrix.shape[2:], dtype=matrix.dtype)
        return self._jnp.concatenate([matrix, z], axis=1)

    def set_plane_rows(self, matrix, slice_idxs, slots, block):
        """Scatter (stale slice, resident slot) cells: only the touched
        rows cross host->device (``set_plane_cells`` over the product)."""
        cells = np.stack(
            np.meshgrid(slice_idxs, slots, indexing="ij"), axis=-1
        ).reshape(-1, 2).astype(np.int32)
        block = np.asarray(block)
        return self.set_plane_cells(matrix, cells, block.reshape(len(cells), block.shape[-1]))

    def set_plane_cells(self, matrix, cells, planes):
        """A new matrix with ``planes[c]`` (host uint32[C, W]) written
        into the (slice, slot) cell ``cells[c]`` (int32[C, 2]; (-1, -1) is
        dropped): one program a cell count
        (``ops.bitwise.set_plane_cells``), whose ops carry the name
        ``pool.set_plane_rows`` in a device trace."""
        if not hasattr(self, "_set_plane_cells_jit"):
            import jax

            from pilosa_tpu.ops.bitwise import set_plane_cells

            self._set_plane_cells_jit = jax.jit(set_plane_cells)
        return self._set_plane_cells_jit(matrix, cells, self._cell_planes(matrix, planes))

    def _cell_planes(self, matrix, planes) -> np.ndarray:
        """Host planes [C, W] in the matrix's storage form, their upload
        counted (the jitted scatter takes them from the host)."""
        planes = np.asarray(planes)
        self._note_upload(planes.nbytes)
        return self._tile_host(planes) if matrix.ndim == 4 else planes

    def build_planes(self, rows, cols):
        """Bulk sort/segment/scatter build on device: the jitted pack
        kernel sorts, dedups, and scatters the bit columns under jax.jit
        on padded power-of-two shapes (see bulk/build.py); the group
        table computes on host, where the fragment commit needs it."""
        from pilosa_tpu.bulk.build import build_planes_jax

        return build_planes_jax(rows, cols, jnp=self._jnp)

    def pair_gram(self, matrix):
        """All-pairs AND-count Gram via one MXU int8 matmul (exact)."""
        if not hasattr(self, "_gram_jit"):
            import jax

            from pilosa_tpu.ops.bitwise import pair_gram

            self._gram_jit = jax.jit(pair_gram)
        return self.to_numpy(self._gram_jit(self._jnp.asarray(matrix))).astype(np.int64)

    def _gram_counts(self, matrix, pairs, span=None) -> np.ndarray:
        """The Gram repair's device work: |row_a & row_b| for each slot
        pair, as one program whose ops carry the name ``pool.gram_update``
        in a device trace (the same dispatch as ``gather_count``)."""
        if not hasattr(self, "_gram_counts_jit"):
            import jax

            def gram_update(rm, prs):
                with jax.named_scope("pool.gram_update"):
                    return self._dispatch.gather_count("and", rm, prs, allow_gram=False)

            self._gram_counts_jit = jax.jit(gram_update)
        return self.to_numpy(self._gram_counts_jit(matrix, pairs)).astype(np.int64)

    def gram_update_rows(self, matrix, gram, slots, old_matrix=None, slice_idxs=None,
                         span=None):
        """Rank-k Gram repair (see NumpyEngine.gram_update_rows), for the
        repairs the compiled step of ``repair_planes`` does not take: one
        batched gather-count dispatch recomputes the dirty rows/columns.
        The dirty-slot axis pads to a power-of-two bucket (recomputing a
        row twice is idempotent) so the jitted dispatch shape stays
        stable across repairs of 1..K rows.

        Per-(row, slice) delta mode (old_matrix + slice_idxs): two
        dispatches restricted to the written slice planes adjust the
        dirty rows by (new - old) — unchanged slices cancel, so a
        single-slice write repairs in O(K*R) counts regardless of the
        state's span.  The restricted slice axis pads to a power-of-two
        bucket with a CLEAN (unwritten) slice so jitted shapes stay
        stable: a clean slice's old and new planes are identical, so its
        padded contribution cancels exactly.  Falls back to the full
        recompute when no clean pad slice exists or the restriction
        wouldn't pay (>= half the slices dirty after padding)."""
        slots = sorted({int(s) for s in slots})
        k = len(slots)
        kb = _pow2(k)
        padded = np.asarray(slots + [slots[0]] * (kb - k), dtype=np.int32)
        n = gram.shape[0]
        pairs = np.empty((kb * n, 2), dtype=np.int32)
        pairs[:, 0] = np.repeat(padded, n)
        pairs[:, 1] = np.tile(np.arange(n, dtype=np.int32), kb)
        idx = np.asarray(slots, dtype=np.int64)
        n_slices = matrix.shape[0]
        si = sorted({int(s) for s in slice_idxs}) if slice_idxs is not None else None
        if old_matrix is not None and si:
            sb = _pow2(len(si))
            clean = next((s for s in range(n_slices) if s not in set(si)), None)
            if clean is not None and 2 * sb < n_slices:
                sel = self._jnp.asarray(
                    np.asarray(si + [clean] * (sb - len(si)), dtype=np.int32)
                )
                if 2 * k >= n:
                    # Wide repairs (a coalesced burst dirtying most of the
                    # matrix): k*R direct pair counts approach the cost of
                    # the whole Gram — two restricted-slice pair_gram
                    # builds (MXU matmul shape; fixed R^2 cost) beat the
                    # gather dispatch past k ~ R/2 (measured on the CPU
                    # build host; the MXU makes them cheaper still), and
                    # the FULL-gram delta is exact (pairs with no dirty
                    # row have identical planes in old and new, so their
                    # delta is zero).
                    pg_new = self.pair_gram(matrix[sel])
                    pg_old = None if pg_new is None else self.pair_gram(old_matrix[sel])
                    if pg_old is not None:
                        return (
                            np.asarray(gram) + (pg_new - pg_old)
                        ).astype(gram.dtype)
                new_c = self._gram_counts(matrix[sel], pairs, span)
                old_c = self._gram_counts(old_matrix[sel], pairs, span)
                delta = (new_c - old_c).reshape(kb, n)[:k]
                block = (np.asarray(gram)[idx, :] + delta).astype(gram.dtype)
                out = np.array(gram, copy=True)
                out[idx, :] = block
                out[:, idx] = block.T
                return out
        block = self._gram_counts(matrix, pairs, span).reshape(kb, n)[:k].astype(gram.dtype)
        out = np.array(gram, copy=True)
        out[idx, :] = block
        out[:, idx] = block.T
        return out

    def repair_planes(self, matrix, gram, groups, donate=False):
        """One compiled step (``ops.bitwise.repair_planes``, jitted with
        the matrix donated; on the mesh engine the same step on every
        device's own shard, ``_repair_step``) for the repairs that
        ``gram_update_rows`` would answer by its restricted-slice delta:
        one upload of the written planes, one dispatch that writes them
        into the pool's own buffer and counts what each write does to the
        Gram, and in ``finish()`` one blocking read of int32[cells, n],
        folded into a copy of the host Gram.  The cell axis pads to a
        power-of-two bucket so the compiled shapes stay few.  Without
        ``donate`` a reader still holds ``matrix``: the step then runs on
        a copy made first (which keeps the matrix's sharding).  Repairs
        without a Gram, wide ones and those over half the slices keep the
        composed form."""
        cells, idx, planes = _padded_cells(groups)
        k = len({slot for _, slot in cells})
        sb = _pow2(len({si for si, _ in cells}))
        if gram is None or 2 * k >= gram.shape[0] or 2 * sb >= matrix.shape[0]:
            return _repair_planes_composed(self, matrix, gram, groups)
        self._note_upload(planes.nbytes)
        if matrix.ndim == 4:
            planes = self._tile_host(planes)
        if not donate:
            matrix = self._jnp.copy(matrix)
        matrix, delta = self._repair_step(matrix, idx, planes, gram.shape[0])

        def finish(span=None):
            out = np.array(gram, copy=True)
            for (_, slot), d in zip(cells, self.to_numpy(delta, span)):
                out[slot, :] += d
                out[:, slot] += d
                out[slot, slot] -= d[slot]
            return out

        return matrix, finish, donate, "step"

    def _repair_step(self, matrix, idx, planes, n: int):
        """``ops.bitwise.repair_planes`` as one program, ``matrix``
        donated: ``(matrix, delta int32[C, n])``."""
        if not hasattr(self, "_repair_jit"):
            import jax

            from pilosa_tpu.ops.bitwise import repair_planes

            self._repair_jit = jax.jit(
                repair_planes, static_argnums=3, donate_argnums=0
            )
        return self._repair_jit(matrix, idx, planes, n)

    def slice_axis_devices(self, n_slices: int) -> int:
        """Devices that share the slice axis of an ``[n_slices, ...]``
        array of this engine (what the row pool's budget follows): one."""
        return 1

    def to_numpy(self, x, span=None) -> np.ndarray:
        """``x`` on the host.  Under a sampled request's ``span`` the
        conversion is its ``device.fetch`` child: the host blocks here
        until the device has computed ``x`` and copied it over
        (``mesh.fetch`` on the mesh engine)."""
        if span is None:
            return np.asarray(x)
        sp = span.child("device.fetch")
        out = np.asarray(x)
        sp.finish()
        return out

    def _devices(self) -> list:
        import jax

        return jax.devices()

    def device_info(self) -> dict:
        """The devices this engine computes on, as jax reports them,
        with each one's allocator counters (``memory_stats()``; the CPU
        backend reports none)."""
        devs = self._devices()
        per_dev = []
        for d in devs:
            ms = d.memory_stats() or {}
            per_dev.append({
                "id": d.id,
                "bytes_in_use": ms.get("bytes_in_use"),
                "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
            })
        return {"engine": self.name, "platform": devs[0].platform,
                "device_kind": devs[0].device_kind, "count": len(devs),
                "devices": per_dev}


class MeshEngine(JaxEngine):
    """JaxEngine whose slice stacks are sharded over a local device mesh.

    The executor's local map phase becomes a single GSPMD computation: the
    leading (slice) axis of every stack is partitioned over the
    ``SliceMesh`` (parallel/sharded.py), elementwise set ops stay
    shard-local, and reductions (Count, TopN candidate counts) get their
    cross-device psum/all-gather inserted by XLA from the shardings — the
    in-process analog of the reference's goroutine-per-slice fan-out
    (executor.go:1209-1244), with ICI replacing channels.

    Falls back to replication for stacks whose leading axis can't shard
    (empty or single-slice).
    """

    name = "mesh"

    # Mesh matrices shard the SLICE axis; a row-major layout would shard
    # rows instead — keep streaming transients slice-major on meshes.
    supports_row_major_gather = False

    @property
    def supports_row_scorer(self) -> bool:
        """Always true: single-process meshes use the eager per-slice row
        indexing path; multi-process meshes route through the shard_map'd
        all-slice scorer (topn_scorer_counts + allgather) instead, since
        eagerly indexing ``matrix[si]`` requires every shard to be
        process-addressable."""
        return True

    @property
    def row_scorer_all_slices(self) -> bool:
        """Meshes always route through the hybrid scorer factory; the
        single-vs-all-slice dispatch decision lives there, gated by
        supports_single_slice_score (multi-process meshes must stay
        SPMD — eager matrix[si] indexing would touch non-addressable
        shards)."""
        return True

    @property
    def supports_single_slice_score(self) -> bool:
        """Multi-process meshes cannot index ``matrix[si]`` eagerly —
        shards live on other processes; single-process meshes can."""
        import jax

        return jax.process_count() == 1

    def prepare_topn_src(self, src_stack: np.ndarray):
        """Upload a host [S, W] src stack ONCE per TopN query (tiled +
        slice-sharded) for repeated topn_scorer_counts dispatches."""
        return self._shard_stack(self._tile_host(np.ascontiguousarray(src_stack)))

    def topn_scorer_counts(self, matrix, pos, src_dev) -> np.ndarray:
        """Per-(slice, candidate) |row & src| counts over the WHOLE mesh
        in one SPMD dispatch: int32[S, K] fetched (allgathered) to every
        rank.  src_dev: the prepare_topn_src result (device-resident —
        re-uploading ~S*128 KiB per candidate chunk would dominate)."""
        from pilosa_tpu.parallel.sharded import sharded_scorer_counts

        ids = self._jnp.asarray(np.asarray(pos, dtype=np.int32))
        out = sharded_scorer_counts(self.mesh, matrix, ids, src_dev)
        return self._fetch(out).astype(np.int64)

    def __init__(self, devices=None):
        super().__init__()
        from pilosa_tpu.parallel import SliceMesh
        from pilosa_tpu.ops import bitwise as _bw

        import jax

        self._jax = jax
        self.mesh = SliceMesh(devices)
        # One jitted callable per fused path — constructing jax.jit per
        # call would re-trace and miss the dispatch cache every time.
        self._gather_jit = jax.jit(_bw.gather_count, static_argnums=0)
        self._gather_multi_jit = jax.jit(_bw.gather_count_multi, static_argnums=0)
        self._tree_jit = None  # built on first tree batch
        self._count_jit = jax.jit(_bw.count)

        jnp = self._jnp

        def and_count(rows, src, tiled):
            axes = (-2, -1) if tiled else (-1,)
            inter = jax.lax.population_count(jnp.bitwise_and(rows, src))
            return jnp.sum(inter.astype(jnp.int32), axis=axes)

        self._and_count_jit = jax.jit(and_count, static_argnums=2)

    # A pallas_call cannot be partitioned by GSPMD ("Mosaic kernels cannot
    # be automatically partitioned"), and every array this engine hands
    # out lives on the whole mesh — sharded, or replicated like a slice
    # indexed out of a sharded matrix.  The parent's single-chip kernel
    # dispatch for these two reductions therefore fails on real devices
    # (CPU meshes never saw it: there dispatch picks the jnp form anyway).
    # Both are one elementwise pass + reduce, which XLA partitions along
    # the slice axis itself with no communication.

    def count(self, batch) -> np.ndarray:
        if batch.size == 0:
            return np.zeros(batch.shape[:-1], dtype=np.int64)
        return self._fetch(self._count_jit(batch)).astype(np.int64)

    def batch_intersection_count(self, rows, src, tiled: bool = False) -> np.ndarray:
        return self._fetch(self._and_count_jit(rows, src, tiled)).astype(np.int64)

    def tile_src(self, src_dense: np.ndarray):
        """A dense [W] operand in tiled form, REPLICATED over the mesh: it
        pairs with rows indexed out of a sharded matrix (``matrix[si]``
        comes back replicated), and one jitted call takes all its
        operands on the same devices."""
        src = np.asarray(src_dense)
        self._note_upload(src.nbytes)
        return self.mesh.replicate(self._tile_host(src))

    def _devices(self) -> list:
        return list(self.mesh.mesh.devices.flat)

    def slice_axis_devices(self, n_slices: int) -> int:
        """The mesh's devices where ``_shard_stack`` partitions a slice
        axis of this length over them (device_put requires even shards);
        a ragged or single-slice axis stays on one device."""
        if n_slices < 2 or n_slices % self.mesh.n_devices:
            return 1
        return self.mesh.n_devices

    def _shard_stack(self, x):
        # Shard only cleanly-divisible leading axes; ragged slice counts
        # stay unsharded — correctness first, placement when the shapes
        # allow it.  Only stack_slices routes here, so the leading axis is
        # always the slice axis.
        if isinstance(x, np.ndarray):
            self._note_upload(x.nbytes)
        if x.ndim < 2 or self.slice_axis_devices(x.shape[0]) == 1:
            return self._jnp.asarray(x)
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(self.mesh.AXIS, *([None] * (x.ndim - 1)))
        return self._jax.device_put(x, NamedSharding(self.mesh.mesh, spec))

    def stack(self, rows: list):
        return self.stack_slices(rows)

    def stack_slices(self, stacks: list):
        return self._shard_stack(super().stack_rows(stacks))

    def matrix(self, host_matrix: np.ndarray):
        """One sharded transfer: the slice axis lands partitioned; stored
        in the same tiled 4D form as JaxEngine (relayout-free kernels)."""
        return self._shard_stack(self._tile_host(host_matrix))

    def _match_block(self, matrix, block):
        """A pool miss block spans every slice ([S, k, W]): upload it
        sharded like the matrix it is scattered into, so each device
        receives only its own slices — the parent's default-device upload
        would put every 2 GiB block whole on device 0.  Blocks over a
        subset of slices (plane refreshes) stay small and take the
        parent's path."""
        block = np.asarray(block)
        if block.shape[0] != matrix.shape[0]:
            return super()._match_block(matrix, block)
        if matrix.ndim == block.ndim + 1:
            block = self._tile_host(block)
        return self._shard_stack(block)  # counts the upload bytes itself

    def _repin(self, out, like):
        # Scatter/concat along or around the sharded slice axis may leave
        # the result replicated; pin it back to the source's sharding.
        sharding = getattr(like, "sharding", None)
        if sharding is not None:
            out = self._jax.device_put(out, sharding)
        return out

    def update_slices(self, matrix, slice_idxs, planes):
        return self._repin(super().update_slices(matrix, slice_idxs, planes), matrix)

    def append_rows(self, matrix, block):
        return self._repin(super().append_rows(matrix, block), matrix)

    def set_rows(self, matrix, row_start, block):
        return self._repin(super().set_rows(matrix, row_start, block), matrix)

    def _set_rows(self, matrix, slots, block, axis: int, donate: bool):
        if axis != 1 or self.slice_axis_devices(matrix.shape[0]) == 1:
            sharding = matrix.sharding  # read before a donation takes the array
            return self._jax.device_put(
                super()._set_rows(matrix, slots, block, axis, donate), sharding
            )
        from pilosa_tpu.parallel.sharded import sharded_set_rows

        # Every device scatters its own slices of the block into its own
        # shard (a copy of it, or with ``donate`` the shard itself).
        return sharded_set_rows(
            self.mesh, self._shard_stack(matrix), np.asarray(slots, dtype=np.int32),
            self._match_block(matrix, block), donate,
        )

    def _scatter_words(self, matrix, slots, cells, values, axis: int, donate: bool):
        if axis != 1 or self.slice_axis_devices(matrix.shape[0]) == 1:
            sharding = matrix.sharding  # read before a donation takes the array
            return self._jax.device_put(
                super()._scatter_words(matrix, slots, cells, values, axis, donate), sharding
            )
        from pilosa_tpu.parallel.sharded import sharded_set_row_words

        # Cells and values go to every device; each keeps the words of its
        # own slices and drops the rest.
        return sharded_set_row_words(
            self.mesh, self._shard_stack(matrix), slots, cells, values, donate
        )

    def grow_rows(self, matrix, n):
        # The zero rows are born with the matrix's sharding: made on the
        # default device (the parent's way) they are one device's to hold
        # whole - 4 GiB for 128 rows at 256 slices, and a 15.1 GB peak on
        # device 0 of a 16 GB chip when a four-chip pool grew to 256 slots.
        z = self._jnp.zeros(
            (matrix.shape[0], n) + matrix.shape[2:], dtype=matrix.dtype,
            device=matrix.sharding,
        )
        return self._repin(self._jnp.concatenate([matrix, z], axis=1), matrix)

    def set_plane_cells(self, matrix, cells, planes):
        if self.slice_axis_devices(matrix.shape[0]) == 1:
            return super().set_plane_cells(matrix, cells, planes)
        from pilosa_tpu.parallel.sharded import sharded_set_plane_cells

        # Every device patches its own slices of its own shard.
        return sharded_set_plane_cells(
            self.mesh, self._shard_stack(matrix), cells, self._cell_planes(matrix, planes)
        )

    def pair_gram(self, matrix):
        """Every device's Gram of its own slices, psummed (a matrix the
        mesh cannot shard takes the parent's single program)."""
        if self.slice_axis_devices(matrix.shape[0]) == 1:
            return super().pair_gram(matrix)
        from pilosa_tpu.parallel.sharded import sharded_pair_gram

        out = sharded_pair_gram(self.mesh, self._shard_stack(matrix))
        return self._fetch(out).astype(np.int64)

    def gram_update_rows(self, matrix, gram, slots, old_matrix=None, slice_idxs=None,
                         span=None):
        # No restricted-slice delta on meshes: indexing a subset of the
        # sharded slice axis breaks the shard_map divisibility the
        # kernels need (and touches non-addressable shards on
        # multi-process jobs).  The full rank-k recompute stays
        # SPMD-safe on every rank.
        return super().gram_update_rows(matrix, gram, slots, span=span)

    def _gram_counts(self, matrix, pairs, span=None) -> np.ndarray:
        """``JaxEngine._gram_counts`` over the mesh: every device counts
        its own slices (the shard_map'd kernels, or the jnp form where the
        mesh cannot shard the axis) and a psum merges them; ``mesh.fetch``
        under ``span`` is the host's wait for the reduced counts."""
        out = self._gram_counts_program()(self._shard_stack(matrix), pairs)
        return self._fetch(out, span).astype(np.int64)

    def _gram_counts_program(self):
        if not hasattr(self, "_gram_counts_jit"):
            from pilosa_tpu.ops import bitwise as _bw
            from pilosa_tpu.ops.pallas_kernels import rm_words
            from pilosa_tpu.parallel.sharded import sharded_gather_count

            def gram_update(rm, prs):
                with self._jax.named_scope("pool.gram_update"):
                    mode = self._pallas_mode(rm.shape[0], rm_words(rm))
                    if not mode:
                        return _bw.gather_count("and", rm, prs)
                    return sharded_gather_count(
                        self.mesh, "and", rm, prs, interpret=(mode == "interpret")
                    )

            self._gram_counts_jit = self._jax.jit(gram_update)
        return self._gram_counts_jit

    def _repair_step(self, matrix, idx, planes, n: int):
        """The compiled step on every device's own shard, under
        ``shard_map`` with the pool donated: each device writes the cells
        whose slice it holds and counts them against that one slice's
        rows, and a psum hands the whole delta to every device (a matrix
        the mesh cannot shard takes the parent's single program)."""
        if self.slice_axis_devices(matrix.shape[0]) == 1:
            return super()._repair_step(matrix, idx, planes, n)
        from pilosa_tpu.parallel.sharded import sharded_repair_planes

        return sharded_repair_planes(self.mesh, matrix, idx, planes, n)

    def _pallas_mode(self, n_slices: int, w: int) -> str:
        """How to run kernels under the mesh: "pallas" (shard_map'd
        hand-tuned kernels, TPU), "interpret" (same composition, Pallas
        interpret mode — CPU meshes under PILOSA_TPU_PALLAS_INTERPRET=1,
        used by tests and the driver dryrun), or "" (jnp fallback)."""
        from pilosa_tpu.ops.pallas_kernels import _tileable

        if n_slices < 2 or n_slices % self.mesh.n_devices or not _tileable(w):
            return ""
        from pilosa_tpu.ops.dispatch import use_pallas

        if use_pallas():
            return "pallas"
        # analysis-ok: lockstep-determinism: deployment config, launcher sets identical env on every rank
        if os.environ.get("PILOSA_TPU_PALLAS_INTERPRET", "").lower() in ("1", "true", "yes"):
            return "interpret"
        return ""

    def gather_count(self, op, row_matrix, pairs):
        out = self.gather_count_dev(op, row_matrix, pairs)
        return self._fetch(out)[: len(pairs)].astype(np.int64)

    def gather_count_dev(self, op, row_matrix, pairs):
        """The pair counts of the whole mesh, enqueued and un-fetched, at
        the engine's bucket for ``len(pairs)`` (the caller drops the
        tail): psummed and replicated, so any device's copy is the
        answer and ``to_numpy(counts, span)`` is the one wait."""
        # A pallas_call can't lower under GSPMD partitioning directly, but
        # shard_map restores the kernel tier: each shard runs the SAME
        # hand-tuned Pallas kernel on its local block and psum merges over
        # ICI (parallel/sharded.py).  Shapes the mesh can't shard evenly
        # (or non-TPU without interpret mode) keep the jnp form, which XLA
        # partitions itself.
        from pilosa_tpu.ops.pallas_kernels import rm_words

        rm = self._shard_stack(self._jnp.asarray(row_matrix))
        mode = self._pallas_mode(rm.shape[0], rm_words(rm))
        pairs = self._jnp.asarray(_padded_batch(pairs))
        if mode:
            from pilosa_tpu.parallel.sharded import sharded_gather_count

            return sharded_gather_count(
                self.mesh, op, rm, pairs, interpret=(mode == "interpret"),
            )
        return self._gather_jit(op, rm, pairs)

    def _fetch(self, arr, span=None) -> np.ndarray:
        """Fetch an engine array to host, allgathering when its shards
        span other processes (multi-host mesh) — the DCN analog of the
        reference streaming result segments back to the coordinator.
        Under a sampled request's ``span`` the wait is its ``mesh.fetch``
        child: the host blocks here on the mesh's reduced result."""
        sp = span.child("mesh.fetch") if span is not None else None
        if getattr(arr, "is_fully_addressable", True) or getattr(
            arr, "is_fully_replicated", False
        ):
            out = np.asarray(arr)
        else:
            from jax.experimental import multihost_utils

            out = np.asarray(multihost_utils.process_allgather(arr, tiled=True))
        if sp is not None:
            sp.finish()
        return out

    def to_numpy(self, x, span=None) -> np.ndarray:
        # Every inherited JaxEngine host conversion routes through here,
        # so allgather-aware fetching covers them all on multi-host.
        return self._fetch(x, span)

    def _chunked(self, run, n: int, chunk: int):
        """``run(i, j)`` over ``[0, n)`` in chunks of ``chunk``, the
        results joined on the device (nothing is fetched)."""
        outs = [run(i, min(n, i + chunk)) for i in range(0, n, chunk)]
        return outs[0] if len(outs) == 1 else self._jnp.concatenate(outs)

    def gather_count_multi(self, op, row_matrix, idx):
        return self._fetch(self.gather_count_multi_dev(op, row_matrix, idx)).astype(np.int64)

    def gather_count_multi_dev(self, op, row_matrix, idx):
        """``gather_count_multi``'s counts un-fetched (psummed and
        replicated under the kernels, as ``gather_count_dev``)."""
        from pilosa_tpu.ops.pallas_kernels import rm_words

        rm = self._shard_stack(self._jnp.asarray(row_matrix))
        s, w = rm.shape[0], rm_words(rm)
        mode = self._pallas_mode(s, w)
        if mode:
            # Kernel tier under the mesh (no materialized gather); it
            # bounds the prefetched id footprint like single-chip dispatch
            # does, a chunk a program.
            from pilosa_tpu.parallel.sharded import sharded_gather_count_multi

            return sharded_gather_count_multi(
                self.mesh, op, rm, self._jnp.asarray(idx), interpret=(mode == "interpret"),
            )
        # The jnp form materializes the [S, chunk, K, W] gather per shard;
        # chunk the batch so that transient stays bounded (the same budget
        # dispatch.py applies to its XLA fallback).
        from pilosa_tpu.pilosa import OR_MULTI_BUDGET_DEVICE, or_multi_chunk_size

        return self._chunked(
            lambda i, j: self._gather_multi_jit(op, rm, self._jnp.asarray(idx[i:j])),
            idx.shape[0], or_multi_chunk_size(s, idx.shape[1], w, OR_MULTI_BUDGET_DEVICE),
        )

    def gather_count_or_multi(self, row_matrix, idx):
        return self.gather_count_multi("or", row_matrix, idx)

    def gather_count_tree(self, row_matrix, leaves, opc):
        return self._fetch(self.gather_count_tree_dev(row_matrix, leaves, opc)).astype(np.int64)

    def gather_count_tree_dev(self, row_matrix, leaves, opc):
        """``gather_count_tree``'s counts un-fetched."""
        from pilosa_tpu.ops.pallas_kernels import rm_words

        rm = self._shard_stack(self._jnp.asarray(row_matrix))
        s, w = rm.shape[0], rm_words(rm)
        mode = self._pallas_mode(s, w)
        if mode:
            from pilosa_tpu.parallel.sharded import sharded_gather_count_tree

            return sharded_gather_count_tree(
                self.mesh, rm, self._jnp.asarray(leaves),
                self._jnp.asarray(opc), interpret=(mode == "interpret"),
            )
        # jnp form materializes the gather per shard: bound the transient
        # exactly like gather_count_multi's fallback.
        from pilosa_tpu.ops import bitwise as _bw
        from pilosa_tpu.pilosa import OR_MULTI_BUDGET_DEVICE, or_multi_chunk_size

        if self._tree_jit is None:
            self._tree_jit = self._jax.jit(_bw.gather_count_tree)
        return self._chunked(
            lambda i, j: self._tree_jit(
                rm, self._jnp.asarray(leaves[i:j]), self._jnp.asarray(opc[i:j])
            ),
            leaves.shape[0], or_multi_chunk_size(s, leaves.shape[1], w, OR_MULTI_BUDGET_DEVICE),
        )


def engine_name(name: str = "auto") -> str:
    """The engine a configured name selects: "auto" honors
    PILOSA_TPU_ENGINE and otherwise means jax."""
    if name == "auto":
        return os.environ.get("PILOSA_TPU_ENGINE") or "jax"
    return name


def new_engine(name: str = "auto"):
    """Engine factory.  A jax backend that cannot initialize raises the
    backend's own error here, at construction.  The numpy engine serves
    only when it is named — a server that lost its chip must not answer
    from the host unannounced."""
    name = engine_name(name)
    if name == "numpy":
        return NumpyEngine()
    if name == "mesh":
        return MeshEngine()
    if name == "jax":
        import jax

        jax.devices()  # backend probe: fail at startup, not at the first query
        return JaxEngine()
    raise ValueError(f"unknown engine: {name!r}")


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """Where jax's persistent compilation cache lives: wherever
    JAX_COMPILATION_CACHE_DIR says, else ``<checkout>/.jax_cache`` — a
    fixed path, because the path is part of every cache key."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def configure_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; call before the first
    jit.  With JAX_COMPILATION_CACHE_DIR set, jax already has its
    directory and none is set here.  Most kernels of this program
    compile in under a second — below jax's default threshold for
    storing an entry — so the threshold drops to zero."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return compile_cache_dir()
