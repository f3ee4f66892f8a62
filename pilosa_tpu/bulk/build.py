"""Sort/segment/scatter bulk build kernels.

A bulk chunk is two uint64 columns (row ids, global column ids).  The
build turns them into packed-uint32 word planes — one ``uint32[W]``
plane per touched (slice, row), ``W = SLICE_WIDTH // 32`` — which is
EXACTLY the engine's HBM row layout, so a committed plane needs no
further transformation to serve.

Three stages, shared by both lanes:

1. **sort** — order pairs by (slice, row, local column);
2. **segment** — find the (slice, row) group boundaries (the group
   table is what the fragment commit keys on) and drop duplicate
   positions;
3. **scatter** — OR each position's bit into its group's word plane.

:func:`build_planes_numpy` is the host twin (vectorized lexsort +
``bitwise_or.reduceat``); :func:`build_planes_jax` sorts on host too
(the group table comes out of that sort) and runs the segment/scatter on
device under ``jax.jit`` with padded shapes (deduped positions make
scatter-add equal scatter-or, which XLA lacks natively).  Both return
identical planes for identical input — the differential suite in
tests/test_bulk.py holds them to it.
"""

from __future__ import annotations

import numpy as np

from pilosa_tpu.pilosa import SLICE_WIDTH

# Words per (slice, row) plane: the packed-uint32 device row layout.
WORDS_PER_PLANE = SLICE_WIDTH // 32


def group_pairs(rows, cols):
    """Sort + segment: order (row, col) pairs by (slice, row, local) and
    return the group table.

    Returns ``(slice_ids i64[G], row_ids i64[G], gid_sorted i64[N],
    local_sorted i64[N])`` where ``gid_sorted`` maps each sorted pair to
    its dense (slice, row) group and ``local_sorted`` is its in-slice
    column.  The sorted order makes every downstream flat index
    nondecreasing, which is what both scatter lanes lean on.
    """
    rows = np.asarray(rows, dtype=np.uint64)
    cols = np.asarray(cols, dtype=np.uint64)
    if len(rows) != len(cols):
        raise ValueError("row/col length mismatch")
    if len(rows) == 0:
        z = np.empty(0, dtype=np.int64)
        return z, z, z, z
    slices = (cols // np.uint64(SLICE_WIDTH)).astype(np.int64)
    local = (cols % np.uint64(SLICE_WIDTH)).astype(np.int64)
    r = rows.astype(np.int64)
    # The sort is the whole kernel's hot spot.  When (slice, row) fit
    # beside the 20 local bits in one uint64 — every realistic shape;
    # slice and row ids past 2^22 apiece do not — pack the three keys
    # into ONE composite word and radix the VALUES (np.sort, no argsort,
    # no gather): ~20x over the three-pass lexsort on million-pair
    # chunks.  The decomposed fields are exactly the sorted columns.
    sb = int(slices.max()).bit_length()
    rb = int(r.max()).bit_length()
    if sb + rb <= 44:
        key = np.sort(
            (slices.astype(np.uint64) << np.uint64(rb + 20))
            | (r.astype(np.uint64) << np.uint64(20))
            | local.astype(np.uint64)
        )
        ll = (key & np.uint64(SLICE_WIDTH - 1)).astype(np.int64)
        rr = ((key >> np.uint64(20)) & np.uint64((1 << rb) - 1)).astype(
            np.int64
        )
        ss = (key >> np.uint64(rb + 20)).astype(np.int64)
    else:
        order = np.lexsort((local, r, slices))
        ss, rr, ll = slices[order], r[order], local[order]
    newgrp = np.empty(len(ss), dtype=bool)
    newgrp[0] = True
    newgrp[1:] = (ss[1:] != ss[:-1]) | (rr[1:] != rr[:-1])
    gid = np.cumsum(newgrp) - 1
    firsts = np.flatnonzero(newgrp)
    return ss[firsts], rr[firsts], gid, ll


def _nonzero_words(gid, local):
    """Segment+scatter core shared by both host lanes: the UNIQUE flat
    word indices (``gid * W + word``, ascending) and each word's OR'd
    bit value, from the sorted group/local columns."""
    flat = gid * WORDS_PER_PLANE + (local >> 5)
    val = (np.uint32(1) << (local & 31).astype(np.uint32)).astype(np.uint32)
    # ``flat`` is already nondecreasing (sorted by (slice, row, local)),
    # so the word boundaries are plain diffs — no np.unique re-sort.
    start = np.flatnonzero(
        np.concatenate([np.ones(1, dtype=bool), flat[1:] != flat[:-1]])
    )
    return flat[start], np.bitwise_or.reduceat(val, start)


def build_planes_numpy(rows, cols):
    """Host build twin: ``(slice_ids, row_ids, planes uint32[G, W])``.

    ``bitwise_or.reduceat`` over the sorted flat word index does the
    segment+scatter in two vectorized passes (duplicate positions OR
    harmlessly, so no explicit dedup pass is needed on host).
    """
    slice_ids, row_ids, gid, local = group_pairs(rows, cols)
    g = len(slice_ids)
    planes = np.zeros((g, WORDS_PER_PLANE), dtype=np.uint32)
    if g == 0:
        return slice_ids, row_ids, planes
    uf, orv = _nonzero_words(gid, local)
    planes[uf // WORDS_PER_PLANE, uf % WORDS_PER_PLANE] = orv
    return slice_ids, row_ids, planes


def build_words_numpy(rows, cols):
    """Sparse host lane: ``(slice_ids, row_ids, counts, word_idx,
    word_vals)`` — the SAME planes as :func:`build_planes_numpy`, in
    CSR form over their nonzero words (``counts[i]`` words belong to
    group ``i``; ``word_idx`` is each word's in-plane index, unique and
    ascending within a group; ``word_vals`` its OR'd uint32 value).

    This is what the commit path wants on host: a chunk's pairs touch
    a few hundred words per plane, so materializing (and then OR-ing)
    full 32768-word planes per chunk is almost all page traffic for
    zeros.  ``Fragment.bulk_or_words`` scatters exactly these words
    into the persistent overlay instead.
    """
    slice_ids, row_ids, gid, local = group_pairs(rows, cols)
    if len(slice_ids) == 0:
        z = np.empty(0, dtype=np.int64)
        return slice_ids, row_ids, z, z, np.empty(0, dtype=np.uint32)
    uf, orv = _nonzero_words(gid, local)
    counts = np.bincount(uf // WORDS_PER_PLANE, minlength=len(slice_ids))
    return (slice_ids, row_ids, counts.astype(np.int64),
            uf % WORDS_PER_PLANE, orv)


def _pad_pow2(n: int, floor: int = 1024) -> int:
    """Next power-of-two bucket >= n (floor bounds jit recompiles)."""
    n = max(int(n), floor)
    return 1 << (n - 1).bit_length()


from pilosa_tpu.analysis import lockcheck as _lockcheck

# Registered memo for the jitted pack kernel (jax.jit memoizes compiles
# per shape itself; this holds the single traced callable).
_JIT_CACHE = _lockcheck.named_global("bulk.build.jit_kernel", max_entries=4)


# Planes one kernel call builds: 16384 x 128 KiB = 2 GiB of words, every
# word index (and the scratch slot past them) inside int32 — jax runs
# without x64, so a 64-bit composite position key (group * 2^20 + column)
# would be truncated in silence past 2048 groups.
_GROUPS_PER_CALL = 16384


def _jax_kernel(jnp, jax):
    """The jitted segment/scatter body (one compile per padded (P, GW)
    bucket pair, memoized by jax.jit itself).  Its inputs arrive sorted
    by (group, in-slice column): the host sort that yields the group
    table (:func:`group_pairs`) already ordered them, and a second sort
    on device would cost the TPU compiler tens of seconds per bucket."""

    def pack(gid, local, n_out):
        # segment: the first occurrence of each position keeps its bit,
        # duplicates contribute zero — after which scatter-ADD is exactly
        # scatter-OR.
        first = jnp.concatenate([
            jnp.ones((1,), dtype=bool),
            (gid[1:] != gid[:-1]) | (local[1:] != local[:-1]),
        ])
        val = jnp.where(
            first, jnp.uint32(1) << (local & 31).astype(jnp.uint32), jnp.uint32(0)
        )
        # scatter: one segment-sum over the padded word arena; pad
        # entries carry a group id past every real one and land on the
        # scratch slot past the planes.  The indices stay nondecreasing.
        flat = jnp.minimum(gid * WORDS_PER_PLANE + (local >> 5), n_out)
        out = jnp.zeros(n_out + 1, dtype=jnp.uint32)
        return out.at[flat].add(val, indices_are_sorted=True)[:n_out]

    return jax.jit(pack, static_argnums=(2,))


def build_planes_jax(rows, cols, jnp=None):
    """Device build lane: same contract as :func:`build_planes_numpy`,
    with the segment/scatter running under ``jax.jit`` on padded
    power-of-two shapes (stable compile buckets).  The sort and the group
    table are computed on host (the fragment commit needs host ids
    regardless); the bit data itself dedups and scatters into planes on
    device, at most ``_GROUPS_PER_CALL`` planes per kernel call.
    """
    import jax

    if jnp is None:
        import jax.numpy as jnp_mod

        jnp = jnp_mod
    slice_ids, row_ids, gid, local = group_pairs(rows, cols)
    g = len(slice_ids)
    if g == 0:
        return slice_ids, row_ids, np.zeros((0, WORDS_PER_PLANE), np.uint32)
    kern = _JIT_CACHE.get("pack")
    if kern is None:
        kern = _jax_kernel(jnp, jax)  # tracing outside any lock
        _JIT_CACHE.put("pack", kern)
    blocks = []
    for g0 in range(0, g, _GROUPS_PER_CALL):
        g1 = min(g, g0 + _GROUPS_PER_CALL)
        lo, hi = np.searchsorted(gid, [g0, g1])  # gid is nondecreasing
        p = _pad_pow2(hi - lo)
        gp = _pad_pow2(g1 - g0, floor=1)
        n_out = gp * WORDS_PER_PLANE
        gid_p = np.full(p, gp, dtype=np.int32)  # past every real group
        gid_p[: hi - lo] = gid[lo:hi] - g0
        local_p = np.zeros(p, dtype=np.int32)
        local_p[: hi - lo] = local[lo:hi]
        words = kern(jnp.asarray(gid_p), jnp.asarray(local_p), n_out)
        blocks.append(np.asarray(words).reshape(gp, WORDS_PER_PLANE)[: g1 - g0])
    planes = blocks[0] if len(blocks) == 1 else np.concatenate(blocks)
    return slice_ids, row_ids, np.ascontiguousarray(planes)


def plane_positions(words: np.ndarray, base: int = 0) -> np.ndarray:
    """Set-bit positions of a packed-uint32 plane (uint64, ascending),
    offset by ``base`` — the dense→roaring bridge used by overlay
    materialization and the Arrow egress (matches
    ``roaring.Bitmap.from_dense_words`` bit order).
    """
    words = np.ascontiguousarray(words, dtype=np.uint32)
    bits = np.unpackbits(words.view(np.uint8), bitorder="little")
    return np.nonzero(bits)[0].astype(np.uint64) + np.uint64(base)
