"""Dense packed-bitmap ops in jnp (XLA), plus numpy host helpers.

Layout: a slice of a row is a dense bit vector of SLICE_WIDTH (2^20) bits,
packed little-endian-within-word into 32768 ``uint32`` words (bit ``i`` of
the slice lives at ``words[i >> 5] >> (i & 31) & 1``).  A fragment's working
set on device is ``uint32[rows, 32768]``; batched query execution stacks
slices into ``uint32[n_slices, 32768]``.

Reference analogs:
- ``bit_and``/``bit_or``/``bit_xor``/``bit_andnot`` — the container set-op
  kernels (roaring/roaring.go:1192-1558), dense case.
- ``count_and``/``count_or``/``count_xor``/``count_andnot`` — the fused
  popcount SIMD loops ``popcntAndSliceAsm`` etc.
  (roaring/assembly_amd64.s:25-115).  XLA fuses the elementwise op,
  ``population_count`` and the sum into a single pass over HBM, which is the
  TPU-native equivalent of the hand-scheduled asm loop.
- ``batch_intersection_count`` — the TopN ``Src.IntersectionCount`` hot loop
  (fragment.go:553-560): counts |row_k & src| for a whole stack of candidate
  rows in one batched kernel instead of a per-row scalar loop.

Counts are returned as int32 on device (a slice holds at most 2^20 bits so
per-slice counts can never overflow); cross-slice/cross-device totals are
accumulated host-side in Python ints (arbitrary precision), or as int64
equivalents via two-level reductions in the sharded path.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from pilosa_tpu.pilosa import SLICE_WIDTH

WORD_BITS = 32
WORDS_PER_SLICE = SLICE_WIDTH // WORD_BITS  # 32768


# ---------------------------------------------------------------------------
# Elementwise set algebra (jit-friendly; shapes [..., W])
# ---------------------------------------------------------------------------

def bit_and(a, b):
    return jnp.bitwise_and(a, b)


def bit_or(a, b):
    return jnp.bitwise_or(a, b)


def bit_xor(a, b):
    return jnp.bitwise_xor(a, b)


def bit_andnot(a, b):
    """a &^ b — bits in a that are not in b (Difference)."""
    return jnp.bitwise_and(a, jnp.bitwise_not(b))


# Operator-based pair-op table: works on jnp arrays AND inside Pallas
# kernel bodies (tracers lower &,|,^,~ to the bitwise ops).  Owned here so
# the jnp fallback never depends on the Pallas modules.
_PAIR_OPS = {
    "and": lambda a, b: a & b,
    "or": lambda a, b: a | b,
    "xor": lambda a, b: a ^ b,
    "andnot": lambda a, b: a & ~b,
}


def apply_pair_op(op: str, a, b):
    try:
        f = _PAIR_OPS[op]
    except KeyError:
        raise ValueError(f"unknown op {op!r}") from None
    return f(a, b)


def popcount_words(x):
    """Per-word popcount (the POPCNTQ analog, vectorized over all words)."""
    return lax.population_count(x)


# ---------------------------------------------------------------------------
# Fused op + popcount + reduce (the popcnt*Slice asm analogs)
# ---------------------------------------------------------------------------

def count(x):
    """Total set bits over the last axis. [..., W] -> [...] int32."""
    return jnp.sum(lax.population_count(x).astype(jnp.int32), axis=-1)


def count_and(a, b):
    """sum(popcount(a & b)) — IntersectionCount (popcntAndSliceAsm analog)."""
    return count(jnp.bitwise_and(a, b))


def count_or(a, b):
    return count(jnp.bitwise_or(a, b))


def count_xor(a, b):
    return count(jnp.bitwise_xor(a, b))


def count_andnot(a, b):
    return count(bit_andnot(a, b))


def batch_intersection_count(rows, src):
    """|rows[k] & src| for a stack of rows.

    rows: uint32[K, W]; src: uint32[W] (or broadcastable). Returns int32[K].
    Used by TopN's exact-count phase (fragment.go:553-560 analog) — one
    batched VPU pass instead of K scalar loops.
    """
    return count(jnp.bitwise_and(rows, src[..., None, :] if src.ndim == rows.ndim - 1 else src))


def gather_count_and(row_matrix, pairs):
    """Batched Count(Intersect(Bitmap(p0), Bitmap(p1))) over all slices.

    row_matrix: uint32[n_slices, n_rows, W]; pairs: int32[B, 2].
    Returns int32[B]: per-query counts summed over slices and words.
    XLA form of the fused gather kernel (gather → AND → popcount → reduce);
    the Pallas version in pallas_kernels.fused_gather_count2 avoids
    materializing the gathered stacks.
    """
    return gather_count("and", row_matrix, pairs)


def gather_count(op: str, row_matrix, pairs):
    """Batched Count(<op>(Bitmap(p0), Bitmap(p1))) over all slices — the
    generalization of :func:`gather_count_and` to Union ("or"),
    Difference ("andnot"), and Xor ("xor")."""
    if row_matrix.ndim == 4:  # tiled engine form: flatten the word axis
        row_matrix = row_matrix.reshape(*row_matrix.shape[:2], -1)
    a = jnp.take(row_matrix, pairs[:, 0], axis=1)  # [n_slices, B, W]
    b = jnp.take(row_matrix, pairs[:, 1], axis=1)
    return jnp.sum(lax.population_count(apply_pair_op(op, a, b)).astype(jnp.int32), axis=(0, 2))


def gather_count_multi(op: str, row_matrix, idx):
    """Batched Count over a left-fold of K gathered rows per query —
    N-operand Intersect ("and"), Union ("or"), Difference ("andnot"),
    and the time-quantum Range view cover (op="or"; time.go:95-167 +
    executor.go:498-554: a Range unions the minimal cover, then Count
    popcounts it).

    row_matrix: uint32[n_slices, n_rows, W]; idx: int32[B, K] row ids,
    short operand lists padded with a fold-idempotent id (and/or: any
    operand repeated; andnot: any non-first operand).  Returns int32[B]
    summed over slices.  XLA form (gather → reduce → popcount); the
    Pallas version streams one row per grid step without materializing
    the gather.
    """
    if row_matrix.ndim == 4:  # tiled engine form: flatten the word axis
        row_matrix = row_matrix.reshape(*row_matrix.shape[:2], -1)
    return _fold_count(op, jnp.take(row_matrix, idx, axis=1))


def gather_count_xframe(op: str, rm_a, idx_a, rm_b, idx_b):
    """:func:`gather_count_multi` over operands of two row matrices (two
    frames' pools: the same slices and words, any rows each): per query the
    left fold of ``rm_a``'s rows ``idx_a[q]`` then ``rm_b``'s ``idx_b[q]``.
    XLA form; the Pallas version is ``fused_gather_count_xframe``."""
    flat = [m.reshape(*m.shape[:2], -1) if m.ndim == 4 else m for m in (rm_a, rm_b)]
    return _fold_count(op, jnp.concatenate(
        [jnp.take(flat[0], idx_a, axis=1), jnp.take(flat[1], idx_b, axis=1)], axis=2))


def _fold_count(op: str, g):
    """Per-query popcount of the fold over g's operand axis: [S, B, K, W]
    -> int32[B]."""
    if op == "or":
        acc = lax.reduce(g, np.uint32(0), lax.bitwise_or, (2,))
    elif op == "and":
        acc = lax.reduce(g, np.uint32(0xFFFFFFFF), lax.bitwise_and, (2,))
    elif op == "andnot":
        # a &~ b &~ c … = a & ~(b | c | …)
        rest = lax.reduce(g[:, :, 1:], np.uint32(0), lax.bitwise_or, (2,))
        acc = jnp.bitwise_and(g[:, :, 0], jnp.bitwise_not(rest))
    else:
        raise ValueError(f"unsupported multi-op {op!r}")
    return jnp.sum(lax.population_count(acc).astype(jnp.int32), axis=(0, 2))


def gather_count_or_multi(row_matrix, idx):
    """OR-fold convenience wrapper (the fused Range cover count)."""
    return gather_count_multi("or", row_matrix, idx)


# ---------------------------------------------------------------------------
# Tree-fold counts: one dispatch for ARBITRARY nested Count trees
# (executor.go:261-276's uniform any-depth evaluation, fused)
# ---------------------------------------------------------------------------
#
# A query's boolean expression tree over Bitmap leaves is compiled to a
# PERFECT binary tree of depth D: ``leaves`` holds the 2^D gathered row
# ids (in-order), ``opc`` holds the 2^D - 1 internal-node opcodes
# level-major BOTTOM-UP (the 2^(D-1) leaf-pair nodes first, the root
# last; nodes left-to-right within a level).  Opcodes 0-3 are the pair
# ops in PQL_PAIR_OPS order (and/or/xor/andnot); TREE_PASS takes the
# LEFT child unchanged — the padding op that lets any tree shape (odd
# arities, unbalanced nesting, multi-operand Xor) fill a perfect tree.

TREE_PASS = 4


def tree_select(o, a, b):
    """Combine one node's children by opcode — elementwise over packed
    words.  Works on numpy arrays, jnp arrays, AND inside Pallas kernel
    bodies (o scalar there; array-shaped o broadcasts)."""
    if isinstance(o, np.ndarray):
        w = np.where
    else:
        w = jnp.where
    return w(
        o == 0, a & b,
        w(o == 1, a | b, w(o == 2, a ^ b, w(o == 3, a & ~b, a))),
    )


def gather_count_tree(row_matrix, leaves, opc):
    """Batched ``Count(<tree>)`` over all slices in one computation.

    row_matrix: uint32[S, R, W] (or tiled 4D); leaves: int32[B, K] with
    K = 2^D; opc: int32[B, K-1] level-major bottom-up.  Returns int32[B].
    XLA form (gather → level folds → popcount); the Pallas version
    (fused_gather_count_tree) streams one row per grid step instead of
    materializing the [S, B, K, W] gather.
    """
    if row_matrix.ndim == 4:  # tiled engine form: flatten the word axis
        row_matrix = row_matrix.reshape(*row_matrix.shape[:2], -1)
    k = leaves.shape[1]
    vals = jnp.take(row_matrix, leaves, axis=1)  # [S, B, K, W]
    off = 0
    n = k // 2
    while n >= 1:
        o = opc[None, :, off : off + n, None]  # [1, B, n, 1]
        vals = tree_select(o, vals[:, :, 0::2], vals[:, :, 1::2])
        off += n
        n //= 2
    acc = vals[:, :, 0]
    return jnp.sum(lax.population_count(acc).astype(jnp.int32), axis=(0, 2))


def np_gather_count_tree(
    row_matrix: np.ndarray, leaves: np.ndarray, opc: np.ndarray
) -> np.ndarray:
    """numpy ground truth for gather_count_tree."""
    k = leaves.shape[1]
    vals = row_matrix[:, leaves, :]  # [S, B, K, W]
    off = 0
    n = k // 2
    while n >= 1:
        o = opc[None, :, off : off + n, None]
        vals = tree_select(o, vals[:, :, 0::2], vals[:, :, 1::2])
        off += n
        n //= 2
    acc = vals[:, :, 0]
    return np_popcount(acc).reshape(acc.shape[0], acc.shape[1], -1).sum(axis=(0, 2))


def np_gather_count_multi(op: str, row_matrix: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """numpy ground truth for gather_count_multi."""
    g = row_matrix[:, idx, :]  # [S, B, K, W]
    if op == "or":
        acc = np.bitwise_or.reduce(g, axis=2)
    elif op == "and":
        acc = np.bitwise_and.reduce(g, axis=2)
    elif op == "andnot":
        acc = g[:, :, 0] & ~np.bitwise_or.reduce(g[:, :, 1:], axis=2)
    else:
        raise ValueError(f"unsupported multi-op {op!r}")
    return np_popcount(acc).reshape(acc.shape[0], acc.shape[1], -1).sum(axis=(0, 2))


def np_gather_count_xframe(
    op: str, rm_a: np.ndarray, idx_a: np.ndarray, rm_b: np.ndarray, idx_b: np.ndarray
) -> np.ndarray:
    """numpy ground truth for gather_count_xframe: the multi fold over the
    two matrices stacked along the row axis, ``idx_b`` shifted past
    ``rm_a``'s rows."""
    stacked = np.concatenate([rm_a, rm_b], axis=1)
    return np_gather_count_multi(
        op, stacked, np.concatenate([idx_a, idx_b + rm_a.shape[1]], axis=1))


def np_gather_count_or_multi(row_matrix: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """numpy ground truth for gather_count_or_multi."""
    return np_gather_count_multi("or", row_matrix, idx)


# One-shot Gram unpack budget: past this, the int8 bit matrix streams
# chunk-by-chunk through the MXU instead (pair_gram's scan path).
GRAM_ONESHOT_BYTES = 1536 * 1024 * 1024

# Per-step unpack budget for the streamed builder.  A step's live int8
# bits are R * chunk_words * 32 bytes; tall row sets (4k+ rows, where a
# single slice's unpack would be 4+ GB) subdivide the word axis until a
# step fits, so the builder has NO row-count ceiling — only the Gram
# matrix itself (R^2 ints) and the int32 count bound gate it (callers).
GRAM_STEP_BYTES = 768 * 1024 * 1024


def pair_gram(row_matrix):
    """All-pairs intersection-count Gram matrix G[i,j] = |row_i & row_j|
    summed over slices, on the MXU.

    The MXU strategy for cacheable working sets: slices are disjoint bit
    ranges of the same rows, so the Gram over the concatenated unpacked
    bit vectors equals the per-slice sum — and any word-axis subdivision
    of a slice splits it further into disjoint bit ranges, so the same
    identity lets one step carry an arbitrarily small column chunk.
    int8×int8→int32 accumulation is exact (products are 0/1; per-pair
    counts are ≤ S * 2^20, so int32 holds up to 2047 slices — gate at
    the caller).  G answers every pair op through count identities (see
    gram_pair_counts), and — being a pure function of the row matrix —
    XLA hoists it out of query-stream loops, so a stream of fused
    batches pays for it once.

    Small matrices unpack once and do ONE matmul; large ones (a 1024-
    slice x 64-row matrix is 8 GB packed = 64 GB unpacked) scan
    (slice, word-chunk) steps, accumulating ``G += bits @ bits.T`` with
    only one chunk's int8 bits (R * chunk_words * 32 bytes, bounded by
    GRAM_STEP_BYTES) live per step — billion-column indexes AND
    thousand-row working sets get all-pairs answers for one streamed
    pass of MXU work.
    """
    if row_matrix.ndim == 4:  # tiled engine form (word order is identical)
        s, r = row_matrix.shape[:2]
        w = row_matrix.shape[2] * row_matrix.shape[3]
    else:
        s, r, w = row_matrix.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)

    def unpack2(x):  # [r, ...words] -> int8 [r, words*32]
        b = ((x[..., None] >> shifts) & jnp.uint32(1)).astype(jnp.int8)
        return b.reshape(x.shape[0], -1)

    if s * r * w * 32 <= GRAM_ONESHOT_BYTES:
        if row_matrix.ndim == 4:
            row_matrix = row_matrix.reshape(s, r, w)
        flat = row_matrix.transpose(1, 0, 2).reshape(r, s * w)
        bits = unpack2(flat)
        return lax.dot_general(
            bits, bits, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
        )

    # Word-axis subdivision: split each slice into nc equal chunks (nc a
    # power-of-two divisor of the chunkable axis) until a step's unpack
    # fits the budget.  nc=1 reproduces the per-slice scan exactly.
    chunk_axis = row_matrix.shape[2]  # 4D: tile count; 3D: words
    nc = 1
    while (
        r * (w // nc) * 32 > GRAM_STEP_BYTES
        and nc * 2 <= chunk_axis
        and chunk_axis % (nc * 2) == 0
    ):
        nc *= 2

    def step(acc, i):
        # One (slice, chunk) per step, fetched by index: scanning rm's
        # leading axis directly (or reshaping the unpacked bits) made
        # XLA relayout the whole CARRIED matrix into an MXU-friendly
        # transposed tiling — an 8 GB HLO-temp copy at the 1024-slice
        # shape.  Indexed access keeps the matrix in its born layout;
        # only the per-step chunk gets copied/transposed.
        if nc == 1:
            sl = lax.dynamic_index_in_dim(row_matrix, i, 0, keepdims=False)
        else:
            si, ci = i // nc, i % nc
            cw = chunk_axis // nc
            if row_matrix.ndim == 4:
                sl = lax.dynamic_slice(
                    row_matrix,
                    (si, 0, ci * cw, 0),
                    (1, r, cw, row_matrix.shape[3]),
                )[0]
            else:
                sl = lax.dynamic_slice(
                    row_matrix, (si, 0, ci * cw), (1, r, cw)
                )[0]
        # The barrier stops the MXU's layout preference from propagating
        # through the slice to the carried matrix (verified: without it
        # XLA still inserts the full transposed copy).
        sl = lax.optimization_barrier(sl)
        b = ((sl[..., None] >> shifts) & jnp.uint32(1)).astype(jnp.int8)
        dims = tuple(range(1, b.ndim))
        return acc + lax.dot_general(
            b, b, ((dims, dims), ((), ())), preferred_element_type=jnp.int32
        ), None

    return lax.scan(step, jnp.zeros((r, r), jnp.int32), jnp.arange(s * nc))[0]


def set_plane_cells(row_matrix, cells, planes, first_slice=0):
    """A pool matrix with ``planes[c]`` written into the (slice, slot)
    cell ``cells[c]`` (int32[C, 2]): the scatter of the copying repair and
    of the blind refresh (a new array; jitted by the engines, one program
    a cell count).  A cell whose slice, less ``first_slice``, is not one
    of ``row_matrix``'s is dropped: a bucket's (-1, -1) tail, and on a
    mesh the cells of the other devices' shards.  Its ops carry
    ``pool.set_plane_rows`` in a device trace."""
    with jax.named_scope("pool.set_plane_rows"):
        n = row_matrix.shape[0]
        si = cells[:, 0] - first_slice
        si = jnp.where((cells[:, 0] >= 0) & (si >= 0) & (si < n), si, n)  # past the end: dropped
        return row_matrix.at[si, cells[:, 1]].set(planes, mode="drop")


def _write_slots(row_matrix, slots, axis: int, new_row):
    """``row_matrix`` with ``new_row(i, old)`` written into slot
    ``slots[i]`` along ``axis``, one slot after another into one copy of
    the pool; a negative slot is dropped (it puts slot 0's own contents
    back)."""

    def write(i, m):
        slot = slots[i]
        at = jnp.maximum(slot, 0)
        old = lax.dynamic_slice_in_dim(m, at, 1, axis)
        row = jnp.where(slot >= 0, new_row(i, old), old)
        return lax.dynamic_update_slice_in_dim(m, row, at, axis)

    return lax.fori_loop(0, slots.shape[0], write, row_matrix)


def set_rows(row_matrix, slots, block, axis: int = 1):
    """A pool matrix with ``block``'s rows written into ``slots``
    (int32[k]) along ``axis`` (1: slice-major ``[S, cap, ...]`` and
    ``block`` ``[S, k, ...]``; 0: row-major): the dense form of a pool
    miss's scatter (a new array; jitted by the engines, one program a
    ``k``).  A negative slot - the tail of a miss's bucket - is dropped.
    One row after another into one copy of the pool: XLA's own scatter
    kept a second copy of a block of 64 rows or more (found by compiling
    for a described v5e).  Its ops carry ``pool.set_rows`` in a device
    trace."""
    with jax.named_scope("pool.set_rows"):
        return _write_slots(
            row_matrix, slots, axis, lambda i, _: lax.dynamic_slice_in_dim(block, i, 1, axis)
        )


def set_row_words(row_matrix, slots, cells, values, axis: int = 1):
    """A pool matrix with the rows of ``slots`` (int32[k], a negative one
    dropped) made of ``values`` alone: the sparse form of a pool miss's
    scatter.  Each slot's row is zeroed first (the row it held before
    must go), then ``values[c]`` (uint32) is written at word
    ``cells[c, 2]`` of the (slice ``cells[c, 0]``, slot ``cells[c, 1]``)
    plane - no two cells name one word (the host has OR-ed equal words,
    so the scatter may take them in any order).  ``axis`` as in
    ``set_rows``: 1 for a slice-major pool ``[S, cap, ...]``, 0 for a
    row-major one ``[cap, S, ...]`` (the cell stays (slice, slot, word)).
    A cell whose slice is not one of the matrix's is dropped: a word
    bucket's (-1, -1, -1) tail.  The one-chip engine sends it the whole
    chunk; on a mesh each device runs it on its own shard with the words
    of its own slices alone, their slices already local
    (``parallel.sharded.sharded_set_row_words``).  A new array, or the
    caller's own where it is donated; jitted by the engines, one program
    a word bucket.  Its ops carry ``pool.set_rows`` in a device trace."""
    return set_row_word_columns(
        row_matrix, slots, cells[:, 0], cells[:, 1], cells[:, 2], values, axis
    )


def set_row_word_columns(row_matrix, slots, si, slot, w, values, axis: int = 1):
    """``set_row_words`` with the cells as three int32[N] columns (slice,
    slot, word): the form a mesh's shard is handed its words in."""
    with jax.named_scope("pool.set_rows"):
        m = _write_slots(row_matrix, slots, axis, lambda _, old: jnp.zeros_like(old))
        n = m.shape[1 - axis]
        si = jnp.where((si >= 0) & (si < n), si, n)  # past the end: dropped
        lead = (si, slot) if axis == 1 else (slot, si)
        if m.ndim == 4:  # tiled: a plane is [W / lanes, lanes]
            lanes = m.shape[3]
            word = (w // lanes, w % lanes)
        else:
            word = (w,)
        return m.at[lead + word].set(values, mode="drop", unique_indices=True)


# A src row of at most this many words that are not zero meets the
# candidates' words by comparison (a pass over them a src word); a larger
# one is gathered at every candidate word.
TOPN_SRC_COMPARED = 16


def _cumsum_long(x):
    """``jnp.cumsum`` of a long 1-D array as row sums of a [n/1024, 1024]
    view plus their carries: the plain form of a million elements took the
    TPU compiler half a minute."""
    n = x.shape[0]
    if n < 4096:
        return jnp.cumsum(x)
    rows = jnp.cumsum(x.reshape(n // 1024, 1024), axis=1)
    carry = jnp.cumsum(rows[:, -1]) - rows[:, -1]
    return (rows + carry[:, None]).reshape(n)


def topn_sparse_counts(src, widx, vals, ends):
    """|row_k & src| for every candidate ``k`` of a fragment's TopN, from
    the candidates' words that are not zero, candidate after candidate
    (``widx`` int32[N], the word of the slice; ``vals`` uint32[N], the
    candidate's bits there; candidate ``k``'s words end at ``ends[k]``,
    int32[K], ascending) and the slice's src words ``src`` uint32[W]:
    int32[K].  Each candidate word is met with the src's word at the same
    place - by comparison with each of the src's words that are not zero
    where there are at most TOPN_SRC_COMPARED of them (a user of a few
    stars), else by a gather of ``src`` - then a popcount of the AND and a
    sum a candidate (differences of one running sum at ``ends``).  A padded
    word (value 0) counts nothing.  Jitted by the jax engine, one program a
    (word, candidate) bucket; its ops carry ``topn.count`` in a device
    trace."""
    with jax.named_scope("topn.count"):

        def compared(_):
            at = jnp.nonzero(src, size=TOPN_SRC_COMPARED, fill_value=-1)[0].astype(jnp.int32)
            words = jnp.where(at >= 0, src[jnp.maximum(at, 0)], jnp.uint32(0))
            hit = jnp.zeros_like(vals)
            for j in range(TOPN_SRC_COMPARED):
                hit = hit | jnp.where(widx == at[j], words[j], jnp.uint32(0))
            return hit

        def gathered(_):
            return src.at[widx].get(mode="promise_in_bounds")

        few = jnp.count_nonzero(src) <= TOPN_SRC_COMPARED
        hit = lax.cond(few, compared, gathered, None)
        ones = lax.population_count(hit & vals).astype(jnp.int32)
        total = jnp.concatenate([jnp.zeros(1, jnp.int32), _cumsum_long(ones)])
        starts = jnp.concatenate([jnp.zeros(1, jnp.int32), ends[:-1]])
        return total[ends] - total[starts]


def repair_planes(row_matrix, cells, planes, n: int):
    """Write new planes into a pool matrix and return what each write
    does to the AND-count Gram over the first ``n`` slots.

    ``row_matrix``: uint32[S, cap, ...words] (tiled or logical), meant to
    be donated, so that the writes land in the caller's buffer;
    ``cells``: int32[C, 2] of (slice, slot), a bucket's unused tail
    (-1, -1); ``planes``: uint32[C, ...words], the cells' new contents.
    Returns ``(row_matrix, delta int32[C, n])``.

    One cell after another: ``delta[c, j]`` = |new & row_j| - |old &
    row_j| over the cell's slice as the cells before it left it (one pass
    over that slice's rows: 32 MiB at cap 256), and at the cell's own
    slot |new| - |old|; then the plane is written.  The deltas telescope:
    adding ``delta[c]`` into row and column ``slot_c`` of the Gram, and
    taking ``delta[c, slot_c]`` off the diagonal once, gives the Gram of
    the patched matrix exactly, also where a burst wrote several rows of
    one slice.  Only the first ``count(slice >= 0)`` cells run, so a
    bucket's tail costs its upload alone.
    """
    wd = row_matrix.shape[2:]
    z = (0,) * len(wd)
    axes = tuple(range(1, 1 + len(wd)))

    def ones(x, over):
        return jnp.sum(lax.population_count(x).astype(jnp.int32), axis=over)

    def one_cell(c, carry):
        m, delta = carry
        si, slot = cells[c, 0], cells[c, 1]
        new = planes[c]
        old = lax.dynamic_slice(m, (si, slot) + z, (1, 1) + wd)[0, 0]
        rows = lax.dynamic_slice(m, (si, 0) + z, (1, n) + wd)[0]
        d = ones(rows & new, axes) - ones(rows & old, axes)
        d = d.at[slot].set(ones(new, None) - ones(old, None))
        m = lax.dynamic_update_slice(m, new[None, None], (si, slot) + z)
        return m, lax.dynamic_update_index_in_dim(delta, d, c, 0)

    return lax.fori_loop(
        0, jnp.sum(cells[:, 0] >= 0), one_cell,
        (row_matrix, jnp.zeros((cells.shape[0], n), jnp.int32)),
    )


def gram_pair_counts(op: str, gram, pairs):
    """Per-pair counts for any pair op from the AND-Gram matrix.

    |a|b| = |a|+|b|-|a&b|;  |a^b| = |a|+|b|-2|a&b|;  |a&~b| = |a|-|a&b|.
    Works on numpy or jnp arrays (gram: int32[R,R]; pairs: int[B,2]).
    """
    g_and = gram[pairs[:, 0], pairs[:, 1]]
    if op == "and":
        return g_and
    d0 = gram[pairs[:, 0], pairs[:, 0]]
    d1 = gram[pairs[:, 1], pairs[:, 1]]
    if op == "or":
        return d0 + d1 - g_and
    if op == "xor":
        return d0 + d1 - 2 * g_and
    if op == "andnot":
        return d0 - g_and
    raise ValueError(f"unknown op {op!r}")


# ---------------------------------------------------------------------------
# Host-side numpy helpers (mask building, packing) — used to prepare
# device inputs; never inside jit (they produce constants).
# ---------------------------------------------------------------------------

def make_range_mask(start_bit: int, end_bit: int, n_words: int = WORDS_PER_SLICE) -> np.ndarray:
    """Dense uint32 mask with bits [start_bit, end_bit) set.

    Used for Range/CountRange style queries restricted to a column interval
    within a slice (roaring.go CountRange analog), and to mask the tail of a
    partially-filled last slice.
    """
    start_bit = max(0, min(start_bit, n_words * WORD_BITS))
    end_bit = max(start_bit, min(end_bit, n_words * WORD_BITS))
    mask = np.zeros(n_words, dtype=np.uint32)
    if start_bit == end_bit:
        return mask
    sw, sb = divmod(start_bit, WORD_BITS)
    ew, eb = divmod(end_bit, WORD_BITS)
    if sw == ew:
        mask[sw] = ((np.uint64(1) << np.uint64(eb)) - np.uint64(1)) & ~(
            (np.uint64(1) << np.uint64(sb)) - np.uint64(1)
        )
        return mask
    mask[sw] = np.uint32(0xFFFFFFFF) & np.uint32(~((1 << sb) - 1) & 0xFFFFFFFF)
    mask[sw + 1 : ew] = np.uint32(0xFFFFFFFF)
    if ew < n_words and eb:
        mask[ew] = np.uint32((1 << eb) - 1)
    return mask


def pack_positions(positions: np.ndarray, n_words: int = WORDS_PER_SLICE) -> np.ndarray:
    """Pack sorted (or unsorted) bit positions into a dense uint32 word array."""
    words = np.zeros(n_words, dtype=np.uint32)
    if len(positions) == 0:
        return words
    positions = np.asarray(positions, dtype=np.uint64)
    w = (positions >> np.uint64(5)).astype(np.int64)
    b = (positions & np.uint64(31)).astype(np.uint32)
    np.bitwise_or.at(words, w, np.uint32(1) << b)
    return words


def unpack_positions(words: np.ndarray) -> np.ndarray:
    """Inverse of pack_positions: dense words -> sorted uint64 bit positions."""
    words = np.ascontiguousarray(words, dtype=np.uint32)
    bits = np.unpackbits(words.view(np.uint8), bitorder="little")
    return np.nonzero(bits)[0].astype(np.uint64)


def pack_rows_matrix(rows_positions, n_rows: int, n_words: int = WORDS_PER_SLICE) -> np.ndarray:
    """Build a dense uint32[n_rows, n_words] matrix from per-row position lists."""
    m = np.zeros((n_rows, n_words), dtype=np.uint32)
    for r, pos in rows_positions:
        if r < n_rows and len(pos):
            m[r] = pack_positions(pos, n_words)
    return m


# ---------------------------------------------------------------------------
# numpy reference implementations (ground truth for property tests — the
# analog of the Go SWAR fallbacks in roaring/assembly.go:26-73)
# ---------------------------------------------------------------------------

def np_popcount(x: np.ndarray) -> np.ndarray:
    x = np.ascontiguousarray(x, dtype=np.uint32)
    return np.unpackbits(x.view(np.uint8)).reshape(*x.shape, 32).sum(-1)


# Byte-popcount lookup table for count_words: one gather + sum beats the
# 8x unpackbits expansion by ~20x when only the TOTAL is wanted.
_POP8 = np_popcount(np.arange(256, dtype=np.uint32)).astype(np.uint16)


def count_words(x: np.ndarray) -> int:
    """Total set-bit count of a packed word array (any uint dtype).
    The fast lane for cardinality-only callers — np_popcount stays the
    per-word reference (property tests hold this to it)."""
    x = np.ascontiguousarray(x)
    return int(_POP8[x.view(np.uint8)].sum(dtype=np.int64))


def np_count(x: np.ndarray) -> int:
    return int(np_popcount(x).sum())


def np_count_and(a, b) -> int:
    return np_count(np.bitwise_and(a, b))


def np_count_or(a, b) -> int:
    return np_count(np.bitwise_or(a, b))


def np_count_xor(a, b) -> int:
    return np_count(np.bitwise_xor(a, b))


def np_count_andnot(a, b) -> int:
    return np_count(np.bitwise_and(a, np.bitwise_not(np.asarray(b, dtype=np.uint32))))
