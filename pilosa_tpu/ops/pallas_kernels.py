"""Hand-written Pallas TPU kernels for the fused popcount reductions.

These are the TPU-native equivalents of the reference's hand-written AMD64
SIMD loops (roaring/assembly_amd64.s:25-115): one pass over HBM that applies
the bitwise op, popcounts each word on the VPU, and reduces to a scalar per
row — no intermediate materialization.

A packed row of one slice is 32768 uint32 words, viewed as a (256, 128)
tile-aligned block (int32 min tile is (8, 128)).  The grid iterates over the
leading (row/slice) axis; Pallas double-buffers the HBM→VMEM DMAs across
grid steps, so the kernel streams at HBM bandwidth.

Fallback: on non-TPU backends (or non-tileable word counts) `dispatch`
routes to the jnp implementations in `bitwise`, the analog of the reference
gating its asm path on a CPUID check (roaring/assembly_asm.go:20,
assembly_generic.go).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8  # int32/uint32 min sublane count


# Shared pair-op table (operators lower identically in kernel bodies).
from pilosa_tpu.ops.bitwise import apply_pair_op as _op_apply  # noqa: E402


def _partial_tile(words):
    # words: (1, sub, 128) uint32 -> (8, 128) int32 partial popcount sums.
    # Reducing only across sublane groups keeps the store tile-aligned
    # ((8,128) is the int32 min tile); the final (8,128)->scalar fold is left
    # to XLA outside the kernel where it costs nothing.
    pc = lax.population_count(words).astype(jnp.int32)
    sub = words.shape[1]
    return pc.reshape(sub // 8, 8, _LANES).sum(axis=0)


def _count2_kernel(op, a_ref, b_ref, out_ref):
    out_ref[0] = _partial_tile(_op_apply(op, a_ref[...], b_ref[...]))


def _count1_kernel(a_ref, out_ref):
    out_ref[0] = _partial_tile(a_ref[...])


def _tileable(n_words: int) -> bool:
    return n_words % (_LANES * _SUBLANES) == 0


def rm_words(rm) -> int:
    """Logical word count W of a row matrix in either layout (see _rm4)."""
    return rm.shape[-1] if rm.ndim == 3 else rm.shape[-2] * rm.shape[-1]


def _rm4(rm):
    """Canonical TILED row-matrix form uint32[S, R, W/128, 128].

    Device arrays BORN in this 4D form avoid the relayout XLA otherwise
    inserts when a [S, R, W] array is reshaped inside jit: the physical
    (8, 128) tiling of (R, W) differs from that of (W/128, 128), so the
    reshape materializes a full tiled copy of the matrix in HBM — the
    round-2 OOM at 1024 slices was exactly this 8 GB temp
    (BASELINE.md round-3 note).  Jax engines therefore store matrices 4D
    (engine.matrix) and this helper is an identity no-op; 3D callers
    (tests, numpy-built transients) still work and pay the transient.
    """
    if rm.ndim == 4:
        return rm
    s, r, w = rm.shape
    return rm.reshape(s, r, w // _LANES, _LANES)


@functools.partial(jax.jit, static_argnames=("op", "interpret", "tiled"))
def fused_count2(op: str, a, b, interpret: bool = False, tiled: bool = False):
    """sum(popcount(op(a, b))) over the last axis via a Pallas kernel.

    a: uint32[..., W] with W % 1024 == 0; b: same shape as a, OR uint32[W]
    (a single shared operand, e.g. TopN's src row counted against a whole
    stack of candidate rows).  The shared case streams the one b block into
    VMEM once per grid step instead of materializing a K-way broadcast in
    HBM.  Returns int32[...] (a's shape minus the word axis).

    ``tiled=True`` declares that the trailing TWO axes are the word axis
    in canonical tiled form [..., W/128, 128] (see _rm4): rows sliced out
    of a 4D engine matrix keep their relayout-free path, and b is
    [..., W/128, 128] correspondingly.
    """
    if tiled:
        sub = a.shape[-2] * a.shape[-1] // _LANES
        shape = a.shape[:-2] + (a.shape[-2] * a.shape[-1],)
        shared_b = b.ndim == 2 and a.ndim > 2
    else:
        shape = a.shape
        sub = shape[-1] // _LANES
        shared_b = b.ndim == 1 and a.ndim > 1
    w = sub * _LANES
    m = 1
    for d in shape[:-1]:
        m *= d
    a3 = a.reshape(m, sub, _LANES)
    if shared_b:
        b3 = b.reshape(1, sub, _LANES)
        b_spec = pl.BlockSpec((1, sub, _LANES), lambda i: (0, 0, 0))
    else:
        b3 = jnp.broadcast_to(b, a.shape).reshape(m, sub, _LANES)
        b_spec = pl.BlockSpec((1, sub, _LANES), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_count2_kernel, op),
        out_shape=jax.ShapeDtypeStruct((m, 8, _LANES), jnp.int32),
        grid=(m,),
        in_specs=[
            pl.BlockSpec((1, sub, _LANES), lambda i: (i, 0, 0)),
            b_spec,
        ],
        out_specs=pl.BlockSpec((1, 8, _LANES), lambda i: (i, 0, 0)),
        interpret=interpret,
    )(a3, b3)
    return out.sum(axis=(1, 2)).reshape(shape[:-1])


def _resident_count_kernel(op, n_pairs, pairs_ref, rows_ref, out_ref):
    s, k = pl.program_id(0), pl.program_id(1)

    @pl.when((s == 0) & (k == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    c_sub = rows_ref.shape[2]

    def body(q, carry):
        a = rows_ref[0, pairs_ref[q, 0]]
        b = rows_ref[0, pairs_ref[q, 1]]
        pc = lax.population_count(_op_apply(op, a, b)).astype(jnp.int32)
        part = pc.reshape(c_sub // 8, 8, _LANES).sum(axis=0)
        out_ref[q] = out_ref[q] + part
        return carry

    lax.fori_loop(0, n_pairs, body, 0)


def _resident_chunk_sub(
    n_rows: int, w: int, batch: int = 0, budget_bytes: int = 8 * 1024 * 1024
) -> int:
    """Largest power-of-two sublane chunk (multiple of 8, dividing w/128)
    whose all-rows block fits the VMEM budget; 0 if even 8 doesn't fit.

    The (batch, 8, 128) int32 accumulator block is held fully resident
    across every grid step (constant output index map), so its footprint
    comes out of the same budget — large fused batches must fall back to
    the per-query gather kernel whose output block is (1, 8, 128).

    Budget 8 MB: the block is double-buffered across grid steps, so the
    worst case is 2*(8MB - out) + out <= 16 MB VMEM.  Measured at the
    1024-slice bench shape: 4 MB blocks (this budget) run at 80% of the
    HBM roofline vs 53% with the previous 4 MB budget's 2 MB blocks —
    the v5e DMA descriptor ladder again (BASELINE.md round-3 notes)."""
    out_bytes = batch * 8 * _LANES * 4
    total_sub = w // _LANES
    best = 0
    c = 8
    while c <= total_sub:
        if total_sub % c == 0 and n_rows * c * _LANES * 4 + out_bytes <= budget_bytes:
            best = c
        c *= 2
    return best


def resident_strategy(n_rows: int, w: int, batch: int) -> bool:
    """Whether the VMEM-resident kernel beats the per-query gather for a
    pair-count batch: streaming ALL rows once must beat gathering 2 rows
    per query (R < 2B) and an all-rows chunk must fit the VMEM budget.
    Shared by single-chip dispatch and the shard_map'd mesh tier so the
    heuristic can't drift between them."""
    return n_rows < 2 * batch and bool(_resident_chunk_sub(n_rows, w, batch))


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
@jax.named_scope("gather.count")  # the pair kernels' name in a device trace
def fused_resident_count2(op: str, row_matrix, pairs, interpret: bool = False):
    """Row-resident variant of :func:`fused_gather_count2` for small row
    working sets (the common case: a hot frame has far fewer distinct rows
    than the query batch has row references).

    Instead of DMAing two operand rows per (query, slice) grid step —
    2*B*S row reads — this streams the ENTIRE row matrix HBM→VMEM exactly
    once (grid = (slice, word-chunk), block = all rows of one chunk) and
    answers every query in the batch from VMEM with dynamic row indexing.
    HBM traffic drops from 2*B to R row-equivalents per slice, which for
    the headline bench shape (R=64 rows, B=256 queries) is ~8x less; the
    kernel then runs at VPU popcount speed instead of HBM gather speed.
    TPU-native analog of the reference's rowCache keeping hot rows out of
    the mmap (fragment.go:338-367) — here "cache" is VMEM residency.
    """
    rm4 = _rm4(row_matrix)
    n_slices, n_rows = rm4.shape[:2]
    w = rm4.shape[2] * rm4.shape[3]
    b = pairs.shape[0]
    c_sub = _resident_chunk_sub(n_rows, w, b)
    if c_sub == 0:
        raise ValueError("row matrix + accumulator too large for resident kernel")
    n_chunks = (w // _LANES) // c_sub
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_slices, n_chunks),
        in_specs=[
            pl.BlockSpec((1, n_rows, c_sub, _LANES), lambda s, k, pr: (s, 0, k, 0)),
        ],
        out_specs=pl.BlockSpec((b, 8, _LANES), lambda s, k, pr: (0, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_resident_count_kernel, op, b),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 8, _LANES), jnp.int32),
        interpret=interpret,
    )(pairs, rm4)
    return out.sum(axis=(1, 2))


def _gather_count_kernel(op, pairs_ref, a_ref, b_ref, out_ref):
    s = pl.program_id(1)
    part = _partial_tile(_op_apply(op, a_ref[0], b_ref[0]))

    @pl.when(s == 0)
    def _():
        out_ref[0] = part

    @pl.when(s != 0)
    def _():
        out_ref[0] = out_ref[0] + part


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
@jax.named_scope("gather.count")  # the pair kernels' name in a device trace
def fused_gather_count2(op: str, row_matrix, pairs, interpret: bool = False):
    """Per-query ``sum_s popcount(op(rm[s, p0], rm[s, p1]))`` without
    materializing the gathered operands.

    row_matrix: uint32[n_slices, n_rows, W] with W % 1024 == 0;
    pairs: int32[B, 2] row ids.  Returns int32[B] counts summed over
    slices and words.

    The batched ``Count(Intersect(Bitmap(r1), Bitmap(r2)))`` hot path
    (executor.go:576-605 + roaring/assembly_amd64.s:60-77 analog).  The
    XLA form (`jnp.take` → AND → popcount) writes both gathered stacks to
    HBM before reading them back; this kernel instead scalar-prefetches
    the pair ids and DMAs each operand row HBM→VMEM exactly once per
    (query, slice) grid step, halving HBM traffic.  The slice axis is the
    minor grid dimension so the per-query accumulator tile stays resident
    in VMEM across the reduction.
    """
    rm4 = _rm4(row_matrix)
    n_slices, n_rows, sub = rm4.shape[:3]
    b = pairs.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_slices),
        in_specs=[
            pl.BlockSpec((1, 1, sub, _LANES), lambda q, s, pr: (s, pr[q, 0], 0, 0)),
            pl.BlockSpec((1, 1, sub, _LANES), lambda q, s, pr: (s, pr[q, 1], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 8, _LANES), lambda q, s, pr: (q, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_gather_count_kernel, op),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 8, _LANES), jnp.int32),
        interpret=interpret,
    )(pairs, rm4, rm4)
    return out.sum(axis=(1, 2))


def _topn_counts_kernel(rows_ref, src_ref, out_ref):
    s, k = pl.program_id(1), pl.program_id(2)

    @pl.when((s == 0) & (k == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    inter = rows_ref[0] & src_ref[0][None]  # [r_c, c_sub, 128]
    pc = lax.population_count(inter).astype(jnp.int32)
    r, c_sub, _ = pc.shape
    out_ref[...] = out_ref[...] + pc.reshape(r, c_sub // 8, 8, _LANES).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_topn_counts(row_matrix, src, interpret: bool = False):
    """|row & src| for EVERY row over every slice — TopN's candidate
    scoring phase when the whole row set is scored (fragment.go:493-625's
    device half).

    row_matrix: [S, R, W] or tiled [S, R, W/128, 128]; src: [S, W] or
    tiled [S, W/128, 128].  Returns int32[R].  One auto-pipelined pass
    over the matrix in ~2 MB blocks (near-roofline HBM streaming) with
    the per-row-chunk accumulator tile resident in VMEM — the jnp
    broadcast form ran at 9% of roofline on this shape (BASELINE.md
    round-3 note).  The row axis is chunked too (outermost grid axis, so
    the accumulator block stays resident across its (slice, word-chunk)
    reduction): tall row sets would otherwise need an over-VMEM block.
    """
    rm4 = _rm4(row_matrix)
    if src.ndim == 2:
        src = src.reshape(src.shape[0], src.shape[1] // _LANES, _LANES)
    n_slices, n_rows, sub = rm4.shape[:3]
    budget = 4 * 1024 * 1024
    # Row chunk: halve (stays a divisor of R) until the minimal
    # (r_c, 8, 128) input block + (r_c, 8, 128) accumulator fit.
    r_c = n_rows
    while r_c > 1 and r_c % 2 == 0 and 2 * r_c * 8 * _LANES * 4 > budget:
        r_c //= 2
    c_sub = 8
    c = 8
    while c <= sub:
        if sub % c == 0 and r_c * c * _LANES * 4 + r_c * 8 * _LANES * 4 <= budget:
            c_sub = c
        c *= 2
    n_chunks = sub // c_sub
    out = pl.pallas_call(
        _topn_counts_kernel,
        grid=(n_rows // r_c, n_slices, n_chunks),
        in_specs=[
            pl.BlockSpec((1, r_c, c_sub, _LANES), lambda r, s, k: (s, r, k, 0)),
            pl.BlockSpec((1, c_sub, _LANES), lambda r, s, k: (s, k, 0)),
        ],
        out_specs=pl.BlockSpec((r_c, 8, _LANES), lambda r, s, k: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, 8, _LANES), jnp.int32),
        interpret=interpret,
    )(rm4, src)
    return out.sum(axis=(1, 2))


def _gather_src_counts_kernel(pos_ref, row_ref, src_ref, out_ref):
    out_ref[0, 0] = _partial_tile((row_ref[0, 0] & src_ref[0])[None])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_gather_src_counts(row_matrix, pos, src_stack, interpret: bool = False):
    """Per-(slice, candidate) ``|rm[s, pos[k]] & src[s]|`` in ONE launch —
    TopN's candidate scoring across every slice at once
    (fragment.go:493-625's Src.IntersectionCount phase, cross-slice
    fused; the per-(slice, chunk) dispatch this replaces paid one launch
    per slice).

    row_matrix: uint32[S, R, W] (or tiled 4D); pos: int32[K] candidate
    row slots; src_stack: uint32[S, W] (or tiled [S, W/128, 128]).
    Returns int32[S, K].
    """
    rm4 = _rm4(row_matrix)
    n_slices, n_rows, sub = rm4.shape[:3]
    if src_stack.ndim == 2:
        src_stack = src_stack.reshape(n_slices, sub, _LANES)
    k = pos.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k, n_slices),
        in_specs=[
            pl.BlockSpec((1, 1, sub, _LANES), lambda q, s, pr: (s, pr[q], 0, 0)),
            pl.BlockSpec((1, sub, _LANES), lambda q, s, pr: (s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 8, _LANES), lambda q, s, pr: (q, s, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_src_counts_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k, n_slices, 8, _LANES), jnp.int32),
        interpret=interpret,
    )(pos, rm4, src_stack)
    return out.sum(axis=(2, 3)).T  # [S, K]


def _gather_rowmajor_kernel(op, depth, pairs_ref, rm_ref, out_ref, buf, sems):
    q = pl.program_id(0)
    n_q = pl.num_programs(0)

    def dma(i, o):
        # Whole row (ALL slices) in ONE descriptor: rm is row-major
        # [R, S, sub, 128], so rm[r] is a single contiguous S*W*4-byte
        # region.  The v5e DMA engine spends ~1 us of serial processing
        # per descriptor regardless of size (measured; BASELINE.md
        # round-3 note), so fewer/bigger transfers are the whole game:
        # per-(query, slice) 128 KB descriptors cap well under 20% of HBM
        # bandwidth, one 512 KB descriptor per operand reaches ~40%, 2 MB
        # reaches ~76%.
        return pltpu.make_async_copy(
            rm_ref.at[pairs_ref[i, o]], buf.at[i % depth, o], sems.at[i % depth, o]
        )

    @pl.when(q == 0)
    def _():
        for d in range(depth - 1):
            for o in range(2):
                dma(d, o).start()

    @pl.when(q + depth - 1 < n_q)
    def _():
        for o in range(2):
            dma(q + depth - 1, o).start()

    for o in range(2):
        dma(q, o).wait()
    a = buf[q % depth, 0]
    b = buf[q % depth, 1]
    pc = lax.population_count(_op_apply(op, a, b)).astype(jnp.int32)
    s, sub, _ = pc.shape
    out_ref[0] = pc.reshape(s * sub // 8, 8, _LANES).sum(axis=0)


@functools.partial(jax.jit, static_argnames=("op", "depth", "interpret"))
@jax.named_scope("gather.count")  # the pair kernels' name in a device trace
def fused_gather_count2_rowmajor(
    op: str, row_major, pairs, depth: int = 2, interpret: bool = False
):
    """Pair counts over a ROW-MAJOR tiled matrix uint32[R, S, W/128, 128].

    The gather regime's fast path for working sets too tall for the
    resident kernel: one hand-pipelined DMA per (query, operand) moves the
    operand row across ALL slices in a single contiguous descriptor, with
    ``depth`` queries in flight.  The slice-major form's per-(query,
    slice) descriptors bound that kernel by the DMA engine's serial
    descriptor rate, not HBM bandwidth (see _gather_rowmajor_kernel);
    row-major storage trades the slice-sharding-friendly axis order for
    descriptor-rate relief — callers that keep matrices slice-sharded on
    a mesh stay on :func:`fused_gather_count2`.

    pairs: int32[B, 2].  Returns int32[B].  VMEM: 2*depth row buffers
    (depth*2*S*W*4 bytes) — callers bound S*W accordingly.
    """
    n_rows, n_slices, sub = row_major.shape[:3]
    b = pairs.shape[0]
    # A pipeline deeper than the batch would start DMAs for queries past
    # the id array (and never wait on them — outstanding copies at kernel
    # exit corrupt or hang real hardware).
    depth = max(1, min(depth, b))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 8, _LANES), lambda q, pr: (q, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((depth, 2, n_slices, sub, _LANES), jnp.uint32),
            pltpu.SemaphoreType.DMA((depth, 2)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_gather_rowmajor_kernel, op, depth),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 8, _LANES), jnp.int32),
        interpret=interpret,
    )(pairs, row_major)
    return out.sum(axis=(1, 2))


def _gather_multi_rowmajor_kernel(op, n_ops, depth, idx_ref, rm_ref, out_ref, buf, sems):
    q = pl.program_id(0)
    n_q = pl.num_programs(0)
    fold = _FOLD_OPS[op]

    def dma(i, j):
        return pltpu.make_async_copy(
            rm_ref.at[idx_ref[i, j]], buf.at[i % depth, j], sems.at[i % depth, j]
        )

    @pl.when(q == 0)
    def _():
        for d in range(depth - 1):
            for j in range(n_ops):
                dma(d, j).start()

    @pl.when(q + depth - 1 < n_q)
    def _():
        for j in range(n_ops):
            dma(q + depth - 1, j).start()

    for j in range(n_ops):
        dma(q, j).wait()
    acc = buf[q % depth, 0]
    for j in range(1, n_ops):
        acc = fold(acc, buf[q % depth, j])
    pc = lax.population_count(acc).astype(jnp.int32)
    s, sub, _ = pc.shape
    out_ref[0] = pc.reshape(s * sub // 8, 8, _LANES).sum(axis=0)


@functools.partial(jax.jit, static_argnames=("op", "depth", "interpret"))
def fused_gather_count_multi_rowmajor(
    op: str, row_major, idx, depth: int = 2, interpret: bool = False
):
    """Left-fold counts over a ROW-MAJOR matrix [R, S, W/128, 128]: the
    K-operand form of :func:`fused_gather_count2_rowmajor` (N-ary
    Intersect/Union/Difference and fused Range view covers in the
    streaming gather regime).  One contiguous DMA descriptor per
    (query, operand); idx: int32[B, K] padded with fold-idempotent ids.
    VMEM: depth*K row buffers — callers bound K * S * W * 4."""
    n_rows, n_slices, sub = row_major.shape[:3]
    b, n_ops = idx.shape
    depth = max(1, min(depth, b))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 8, _LANES), lambda q, pr: (q, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((depth, n_ops, n_slices, sub, _LANES), jnp.uint32),
            pltpu.SemaphoreType.DMA((depth, n_ops)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_gather_multi_rowmajor_kernel, op, n_ops, depth),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 8, _LANES), jnp.int32),
        interpret=interpret,
    )(idx, row_major)
    return out.sum(axis=(1, 2))


# Left-fold step for the multi-operand gather kernels: how operand j>0
# combines into the accumulator.  "andnot" folds acc &~ row (Difference's
# left-associative chain); all are pad-idempotent for the right pad id
# (and/or: any repeated operand; andnot: repeat any NON-first operand).
_FOLD_OPS = {
    "and": lambda acc, row: acc & row,
    "or": lambda acc, row: acc | row,
    "andnot": lambda acc, row: acc & ~row,
}


def _gather_multi_kernel(op, n_ops, idx_ref, row_ref, out_ref, acc_ref):
    s, j = pl.program_id(1), pl.program_id(2)
    fold = _FOLD_OPS[op]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = row_ref[0, 0]

    @pl.when(j != 0)
    def _():
        acc_ref[...] = fold(acc_ref[...], row_ref[0, 0])

    @pl.when((j == n_ops - 1) & (s == 0))
    def _():
        out_ref[0] = _partial_tile(acc_ref[...][None])

    @pl.when((j == n_ops - 1) & (s != 0))
    def _():
        out_ref[0] = out_ref[0] + _partial_tile(acc_ref[...][None])


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def fused_gather_count_multi(op: str, row_matrix, idx, interpret: bool = False):
    """Per-query ``sum_s popcount(fold_j rm[s, idx[q, j]])`` for a
    left-fold of up to K gathered rows per query — the fused form of
    Count over N-operand Intersect/Union/Difference trees AND the
    time-quantum Range view cover (op="or").

    row_matrix: uint32[n_slices, n_rows, W] (W % 1024 == 0);
    idx: int32[B, K] row ids; short operand lists pad with an id whose
    repeat is a no-op for the fold (and/or: any operand; andnot: any
    non-first operand).  Returns int32[B].

    One row DMA per (query, slice, operand) grid step folds into a VMEM
    scratch accumulator; at the last operand the accumulated result is
    popcounted into the per-query output tile, which stays resident
    across the slice axis.  The XLA fallback materializes the whole
    [S, B, K, W] gather in HBM first.
    """
    rm4 = _rm4(row_matrix)
    n_slices, n_rows, sub = rm4.shape[:3]
    b, n_ops = idx.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_slices, n_ops),
        in_specs=[
            pl.BlockSpec((1, 1, sub, _LANES), lambda q, s, j, pr: (s, pr[q, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 8, _LANES), lambda q, s, j, pr: (q, 0, 0)),
        scratch_shapes=[pltpu.VMEM((sub, _LANES), jnp.uint32)],
    )
    out = pl.pallas_call(
        functools.partial(_gather_multi_kernel, op, n_ops),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 8, _LANES), jnp.int32),
        interpret=interpret,
    )(idx, rm4)
    return out.sum(axis=(1, 2))


def fused_gather_count_or(row_matrix, idx, interpret: bool = False):
    """OR-fold convenience wrapper (the fused Range cover count)."""
    return fused_gather_count_multi("or", row_matrix, idx, interpret=interpret)


def _gather_tree_kernel(k, leaves_ref, opc_ref, row_ref, out_ref, buf_ref):
    from pilosa_tpu.ops.bitwise import tree_select

    q, s, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    buf_ref[j] = row_ref[0, 0]

    def fold():
        vals = [buf_ref[t] for t in range(k)]
        off = 0
        n = k // 2
        while n >= 1:
            vals = [
                tree_select(opc_ref[q, off + t], vals[2 * t], vals[2 * t + 1])
                for t in range(n)
            ]
            off += n
            n //= 2
        return _partial_tile(vals[0][None])

    @pl.when((j == k - 1) & (s == 0))
    def _():
        out_ref[0] = fold()

    @pl.when((j == k - 1) & (s != 0))
    def _():
        out_ref[0] = out_ref[0] + fold()


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_gather_count_tree(row_matrix, leaves, opc, interpret: bool = False):
    """Per-query ``sum_s popcount(tree(rows))`` for an ARBITRARY nested
    expression tree per query — the fused form of Count over any nesting
    of Intersect/Union/Xor/Difference (executor.go:261-276's uniform
    call-tree evaluation, one kernel launch for the whole batch).

    row_matrix: uint32[n_slices, n_rows, W] (or tiled 4D);
    leaves: int32[B, K] row ids of a PERFECT binary tree (K = 2^D);
    opc: int32[B, K-1] node opcodes level-major bottom-up
    (bitwise.gather_count_tree documents the encoding; TREE_PASS pads).
    Returns int32[B].

    One row DMA per (query, slice, leaf) grid step lands in a VMEM leaf
    buffer; at the last leaf the whole fold (statically unrolled — K is
    small) runs in VMEM and accumulates into the per-query output tile,
    which stays resident across the slice axis.  Per-node opcodes are
    scalar-prefetched, so one compiled kernel serves every tree shape of
    the same depth bucket.
    """
    rm4 = _rm4(row_matrix)
    n_slices, n_rows, sub = rm4.shape[:3]
    b, k = leaves.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_slices, k),
        in_specs=[
            pl.BlockSpec(
                (1, 1, sub, _LANES), lambda q, s, j, lv, oc: (s, lv[q, j], 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 8, _LANES), lambda q, s, j, lv, oc: (q, 0, 0)),
        scratch_shapes=[pltpu.VMEM((k, sub, _LANES), jnp.uint32)],
    )
    out = pl.pallas_call(
        functools.partial(_gather_tree_kernel, k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 8, _LANES), jnp.int32),
        interpret=interpret,
    )(leaves, opc, rm4)
    return out.sum(axis=(1, 2))


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_count1(a, interpret: bool = False):
    """sum(popcount(a)) over the last axis via a Pallas kernel."""
    shape = a.shape
    w = shape[-1]
    m = 1
    for d in shape[:-1]:
        m *= d
    sub = w // _LANES
    a3 = a.reshape(m, sub, _LANES)
    out = pl.pallas_call(
        _count1_kernel,
        out_shape=jax.ShapeDtypeStruct((m, 8, _LANES), jnp.int32),
        grid=(m,),
        in_specs=[pl.BlockSpec((1, sub, _LANES), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, 8, _LANES), lambda i: (i, 0, 0)),
        interpret=interpret,
    )(a3)
    return out.sum(axis=(1, 2)).reshape(shape[:-1])
