"""Backend dispatch: Pallas kernels on TPU, jnp/XLA elsewhere.

The analog of the reference's init-time CPUID gate
(roaring/assembly_asm.go:17-23: use asm if POPCNT is available, else the Go
SWAR fallback).  Here the "feature detect" is the JAX default backend; the
jnp path also serves TPU-less CI (tests force JAX_PLATFORMS=cpu).

Set ``PILOSA_TPU_NO_PALLAS=1`` (or ``true``) to force the jnp path on TPU;
the variable is read on every call so it can be toggled for benchmarking.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from pilosa_tpu.ops import bitwise
from pilosa_tpu.ops.pallas_kernels import (
    _tileable,
    fused_count1,
    fused_count2,
    fused_gather_count2,
    fused_gather_count_multi,
    fused_resident_count2,
    resident_strategy,
    rm_words,
)


def _rm_dims(row_matrix) -> tuple[int, int, int]:
    """(n_slices, n_rows, W) of a row matrix in 3D logical or 4D tiled
    form (see pallas_kernels._rm4)."""
    return row_matrix.shape[0], row_matrix.shape[1], rm_words(row_matrix)


def _rm3(row_matrix):
    """Logical [S, R, W] view (the jnp/numpy fallbacks and the Gram path
    index the word axis flat).  On TPU this reshape materializes a tiled
    relayout copy inside jit — callers only use it off the kernel path."""
    if row_matrix.ndim == 3:
        return row_matrix
    s, r = row_matrix.shape[:2]
    return row_matrix.reshape(s, r, -1)


@functools.lru_cache(maxsize=None)
def _backend_is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas() -> bool:
    # analysis-ok: lockstep-determinism: deployment config, launcher sets identical env on every rank
    if os.environ.get("PILOSA_TPU_NO_PALLAS", "").lower() in ("1", "true", "yes"):
        return False
    return _backend_is_tpu()


def count(x):
    if use_pallas() and _tileable(x.shape[-1]):
        return fused_count1(x)
    return bitwise.count(x)


def count_and(a, b):
    if use_pallas() and _tileable(a.shape[-1]):
        return fused_count2("and", a, b)
    return bitwise.count_and(a, b)


def count_or(a, b):
    if use_pallas() and _tileable(a.shape[-1]):
        return fused_count2("or", a, b)
    return bitwise.count_or(a, b)


def count_xor(a, b):
    if use_pallas() and _tileable(a.shape[-1]):
        return fused_count2("xor", a, b)
    return bitwise.count_xor(a, b)


def count_andnot(a, b):
    if use_pallas() and _tileable(a.shape[-1]):
        return fused_count2("andnot", a, b)
    return bitwise.count_andnot(a, b)


def gather_count_and(row_matrix, pairs):
    """Batched Count(Intersect(...)) over a [n_slices, n_rows, W] row
    matrix for int32[B, 2] row-id pairs — the headline query hot path."""
    return gather_count("and", row_matrix, pairs)


# Gram strategy gate: all-pairs count work may exceed the requested batch
# by this factor before the MXU path stops paying off; one SLICE's
# unpacked int8 bits must fit a transient-HBM budget (the chunked builder
# streams slice by slice — see bitwise.pair_gram), and per-pair counts
# must stay inside int32 (≤ 2047 slices × 2^20 bits).
_GRAM_FACTOR = 16
_GRAM_BYTES_BUDGET = 1536 * 1024 * 1024
_GRAM_SLICES_MAX = 2047


def _use_gram(n_slices: int, n_rows: int, w: int, batch: int) -> bool:
    # analysis-ok: lockstep-determinism: deployment config, launcher sets identical env on every rank
    if os.environ.get("PILOSA_TPU_NO_GRAM", "").lower() in ("1", "true", "yes"):  # analysis-ok: env-knob-outside-config: kernel-layer kill switch shared with non-server embedders
        return False
    return (
        n_rows * n_rows <= _GRAM_FACTOR * batch
        and n_rows * w * 32 <= _GRAM_BYTES_BUDGET
        and n_slices <= _GRAM_SLICES_MAX
    )


# The Pallas kernels scalar-prefetch the pair ids into SMEM (~1 MiB);
# large batches are evaluated in chunks of this many queries.
_GATHER_BATCH_MAX = 1024


def gather_count(op, row_matrix, pairs, allow_gram: bool = True):
    """Batched Count(<op>(Bitmap, Bitmap)) — and/or/xor/andnot (the
    fused forms of Intersect/Union/Xor/Difference count batches).

    ``allow_gram=False`` skips the all-pairs MXU strategy — callers that
    manage their own Gram cache (the executor) or dispatch eagerly
    per-call want the cheaper direct kernels; the Gram branch pays off
    inside jitted query streams where XLA hoists it out of the loop.

    ``row_matrix`` may be 3D logical [S, R, W] or 4D tiled
    [S, R, W/128, 128] (the jax engines' relayout-free storage form)."""
    n_slices, n_rows, w = _rm_dims(row_matrix)
    # Matmul Gram strategy for tiny row sets: one int8 matmul computes ALL
    # pair counts; per-query answers are lookups.  Pure HLO on the row
    # matrix only (no Pallas dependency — any jax backend), so XLA hoists
    # it out of jitted query streams.
    if allow_gram and _use_gram(n_slices, n_rows, w, pairs.shape[0]):
        return bitwise.gram_pair_counts(op, bitwise.pair_gram(row_matrix), pairs)
    if use_pallas() and _tileable(w):
        b = pairs.shape[0]
        if b > _GATHER_BATCH_MAX:
            # Chunk oversized batches: the prefetched pair ids must fit
            # SMEM (observed hard failure at B=4096 on v5e).
            return jnp.concatenate(
                [
                    gather_count(
                        op, row_matrix, pairs[i : i + _GATHER_BATCH_MAX], allow_gram=False
                    )
                    for i in range(0, b, _GATHER_BATCH_MAX)
                ]
            )
        # Resident kernel wins whenever streaming ALL rows once beats
        # gathering 2 rows per query (shared predicate with the mesh
        # tier); otherwise fall back to the per-query gather.
        if resident_strategy(n_rows, w, b):
            return fused_resident_count2(op, row_matrix, pairs)
        return fused_gather_count2(op, row_matrix, pairs)
    return bitwise.gather_count(op, _rm3(row_matrix), pairs)


# Row-major kernel VMEM budget: depth(2) * k row buffers of S*W*4 bytes
# each must fit alongside the output tiles (~16 MB VMEM/core).
_ROWMAJOR_BUF_BYTES_MAX = 8 * 1024 * 1024


def rowmajor_ok(n_slices: int, w: int, k: int = 2) -> bool:
    """Whether the pipelined row-major gather kernels can buffer k
    operand rows of this width per pipeline slot (callers use it to
    decide the transient-matrix layout)."""
    return 2 * k * n_slices * w * 4 <= _ROWMAJOR_BUF_BYTES_MAX


def gather_count_rowmajor(op, row_major, pairs):
    """Batched pair counts over a ROW-MAJOR matrix [R, S, W] (3D logical)
    or [R, S, W/128, 128] (tiled): one contiguous DMA descriptor per
    operand covering every slice — the gather regime's fast path (v5e
    DMA descriptors process serially, so per-(query, slice) block DMAs
    cap well below roofline; see fused_gather_count2_rowmajor)."""
    from pilosa_tpu.ops.pallas_kernels import fused_gather_count2_rowmajor

    n_rows, n_slices = row_major.shape[:2]
    w = row_major.shape[-1] if row_major.ndim == 3 else row_major.shape[-2] * row_major.shape[-1]
    if use_pallas() and _tileable(w) and rowmajor_ok(n_slices, w):
        if row_major.ndim == 3:
            row_major = row_major.reshape(n_rows, n_slices, w // 128, 128)
        b = pairs.shape[0]
        if b > _GATHER_BATCH_MAX:
            return jnp.concatenate(
                [
                    fused_gather_count2_rowmajor(
                        op, row_major, pairs[i : i + _GATHER_BATCH_MAX]
                    )
                    for i in range(0, b, _GATHER_BATCH_MAX)
                ]
            )
        return fused_gather_count2_rowmajor(op, row_major, pairs)
    # Fallback: logical transpose to slice-major (non-TPU backends and
    # shapes the kernel can't buffer; engines gate the lane on
    # use_pallas() so the product path only lands here for oversized
    # rows).
    rm = _rm3(row_major) if row_major.ndim == 4 else row_major
    return bitwise.gather_count(op, jnp.swapaxes(rm, 0, 1), pairs)


def gather_count_multi_rowmajor(op, row_major, idx):
    """K-operand fold counts over a ROW-MAJOR matrix — the multi form of
    :func:`gather_count_rowmajor` (N-ary trees and Range covers in the
    streaming gather regime).  Buffers K rows per pipeline slot, so the
    row-width bound shrinks with K."""
    from pilosa_tpu.ops.pallas_kernels import fused_gather_count_multi_rowmajor

    n_rows, n_slices = row_major.shape[:2]
    w = row_major.shape[-1] if row_major.ndim == 3 else row_major.shape[-2] * row_major.shape[-1]
    b, k = idx.shape
    if use_pallas() and _tileable(w) and rowmajor_ok(n_slices, w, k):
        if row_major.ndim == 3:
            row_major = row_major.reshape(n_rows, n_slices, w // 128, 128)
        chunk = max(1, (2 * _GATHER_BATCH_MAX) // max(1, k))
        if b > chunk:
            return jnp.concatenate(
                [
                    fused_gather_count_multi_rowmajor(op, row_major, idx[i : i + chunk])
                    for i in range(0, b, chunk)
                ]
            )
        return fused_gather_count_multi_rowmajor(op, row_major, idx)
    rm = _rm3(row_major) if row_major.ndim == 4 else row_major
    return gather_count_multi(op, jnp.swapaxes(rm, 0, 1), idx)


def gather_count_multi(op, row_matrix, idx):
    """Batched Count over a left-fold of K gathered rows per query —
    N-operand Intersect/Union/Difference trees and the fused Range view
    cover (op="or").  idx: int32[B, K], padded with fold-idempotent
    ids (and/or: any operand; andnot: any non-first operand)."""
    b, k = idx.shape
    if use_pallas() and _tileable(rm_words(row_matrix)):
        # Prefetched ids must fit SMEM: the pair kernels prefetch B*2 ids
        # under _GATHER_BATCH_MAX, so bound B*K by the same id budget
        # (wide operand lists shrink the per-chunk batch).
        chunk = max(1, (2 * _GATHER_BATCH_MAX) // max(1, k))
        if b > chunk:
            return jnp.concatenate(
                [
                    gather_count_multi(op, row_matrix, idx[i : i + chunk])
                    for i in range(0, b, chunk)
                ]
            )
        return fused_gather_count_multi(op, row_matrix, idx)
    # XLA fallback materializes the gather: bound its transient HBM/host
    # footprint by chunking the batch (shared sizing helper).
    from pilosa_tpu.pilosa import OR_MULTI_BUDGET_DEVICE, or_multi_chunk_size

    s, _, w = _rm_dims(row_matrix)
    rm = _rm3(row_matrix)
    chunk = or_multi_chunk_size(s, k, w, OR_MULTI_BUDGET_DEVICE)
    if b > chunk:
        return jnp.concatenate(
            [
                bitwise.gather_count_multi(op, rm, idx[i : i + chunk])
                for i in range(0, b, chunk)
            ]
        )
    return bitwise.gather_count_multi(op, rm, idx)


def gather_count_or_multi(row_matrix, idx):
    """OR-fold convenience wrapper (the fused Range cover count)."""
    return gather_count_multi("or", row_matrix, idx)


def gather_count_tree(row_matrix, leaves, opc):
    """Batched Count over ARBITRARY nested expression trees — one
    dispatch per batch (executor.go:261-276 fused).  leaves: int32[B, K]
    (K = 2^D perfect-tree row ids); opc: int32[B, K-1] level-major
    bottom-up opcodes (see bitwise.gather_count_tree)."""
    from pilosa_tpu.ops.pallas_kernels import fused_gather_count_tree

    b, k = leaves.shape
    if use_pallas() and _tileable(rm_words(row_matrix)):
        # Prefetched ids per query: K leaves + K-1 opcodes ~ 2K — bound
        # by the same SMEM id budget as the pair/multi kernels.
        chunk = max(1, (2 * _GATHER_BATCH_MAX) // max(1, 2 * k - 1))
        if b > chunk:
            return jnp.concatenate(
                [
                    fused_gather_count_tree(
                        row_matrix, leaves[i : i + chunk], opc[i : i + chunk]
                    )
                    for i in range(0, b, chunk)
                ]
            )
        return fused_gather_count_tree(row_matrix, leaves, opc)
    # XLA fallback materializes the [S, chunk, K, W] gather: bound the
    # transient like the multi fallback does.
    from pilosa_tpu.pilosa import OR_MULTI_BUDGET_DEVICE, or_multi_chunk_size

    s, _, w = _rm_dims(row_matrix)
    rm = _rm3(row_matrix)
    chunk = or_multi_chunk_size(s, k, w, OR_MULTI_BUDGET_DEVICE)
    if b > chunk:
        return jnp.concatenate(
            [
                bitwise.gather_count_tree(rm, leaves[i : i + chunk], opc[i : i + chunk])
                for i in range(0, b, chunk)
            ]
        )
    return bitwise.gather_count_tree(rm, leaves, opc)


def topn_scorer_counts(row_matrix, pos, src_stack):
    """Per-(slice, candidate) intersection counts |rm[s, pos[k]] & src[s]|
    in one dispatch (int32[S, K]) — TopN candidate scoring across every
    slice at once.  Pallas on TPU; jnp per-slice fallback elsewhere (the
    fallback's whole-gather transient is bounded by looping slices)."""
    from pilosa_tpu.ops.pallas_kernels import fused_gather_src_counts
    from pilosa_tpu.pilosa import OR_MULTI_BUDGET_DEVICE

    n_slices, _, w = _rm_dims(row_matrix)
    if use_pallas() and _tileable(w):
        k = pos.shape[0]
        # The kernel's HBM partial-tile output is k * S * 4096 bytes
        # (summed on the XLA side), so the per-dispatch candidate chunk
        # must shrink with the slice count — a fixed k-chunk at
        # thousand-slice shapes would materialize a multi-GB transient
        # (the round-2 OOM class).
        chunk = max(1, min(
            _GATHER_BATCH_MAX,
            OR_MULTI_BUDGET_DEVICE // max(1, n_slices * 8 * 128 * 4),
        ))
        if k > chunk:
            # Pad the ragged tail to the chunk size (pad scores are
            # sliced off) so every dispatch shares ONE jitted shape.
            if k % chunk:
                pad = chunk - (k % chunk)
                pos = jnp.concatenate([pos, jnp.broadcast_to(pos[:1], (pad,))])
            out = jnp.concatenate(
                [
                    fused_gather_src_counts(
                        row_matrix, pos[i : i + chunk], src_stack
                    )
                    for i in range(0, pos.shape[0], chunk)
                ],
                axis=1,
            )
            return out[:, :k]
        return fused_gather_src_counts(row_matrix, pos, src_stack)
    rm = _rm3(row_matrix)
    if src_stack.ndim == 3:
        src_stack = src_stack.reshape(n_slices, -1)
    outs = [
        jnp.sum(
            jax.lax.population_count(
                jnp.take(rm[s], pos, axis=0) & src_stack[s][None]
            ).astype(jnp.int32),
            axis=-1,
        )
        for s in range(n_slices)
    ]
    return jnp.stack(outs)


def batch_intersection_count(rows, src, tiled: bool = False):
    """|rows[k] & src| for a stack of rows — TopN's exact-count hot loop.

    On TPU this streams the single src block through the fused Pallas
    kernel (no K-way broadcast in HBM).  ``tiled=True``: rows/src carry
    the word axis as trailing [W/128, 128] dims (rows sliced from a 4D
    engine matrix — no relayout on the way in).
    """
    if tiled:
        if use_pallas() and _tileable(rows.shape[-2] * rows.shape[-1]):
            return fused_count2("and", rows, src, tiled=True)
        rows = rows.reshape(*rows.shape[:-2], -1)
        src = src.reshape(*src.shape[:-2], -1)
        return bitwise.batch_intersection_count(rows, src)
    if use_pallas() and rows.ndim >= 2 and _tileable(rows.shape[-1]):
        return fused_count2("and", rows, src)
    return bitwise.batch_intersection_count(rows, src)
