"""Kernel-layer property tests: JAX ops vs numpy ground truth.

The analog of the reference's asm-vs-Go equivalence tests
(roaring/assembly_test.go): every fused count kernel must agree with a
straightforward numpy popcount reference on random inputs.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from pilosa_tpu.ops import (
    WORDS_PER_SLICE,
    bit_and,
    bit_or,
    bit_xor,
    bit_andnot,
    count,
    count_and,
    count_or,
    count_xor,
    count_andnot,
    batch_intersection_count,
    make_range_mask,
    pack_positions,
    unpack_positions,
)
from pilosa_tpu.ops import bitwise as bw
from pilosa_tpu.ops import dispatch
from pilosa_tpu.pilosa import SLICE_WIDTH

W = 1024  # small word count for speed; tileable (1024 = 8*128)


def rand_words(rng, shape):
    return rng.integers(0, 1 << 32, size=shape, dtype=np.uint32)


@pytest.mark.parametrize("seed", range(5))
def test_counts_match_numpy(seed):
    rng = np.random.default_rng(seed)
    a = rand_words(rng, (W,))
    b = rand_words(rng, (W,))
    assert int(count(jnp.asarray(a))) == bw.np_count(a)
    assert int(count_and(jnp.asarray(a), jnp.asarray(b))) == bw.np_count_and(a, b)
    assert int(count_or(jnp.asarray(a), jnp.asarray(b))) == bw.np_count_or(a, b)
    assert int(count_xor(jnp.asarray(a), jnp.asarray(b))) == bw.np_count_xor(a, b)
    assert int(count_andnot(jnp.asarray(a), jnp.asarray(b))) == bw.np_count_andnot(a, b)


def test_elementwise_ops(rng):
    a = rand_words(rng, (W,))
    b = rand_words(rng, (W,))
    np.testing.assert_array_equal(np.asarray(bit_and(jnp.asarray(a), jnp.asarray(b))), a & b)
    np.testing.assert_array_equal(np.asarray(bit_or(jnp.asarray(a), jnp.asarray(b))), a | b)
    np.testing.assert_array_equal(np.asarray(bit_xor(jnp.asarray(a), jnp.asarray(b))), a ^ b)
    np.testing.assert_array_equal(np.asarray(bit_andnot(jnp.asarray(a), jnp.asarray(b))), a & ~b)


def test_batched_counts(rng):
    a = rand_words(rng, (7, W))
    b = rand_words(rng, (7, W))
    got = np.asarray(count_and(jnp.asarray(a), jnp.asarray(b)))
    want = np.array([bw.np_count_and(a[i], b[i]) for i in range(7)])
    np.testing.assert_array_equal(got, want)


def test_batch_intersection_count(rng):
    rows = rand_words(rng, (5, W))
    src = rand_words(rng, (W,))
    got = np.asarray(batch_intersection_count(jnp.asarray(rows), jnp.asarray(src)))
    want = np.array([bw.np_count_and(rows[i], src) for i in range(5)])
    np.testing.assert_array_equal(got, want)


def test_dispatch_layer(rng):
    # On CPU CI this exercises the jnp fallback path of the dispatcher.
    a = rand_words(rng, (W,))
    b = rand_words(rng, (W,))
    assert int(dispatch.count(jnp.asarray(a))) == bw.np_count(a)
    assert int(dispatch.count_and(jnp.asarray(a), jnp.asarray(b))) == bw.np_count_and(a, b)


@pytest.mark.parametrize(
    "start,end",
    [(0, 0), (0, 32), (5, 9), (0, SLICE_WIDTH), (31, 33), (64, 64), (100, 1000), (SLICE_WIDTH - 1, SLICE_WIDTH)],
)
def test_make_range_mask(start, end):
    mask = make_range_mask(start, end)
    got = set(unpack_positions(mask).tolist())
    want = set(range(start, end))
    assert got == want


@pytest.mark.parametrize("seed", range(3))
def test_pack_unpack_roundtrip(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(0, 5000))
    pos = np.unique(rng.integers(0, SLICE_WIDTH, size=n, dtype=np.uint64))
    words = pack_positions(pos)
    back = unpack_positions(words)
    np.testing.assert_array_equal(back, pos)
    assert bw.np_count(words) == len(pos)


def test_gather_count_and_matches_numpy(rng):
    # Batched Count(Intersect(r1, r2)) over a row matrix — the headline
    # query path (executor.go:576-605 analog), jnp/XLA form.
    n_slices, n_rows, batch = 3, 7, 11
    rm = rand_words(rng, (n_slices, n_rows, W))
    pairs = rng.integers(0, n_rows, size=(batch, 2)).astype(np.int32)
    got = np.asarray(dispatch.gather_count_and(jnp.asarray(rm), jnp.asarray(pairs)))
    want = np.array(
        [
            sum(bw.np_count_and(rm[s, p0], rm[s, p1]) for s in range(n_slices))
            for p0, p1 in pairs
        ]
    )
    np.testing.assert_array_equal(got, want)


def test_pallas_partial_tile_math(rng):
    # The kernel body's reduction (`_partial_tile`) is pure jnp — verify it on
    # CPU against numpy.  (Full kernels: interpret mode in
    # test_differential_kernels.py, the v5e compiler in test_tpu_compile.py,
    # the chip itself in chip_smoke.py's kernels phase.)
    import jax
    from pilosa_tpu.ops import pallas_kernels as pk

    a = rand_words(rng, (1, W // 128, 128))
    tile = np.asarray(pk._partial_tile(jnp.asarray(a)))
    assert tile.shape == (8, 128)
    assert int(tile.sum()) == bw.np_count(a)


def test_validate_names():
    from pilosa_tpu.pilosa import validate_name, validate_label, ErrName, ErrLabel

    validate_name("a" * 65)
    validate_name("my-index_0")
    for bad in ("myindex\n", "A", "9x", "a" * 66, ""):
        with pytest.raises(ErrName):
            validate_name(bad)
    validate_label("ColumnID")
    with pytest.raises(ErrLabel):
        validate_label("col\n")


@pytest.mark.parametrize("op", ["and", "or", "xor", "andnot"])
def test_pair_gram_identities(rng, op):
    """The AND-Gram + count identities reproduce every pair op's counts
    (the MXU all-pairs strategy; exact int8->int32 accumulation)."""
    rm = rand_words(rng, (3, 6, W))
    pairs = rng.integers(0, 6, size=(9, 2)).astype(np.int32)
    G = np.asarray(bw.pair_gram(jnp.asarray(rm)))
    got = np.asarray(bw.gram_pair_counts(op, G, pairs))
    f = {"and": lambda a, b: a & b, "or": lambda a, b: a | b,
         "xor": lambda a, b: a ^ b, "andnot": lambda a, b: a & ~b}[op]
    want = np.array(
        [sum(bw.np_count(f(rm[s, p0], rm[s, p1])) for s in range(3)) for p0, p1 in pairs]
    )
    np.testing.assert_array_equal(got, want)


def test_gather_count_chunks_large_batches(rng, monkeypatch):
    """Batches beyond the SMEM prefetch budget are evaluated in chunks
    with identical results (observed hard failure at B=4096 on v5e).
    The Pallas gate is forced on and the kernels stubbed with the jnp
    forms so CI actually executes the chunk/concatenate logic."""
    from pilosa_tpu.ops.dispatch import _GATHER_BATCH_MAX

    chunk_sizes = []

    def fake_kernel(op, rm_, prs, interpret=False):
        chunk_sizes.append(int(prs.shape[0]))
        return bw.gather_count(op, rm_, prs)

    monkeypatch.setattr(dispatch, "use_pallas", lambda: True)
    monkeypatch.setattr(dispatch, "fused_gather_count2", fake_kernel)
    monkeypatch.setattr(dispatch, "fused_resident_count2", fake_kernel)

    n_slices, n_rows = 2, 5
    rm = rand_words(rng, (n_slices, n_rows, W))
    b = _GATHER_BATCH_MAX + 37
    pairs = rng.integers(0, n_rows, size=(b, 2)).astype(np.int32)
    got = np.asarray(
        dispatch.gather_count("and", jnp.asarray(rm), jnp.asarray(pairs), allow_gram=False)
    )
    assert got.shape == (b,)
    assert chunk_sizes == [_GATHER_BATCH_MAX, 37]  # chunking really ran
    for k in (0, _GATHER_BATCH_MAX - 1, _GATHER_BATCH_MAX, b - 1):
        p0, p1 = pairs[k]
        want = sum(bw.np_count_and(rm[s, p0], rm[s, p1]) for s in range(n_slices))
        assert got[k] == want


def test_gather_count_or_multi_matches_numpy(rng):
    # Fused time-quantum Range count: OR a per-query view cover, popcount,
    # sum over slices (time.go:95-167 + executor.go:498-554 analog).
    n_slices, n_rows, batch, vmax = 2, 9, 7, 4
    rm = rand_words(rng, (n_slices, n_rows, W))
    idx = rng.integers(0, n_rows, size=(batch, vmax)).astype(np.int32)
    # Short covers pad by repeating the first id (OR-idempotent).
    idx[0, 1:] = idx[0, 0]
    idx[1, 2:] = idx[1, 0]
    got = np.asarray(
        dispatch.gather_count_or_multi(jnp.asarray(rm), jnp.asarray(idx))
    )
    want = bw.np_gather_count_or_multi(rm, idx)
    np.testing.assert_array_equal(got, want)
    # Degenerate single-view cover equals a plain row count.
    one = np.asarray(
        dispatch.gather_count_or_multi(jnp.asarray(rm), jnp.asarray(idx[:, :1]))
    )
    want_one = np.array(
        [sum(bw.np_count(rm[s, idx[q, 0]]) for s in range(n_slices)) for q in range(batch)]
    )
    np.testing.assert_array_equal(one, want_one)


@pytest.mark.parametrize("op", ["and", "or", "andnot"])
def test_gather_count_multi_matches_numpy(rng, op):
    # N-operand fold counts (Count over 3+-operand Intersect/Union/
    # Difference trees) — jnp/XLA form vs numpy ground truth.
    n_slices, n_rows, batch, k = 2, 9, 6, 5
    rm = rand_words(rng, (n_slices, n_rows, W))
    idx = rng.integers(0, n_rows, size=(batch, k)).astype(np.int32)
    # Fold-idempotent padding: and/or repeat the first id, andnot a
    # non-first id.
    idx[0, 3:] = idx[0, 0] if op != "andnot" else idx[0, 1]
    got = np.asarray(
        dispatch.gather_count_multi(op, jnp.asarray(rm), jnp.asarray(idx))
    )
    want = bw.np_gather_count_multi(op, rm, idx)
    np.testing.assert_array_equal(got, want)


def test_fused_gather_count2_rowmajor_interpret(rng):
    """Row-major pipelined gather kernel (manual DMA double buffering) vs
    numpy ground truth, all four pair ops, interpret mode."""
    from pilosa_tpu.ops.pallas_kernels import fused_gather_count2_rowmajor

    S, R, W, B = 3, 40, 2048, 17
    rm = rng.integers(0, 1 << 32, size=(S, R, W), dtype=np.uint32)
    pairs = rng.integers(0, R, size=(B, 2), dtype=np.int32)
    rm_t = np.ascontiguousarray(rm.transpose(1, 0, 2)).reshape(R, S, W // 128, 128)
    for op in ("and", "or", "xor", "andnot"):
        got = np.asarray(
            fused_gather_count2_rowmajor(
                op, jnp.asarray(rm_t), jnp.asarray(pairs), interpret=True
            )
        )
        a = rm[:, pairs[:, 0], :]
        b = rm[:, pairs[:, 1], :]
        r = {"and": a & b, "or": a | b, "xor": a ^ b, "andnot": a & ~b}[op]
        want = bw.np_popcount(r).reshape(S, B, -1).sum(axis=(0, 2))
        assert np.array_equal(got, want), op


def test_gather_count_tiled_4d_matches_3d(rng):
    """4D tiled row matrices give identical results to 3D logical ones
    through the public dispatch entry points."""
    from pilosa_tpu.ops import dispatch

    S, R, W, B = 2, 12, 1024, 9
    rm = rng.integers(0, 1 << 32, size=(S, R, W), dtype=np.uint32)
    rm4 = rm.reshape(S, R, W // 128, 128)
    pairs = rng.integers(0, R, size=(B, 2), dtype=np.int32)
    idx = rng.integers(0, R, size=(B, 3), dtype=np.int32)
    for op in ("and", "or", "xor", "andnot"):
        a = np.asarray(dispatch.gather_count(op, jnp.asarray(rm), jnp.asarray(pairs)))
        b = np.asarray(dispatch.gather_count(op, jnp.asarray(rm4), jnp.asarray(pairs)))
        assert np.array_equal(a, b), op
    for op in ("and", "or", "andnot"):
        a = np.asarray(dispatch.gather_count_multi(op, jnp.asarray(rm), jnp.asarray(idx)))
        b = np.asarray(dispatch.gather_count_multi(op, jnp.asarray(rm4), jnp.asarray(idx)))
        assert np.array_equal(a, b), op


def test_pair_gram_chunked_matches_oneshot(rng):
    """The slice-streaming Gram builder (large matrices) must equal the
    one-shot unpack+matmul and the numpy ground truth, in both layouts."""
    S, R, W = 5, 9, 1024
    rm = rng.integers(0, 1 << 32, size=(S, R, W), dtype=np.uint32)
    g1 = np.asarray(bw.pair_gram(jnp.asarray(rm)))
    orig = bw.GRAM_ONESHOT_BYTES
    orig_step = bw.GRAM_STEP_BYTES
    bw.GRAM_ONESHOT_BYTES = 1  # force the scan path
    try:
        g2 = np.asarray(bw.pair_gram(jnp.asarray(rm)))
        g3 = np.asarray(bw.pair_gram(jnp.asarray(rm.reshape(S, R, W // 128, 128))))
        # Force word-axis subdivision too (tall-row-set regime): a tiny
        # step budget splits each slice into power-of-two chunks.
        bw.GRAM_STEP_BYTES = R * (W // 4) * 32
        g4 = np.asarray(bw.pair_gram(jnp.asarray(rm)))
        g5 = np.asarray(bw.pair_gram(jnp.asarray(rm.reshape(S, R, W // 128, 128))))
    finally:
        bw.GRAM_ONESHOT_BYTES = orig
        bw.GRAM_STEP_BYTES = orig_step
    want = np.zeros((R, R), dtype=np.int64)
    for i in range(R):
        for j in range(R):
            want[i, j] = sum(bw.np_count_and(rm[s, i], rm[s, j]) for s in range(S))
    assert np.array_equal(g1, want)
    assert np.array_equal(g2, want)
    assert np.array_equal(g3, want)
    assert np.array_equal(g4, want)
    assert np.array_equal(g5, want)


def test_gather_count_rowmajor_wrapper_parity(rng):
    """dispatch.gather_count_rowmajor (3D and tiled 4D inputs, including
    a batch larger than the chunk cap) must match slice-major
    dispatch.gather_count on the same data."""
    S, R, W = 3, 48, 1024
    rm = rng.integers(0, 1 << 32, size=(S, R, W), dtype=np.uint32)
    rm_t = np.ascontiguousarray(rm.transpose(1, 0, 2))
    rm_t4 = rm_t.reshape(R, S, W // 128, 128)
    import pilosa_tpu.ops.dispatch as dispatch_mod

    old = dispatch_mod._GATHER_BATCH_MAX
    dispatch_mod._GATHER_BATCH_MAX = 8  # force the concat path
    try:
        pairs = rng.integers(0, R, size=(21, 2), dtype=np.int32)
        for op in ("and", "or", "xor", "andnot"):
            want = np.asarray(
                dispatch.gather_count(op, jnp.asarray(rm), jnp.asarray(pairs),
                                      allow_gram=False)
            )
            for rmj in (rm_t, rm_t4):
                got = np.asarray(
                    dispatch.gather_count_rowmajor(op, jnp.asarray(rmj), jnp.asarray(pairs))
                )
                assert np.array_equal(got, want), (op, rmj.ndim)
    finally:
        dispatch_mod._GATHER_BATCH_MAX = old


def test_fused_gather_count_multi_rowmajor_interpret(rng):
    """Row-major K-operand fold kernel vs numpy ground truth."""
    from pilosa_tpu.ops.pallas_kernels import fused_gather_count_multi_rowmajor

    S, R, W, B, K = 3, 40, 2048, 11, 4
    rm = rng.integers(0, 1 << 32, size=(S, R, W), dtype=np.uint32)
    idx = rng.integers(0, R, size=(B, K), dtype=np.int32)
    rm_t = np.ascontiguousarray(rm.transpose(1, 0, 2)).reshape(R, S, W // 128, 128)
    for op in ("and", "or", "andnot"):
        got = np.asarray(
            fused_gather_count_multi_rowmajor(
                op, jnp.asarray(rm_t), jnp.asarray(idx), interpret=True
            )
        )
        want = bw.np_gather_count_multi(op, rm, idx)
        assert np.array_equal(got, want), op


def test_gather_count_multi_rowmajor_wrapper_parity(rng):
    """dispatch.gather_count_multi_rowmajor matches the slice-major
    dispatch on the same data (3D + tiled 4D row-major inputs)."""
    S, R, W, B, K = 2, 24, 1024, 9, 3
    rm = rng.integers(0, 1 << 32, size=(S, R, W), dtype=np.uint32)
    rm_t = np.ascontiguousarray(rm.transpose(1, 0, 2))
    idx = rng.integers(0, R, size=(B, K), dtype=np.int32)
    for op in ("and", "or", "andnot"):
        want = np.asarray(dispatch.gather_count_multi(op, jnp.asarray(rm), jnp.asarray(idx)))
        for rmj in (rm_t, rm_t.reshape(R, S, W // 128, 128)):
            got = np.asarray(
                dispatch.gather_count_multi_rowmajor(op, jnp.asarray(rmj), jnp.asarray(idx))
            )
            assert np.array_equal(got, want), (op, rmj.ndim)


# --- fused tree lane (arbitrary nested Count trees; executor.go:261-276) ---


def _rand_tree_arrays(rng, R, B, D):
    """Random perfect-tree programs: leaves int32[B, 2^D], opcodes
    int32[B, 2^D - 1] drawn over all five opcodes (incl. TREE_PASS)."""
    K = 1 << D
    leaves = rng.integers(0, R, size=(B, K), dtype=np.int32)
    opc = rng.integers(0, 5, size=(B, K - 1), dtype=np.int32)
    return leaves, opc


@pytest.mark.parametrize("seed", range(3))
def test_gather_count_tree_matches_numpy(seed):
    """jnp tree fold vs numpy ground truth on random programs, every
    depth bucket the executor emits (D=1..4), 3D and tiled 4D inputs."""
    rng = np.random.default_rng(seed)
    S, R, B = 3, 12, 7
    rm = rng.integers(0, 1 << 32, size=(S, R, W), dtype=np.uint32)
    rm4 = rm.reshape(S, R, W // 128, 128)
    for D in (1, 2, 3, 4):
        leaves, opc = _rand_tree_arrays(rng, R, B, D)
        want = bw.np_gather_count_tree(rm, leaves, opc)
        got = np.asarray(
            bw.gather_count_tree(jnp.asarray(rm), jnp.asarray(leaves), jnp.asarray(opc))
        )
        assert np.array_equal(got, want), D
        got4 = np.asarray(
            dispatch.gather_count_tree(
                jnp.asarray(rm4), jnp.asarray(leaves), jnp.asarray(opc)
            )
        )
        assert np.array_equal(got4, want), D


def test_fused_gather_count_tree_interpret(rng):
    """Pallas tree kernel vs numpy ground truth (interpret mode)."""
    from pilosa_tpu.ops.pallas_kernels import fused_gather_count_tree

    S, R, B, D = 2, 10, 5, 3
    rm = rng.integers(0, 1 << 32, size=(S, R, W), dtype=np.uint32)
    leaves, opc = _rand_tree_arrays(rng, R, B, D)
    got = np.asarray(
        fused_gather_count_tree(
            jnp.asarray(rm), jnp.asarray(leaves), jnp.asarray(opc), interpret=True
        )
    )
    assert np.array_equal(got, bw.np_gather_count_tree(rm, leaves, opc))


def test_gather_count_tree_chunks_large_batches(rng, monkeypatch):
    """The dispatch chunking for tree batches preserves results (same
    contract as the pair/multi chunk tests)."""
    from pilosa_tpu.ops import dispatch as dispatch_mod
    from pilosa_tpu.pilosa import OR_MULTI_BUDGET_DEVICE

    S, R, B, D = 2, 8, 9, 2
    rm = rng.integers(0, 1 << 32, size=(S, R, W), dtype=np.uint32)
    leaves, opc = _rand_tree_arrays(rng, R, B, D)
    want = bw.np_gather_count_tree(rm, leaves, opc)
    # Shrink the fallback budget so the jnp path chunks (CPU suite).
    monkeypatch.setattr(
        "pilosa_tpu.pilosa.OR_MULTI_BUDGET_DEVICE", S * (1 << D) * W * 4 * 2
    )
    got = np.asarray(
        dispatch_mod.gather_count_tree(
            jnp.asarray(rm), jnp.asarray(leaves), jnp.asarray(opc)
        )
    )
    assert np.array_equal(got, want)


def test_numpy_engine_tree_matches_ground_truth(rng):
    """NumpyEngine's inline per-opcode tree fold (jax-free path) must
    equal the bitwise ground truth on random programs."""
    from pilosa_tpu.engine import NumpyEngine

    S, R, B, D = 2, 9, 11, 3
    rm = rng.integers(0, 1 << 32, size=(S, R, W), dtype=np.uint32)
    leaves, opc = _rand_tree_arrays(rng, R, B, D)
    got = NumpyEngine().gather_count_tree(rm, leaves, opc)
    assert got.tolist() == bw.np_gather_count_tree(rm, leaves, opc).tolist()


def test_fused_gather_src_counts_interpret(rng):
    """All-slice TopN scorer kernel vs numpy ground truth."""
    from pilosa_tpu.ops.pallas_kernels import fused_gather_src_counts

    S, R, K = 3, 10, 7
    rm = rng.integers(0, 1 << 32, size=(S, R, W), dtype=np.uint32)
    src = rng.integers(0, 1 << 32, size=(S, W), dtype=np.uint32)
    pos = rng.integers(0, R, size=(K,), dtype=np.int32)
    got = np.asarray(
        fused_gather_src_counts(
            jnp.asarray(rm), jnp.asarray(pos), jnp.asarray(src), interpret=True
        )
    )
    want = np.stack([
        np.array([bw.np_count(rm[s, p] & src[s]) for p in pos]) for s in range(S)
    ])
    assert np.array_equal(got, want)
    # dispatch fallback parity (jnp path on CPU)
    got_d = np.asarray(
        dispatch.topn_scorer_counts(jnp.asarray(rm), jnp.asarray(pos), jnp.asarray(src))
    )
    assert np.array_equal(got_d, want)
