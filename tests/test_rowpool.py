"""Unit tests for the paged device row pool (rowpool.py).

The pool is the round-2 replacement for the fixed row-matrix cache: rows
page in on demand, LRU rows page out, capacity doubles up to a budget.
Ground truth is a plain dict of host rows; the pool must agree under
arbitrary interleavings of acquire / mutation (generation bumps).
"""

from __future__ import annotations

import numpy as np
import pytest

from pilosa_tpu.engine import NumpyEngine
from pilosa_tpu.rowpool import DeviceRowPool, chunk_queries, pool_capacity

W = 16  # small word count: pool logic is W-agnostic


def make_pool(n_slices=2, cap_max=8, rows=None, fetch_log=None, engine=None):
    rows = rows if rows is not None else {}

    def fetch(row_ids, slice_idxs):
        if fetch_log is not None:
            fetch_log.append((tuple(row_ids), tuple(slice_idxs)))
        block = np.zeros((len(slice_idxs), len(row_ids), W), dtype=np.uint32)
        for bi, si in enumerate(slice_idxs):
            for k, r in enumerate(row_ids):
                block[bi, k] = rows.get((si, r), np.zeros(W, np.uint32))
        return block

    return DeviceRowPool(engine or NumpyEngine(), n_slices, W, fetch, cap_max=cap_max), rows


def fill_rows(rng, n_slices, row_ids):
    return {
        (si, r): rng.integers(0, 1 << 32, size=W, dtype=np.uint32)
        for si in range(n_slices)
        for r in row_ids
    }


def check(pool, rows, want, gens):
    id_pos, matrix, box = pool.acquire(want, gens)
    for r in want:
        for si in range(pool.n_slices):
            np.testing.assert_array_equal(
                matrix[si, id_pos[r]], rows.get((si, r), np.zeros(W, np.uint32))
            )
    return id_pos, matrix, box


def test_grow_and_hit():
    rng = np.random.default_rng(1)
    rows = fill_rows(rng, 2, range(10))
    pool, _ = make_pool(rows=rows, cap_max=16)
    g = (1, 1)
    check(pool, rows, [0, 1], g)
    assert pool.cap == 2
    check(pool, rows, [2, 3, 4], g)
    assert pool.cap == 8  # doubled past 5 -> pow2
    # Pure hit: box persists, hits climb.
    _, _, box = pool.acquire([0, 4], g)
    hits = box["hits"]
    _, _, box2 = pool.acquire([1, 2], g)
    assert box2 is box and box2["hits"] == hits + 1
    assert pool.stat_evictions == 0


def test_eviction_lru_order():
    rng = np.random.default_rng(2)
    rows = fill_rows(rng, 2, range(20))
    pool, _ = make_pool(rows=rows, cap_max=4)
    g = (1, 1)
    check(pool, rows, [0, 1, 2, 3], g)
    check(pool, rows, [0, 1], g)  # refresh 0,1 in LRU
    check(pool, rows, [4], g)  # evicts 2 (least recent)
    assert 2 not in pool.slot_of and 4 in pool.slot_of
    assert pool.stat_evictions == 1
    # Evicted row pages back in correctly.
    check(pool, rows, [2], g)
    assert 3 not in pool.slot_of  # 3 was next-least-recent
    # The request's own rows are never chosen as victims.
    check(pool, rows, [5, 6, 7, 8], g)
    assert all(r in pool.slot_of for r in (5, 6, 7, 8))


def test_acquire_too_large_raises():
    pool, _ = make_pool(cap_max=4)
    with pytest.raises(ValueError, match="chunk the batch"):
        pool.acquire(list(range(5)), (1, 1))


def test_snapshot_isolation_across_eviction():
    """A reader's (id_pos, matrix) snapshot stays valid after later
    acquires evict its rows (functional updates: new array each time)."""
    rng = np.random.default_rng(3)
    rows = fill_rows(rng, 2, range(8))
    pool, _ = make_pool(rows=rows, cap_max=4)
    g = (1, 1)
    id_pos, matrix, _ = pool.acquire([0, 1, 2, 3], g)
    snap = {r: (id_pos[r], np.array([matrix[si, id_pos[r]] for si in range(2)])) for r in (0, 1)}
    pool.acquire([4, 5, 6], g)  # evicts some of 0..3
    for r, (slot, want_rows) in snap.items():
        for si in range(2):
            np.testing.assert_array_equal(matrix[si, slot], want_rows[si])


def test_stale_slice_plane_refresh():
    rng = np.random.default_rng(4)
    rows = fill_rows(rng, 3, range(6))
    pool, live = make_pool(n_slices=3, rows=rows, cap_max=8)
    g1 = (1, 1, 1)
    check(pool, rows, [0, 1, 2], g1)
    box1 = pool.box
    # Mutate slice 1's data for rows 0 and 5; bump slice 1's generation.
    live[(1, 0)] = rng.integers(0, 1 << 32, size=W, dtype=np.uint32)
    g2 = (1, 2, 1)
    id_pos, matrix, box2 = check(pool, rows, [0, 1], g2)
    assert box2 is not box1  # content changed -> fresh box (Gram dies)
    # Unchanged slices kept their planes; changed slice reflects new data.
    np.testing.assert_array_equal(matrix[1, id_pos[0]], live[(1, 0)])
    assert pool.stat_resets == 0


def test_stale_refresh_over_budget_resets(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_POOL_REFRESH_BYTES", "8")  # force reset
    rng = np.random.default_rng(5)
    rows = fill_rows(rng, 2, range(6))
    pool, live = make_pool(rows=rows, cap_max=8)
    check(pool, rows, [0, 1, 2], (1, 1))
    live[(0, 1)] = rng.integers(0, 1 << 32, size=W, dtype=np.uint32)
    check(pool, rows, [0, 1, 2], (2, 1))
    assert pool.stat_resets == 1  # repopulated on demand, still correct


def test_box_id_pos_is_full_resident_snapshot():
    rng = np.random.default_rng(6)
    rows = fill_rows(rng, 2, range(6))
    pool, _ = make_pool(rows=rows, cap_max=8)
    g = (1, 1)
    pool.acquire([0, 1, 2], g)
    id_pos, _, box = pool.acquire([1], g)
    assert set(id_pos) == {0, 1, 2}  # full resident set, not just want
    assert box["n_used"] == 3


def test_fifty_thousand_rows_page_through_small_pool():
    """Rank-cache scale (DefaultCacheSize=50000, frame.go:33-40): 50k
    distinct rows stream through a 512-slot pool; counts stay exact."""
    pool, rows = make_pool(n_slices=1, cap_max=512)
    # Virtual rows: row r has word pattern r (cheap, deterministic).
    def fetch(row_ids, slice_idxs):
        block = np.zeros((len(slice_idxs), len(row_ids), W), dtype=np.uint32)
        for k, r in enumerate(row_ids):
            block[:, k, :] = np.uint32(r)
        return block

    pool.fetch = fetch
    g = (1,)
    rng = np.random.default_rng(7)
    seen = 0
    for _ in range(100):
        want = sorted(set(rng.integers(0, 50000, size=256).tolist()))
        id_pos, matrix, _ = pool.acquire(want, g)
        sample = want[:: max(1, len(want) // 8)]
        for r in sample:
            assert int(matrix[0, id_pos[r], 0]) == r
        seen += len(want)
    assert pool.cap <= 512
    assert pool.stat_evictions > 20000  # genuinely paged, not grown


def test_chunk_queries():
    qs = [(0, 1), (1, 2), (3, 4), (5, 6), (0, 5)]
    chunks = chunk_queries(qs, lambda q: q, 4)
    assert [len(c) for c in chunks] == [2, 2, 1]
    assert sum(chunks, []) == qs  # order preserved
    with pytest.raises(ValueError):
        chunk_queries([(0, 1, 2)], lambda q: q, 2)
    assert chunk_queries([], lambda q: q, 4) == []


def test_pool_capacity_budget():
    assert pool_capacity(16, 32768, budget_bytes=2 << 30) == 1024
    assert pool_capacity(1024, 32768, budget_bytes=2 << 30) == 16


def test_chunk_queries_oversize_ok():
    qs = [(0, 1), tuple(range(10)), (2, 3)]
    chunks = chunk_queries(qs, lambda q: q, 4, oversize_ok=True)
    assert chunks == [[(0, 1)], [tuple(range(10))], [(2, 3)]]


def _np_gram(pool, rows, resident, n):
    """Ground-truth AND-count Gram over the pool's slot assignment."""
    from pilosa_tpu.roaring import _popcount_words

    g = np.zeros((n, n), dtype=np.int64)
    slot = {r: pool.slot_of[r] for r in resident}
    for a in resident:
        for b in resident:
            c = 0
            for si in range(pool.n_slices):
                wa = rows.get((si, a), np.zeros(W, np.uint32))
                wb = rows.get((si, b), np.zeros(W, np.uint32))
                c += _popcount_words(wa & wb)
            g[slot[a], slot[b]] = c
    return g


def test_acquire_dirty_rows_repairs_in_place():
    """The PATCH lane: a generation bump with a known dirty-row set
    rewrites only those rows' planes, keeps the box (and its Gram/glut)
    alive, and rank-k-updates the Gram to exact counts."""
    rng = np.random.default_rng(7)
    rows = fill_rows(rng, 2, range(4))
    pool, live = make_pool(n_slices=2, rows=rows, cap_max=8)
    id_pos, _, box1 = pool.acquire([0, 1, 2, 3], (1, 1))
    # Seed a warm Gram + glut the way the executor does (bucket = pow2(4)).
    gram = _np_gram(pool, live, [0, 1, 2, 3], 4)
    box1["gram"] = gram
    rs = np.array(sorted(id_pos), dtype=np.int64)
    ps = np.fromiter((id_pos[int(v)] for v in rs), dtype=np.int32, count=len(rs))
    box1["gram_lut"] = (rs, np.ascontiguousarray(gram), ps)
    # Mutate row 2 on slice 1 only; bump slice 1's generation.
    live[(1, 2)] = rng.integers(0, 1 << 32, size=W, dtype=np.uint32)
    id_pos2, matrix, box2 = pool.acquire([0, 1], (1, 2), dirty_rows={2})
    assert box2 is box1, "box must survive the patch lane"
    assert pool.stat_repairs == 1 and pool.stat_resets == 0
    # Matrix reflects the new row data; untouched rows kept their planes.
    np.testing.assert_array_equal(matrix[1, id_pos2[2]], live[(1, 2)])
    np.testing.assert_array_equal(matrix[0, id_pos2[0]], live[(0, 0)])
    # The repaired Gram matches a from-scratch recount, and the glut's
    # count table was swapped to it (copy-on-write: the old array is not
    # mutated).
    want = _np_gram(pool, live, [0, 1, 2, 3], 4)
    np.testing.assert_array_equal(box2["gram"], want)
    np.testing.assert_array_equal(box2["gram_lut"][1], want)
    assert box2["gram"] is not gram


def test_acquire_dirty_rows_per_slice_granularity():
    """Per-(row, slice) patch: a {slice: rows} dirty mapping re-fetches
    ONLY the planes actually written — row 2 for slice 1 and row 3 for
    slice 2, not the cross product — and the rank-k Gram repair still
    lands on exact counts."""
    rng = np.random.default_rng(11)
    rows = fill_rows(rng, 3, range(4))
    log: list = []
    pool, live = make_pool(n_slices=3, rows=rows, cap_max=8, fetch_log=log)
    id_pos, _, box1 = pool.acquire([0, 1, 2, 3], (1, 1, 1))
    gram = _np_gram(pool, live, [0, 1, 2, 3], 4)
    box1["gram"] = gram
    rs = np.array(sorted(id_pos), dtype=np.int64)
    ps = np.fromiter((id_pos[int(v)] for v in rs), dtype=np.int32, count=len(rs))
    box1["gram_lut"] = (rs, np.ascontiguousarray(gram), ps)
    # Row 2 written in slice 1; row 3 written in slice 2.
    live[(1, 2)] = rng.integers(0, 1 << 32, size=W, dtype=np.uint32)
    live[(2, 3)] = rng.integers(0, 1 << 32, size=W, dtype=np.uint32)
    log.clear()
    id_pos2, matrix, box2 = pool.acquire(
        [0, 1], (1, 2, 2), dirty_rows={1: {2}, 2: {3}}
    )
    assert box2 is box1 and pool.stat_repairs == 1 and pool.stat_resets == 0
    # Exactly the two written planes were fetched (in either group order).
    assert sorted(log) == [((2,), (1,)), ((3,), (2,))]
    assert pool.stat_patch_planes == 2
    np.testing.assert_array_equal(matrix[1, id_pos2[2]], live[(1, 2)])
    np.testing.assert_array_equal(matrix[2, id_pos2[3]], live[(2, 3)])
    # Unwritten planes of the dirty rows are untouched.
    np.testing.assert_array_equal(matrix[0, id_pos2[2]], live[(0, 2)])
    want = _np_gram(pool, live, [0, 1, 2, 3], 4)
    np.testing.assert_array_equal(box2["gram"], want)
    np.testing.assert_array_equal(box2["gram_lut"][1], want)


def test_acquire_dirty_dict_slices_share_fetch():
    """Stale slices dirtied with the SAME row set batch into one fetch
    (one transfer per distinct row group, not per slice)."""
    rng = np.random.default_rng(12)
    rows = fill_rows(rng, 3, range(4))
    log: list = []
    pool, live = make_pool(n_slices=3, rows=rows, cap_max=8, fetch_log=log)
    pool.acquire([0, 1, 2], (1, 1, 1))
    live[(0, 1)] = rng.integers(0, 1 << 32, size=W, dtype=np.uint32)
    live[(2, 1)] = rng.integers(0, 1 << 32, size=W, dtype=np.uint32)
    log.clear()
    id_pos, matrix, _ = pool.acquire([0, 1], (2, 1, 2), dirty_rows={0: {1}, 2: {1}})
    assert log == [((1,), (0, 2))]  # one grouped fetch for both slices
    np.testing.assert_array_equal(matrix[0, id_pos[1]], live[(0, 1)])
    np.testing.assert_array_equal(matrix[2, id_pos[1]], live[(2, 1)])


def test_gram_update_rows_delta_matches_full_recompute():
    """The per-(row, slice) delta form of gram_update_rows (old matrix +
    written slice planes) must agree exactly with the full recompute, on
    the numpy engine and on jax (which pads the restricted slice axis
    with a clean slice)."""
    rng = np.random.default_rng(13)
    S, R = 8, 4
    old = rng.integers(0, 1 << 32, size=(S, R, W), dtype=np.uint32)
    new = old.copy()
    dirty_slots = [1, 3]
    dirty_slices = [2, 5]
    for sl in dirty_slots:
        for si in dirty_slices:
            new[si, sl] = rng.integers(0, 1 << 32, size=W, dtype=np.uint32)

    from pilosa_tpu.roaring import _popcount_words

    def np_gram(m):
        g = np.zeros((R, R), dtype=np.int64)
        for a in range(R):
            for b in range(R):
                g[a, b] = sum(
                    _popcount_words(m[si, a] & m[si, b]) for si in range(S)
                )
        return g

    gram_old = np_gram(old)
    want = np_gram(new)
    eng = NumpyEngine()
    got = eng.gram_update_rows(
        new, gram_old, dirty_slots, old_matrix=old, slice_idxs=dirty_slices
    )
    np.testing.assert_array_equal(got, want)
    # Full-recompute form agrees too (no delta args).
    np.testing.assert_array_equal(
        eng.gram_update_rows(new, gram_old, dirty_slots), want
    )

    from pilosa_tpu.engine import JaxEngine

    jeng = JaxEngine()
    got_j = jeng.gram_update_rows(
        jeng.matrix(new), gram_old, dirty_slots,
        old_matrix=jeng.matrix(old), slice_idxs=dirty_slices,
    )
    np.testing.assert_array_equal(got_j, want)


def test_gram_update_rows_delta_all_slices_dirty_falls_back():
    """Every slice dirty -> no clean pad slice / no restriction win: both
    engines take the full-recompute path and stay exact."""
    rng = np.random.default_rng(14)
    S, R = 2, 3
    old = rng.integers(0, 1 << 32, size=(S, R, W), dtype=np.uint32)
    new = old.copy()
    new[:, 1] = rng.integers(0, 1 << 32, size=(S, W), dtype=np.uint32)

    from pilosa_tpu.roaring import _popcount_words

    def np_gram(m):
        g = np.zeros((R, R), dtype=np.int64)
        for a in range(R):
            for b in range(R):
                g[a, b] = sum(
                    _popcount_words(m[si, a] & m[si, b]) for si in range(S)
                )
        return g

    want = np_gram(new)
    eng = NumpyEngine()
    got = eng.gram_update_rows(
        new, np_gram(old), [1], old_matrix=old, slice_idxs=[0, 1]
    )
    np.testing.assert_array_equal(got, want)


def test_acquire_dirty_rows_nonresident_keeps_box():
    """Writes to rows the pool does not hold need no matrix or Gram work
    at all — the box survives untouched."""
    rng = np.random.default_rng(8)
    rows = fill_rows(rng, 2, range(6))
    pool, live = make_pool(n_slices=2, rows=rows, cap_max=4)
    _, _, box1 = pool.acquire([0, 1], (1, 1))
    live[(0, 5)] = rng.integers(0, 1 << 32, size=W, dtype=np.uint32)
    _, _, box2 = pool.acquire([0, 1], (2, 1), dirty_rows={5})
    assert box2 is box1 and pool.stat_repairs == 1


def test_acquire_without_dirty_rows_still_resets_box():
    """No delta information -> the conservative full refresh + box reset
    (the pre-repair behavior) is unchanged."""
    rng = np.random.default_rng(9)
    rows = fill_rows(rng, 2, range(4))
    pool, live = make_pool(n_slices=2, rows=rows, cap_max=8)
    _, _, box1 = pool.acquire([0, 1], (1, 1))
    live[(0, 0)] = rng.integers(0, 1 << 32, size=W, dtype=np.uint32)
    id_pos, matrix, box2 = pool.acquire([0, 1], (2, 1))
    assert box2 is not box1 and pool.stat_repairs == 0
    np.testing.assert_array_equal(matrix[0, id_pos[0]], live[(0, 0)])


# -- the repair as one engine call (engine.repair_planes) -----------------

ENGINES = ["numpy", "jax", "mesh4"]
STEPPING = ("jax", "mesh4")  # engines with the compiled in-place step


def _engine(name):
    if name == "numpy":
        return NumpyEngine()
    from pilosa_tpu.engine import JaxEngine, MeshEngine

    if name == "mesh4":  # 16 slices: four a device, of conftest's eight
        import jax

        return MeshEngine(devices=jax.devices()[:4])
    return JaxEngine()


def _warm_pool(engine, rng, n_slices=16, n_rows=6):
    """A pool with rows 0..n_rows-1 resident (cap 8), handed to a reader
    once, and a warm Gram + glut in its box, as the executor leaves it."""
    rows = fill_rows(rng, n_slices, range(n_rows))
    pool, live = make_pool(n_slices=n_slices, rows=rows, cap_max=8, engine=engine)
    gens = [1] * n_slices
    id_pos, matrix, box = pool.acquire(list(range(n_rows)), tuple(gens))
    gram = _np_gram(pool, live, range(n_rows), 8)
    box["gram"] = gram
    rs = np.array(sorted(id_pos), dtype=np.int64)
    ps = np.fromiter((id_pos[int(v)] for v in rs), dtype=np.int32, count=len(rs))
    box["gram_lut"] = (rs, np.ascontiguousarray(gram), ps)
    return pool, live, gens, matrix, box


def _write(rng, pool, live, gens, burst, want=()):
    """New contents for the burst's (slice, row) cells, then the acquire
    that repairs them."""
    for si, rs in burst.items():
        gens[si] += 1
        for r in rs:
            live[(si, r)] = rng.integers(0, 1 << 32, size=W, dtype=np.uint32)
    return pool.acquire(list(want), tuple(gens), dirty_rows=burst)


def _assert_pool_is(pool, live, n_rows=6):
    m = np.asarray(pool.matrix).reshape(pool.n_slices, pool.cap, W)
    for (si, r), words in live.items():
        np.testing.assert_array_equal(m[si, pool.slot_of[r]], words)
    want = _np_gram(pool, live, range(n_rows), 8)
    np.testing.assert_array_equal(pool.box["gram"], want)
    np.testing.assert_array_equal(pool.box["gram_lut"][1], want)
    full = pool.engine.pair_gram(pool.matrix)
    if full is not None:  # the jax engine's own full recompute
        np.testing.assert_array_equal(pool.box["gram"], full)


_BURSTS = {
    "one_cell": {2: {1}},
    "two_rows_in_one_slice": {3: {0, 4}},   # dirty-by-dirty entries
    "one_row_in_two_slices": {1: {2}, 5: {2}},
    "three_cells_pad_to_four": {0: {1, 3}, 2: {3}},
    "five_cells_pad_to_eight": {0: {1, 3}, 2: {3}, 7: {5}, 9: {1}},
    "half_the_slices": {si: {2} for si in range(8)},   # the composed form on every engine
    "wide": {4: {0, 1, 2, 3}},                         # 2k >= n: likewise
}


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("burst", sorted(_BURSTS))
def test_repair_planes_gram_equals_the_full_recompute(engine, burst):
    """Whatever burst the journals produce, patched planes and repaired
    Gram equal storage and the full recompute: once on an array a reader
    holds (a copy is patched), once on the pool's own (in place, on the
    jax and mesh engines, for the bursts their compiled step takes)."""
    rng = np.random.default_rng(21)
    pool, live, gens, _, box = _warm_pool(_engine(engine), rng)
    for nth in (1, 2):
        _, _, box2 = _write(rng, pool, live, gens, _BURSTS[burst])
        assert box2 is box and pool.stat_repairs == nth
        _assert_pool_is(pool, live)
    stepped = engine in STEPPING and burst not in ("half_the_slices", "wide")
    assert pool.stat_repairs_in_place == (1 if stepped else 0)


@pytest.mark.parametrize("engine", ENGINES)
def test_snapshot_isolation_across_repair(engine):
    """test_snapshot_isolation_across_eviction's contract on the repair
    path: the array a reader took for rows it wanted stays readable and
    unchanged; the repair that finds it handed out patches a copy, the
    next one (nobody took the new array) updates in place."""
    rng = np.random.default_rng(22)
    pool, live, gens, matrix, _ = _warm_pool(_engine(engine), rng)
    snap = np.array(matrix)
    _write(rng, pool, live, gens, {2: {1}})
    assert pool.stat_repairs == 1 and pool.stat_repairs_in_place == 0
    unread = pool.matrix
    assert unread is not matrix
    _write(rng, pool, live, gens, {2: {1}, 6: {4}})
    assert pool.stat_repairs == 2
    assert pool.stat_repairs_in_place == (1 if engine in STEPPING else 0)
    if engine in STEPPING:
        assert unread.is_deleted() and not matrix.is_deleted()
        assert pool.matrix.sharding == matrix.sharding
    np.testing.assert_array_equal(np.asarray(matrix), snap)
    _assert_pool_is(pool, live)
    # A reader that wants rows takes the array again: hands off until the
    # next functional update.
    _, taken, _ = _write(rng, pool, live, gens, {3: {0}}, want=[0])
    snap = np.array(taken)
    _write(rng, pool, live, gens, {3: {0}})
    assert pool.stat_repairs_in_place == (2 if engine in STEPPING else 0)
    np.testing.assert_array_equal(np.asarray(taken), snap)
    _assert_pool_is(pool, live)


@pytest.mark.parametrize("engine", ENGINES)
def test_failed_repair_leaves_a_pool_the_next_acquire_rebuilds(engine, monkeypatch):
    """A step that raises after it took the array (jax, mesh), or between
    planes and Gram (numpy), leaves nothing to trust: the pool drops to
    its empty state, the error surfaces, the next acquire pages in."""
    rng = np.random.default_rng(23)
    eng = _engine(engine)
    pool, live, gens, _, box = _warm_pool(eng, rng)
    _write(rng, pool, live, gens, {2: {1}})  # the pool's array is its own now

    def boom(matrix, *args, **kw):
        if engine in STEPPING:
            matrix.delete()  # what a donated argument is after the call
        raise RuntimeError("device lost")

    monkeypatch.setattr(eng, "_repair_step" if engine in STEPPING else "gram_update_rows", boom)
    with pytest.raises(RuntimeError, match="device lost"):
        _write(rng, pool, live, gens, {2: {1}})
    assert pool.matrix is None and pool.cap == 0 and not pool.slot_of
    assert pool.gens is None and pool.box is not box and "gram" not in pool.box
    assert pool.stat_repairs == 1
    monkeypatch.undo()
    check(pool, live, [0, 1, 2, 5], tuple(gens))
    assert pool.cap == 4 and pool.box.get("gens") == tuple(gens)


def test_repair_planes_on_a_tiled_matrix():
    """The jax engine stores W % 128 == 0 matrices tiled ([S, R, W/128,
    128]): the step takes its planes in that form, over a Gram narrower
    than the pool's capacity."""
    from pilosa_tpu.engine import JaxEngine

    rng = np.random.default_rng(24)
    eng, (S, R, n, w) = JaxEngine(), (8, 8, 4, 256)
    host = rng.integers(0, 1 << 32, size=(S, R, w), dtype=np.uint32)
    gram = eng.pair_gram(eng.matrix(host[:, :n]))
    block = rng.integers(0, 1 << 32, size=(2, 1, w), dtype=np.uint32)
    given = eng.matrix(host.copy())  # the CPU backend may alias a numpy buffer
    matrix, finish, in_place, form = eng.repair_planes(given, gram, [([1, 6], [3], block)], donate=True)
    assert in_place and form == "step" and given.is_deleted() and matrix.shape == (S, R, w // 128, 128)
    host[[1, 6], 3] = block[:, 0]
    np.testing.assert_array_equal(np.asarray(matrix).reshape(S, R, w), host)
    np.testing.assert_array_equal(finish(), eng.pair_gram(eng.matrix(host[:, :n])))


# -- a tall frame paged through the pool (ISSUE 32) ---------------------------
#
# A fixed set of programs and no Python per plane.  Small and seeded: 128 rows
# x 2 slices (4 on the mesh), a pool of 16 slots by ``PILOSA_TPU_POOL_BYTES``,
# a hot set that fits and a cold tail that evicts - what ``seg64.tall_pairs``
# does on the chip with 8,192 rows, 64 slices and 256 slots.

import jax  # noqa: E402

from pilosa_tpu.core.frame import FrameOptions  # noqa: E402
from pilosa_tpu.core.holder import Holder  # noqa: E402
from pilosa_tpu.executor import ExecOptions, Executor  # noqa: E402
from pilosa_tpu.pilosa import SLICE_WIDTH  # noqa: E402
from pilosa_tpu.stats import ExpvarStatsClient  # noqa: E402
from pilosa_tpu.trace import Span  # noqa: E402

TALL_ROWS, SLOTS, HOT = 128, 16, 10
PLANE_WORDS = SLICE_WIDTH // 32
OPS = ("Intersect", "Union", "Difference", "Xor")
SET_OPS = {"Intersect": set.__and__, "Union": set.__or__,
           "Difference": set.__sub__, "Xor": set.__xor__}


def _paging_engine(kind):
    if kind != "mesh":
        return kind
    from pilosa_tpu.engine import MeshEngine

    return MeshEngine(devices=jax.devices()[:4])


def _tall_frame(tmp_path, monkeypatch, kind, n_slices=0):
    """A holder with one frame of TALL_ROWS rows, 1-5 bits per (row, slice) from
    a pool of 64 columns a slice (so that some pairs intersect), an
    executor on ``kind`` whose pool holds SLOTS rows, and the plain
    reference: a set of columns per row."""
    n_slices = n_slices or (4 if kind == "mesh" else 2)
    monkeypatch.setenv("PILOSA_TPU_POOL_BYTES", str(n_slices * SLOTS * PLANE_WORDS * 4))
    h = Holder(str(tmp_path / "data"))
    h.open()
    h.create_index("i").create_frame("stargazer", FrameOptions())
    fr = h.index("i").frame("stargazer")
    rng = np.random.default_rng(32)
    cols_of = {}
    for r in range(TALL_ROWS):
        cols = set()
        for s in range(n_slices):
            local = rng.choice(64, size=1 + (r * 3) % 5, replace=False) * 1021
            cols |= {int(s * SLICE_WIDTH + c) for c in local}
        cols_of[r] = cols
        for c in sorted(cols):
            fr.set_bit("standard", r, c)
    stats = ExpvarStatsClient()
    return h, Executor(h, engine=_paging_engine(kind), stats=stats), cols_of, stats


def _body(rng, n_pairs=8):
    """(PQL, [(op, a, b)]): pairs whose rows are hot 9 draws in 10."""
    calls = []
    for i in range(n_pairs):
        a, b = (int(rng.integers(0, HOT)) if rng.random() < 0.9 else int(rng.integers(HOT, TALL_ROWS))
                for _ in range(2))
        calls.append((OPS[i % 4], a, b if b != a else (a + 1) % TALL_ROWS))
    return " ".join(
        f'Count({op}(Bitmap(rowID={a}, frame="stargazer"), Bitmap(rowID={b}, frame="stargazer")))'
        for op, a, b in calls), calls


def _counter(stats, name):
    return stats.snapshot().get(name, 0)


@pytest.mark.parametrize("kind", ["numpy", "jax", "mesh"])
def test_paged_pair_bodies_equal_the_set_reference(tmp_path, monkeypatch, kind):
    """(a) A few hundred pair bodies with a cold tail, through
    ``Executor.execute``, equal plain set arithmetic whichever rows were
    resident, and the pool evicted on the way."""
    h, ex, cols_of, stats = _tall_frame(tmp_path, monkeypatch, kind)
    rng = np.random.default_rng(7)
    for _ in range(60 if kind == "mesh" else 240):
        body, calls = _body(rng)
        want = [len(SET_OPS[op](cols_of[a], cols_of[b])) for op, a, b in calls]
        assert ex.execute("i", body) == want
    assert _counter(stats, "rowpool.evictions") > 0
    assert _counter(stats, "rowpool.misses") > _counter(stats, "rowpool.evictions")
    assert _counter(stats, "gather.dispatches") > 0
    assert _counter(stats, "gather.pairs") >= _counter(stats, "gather.dispatches")
    h.close()


def _word_buckets():
    """The word buckets a sparse chunk can pad to: 1, 4, .. MISS_WORDS_MAX."""
    from pilosa_tpu import rowpool

    return [4 ** e for e in range(20) if 4 ** e <= rowpool.MISS_WORDS_MAX]


@pytest.mark.parametrize("kind", ["jax", "mesh"])
def test_miss_counts_compile_one_program_a_bucket(tmp_path, monkeypatch, kind):
    """(b) Miss counts 1-9 in turn page sparse (a chunk of up to 8 rows as
    its words, padded to their power-of-four bucket, the slots to 8),
    copying for a miss's first chunk and donating for the rest.  Every
    program a miss can meet is compiled at the pool's first eviction -
    the sparse scatter at every word bucket and the dense one at four
    block sizes (1, 2, 4, 8: a chunk with a bitmap container goes dense),
    both forms of each - and a second pass compiles none.  Counted where
    jax counts them: the jitted scatters' own caches (which every jit of
    a function shares, so this test has a slice count of its own)."""
    n_slices = 8 if kind == "mesh" else 3
    h, ex, cols_of, stats = _tall_frame(tmp_path, monkeypatch, kind, n_slices)
    engine = ex.engine
    pool = ex._pool_for("i", "stargazer", "standard", list(range(n_slices)))
    gens = tuple(0 for _ in range(pool.n_slices))

    def programs():
        if kind == "mesh":
            from pilosa_tpu.parallel import sharded

            return sum(k(engine.mesh.mesh, engine.mesh.AXIS, 4, d)._cache_size()
                       for k in (sharded._sharded_set_rows_kernel,
                                 sharded._sharded_set_row_words_kernel) for d in (False, True))
        from pilosa_tpu.ops.bitwise import set_row_words, set_rows

        return sum(jax.jit(f, static_argnames="axis", donate_argnums=d)._cache_size()
                   for f in (set_rows, set_row_words) for d in ((), (0,)))

    before = programs()
    pool.acquire(list(range(SLOTS)), gens)           # the pool is full: every later miss evicts
    assert pool.cap == SLOTS and pool.stat_evictions == 0
    at_full = programs()
    # the fill: a chunk of 8 rows copied, one donated, 66-192 words each: bucket 256
    assert at_full - before == 2
    nxt = SLOTS
    for n in range(1, 10):
        pool.acquire(list(range(nxt, nxt + n)), gens)
        nxt += n
    # The first eviction compiled both ladders; the sparse 256 and 256 were there already.
    assert programs() - at_full == 8 + 2 * len(_word_buckets()) - 2
    assert pool.miss_buckets == {1, 2, 4, 8}
    assert _counter(stats, "rowpool.miss_buckets") == 4
    assert _counter(stats, "rowpool.miss_chunks_dense") == 0
    assert _counter(stats, "rowpool.miss_chunks_sparse") == 2 + 10   # 9 misses, the last of two chunks
    before = programs()
    for n in range(1, 10):
        pool.acquire(list(range(nxt, nxt + n)), gens)
        nxt += n
    assert programs() == before
    # ... and every row that was paged in reads back as storage has it.
    id_pos, matrix, _ = pool.acquire(list(range(nxt - 9, nxt)), gens)
    for s in (0, n_slices - 1):
        frag = h.fragment("i", "stargazer", "standard", s)
        for r in range(nxt - 9, nxt):
            got = np.asarray(matrix)[s, id_pos[r]].reshape(-1)
            assert (got == frag.row_dense(r)).all() and got.any()
    h.close()


def test_numpy_engine_gets_its_misses_unpadded(tmp_path, monkeypatch):
    """(b) The numpy engine compiles nothing: its blocks are the miss
    counts themselves."""
    h, ex, _, _ = _tall_frame(tmp_path, monkeypatch, "numpy")
    pool = ex._pool_for("i", "stargazer", "standard", [0, 1])
    pool.acquire(list(range(SLOTS)), (0, 0))
    pool.acquire([20, 21, 22], (0, 0))
    assert pool.miss_buckets == {8, 3} and pool.stat_evictions == 3      # 16 rows: two chunks of 8
    h.close()


@pytest.mark.parametrize("row_major", [False, True])
def test_block_of_the_new_fetch_equals_row_dense(tmp_path, monkeypatch, row_major):
    """(c) A block from the pool's fetch equals ``row_dense`` plane for
    plane - array containers, a bitmap container, a pending bulk overlay,
    an absent row and a bucket's tail (row -1) - and leaves the fragments'
    row caches as it found them."""
    h, ex, _, _ = _tall_frame(tmp_path, monkeypatch, "numpy")
    fr = h.index("i").frame("stargazer")
    frag0 = h.fragment("i", "stargazer", "standard", 0)
    dense = np.random.default_rng(3).choice(1 << 16, size=5000, replace=False) + (1 << 17)
    frag0.set_bits(np.full(len(dense), 5, dtype=np.uint64), dense.astype(np.uint64))  # a bitmap container
    overlay = np.zeros(PLANE_WORDS, dtype=np.uint32)
    overlay[[3, 2048 + 7, PLANE_WORDS - 1]] = [0x80000001, 0xF0, 0x1]
    frag0.bulk_or_words(np.array([7], dtype=np.uint64), np.array([3]),
                        np.array([3, 2048 + 7, PLANE_WORDS - 1]), overlay[[3, 2048 + 7, PLANE_WORDS - 1]])
    assert 7 in frag0._bulk_planes
    rows = [5, 7, 40, 4000, -1, 127]
    cached = {s: list(h.fragment("i", "stargazer", "standard", s)._row_cache) for s in (0, 1)}
    block = ex._densify_block("i", "stargazer", "standard", [0, 1], rows, row_major=row_major)
    assert block.shape == ((len(rows), 2, PLANE_WORDS) if row_major else (2, len(rows), PLANE_WORDS))
    for s in (0, 1):
        frag = h.fragment("i", "stargazer", "standard", s)
        assert list(frag._row_cache) == cached[s]
        for k, r in enumerate(rows):
            plane = block[k, s] if row_major else block[s, k]
            want = frag.row_dense(r) if r >= 0 else np.zeros(PLANE_WORDS, dtype=np.uint32)
            assert (plane == want).all(), (s, r)
    assert block[(1, 0) if row_major else (0, 1)].any()      # the overlay's row is not empty
    h.close()


@pytest.mark.parametrize("kind", ["numpy", "jax"])
def test_a_sampled_miss_says_what_it_paged_and_gathered(tmp_path, monkeypatch, kind):
    """(d) A sampled request that misses carries ``pool.miss`` with
    ``bucket`` and its two stages, and the gather dispatch's ``device``
    span says what it gathered."""
    h, ex, cols_of, _ = _tall_frame(tmp_path, monkeypatch, kind)
    root = Span("root")
    body = " ".join(
        f'Count({op}(Bitmap(rowID={a}, frame="stargazer"), Bitmap(rowID={b}, frame="stargazer")))'
        for op, a, b in (("Intersect", 1, 2), ("Intersect", 3, 1), ("Union", 2, 50)))
    assert ex.execute("i", body, opt=ExecOptions(span=root)) == [
        len(cols_of[1] & cols_of[2]), len(cols_of[3] & cols_of[1]), len(cols_of[2] | cols_of[50])]

    def walk(sp):
        yield sp
        for c in sp.children:
            yield from walk(c)

    spans = list(walk(root))
    miss = next(s for s in spans if s.name == "pool.miss")
    assert miss.tags["rows"] == 4 and miss.tags["bucket"] == 4 and miss.tags["evicted"] == 0
    # rows 1, 2, 3 and 50 hold 4 + 2 + 5 + 1 bits a slice, each in a word of its own
    assert (miss.tags["sparse"], miss.tags["words"]) == (1, 24)
    # bytes handed to the device: (slice, slot, word) and a value, 16 a word of the bucket of 64
    assert miss.tags["upload_bytes"] == (64 * 16 if kind == "jax" else 0)
    assert [c.name for c in miss.children] == ["pool.miss.fetch", "pool.miss.scatter"]  # one chunk
    assert all(c.ms is not None for c in miss.children)
    gathers = [s for s in spans if s.name == "device" and s.tags.get("lane") == "gather"]
    assert sorted((g.tags["pairs"], g.tags["unique_rows"]) for g in gathers) == [(1, 2), (2, 3)]
    assert {g.tags["layout"] for g in gathers} == {"slice_major"}
    # the engine that compiles runs a batch at its power-of-four bucket
    assert sorted(g.tags["bucket"] for g in gathers) == ([1, 4] if kind == "jax" else [1, 2])
    h.close()


def _paged_pool(ex, kind, n_slices, row_major):
    return ex._pool_for("i", "stargazer", "standard", list(range(n_slices)),
                        lane="rmgather" if row_major else "")


def _assert_planes_equal_storage(h, pool, matrix, id_pos, rows):
    m = np.asarray(matrix)
    for s in range(pool.n_slices):
        frag = h.fragment("i", "stargazer", "standard", s)
        for r in rows:
            plane = (m[id_pos[r], s] if pool.row_major else m[s, id_pos[r]]).reshape(-1)
            assert (plane == frag.row_dense(r)).all(), (s, r)


def _miss_spans(root):
    return [c for c in root.children if c.name == "pool.miss"]


@pytest.mark.parametrize("kind,row_major", [
    ("numpy", False), ("jax", False), ("jax", True), ("mesh", False), ("mesh", True)],
    ids=["numpy", "jax", "jax_row_major", "mesh", "mesh_row_major"])    # numpy: no row-major pool
def test_rows_paged_in_sparse_equal_row_dense(tmp_path, monkeypatch, kind, row_major):
    """(e) A miss ships its rows' set words, and what lands in the pool
    is ``row_dense`` plane for plane: a full chunk, a chunk of three (the
    slots' tail, the word bucket's tail), an absent row, and slots that
    held other rows before (their bits must be gone).  The counters and
    the span say that every chunk went sparse, how many words, and how
    many bytes were handed to the device: 16 a word of the bucket."""
    from pilosa_tpu.engine import _pow4

    n_slices = 4 if kind == "mesh" else 2
    h, ex, cols_of, stats = _tall_frame(tmp_path, monkeypatch, kind)
    pool = _paged_pool(ex, kind, n_slices, row_major)
    gens = (0,) * n_slices
    root = Span("root")
    id_pos, matrix, _ = pool.acquire(list(range(SLOTS)), gens, span=root)
    _assert_planes_equal_storage(h, pool, matrix, id_pos, range(SLOTS))
    held = dict(id_pos)
    late = [20, 4000, 21]                                # 4000: no such row
    id_pos, matrix, _ = pool.acquire(late, gens, span=root)
    assert pool.stat_evictions == 3
    assert {id_pos[r] for r in late} == {held[r] for r in (0, 1, 2)}     # rows 0-2 lay there
    _assert_planes_equal_storage(h, pool, matrix, id_pos, late + list(range(3, SLOTS)))
    empty = np.asarray(matrix)[id_pos[4000]] if row_major else np.asarray(matrix)[:, id_pos[4000]]
    assert not empty.any()
    words = [sum(len(cols_of[r]) for r in rows) for rows in (range(8), range(8, 16), (20, 21))]
    assert _counter(stats, "rowpool.miss_chunks_sparse") == 3
    assert _counter(stats, "rowpool.miss_chunks_dense") == 0
    assert _counter(stats, "rowpool.miss_words") == sum(words)
    fill, miss = _miss_spans(root)
    assert (fill.tags["sparse"], fill.tags["words"]) == (2, words[0] + words[1])
    assert (miss.tags["sparse"], miss.tags["words"], miss.tags["bucket"]) == (1, words[2], 3 if kind == "numpy" else 4)
    compiled = kind != "numpy"                           # the numpy engine counts no upload
    assert fill.tags["upload_bytes"] == (16 * (_pow4(words[0]) + _pow4(words[1])) if compiled else 0)
    assert miss.tags["upload_bytes"] == (16 * _pow4(words[2]) if compiled else 0)
    h.close()


@pytest.mark.parametrize("why", ["bitmap_container", "bulk_overlay", "too_many_words"])
@pytest.mark.parametrize("kind", ["numpy", "jax", "mesh"])
def test_a_chunk_the_word_list_cannot_hold_goes_dense(tmp_path, monkeypatch, kind, why):
    """(f) A chunk is sparse or dense by what the walk found: one row
    with a bitmap container, or with a pending bulk overlay, or more
    words than the largest bucket, and the chunk goes up as the dense
    block; the chunk beside it stays sparse; both equal ``row_dense``."""
    from pilosa_tpu import rowpool
    from pilosa_tpu.engine import _pow4

    n_slices = 4 if kind == "mesh" else 2
    h, ex, cols_of, stats = _tall_frame(tmp_path, monkeypatch, kind)
    frag0 = h.fragment("i", "stargazer", "standard", 0)
    if why == "bitmap_container":
        dense = np.random.default_rng(3).choice(1 << 16, size=5000, replace=False) + (1 << 17)
        frag0.set_bits(np.full(len(dense), 5, dtype=np.uint64), dense.astype(np.uint64))
    elif why == "bulk_overlay":
        frag0.bulk_or_words(np.array([5], dtype=np.uint64), np.array([3]),
                            np.array([3, 2048 + 7, PLANE_WORDS - 1]),
                            np.array([0x80000001, 0xF0, 0x1], dtype=np.uint32))
        assert 5 in frag0._bulk_planes
    else:
        first = sum(len(cols_of[r]) for r in range(8))
        second = sum(len(cols_of[r]) for r in range(8, 16))
        assert first < second
        monkeypatch.setattr(rowpool, "MISS_WORDS_MAX", first)        # rows 0-7 just fit
    pool = _paged_pool(ex, kind, n_slices, False)
    root = Span("root")
    id_pos, matrix, _ = pool.acquire(list(range(SLOTS)), (0,) * n_slices, span=root)
    _assert_planes_equal_storage(h, pool, matrix, id_pos, range(SLOTS))
    assert _counter(stats, "rowpool.miss_chunks_dense") == 1
    assert _counter(stats, "rowpool.miss_chunks_sparse") == 1
    sparse_rows = range(8, 16) if why != "too_many_words" else range(8)
    (fill,) = _miss_spans(root)
    assert (fill.tags["sparse"], fill.tags["words"]) == (1, sum(len(cols_of[r]) for r in sparse_rows))
    assert _counter(stats, "rowpool.miss_words") == fill.tags["words"]
    if kind != "numpy":       # the dense chunk's 8 rows of planes, and the other's words
        assert fill.tags["upload_bytes"] == (
            8 * n_slices * PLANE_WORDS * 4 + 16 * _pow4(fill.tags["words"]))
    h.close()


def test_one_walk_feeds_the_block_and_the_word_list(tmp_path, monkeypatch):
    """(g) ``RowPieces``: the word list of a block is what ``fill`` writes
    besides the dense pieces - the words that are not zero, once each,
    equal words OR-ed - and it is computed once, however many consumers."""
    h, ex, cols_of, _ = _tall_frame(tmp_path, monkeypatch, "numpy")
    rows = [3, -1, 9, 4000]
    pieces = ex._walk_block("i", "stargazer", "standard", [0, 1], rows)
    word, bits = pieces.words()
    assert pieces.words()[0] is word and not pieces.dense
    block = ex._densify_block("i", "stargazer", "standard", [0, 1], rows)
    flat = block.reshape(-1)
    assert (np.flatnonzero(flat) == np.sort(word)).all() and len(set(word.tolist())) == len(word)
    assert (flat[word] == bits).all()
    assert int(np.bitwise_count(bits).sum()) == len(cols_of[3]) + len(cols_of[9])
    # two bits of one word: one word, both bits
    fr = h.index("i").frame("stargazer")
    fr.set_bit("standard", 200, 64)
    fr.set_bit("standard", 200, 65)
    word, bits = ex._walk_block("i", "stargazer", "standard", [0], [200]).words()
    assert word.tolist() == [2] and bits.tolist() == [3]
    h.close()


# -- a block's walk reads the view's columns (core/columns.py) ---------------

WALK_ROWS = [5, 7, -1, 4000, 40, 127]     # -1: a bucket's tail; 4000: no such row


def _dict_walk(h, slices, rows, row_major):
    """The block's pieces by ``Fragment.walk_rows`` alone: the parent's walk."""
    from pilosa_tpu.core.fragment import RowPieces

    pieces = RowPieces(rows, stride=len(slices) if row_major else 1)
    for bi, s in enumerate(slices):
        h.fragment("i", "stargazer", "standard", s).walk_rows(
            pieces, bi if row_major else bi * len(rows))
    return pieces


def _assert_same_block(h, got, slices, rows, row_major):
    """``got`` equals the dict walk's pieces - the word list as a set of
    (word, bits), the dense pieces, the filled block - and the block
    equals ``row_dense`` plane for plane."""
    want = _dict_walk(h, slices, rows, row_major)
    (gw, gb), (ww, wb) = got.words(), want.words()
    assert sorted(zip(gw.tolist(), gb.tolist())) == sorted(zip(ww.tolist(), wb.tolist()))
    assert sorted(w0 for w0, _ in got.dense) == sorted(w0 for w0, _ in want.dense)
    shape = (len(rows), len(slices)) if row_major else (len(slices), len(rows))
    blocks = [np.zeros(shape + (PLANE_WORDS,), dtype=np.uint32) for _ in range(2)]
    got.fill(blocks[0])
    want.fill(blocks[1])
    assert (blocks[0] == blocks[1]).all()
    for bi, s in enumerate(slices):
        frag = h.fragment("i", "stargazer", "standard", s)
        for k, r in enumerate(rows):
            plane = blocks[0][k, bi] if row_major else blocks[0][bi, k]
            dense = frag.row_dense(r) if r >= 0 else np.zeros(PLANE_WORDS, dtype=np.uint32)
            assert (plane == dense).all(), (s, r)
    return blocks[0]


def _walk_counters(stats):
    return tuple(_counter(stats, "walk." + n)
                 for n in ("fragments_snapshot", "fragments_dict", "snapshot_builds"))


@pytest.mark.parametrize("case", ["arrays", "bitmap_container", "bulk_overlay", "set_bit"])
@pytest.mark.parametrize("row_major", [False, True], ids=["slice_major", "row_major"])
@pytest.mark.parametrize("kind", ["numpy", "jax", "mesh"])
def test_a_block_from_the_columns_equals_the_dict_walk(tmp_path, monkeypatch, kind, row_major, case):
    """(h) ``_walk_block`` reads a fragment from the view's columns from
    its second walk at one generation on, and what it returns equals the
    walk of the dicts (``words()``, ``fill()``, ``row_dense``): array
    containers only; a bitmap container among the block's keys (that
    fragment falls back, the dense piece is there); a pending bulk
    overlay; a ``SetBit`` (the written fragment's part is not used on
    the next walk, the others' are; the walk after rebuilds it, with the
    bit).  The three counters say which way each fragment went."""
    h, ex, cols_of, stats = _tall_frame(tmp_path, monkeypatch, kind)
    n = 4 if kind == "mesh" else 2
    slices = list(range(n))
    frag0 = h.fragment("i", "stargazer", "standard", 0)
    if case == "bitmap_container":
        dense = np.random.default_rng(3).choice(1 << 16, size=5000, replace=False) + (1 << 17)
        frag0.set_bits(np.full(len(dense), 5, dtype=np.uint64), dense.astype(np.uint64))
    elif case == "bulk_overlay":
        frag0.bulk_or_words(np.array([7], dtype=np.uint64), np.array([3]),
                            np.array([3, 2048 + 7, PLANE_WORDS - 1]),
                            np.array([0x80000001, 0xF0, 0x1], dtype=np.uint32))
        assert 7 in frag0._bulk_planes

    def walk(rows=WALK_ROWS):
        pieces = ex._walk_block("i", "stargazer", "standard", slices, rows, row_major)
        _assert_same_block(h, pieces, slices, rows, row_major)
        return pieces

    first = walk()
    assert first.served == 0 and first.cols is None
    assert _walk_counters(stats) == (0, n, 0)            # the first walk: every dict, no part built
    second = walk()                                      # the second at these generations: every part
    fell_back = 0 if case in ("arrays", "set_bit") else 1
    assert second.served == n - fell_back and second.cols is not None
    assert bool(second.dense) == bool(fell_back)
    assert _walk_counters(stats) == (n - fell_back, n + fell_back, n)
    third = walk()                                       # the write epoch has not moved: no fragment is looked at
    assert third.served == n - fell_back
    assert _walk_counters(stats) == (2 * (n - fell_back), n + 2 * fell_back, n)
    before = _walk_counters(stats)
    if case == "bitmap_container":
        # rows without the bitmap container's: the fragment is the columns' again
        assert walk([7, -1, 40, 9]).served == n and not walk([7, -1, 40, 9]).dense
        assert _walk_counters(stats) == (before[0] + 2 * n, before[1], n)
    elif case == "bulk_overlay":
        # the overlay is paid (a write: the generation moves), two walks later the part serves
        assert frag0.materialize_bulk() == 1
        assert [walk().served for _ in range(3)] == [n - 1, n, n]
        assert _walk_counters(stats) == (before[0] + 3 * n - 1, before[1] + 1, n + 1)
    elif case == "set_bit":
        fr = h.index("i").frame("stargazer")
        col = (n - 1) * SLICE_WIDTH + 40 * 1021 + 3      # a new bit of row 40, in the last slice
        assert fr.set_bit("standard", 40, col)
        written = walk()
        assert written.served == n - 1                   # the written fragment: its dict, once
        assert _walk_counters(stats) == (before[0] + n - 1, before[1] + 1, n)
        rebuilt = walk()                                 # quiet for two walks: rebuilt, with the bit
        assert rebuilt.served == n and _walk_counters(stats) == (before[0] + 2 * n - 1, before[1] + 1, n + 1)
        block = np.zeros(((len(WALK_ROWS), n) if row_major else (n, len(WALK_ROWS))) + (PLANE_WORDS,), np.uint32)
        rebuilt.fill(block)
        plane = block[4, n - 1] if row_major else block[n - 1, 4]
        local = col % SLICE_WIDTH
        assert plane[local >> 5] >> (local & 31) & 1
    assert _counter(stats, "walk.snapshot_bytes") > 0    # the gauge: host bytes the columns hold
    h.close()


def test_a_walk_of_some_fragments_builds_no_part(tmp_path, monkeypatch):
    """(i) A part is built by a walk that crosses every fragment of the
    view, and by no other: a repair's fetch of the slice just written,
    made twice at one generation (two readers that took different
    generations), walks the dict both times; the parts that are there
    serve a walk of some fragments too."""
    h, ex, _, stats = _tall_frame(tmp_path, monkeypatch, "numpy")
    fr = h.index("i").frame("stargazer")

    def walk(slices):
        pieces = ex._walk_block("i", "stargazer", "standard", slices, WALK_ROWS)
        _assert_same_block(h, pieces, slices, WALK_ROWS, False)
        return pieces.served

    assert [walk([1]) for _ in range(3)] == [0, 0, 0]
    assert _walk_counters(stats) == (0, 3, 0)
    assert [walk([0, 1]) for _ in range(2)] == [1, 2]    # slice 1 was walked before at its generation
    assert _walk_counters(stats) == (3, 4, 2)
    assert walk([1]) == 1 and _walk_counters(stats) == (4, 4, 2)
    assert fr.set_bit("standard", 40, SLICE_WIDTH + 40 * 1021 + 3)
    assert [walk([1]) for _ in range(3)] == [0, 0, 0]    # the written slice, fetched again and again
    assert _walk_counters(stats) == (4, 7, 2)
    assert walk([0, 1]) == 2 and _walk_counters(stats) == (6, 7, 3)
    h.close()


def test_one_bucketing_rule_each():
    """Rows uploaded pad to powers of two, a gather's batch to powers of
    four; the padded batch repeats its first tuple."""
    from pilosa_tpu import rowpool
    from pilosa_tpu.engine import JaxEngine, NumpyEngine, _padded_batch, _pow2, _pow4

    assert rowpool._pow2 is _pow2
    assert [_pow2(n) for n in (1, 2, 3, 5, 9, 33)] == [1, 2, 4, 8, 16, 64]
    assert [_pow4(n) for n in (1, 2, 4, 5, 16, 17, 64, 65)] == [1, 4, 4, 16, 16, 64, 64, 256]
    pairs = np.array([[3, 4], [5, 6], [7, 8]], dtype=np.int32)
    assert _padded_batch(pairs).tolist() == [[3, 4], [5, 6], [7, 8], [3, 4]]
    assert _padded_batch(pairs[:1]) .tolist() == [[3, 4]]
    assert NumpyEngine().gather_bucket(3) == 3 and JaxEngine.gather_bucket(None, 3) == 4
