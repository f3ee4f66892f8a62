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
