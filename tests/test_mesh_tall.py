"""A tall frame read across a four-device slice mesh (``engine = "mesh"``):
what ``seg256.tall_pairs`` does on four chips with 8,192 rows, 256 slices and
256 slots, here seeded and small - 160 rows x 8 slices (two a device), a pool
of 64 slots by ``PILOSA_TPU_POOL_BYTES`` - through the door, against the
benchmark's plain reference (``benchmark/lib/oracle.py``'s ``SetOracle``:
sorted column arrays per row, nothing of the program).  Reads evict across
the sharded pool; chunks go up sparse at two word buckets and dense; the four
ops and plain row counts are exact whichever rows were resident.  Then the
engines' un-fetched ``_dev`` forms against their blocking forms (pairs, multi,
tree; numpy, jax, mesh under the kernels and under the jnp form), and the
order of a sampled pass on the mesh: every gather dispatch goes out before the
host first waits for the mesh.

Pallas kernels run in interpret mode (``PILOSA_TPU_PALLAS_INTERPRET=1``); the
mesh is the first four of the eight virtual CPU devices ``conftest.py`` makes.
"""

import http.client
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from pilosa_tpu.config import Config
from pilosa_tpu.pilosa import SLICE_WIDTH

N_SLICES, N_ROWS, HOT, SLOTS, DEVICES = 8, 160, 40, 64, 4
PLANE_WORDS = SLICE_WIDTH // 32
OPS = ("Intersect", "Union", "Difference", "Xor")
FAT_ROWS = (150, 151)        # 200 bits a slice: a chunk with one is in the next word bucket
BITMAP_ROW = 152             # 5,000 bits in one container of slice 3: a bitmap container, a dense chunk


def _oracle_mod():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "lib", "oracle.py")
    spec = importlib.util.spec_from_file_location("bench_lib_oracle", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _frame_bits(seed=34):
    """(rows, cols) uint64: 1-5 bits per (row, slice) in windows of a pool of
    96 columns a slice, so that some pairs intersect; two fat rows; one row
    with a bitmap container."""
    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for s in range(N_SLICES):
        pool = rng.choice(SLICE_WIDTH, size=96, replace=False)
        for r in range(N_ROWS):
            if r in FAT_ROWS:
                local = rng.choice(SLICE_WIDTH, size=200, replace=False)
            else:
                local = pool[(int(rng.integers(0, 96)) + np.arange(1 + (r * 3) % 5)) % 96]
            rows += [r] * len(local)
            cols += (local + s * SLICE_WIDTH).tolist()
    dense = rng.choice(1 << 16, size=5000, replace=False) + (1 << 17) + 3 * SLICE_WIDTH
    rows += [BITMAP_ROW] * len(dense)
    cols += dense.tolist()
    return np.array(rows, dtype=np.uint64), np.array(cols, dtype=np.uint64)


def _post(host, body, trace=False):
    conn = http.client.HTTPConnection(host, timeout=300)
    try:
        conn.request("POST", "/index/bench/query", body.encode(),
                     {"X-Pilosa-Trace": "1"} if trace else {})
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        assert resp.status == 200, payload
        spans = resp.getheader("X-Pilosa-Trace-Spans")
        return payload["results"], (json.loads(spans) if spans else None)
    finally:
        conn.close()


def _bitmap(r):
    return f'Bitmap(frame="stargazer", rowID={r})'


def _body(calls):
    return " ".join(f"Count({_bitmap(c[1])})" if c[0] == "Row"
                    else f"Count({c[0]}({_bitmap(c[1])}, {_bitmap(c[2])}))" for c in calls)


def _want(oracle, calls):
    return [len(oracle.cols(c[1])) if c[0] == "Row" else oracle.count(*c) for c in calls]


def _find(node, name):
    out = []
    for n in node if isinstance(node, list) else [node]:
        if n["name"] == name:
            out.append(n)
        out.extend(_find(n.get("children", []), name))
    return out


@pytest.fixture(scope="module")
def mesh_of_four():
    from pilosa_tpu import engine as engine_mod
    from pilosa_tpu import executor as executor_mod

    real = engine_mod.new_engine

    def four(name="auto"):
        if engine_mod.engine_name(name) == "mesh":
            return engine_mod.MeshEngine(devices=jax.devices()[:DEVICES])
        return real(name)

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PILOSA_TPU_PALLAS_INTERPRET", "1")
        mp.setenv("PILOSA_TPU_POOL_BYTES", str(N_SLICES * SLOTS * PLANE_WORDS * 4))
        mp.setattr(executor_mod, "new_engine", four)
        yield mp


@pytest.fixture(scope="module")
def served(mesh_of_four, tmp_path_factory):
    """The server on the mesh over the seeded frame, loaded through
    ``ingest``; the reference; and, for each of the pool's sparse
    scatters, the word bucket of its chunk and that of its largest
    device share, which the program ran at."""
    from pilosa_tpu.engine import MeshEngine, _pow4
    from pilosa_tpu.server.client import Client
    from pilosa_tpu.server.server import Server

    buckets = []
    scatter = MeshEngine._scatter_words

    def noted(self, matrix, slots, cells, values, axis, donate):
        out, device_words = scatter(self, matrix, slots, cells, values, axis, donate)
        if (slots >= 0).any():                   # a miss's, not the warm-up's
            buckets.append((_pow4(len(values)), _pow4(device_words)))
        return out, device_words

    mesh_of_four.setattr(MeshEngine, "_scatter_words", noted)
    rows, cols = _frame_bits()
    server = Server(Config(data_dir=str(tmp_path_factory.mktemp("mesh_tall")),
                           host="127.0.0.1:0", engine="mesh"))
    server.open()
    c = Client(server.host, timeout=300)
    c.create_index("bench")
    c.create_frame("bench", "stargazer")
    c.ingest_stream("bench", "stargazer", rows, cols, door="ingest")
    yield {"host": server.host, "server": server, "buckets": buckets,
           "oracle": _oracle_mod().SetOracle(rows, cols)}
    server.close()


def _calls(rng, n=8):
    """Pairs whose rows are hot 9 draws in 10, ops cycling."""
    out = []
    for i in range(n):
        a, b = (int(rng.integers(0, HOT)) if rng.random() < 0.9 else int(rng.integers(HOT, N_ROWS))
                for _ in range(2))
        out.append((OPS[i % 4], a, b if b != a else (a + 1) % N_ROWS))
    return out


def test_the_served_mesh_pages_and_answers_exactly(served):
    """Bodies with a cold tail through the door: every count equals the
    reference's whichever rows were resident; the pool is sharded over four
    devices, a fraction of the rows, and evicted on the way."""
    host, oracle = served["host"], served["oracle"]
    status = _get_status(host)
    assert (status["engine"], status["count"]) == ("mesh", DEVICES)
    rng = np.random.default_rng(7)
    for _ in range(40):
        calls = _calls(rng)
        assert _post(host, _body(calls))[0] == _want(oracle, calls)
    (pool,) = served["server"].executor._matrix_cache.values()
    assert pool.cap == pool.cap_max == SLOTS < N_ROWS
    assert pool.engine.slice_axis_devices(pool.n_slices) == DEVICES
    assert len(pool.matrix.sharding.device_set) == DEVICES
    assert pool.stat_evictions > 0 and pool.stat_misses > pool.stat_evictions
    stats = _get(host, "/debug/vars")
    assert stats["gather.dispatches"] > 0
    assert 0 < stats["gather.mesh_fetches"] <= stats["gather.dispatches"]


def test_plain_row_counts_and_all_four_ops_on_cold_rows(served):
    host, oracle = served["host"], served["oracle"]
    cold = list(range(N_ROWS - 24, N_ROWS))         # the fat rows and the bitmap row among them
    calls = [("Row", r) for r in cold[:8]]
    assert _post(host, _body(calls))[0] == _want(oracle, calls)
    for op in OPS:
        calls = [(op, a, b) for a, b in zip(cold[::2], cold[1::2])] + [(op, 3, BITMAP_ROW)]
        got = _post(host, _body(calls))[0]
        assert got == _want(oracle, calls), op
    assert oracle.count("Union", 3, BITMAP_ROW) > 5000      # the dense row was counted whole


def test_chunks_went_sparse_at_two_word_buckets_and_dense(served):
    """The reads above paged chunks of 1-5 bits a (row, slice) (bucket 64 or
    256), chunks with a fat row (4,096) and the chunk with the bitmap
    container (dense).  Every sparse chunk went to the four devices as
    their own shares: a fat row's 200 words a slice make a share of two
    slices at most 1,024, so its chunk of 4,096 ran at 1,024.  (The reads
    above paged the fat rows in one chunk with the bitmap row, so a pair
    body here pages them again without it; the warm-up's scatters, every
    slot dropped, are not counted.)"""
    host, oracle = served["host"], served["oracle"]
    fill = [(OPS[i % 4], 2 * i, 2 * i + 1) for i in range(SLOTS // 2)]   # evicts the fat rows
    assert _post(host, _body(fill))[0] == _want(oracle, fill)
    fat = [(OPS[i % 4], FAT_ROWS[i % 2], 64 + i) for i in range(8)]   # a body not sent before
    assert _post(host, _body(fat))[0] == _want(oracle, fat)
    stats = _get(host, "/debug/vars")
    assert stats["rowpool.miss_chunks_sparse"] > 0 and stats["rowpool.miss_chunks_dense"] > 0
    assert stats["rowpool.miss_chunks_split"] == stats["rowpool.miss_chunks_sparse"]
    met = {chunk for chunk, _ in served["buckets"]}
    assert 4096 in met and met & {16, 64, 256}, sorted(met)
    assert all(share <= chunk for chunk, share in served["buckets"])
    assert {share for chunk, share in served["buckets"] if chunk == 4096} == {1024}


def test_a_sampled_read_on_the_mesh_says_where_it_waited(served):
    """Through the door: the traced request's ``pool.miss`` and gather
    ``device`` spans are tagged ``devices: 4`` and its wait for the mesh is
    a ``mesh.fetch`` under the root."""
    host, oracle = served["host"], served["oracle"]
    calls = [(OPS[i % 4], 100 + i, 120 + i) for i in range(8)]
    results, root = _post(host, _body(calls), trace=True)
    assert results == _want(oracle, calls)
    (miss,) = _find(root, "pool.miss")
    assert miss["tags"]["devices"] == DEVICES and 8 <= miss["tags"]["rows"] <= 16   # a few may be resident
    gathers = [d for d in _find(root, "device") if d["tags"]["lane"] == "gather"]
    assert len(gathers) == 4 and all(g["tags"]["devices"] == DEVICES for g in gathers)
    fetches = _find(root, "mesh.fetch")
    assert len(fetches) == 4
    assert max(g["start_ms"] for g in gathers) <= min(f["start_ms"] for f in fetches)


def test_a_sharded_miss_no_reader_holds_writes_the_pool_in_place(served):
    """Reads through the door give their hold on the sharded pool back once
    their counts are on the host, so a miss finds none out: its first chunk
    is scattered into every device's shard where it lies (the array it
    replaced is gone, and no miss of the server copied the pool), and the
    answers stay exact."""
    host, oracle = served["host"], served["oracle"]
    fill = [(OPS[i % 4], 2 * i, 2 * i + 1) for i in range(SLOTS // 2)]
    assert _post(host, _body(fill))[0] == _want(oracle, fill)
    (pool,) = served["server"].executor._matrix_cache.values()
    assert pool.cap == SLOTS                                      # full: the miss below evicts
    before, stats0 = pool.matrix, _get(host, "/debug/vars")
    calls = [(OPS[i % 4], 101 + i, 131 + i) for i in range(8)]    # a body no test sent: no cached answer
    results, root = _post(host, _body(calls), trace=True)
    assert results == _want(oracle, calls)
    (miss,) = _find(root, "pool.miss")
    assert miss["tags"]["in_place"] is True and miss["tags"]["devices"] == DEVICES
    assert before.is_deleted() and not pool.matrix.is_deleted()
    assert len(pool.matrix.sharding.device_set) == DEVICES
    stats = _get(host, "/debug/vars")
    assert stats["rowpool.miss_in_place"] == stats0["rowpool.miss_in_place"] + 1
    assert stats.get("rowpool.miss_copied", 0) == 0


# -- a sparse miss's words, device by device ------------------------------------

SHARES = {   # words a device's slices hold in the chunk: the largest share sets the bucket
    "all_on_one_device": (0, 0, 37, 0),
    "a_device_with_none": (9, 40, 0, 3),
    "a_bucket_tail": (5, 16, 17, 1),
}


@pytest.mark.parametrize("donate", [False, True], ids=["copied", "in_place"])
@pytest.mark.parametrize("case", list(SHARES))
def test_a_sparse_miss_sends_each_device_its_own_slices_words(case, donate):
    """``set_words_at`` on a pool of 8 slices over four devices: the words
    cross as one array sharded on the slice axis, each device's row its
    own slices' words alone (their slices local), padded to the ``_pow4``
    bucket of the largest share, then the chunk's slots; the pool that
    comes back is, bit for bit, what the numpy engine writes, the slots'
    old rows gone."""
    from pilosa_tpu.engine import MeshEngine, NumpyEngine, _pow4
    from pilosa_tpu.parallel import sharded

    engine = MeshEngine(devices=jax.devices()[:DEVICES])
    host = _host_matrix(seed=5, s=N_SLICES, r=16, w=1024)
    rng = np.random.default_rng(sorted(SHARES).index(case))
    per = N_SLICES // DEVICES
    cells = []
    for d, n in enumerate(SHARES[case]):
        si = d * per + rng.integers(0, per, size=n)
        slot = rng.choice([2, 5, 11], size=n)
        cells.append(np.stack([si, slot, np.zeros(n, dtype=np.int64)], axis=1))
    cells = np.concatenate(cells)
    for key in {(a, b) for a, b, _ in cells}:        # distinct words in each (slice, slot)
        at = np.flatnonzero((cells[:, 0] == key[0]) & (cells[:, 1] == key[1]))
        cells[at, 2] = rng.choice(1024, size=len(at), replace=False)
    order = rng.permutation(len(cells))              # not in slice order
    cells = cells[order].astype(np.int32)
    values = rng.integers(1, 1 << 32, size=len(cells), dtype=np.uint64).astype(np.uint32)
    slots = np.array([2, 5, 11, -1, -1, -1, -1, -1], dtype=np.int32)

    seen = []
    real = sharded.sharded_set_row_words

    def noted(mesh, row_matrix, shares, n_slots, donate=False):
        seen.append((shares, n_slots))
        return real(mesh, row_matrix, shares, n_slots, donate)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sharded, "sharded_set_row_words", noted)
        matrix = engine.matrix(host)
        up0 = engine.stat_upload_bytes
        out, device_words = engine.set_words_at(matrix, slots, cells, values, donate=donate)
    want, _ = NumpyEngine().set_words_at(host.copy(), slots[slots >= 0], cells, values)
    got = np.asarray(out).reshape(want.shape)
    assert np.array_equal(got, want)
    assert matrix.is_deleted() == donate
    assert len(out.sharding.device_set) == DEVICES

    bucket = _pow4(max(SHARES[case]))
    assert device_words == max(SHARES[case])
    ((shares, n_slots),) = seen
    assert n_slots == len(slots) and shares.shape == (DEVICES, 4 * bucket + len(slots))
    assert engine.stat_upload_bytes - up0 == shares.size * 4   # each word once, not once a device
    by_device = {s.device: np.asarray(s.data) for s in shares.addressable_shards}
    for d, dev in enumerate(engine.mesh.mesh.devices.flat):
        row = by_device[dev]
        assert row.shape == (1, 4 * bucket + len(slots))     # this device's row alone
        assert row[0, 4 * bucket :].tolist() == slots.tolist()
        si, slot, word, bits = row[0, : 4 * bucket].reshape(4, bucket)
        n = SHARES[case][d]
        mine = (cells[:, 0] // per) == d
        assert (si[:n] == cells[mine, 0] - d * per).all()
        assert (slot[:n] == cells[mine, 1]).all() and (word[:n] == cells[mine, 2]).all()
        assert (bits[:n].view(np.uint32) == values[mine]).all()
        tail = np.stack([si[n:], slot[n:], word[n:]])       # the bucket's tail
        assert (tail == -1).all() and (bits[n:] == 0).all()


def _get(host, path):
    conn = http.client.HTTPConnection(host, timeout=60)
    try:
        conn.request("GET", path)
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


def _get_status(host):
    return _get(host, "/status")["status"]["device"]


# -- the un-fetched forms against the blocking forms ---------------------------

ENGINES = ("numpy", "jax", "mesh", "mesh_jnp")


def _engine(kind, monkeypatch):
    from pilosa_tpu.engine import MeshEngine, new_engine

    if not kind.startswith("mesh"):
        return new_engine(kind)
    if kind == "mesh":
        monkeypatch.setenv("PILOSA_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PILOSA_TPU_PALLAS_INTERPRET", raising=False)
    return MeshEngine(devices=jax.devices()[:DEVICES])


def _host_matrix(seed=3, s=8, r=12, w=1024):
    rng = np.random.default_rng(seed)
    m = rng.integers(0, 1 << 32, size=(s, r, w), dtype=np.uint64).astype(np.uint32)
    return m & rng.integers(0, 1 << 32, size=(s, r, w), dtype=np.uint64).astype(np.uint32)


def _popcount(x):
    return int(np.unpackbits(np.ascontiguousarray(x).view(np.uint8)).sum())


_NP = {"and": np.bitwise_and, "or": np.bitwise_or, "xor": np.bitwise_xor,
       "andnot": lambda a, b: a & ~b}


@pytest.mark.parametrize("form", ["pairs", "multi", "tree"])
@pytest.mark.parametrize("kind", ENGINES)
def test_unfetched_forms_equal_the_blocking_forms(kind, form, monkeypatch):
    """``gather_count*_dev`` enqueues and hands back the engine's array;
    fetched through ``to_numpy`` and cut to the batch it is the blocking
    form's answer bit for bit, and both are the plain popcount's."""
    engine = _engine(kind, monkeypatch)
    host = _host_matrix()
    matrix = engine.matrix(host)
    rng = np.random.default_rng(11)
    if form == "pairs":
        pairs = rng.integers(0, host.shape[1], size=(5, 2)).astype(np.int32)   # bucket 16 where padded
        for op in ("and", "or", "xor", "andnot"):
            dev = engine.gather_count_dev(op, matrix, pairs)
            got = engine.to_numpy(dev)[: len(pairs)].astype(np.int64)
            blocking = engine.gather_count(op, matrix, pairs)
            want = [_popcount(_NP[op](host[:, a], host[:, b])) for a, b in pairs]
            assert blocking.dtype == np.int64 and got.tolist() == blocking.tolist() == want, op
    elif form == "multi":
        idx = rng.integers(0, host.shape[1], size=(4, 4)).astype(np.int32)
        for op in ("and", "or"):
            dev = engine.gather_count_multi_dev(op, matrix, idx)
            got = engine.to_numpy(dev).astype(np.int64)
            blocking = engine.gather_count_multi(op, matrix, idx)
            want = []
            for row in idx:
                acc = host[:, row[0]]
                for r in row[1:]:
                    acc = _NP[op](acc, host[:, r])
                want.append(_popcount(acc))
            assert got.tolist() == blocking.tolist() == want, op
    else:
        from pilosa_tpu.ops.bitwise import np_gather_count_tree

        leaves = rng.integers(0, host.shape[1], size=(4, 4)).astype(np.int32)
        opc = rng.integers(0, 4, size=(4, 3)).astype(np.int32)
        dev = engine.gather_count_tree_dev(matrix, leaves, opc)
        got = engine.to_numpy(dev).astype(np.int64)
        blocking = engine.gather_count_tree(matrix, leaves, opc)
        assert got.tolist() == blocking.tolist() == np_gather_count_tree(host, leaves, opc).tolist()
    if kind.startswith("mesh"):
        # nothing was fetched on the way: the engine's array, on every device of the mesh
        assert isinstance(dev, jax.Array) and len(dev.sharding.device_set) == DEVICES


# -- the order of a pass --------------------------------------------------------


def test_every_gather_dispatch_goes_out_before_the_first_mesh_fetch(tmp_path, monkeypatch):
    """A sampled pass of four ops through ``Executor.execute`` on the mesh:
    four gather ``device`` spans, each begun before the first ``mesh.fetch``
    begins (the host waits for the mesh once the pass's last dispatch has
    gone out, not once an op), and the waits are the root's own children."""
    from pilosa_tpu.core.frame import FrameOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.engine import MeshEngine
    from pilosa_tpu.executor import ExecOptions, Executor
    from pilosa_tpu.stats import ExpvarStatsClient
    from pilosa_tpu.trace import Span

    monkeypatch.setenv("PILOSA_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("PILOSA_TPU_POOL_BYTES", str(4 * 16 * PLANE_WORDS * 4))
    h = Holder(str(tmp_path / "data"))
    h.open()
    h.create_index("i").create_frame("stargazer", FrameOptions())
    fr = h.index("i").frame("stargazer")
    cols_of = {r: {s * SLICE_WIDTH + 1021 * ((r + k) % 7) for s in range(4) for k in range(1 + r % 3)}
               for r in range(12)}
    for r, cs in cols_of.items():
        for c in sorted(cs):
            fr.set_bit("standard", r, c)
    stats = ExpvarStatsClient()
    ex = Executor(h, engine=MeshEngine(devices=jax.devices()[:DEVICES]), stats=stats)
    sets = {"Intersect": set.__and__, "Union": set.__or__, "Difference": set.__sub__, "Xor": set.__xor__}
    calls = [(OPS[i % 4], i, i + 4) for i in range(8)]
    root = Span("root")
    got = ex.execute("i", " ".join(
        f'Count({op}(Bitmap(rowID={a}, frame="stargazer"), Bitmap(rowID={b}, frame="stargazer")))'
        for op, a, b in calls), opt=ExecOptions(span=root))
    assert got == [len(sets[op](cols_of[a], cols_of[b])) for op, a, b in calls]

    def walk(sp):
        yield sp
        for c in sp.children:
            yield from walk(c)

    spans = list(walk(root))
    gathers = [s for s in spans if s.name == "device" and s.tags.get("lane") == "gather"]
    fetches = [s for s in spans if s.name == "mesh.fetch"]
    assert len(gathers) == 4 and len(fetches) == 4
    assert all(g.tags["devices"] == DEVICES and g.ms is not None for g in gathers)
    assert max(g.t0 for g in gathers) < min(f.t0 for f in fetches)
    assert all(g.t0 + g.ms / 1e3 <= fetches[0].t0 for g in gathers)     # and each had returned
    assert all(f in root.children and f.ms is not None for f in fetches)
    assert stats.snapshot()["gather.mesh_fetches"] == 4 == stats.snapshot()["gather.dispatches"]
    h.close()


# -- TopN's "topn" lane on the mesh -------------------------------------------------


def test_topn_through_the_topn_lane_with_a_miss_between_its_phases(tmp_path, monkeypatch):
    """A TopN over four slices on the mesh scores its candidates through the
    pool's "topn" lane, chunk by chunk; each chunk gives its hold back once
    its counts are on the host.  Between phase 1 and phase 2 other rows page
    in and evict every candidate, so phase 2 misses too; every miss writes
    into the pool where it lies, and each answer equals the host path's
    (the numpy engine: the fragment's own scoring)."""
    from pilosa_tpu.core.fragment import TOPN_SCORE_CHUNK
    from pilosa_tpu.core.frame import FrameOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.engine import MeshEngine
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.stats import ExpvarStatsClient

    n_slices, n_rows = 4, 600                    # three chunks of candidates a slice
    monkeypatch.delenv("PILOSA_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setenv("PILOSA_TPU_POOL_BYTES", str(n_slices * TOPN_SCORE_CHUNK * PLANE_WORDS * 4))
    h = Holder(str(tmp_path / "data"))
    h.open()
    h.create_index("i").create_frame("f", FrameOptions())
    fr = h.index("i").frame("f")
    rng = np.random.default_rng(40)
    for s in range(n_slices):
        window = rng.choice(SLICE_WIDTH, size=64, replace=False)
        for r in range(n_rows):                  # four bits a (row, slice): no early stop
            for c in rng.choice(window, size=4, replace=False):
                fr.set_bit("standard", r, int(s * SLICE_WIDTH + c))
    stats = ExpvarStatsClient()
    ex = Executor(h, engine=MeshEngine(devices=jax.devices()[:DEVICES]), stats=stats)
    host = Executor(h, engine="numpy")
    phase = ex._topn_phase

    def evicting(n, *args, **kw):
        out = phase(n, *args, **kw)
        if n == 1:                               # page other rows over every candidate
            pool = ex._pool_for("i", "f", "standard", list(range(n_slices)), lane="topn")
            _, m, _ = pool.acquire(list(range(n_rows, n_rows + TOPN_SCORE_CHUNK)), pool.gens)
            pool.release(m)
        return out

    monkeypatch.setattr(ex, "_topn_phase", evicting)
    for src in (0, 17):
        q = f'TopN(Bitmap(rowID={src}, frame="f"), frame="f", n=5)'
        got, want = ex.execute("i", q), host.execute("i", q)
        assert [(p.id, p.count) for p in got[0]] == [(p.id, p.count) for p in want[0]]
        assert len(got[0]) == 5 and got[0][0].id == src
    snap = stats.snapshot()
    assert snap["rowpool.miss_in_place"] >= 2 * (3 + 1 + 1) and snap.get("rowpool.miss_copied", 0) == 0
    assert snap["topn.dense_fallbacks"] > 0
    h.close()
