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
    ``ingest``; the reference; and the word buckets the pool's sparse
    scatters were padded to."""
    from pilosa_tpu.engine import MeshEngine
    from pilosa_tpu.server.client import Client
    from pilosa_tpu.server.server import Server

    buckets = []
    scatter = MeshEngine._scatter_words

    def noted(self, matrix, slots, cells, values, axis, donate):
        buckets.append(len(values))
        return scatter(self, matrix, slots, cells, values, axis, donate)

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
    container (dense)."""
    stats = _get(served["host"], "/debug/vars")
    assert stats["rowpool.miss_chunks_sparse"] > 0 and stats["rowpool.miss_chunks_dense"] > 0
    met = {b for b in served["buckets"]}
    assert 4096 in met and met & {16, 64, 256}, sorted(met)


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
