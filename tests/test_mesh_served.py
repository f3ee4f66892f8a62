"""The dashboard index served from a four-device slice mesh
(``engine = "mesh"``), through the door, against a plain reference: sets of
columns per row.  Seeded random data at a small size (8 slices, two a
device; 48 rows); pair counts over the four ops; ``SetBit`` bursts that land
on one device's slices and on several devices', each followed by its
read-back.  The same requests against ``engine = "jax"`` give the same
answers: everything above the engine is shared.  Then the row pool's budget
rule (2 GiB per device that shares the slice axis), and what the mesh's
repair reports of itself: tags, the ``mesh.fetch`` span, counters, /status;
and the repair's compiled step under ``shard_map`` against the numpy engine's
composed form, burst by burst.

Pallas kernels run in interpret mode (``PILOSA_TPU_PALLAS_INTERPRET=1``, as
``tests/test_parallel.py`` runs the mesh tier); the mesh is the first four
of the eight virtual CPU devices ``conftest.py`` makes.
"""

import http.client
import json

import numpy as np
import pytest

from pilosa_tpu.config import Config
from pilosa_tpu.pilosa import SLICE_WIDTH

N_SLICES, N_ROWS, DEVICES = 8, 48, 4
OPS = {"Intersect": set.__and__, "Union": set.__or__,
       "Difference": set.__sub__, "Xor": set.__xor__}
ENGINES = ("mesh", "jax")


def _frame_bits(seed=28):
    """{row: set of columns}: 20-60 bits per (row, slice), drawn from a
    window of 512 columns a slice so that pairs intersect."""
    rng = np.random.default_rng(seed)
    ref = {r: set() for r in range(N_ROWS)}
    for s in range(N_SLICES):
        window = rng.choice(SLICE_WIDTH, size=512, replace=False)
        for r in range(N_ROWS):
            cols = rng.choice(window, size=int(rng.integers(20, 61)), replace=False)
            ref[r].update(int(s * SLICE_WIDTH + c) for c in cols)
    return ref


def _post(host, body, trace=False, no_cache=False):
    conn = http.client.HTTPConnection(host, timeout=120)
    try:
        headers = {"X-Pilosa-Trace": "1"} if trace else {}
        if no_cache:
            headers["X-Pilosa-No-Cache"] = "1"
        conn.request("POST", "/index/i/query", body.encode(), headers)
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        assert resp.status == 200, payload
        spans = resp.getheader("X-Pilosa-Trace-Spans")
        return payload["results"], (json.loads(spans) if spans else None)
    finally:
        conn.close()


def _get(host, path):
    conn = http.client.HTTPConnection(host, timeout=60)
    try:
        conn.request("GET", path)
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


def _pairs_body(calls):
    return " ".join(
        f'Count({op}(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
        for op, a, b in calls)


def _want(ref, calls):
    return [len(OPS[op](ref[a], ref[b])) for op, a, b in calls]


def _find(node, name):
    nodes = node if isinstance(node, list) else [node]
    out = []
    for n in nodes:
        if n["name"] == name:
            out.append(n)
        out.extend(_find(n.get("children", []), name))
    return out


class Served:
    """One server over the seeded frame, and the reference kept beside it."""

    def __init__(self, engine, data_dir):
        from pilosa_tpu.server.client import Client
        from pilosa_tpu.server.server import Server

        self.ref = _frame_bits()
        self.server = Server(Config(data_dir=data_dir, host="127.0.0.1:0", engine=engine))
        self.server.open()
        self.host = self.server.host
        c = Client(self.host, timeout=120)
        c.create_index("i")
        c.create_frame("i", "f")
        rows = np.array([r for r, cols in self.ref.items() for _ in cols], dtype=np.uint64)
        cols = np.array([c_ for _r, cs in self.ref.items() for c_ in sorted(cs)], dtype=np.uint64)
        c.ingest_stream("i", "f", rows, cols, door="ingest")
        for k in range(4):  # page every row in, build the Gram, arm the serve state
            every = [("Intersect", r, (r + 1 + k) % N_ROWS) for r in range(N_ROWS)]
            assert _post(self.host, _pairs_body(every))[0] == _want(self.ref, every)

    def set_bit(self, row, col):
        (changed,), _ = _post(self.host, f'SetBit(rowID={row}, frame="f", columnID={col})')
        assert changed == (col not in self.ref[row])
        self.ref[row].add(col)

    def pool(self):
        (pool,) = self.server.executor._matrix_cache.values()
        return pool

    def repair_of(self, calls):
        """The answers of ``calls`` and the one ``pool.repair`` span the
        read made on its way."""
        results, root = _post(self.host, _pairs_body(calls), trace=True)
        assert results == _want(self.ref, calls)
        (repair,) = _find(root, "pool.repair")
        return repair, root


@pytest.fixture(scope="module")
def mesh_of_four():
    """``engine = "mesh"`` builds its mesh from the first four virtual
    devices while this module runs (a deployment's mesh is every device
    the process has: here there are eight), with the kernels interpreted."""
    import jax

    from pilosa_tpu import engine as engine_mod
    from pilosa_tpu import executor as executor_mod

    real = engine_mod.new_engine

    def four(name="auto"):
        if engine_mod.engine_name(name) == "mesh":
            return engine_mod.MeshEngine(devices=jax.devices()[:DEVICES])
        return real(name)

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PILOSA_TPU_PALLAS_INTERPRET", "1")
        mp.delenv("PILOSA_TPU_POOL_BYTES", raising=False)
        mp.setattr(executor_mod, "new_engine", four)
        yield


@pytest.fixture(scope="module")
def served(mesh_of_four, tmp_path_factory):
    out = {e: Served(e, str(tmp_path_factory.mktemp(e))) for e in ENGINES}
    for s in out.values():
        # The first repair after paging, while a reader still holds the
        # pool's array, as one whose counts are in flight does: this one
        # may not donate it.
        pool = s.pool()
        _, held, _ = pool.acquire([0], pool.gens)
        s.set_bit(0, 4 * SLICE_WIDTH + 7)
        s.first_repair, _ = s.repair_of([("Intersect", 0, 1)])
        pool.release(held)
    yield out
    for s in out.values():
        s.server.close()


# -- (a), (b): answers through the door, on both engines ----------------------


@pytest.mark.parametrize("op", sorted(OPS))
@pytest.mark.parametrize("engine", ENGINES)
def test_served_pair_counts_equal_the_reference(served, engine, op):
    s = served[engine]
    rng = np.random.default_rng([ord(op[0]), 5])
    calls = [(op, int(a), int(b)) for a, b in rng.integers(0, N_ROWS, size=(16, 2)) if a != b]
    results, root = _post(s.host, _pairs_body(calls), trace=True)
    assert results == _want(s.ref, calls)
    # Resident rows and a warm Gram: the host lanes answer, whatever the engine.
    assert [d["tags"]["lane"] for d in _find(root, "device")] == ["native"]


def _burst_columns(burst, placement, rng):
    """Columns of a burst: on one device's slices (slices 2 and 3 are
    device 1's: the mesh shards the slice axis contiguously) or dealt over
    the devices in turn."""
    per_dev = N_SLICES // DEVICES
    if placement == "same_device":
        slices = [2 + i % per_dev for i in range(burst)]
    else:
        slices = [(i % DEVICES) * per_dev + (i // DEVICES) % per_dev for i in range(burst)]
    return [s * SLICE_WIDTH + int(rng.integers(0, SLICE_WIDTH)) for s in slices]


BURSTS = [(1, "same_device"), (2, "same_device"), (2, "different_devices"),
          (8, "same_device"), (8, "different_devices")]


@pytest.mark.parametrize("burst,placement", BURSTS)
@pytest.mark.parametrize("engine", ENGINES)
def test_setbit_burst_then_its_read_back(served, engine, burst, placement):
    """``burst`` SetBits on ``burst`` rows, then one read of those rows:
    every count equals the reference before the burst and after it."""
    s = served[engine]
    rng = np.random.default_rng([burst, len(placement)])
    rows = [int(r) for r in rng.permutation(N_ROWS)[:burst]]
    calls = [(op, r, (r + 7) % N_ROWS) for r in rows for op in sorted(OPS)]
    assert _post(s.host, _pairs_body(calls))[0] == _want(s.ref, calls)
    repairs = s.pool().stat_repairs
    for row, col in zip(rows, _burst_columns(burst, placement, rng)):
        s.set_bit(row, col)
    repair, root = s.repair_of(calls)
    assert s.pool().stat_repairs == repairs + 1 and s.pool().stat_misses == N_ROWS
    assert repair["tags"]["planes"] == burst
    # Eight slices written of eight: over half, the composed form; every
    # other burst takes the compiled step, in place (these reads want no
    # rows of the pool, so nobody holds its array), on either engine.
    stepped = (burst, placement) != (8, "different_devices")
    assert repair["tags"]["form"] == ("step" if stepped else "composed")
    assert repair["tags"]["in_place"] is stepped
    if engine == "mesh":
        assert repair["tags"]["devices"] == DEVICES
        (gram,) = _find(repair, "pool.gram")
        fetches = _find(gram, "mesh.fetch")
        # The step: one wait for the psummed delta.
        assert (len(fetches) == 1 if stepped else fetches)
        assert sum(f["ms"] for f in fetches) <= gram["ms"]
    else:
        assert repair["tags"]["devices"] == 1 and not _find(root, "mesh.fetch")


@pytest.mark.parametrize("op", sorted(OPS))
def test_mesh_and_jax_give_the_same_answers(served, op):
    """The two servers saw the same loads and the same writes."""
    assert served["mesh"].ref == served["jax"].ref
    calls = [(op, a, (a * 5 + 3) % N_ROWS) for a in range(N_ROWS) if a != (a * 5 + 3) % N_ROWS]
    body = _pairs_body(calls)
    assert _post(served["mesh"].host, body)[0] == _post(served["jax"].host, body)[0]


def test_mesh_server_says_what_it_serves_from(served):
    s = served["mesh"]
    dev = _get(s.host, "/status")["status"]["device"]
    assert (dev["engine"], dev["count"], len(dev["devices"])) == ("mesh", DEVICES, DEVICES)
    assert all(d["pool_budget_bytes"] == 2 << 30 for d in dev["devices"])
    one = _get(served["jax"].host, "/status")["status"]["device"]
    assert one["engine"] == "jax" and all(d["pool_budget_bytes"] == 2 << 30 for d in one["devices"])


def test_mesh_repairs_are_counted_in_place(served):
    s = served["mesh"]
    before = _get(s.host, "/debug/vars")
    s.set_bit(3, 5 * SLICE_WIDTH + 11)
    calls = [("Intersect", 3, 4)]
    assert _post(s.host, _pairs_body(calls))[0] == _want(s.ref, calls)
    after = _get(s.host, "/debug/vars")
    assert after["rowpool.repairs_in_place"] == before.get("rowpool.repairs_in_place", 0) + 1
    assert after["rowpool.repairs"] == before.get("rowpool.repairs", 0) + 1
    assert after.get("rowpool.repairs_composed", 0) == before.get("rowpool.repairs_composed", 0)
    assert after["rowpool.budget_bytes_per_device"] == 2 << 30
    # 8 GiB over four devices for 8 slices of 128 KiB planes
    assert after["rowpool.capacity_slots"] == DEVICES * (2 << 30) // (N_SLICES * 131072)
    assert after["rowpool.misses"] == before["rowpool.misses"] == N_ROWS


def test_a_wide_mesh_repair_is_counted_as_composed(served):
    """Two thirds of the rows written before one read (``2 * k >= n``): the
    composed form, on the mesh as on one chip - a functional scatter, the
    Gram recounted on every device."""
    s = served["mesh"]
    before = _get(s.host, "/debug/vars")
    rows = list(range(0, N_ROWS, 3)) + list(range(1, N_ROWS, 3))
    for row in rows:
        s.set_bit(row, 6 * SLICE_WIDTH + 100 + row)
    repair, _ = s.repair_of([("Xor", r, (r + 1) % N_ROWS) for r in rows[:16]])
    assert repair["tags"]["planes"] == len(rows) and repair["tags"]["form"] == "composed"
    assert repair["tags"]["in_place"] is False and repair["tags"]["devices"] == DEVICES
    after = _get(s.host, "/debug/vars")
    assert after["rowpool.repairs_composed"] == before.get("rowpool.repairs_composed", 0) + 1
    assert after["rowpool.repairs_in_place"] == before["rowpool.repairs_in_place"]


@pytest.mark.parametrize("engine", ENGINES)
def test_the_first_repair_after_paging_patches_a_copy(served, engine):
    """A reader held the pool's array when the first write after paging
    came; the repair that found the hold out ran the same step on a
    copy."""
    tags = served[engine].first_repair["tags"]
    assert (tags["form"], tags["in_place"], tags["planes"]) == ("step", False, 1)
    assert tags["devices"] == (DEVICES if engine == "mesh" else 1)


@pytest.mark.parametrize("engine", ENGINES)
def test_repeated_body_stays_on_the_armed_lane(served, engine):
    """A dashboard that polls: one body of pair counts sent 48 times past
    the query cache, one of its rows written before every eighth send.
    Every read is answered from the armed serve state, so no read takes
    the pool's array and every repair updates it in place."""
    s = served[engine]
    rows = [5, 11, 17, 23, 29, 35]
    calls = [(op, r, (r + 13) % N_ROWS) for r in rows for op in sorted(OPS)]
    body = _pairs_body(calls)
    before = _get(s.host, "/debug/vars")
    writes = 0
    for send in range(1, 49):
        if send % 8 == 0:
            s.set_bit(rows[writes % len(rows)], 1 * SLICE_WIDTH + 3000 + send)
            writes += 1
        results, (root,) = _post(s.host, body, trace=True, no_cache=True)
        assert results == _want(s.ref, calls), send
        assert root["tags"]["qcache"] == "bypass"
        assert [d["tags"]["lane"] for d in _find(root, "device")] == ["native"], send
        for repair in _find(root, "pool.repair"):
            assert (repair["tags"]["form"], repair["tags"]["in_place"]) == ("step", True), send
        assert not s.pool()._holds, send
    after = _get(s.host, "/debug/vars")
    assert writes == 6
    assert after["rowpool.repairs_in_place"] == before.get("rowpool.repairs_in_place", 0) + writes
    assert after["rowpool.repairs"] == before["rowpool.repairs"] + writes
    assert after.get("rowpool.repairs_composed", 0) == before.get("rowpool.repairs_composed", 0)


# -- (c): the pool's budget follows the devices that share the slice axis ----


def _engine(kind):
    import jax

    from pilosa_tpu.engine import JaxEngine, MeshEngine, NumpyEngine

    if kind == "mesh4":
        return MeshEngine(devices=jax.devices()[:4])
    return {"numpy": NumpyEngine, "jax": JaxEngine, "none": lambda: None}[kind]()


@pytest.mark.parametrize("n_slices", [64, 256])
@pytest.mark.parametrize("pool_bytes", [None, 1 << 30])
@pytest.mark.parametrize("kind", ["none", "numpy", "jax", "mesh4"])
def test_pool_capacity_follows_the_devices(monkeypatch, kind, pool_bytes, n_slices):
    from pilosa_tpu.rowpool import DeviceRowPool, pool_bytes as budget_of, pool_capacity

    if pool_bytes is None:
        monkeypatch.delenv("PILOSA_TPU_POOL_BYTES", raising=False)
    else:
        monkeypatch.setenv("PILOSA_TPU_POOL_BYTES", str(pool_bytes))
    engine, words = _engine(kind), 32768
    today = (pool_bytes or 2 << 30) // (n_slices * words * 4)   # one pool's 2 GiB, as before
    devices = 4 if kind == "mesh4" else 1
    want = today if pool_bytes else devices * today
    assert want == {(64, None): 256, (256, None): 64, (64, 1 << 30): 128,
                    (256, 1 << 30): 32}[n_slices, pool_bytes] * (devices if not pool_bytes else 1)
    assert pool_capacity(n_slices, words, engine) == want
    assert DeviceRowPool.default_cap(n_slices, words, engine) == want
    assert budget_of(engine, n_slices) == (pool_bytes or devices * (2 << 30), devices)
    if engine is not None:
        pool = DeviceRowPool(engine, n_slices, words, fetch=None)
        assert pool.cap_max == want
        pool.cap_max = 7            # an explicit cap still wins
        assert pool.cap_max == 7


@pytest.mark.parametrize("n_slices,devices", [(1, 1), (6, 1), (63, 1), (4, 4), (64, 4)])
def test_a_slice_axis_the_mesh_cannot_shard_gets_one_devices_budget(monkeypatch, n_slices, devices):
    """``_shard_stack`` leaves a ragged or single-slice axis on one device;
    the budget follows what the array will really span."""
    from pilosa_tpu.rowpool import pool_bytes

    monkeypatch.delenv("PILOSA_TPU_POOL_BYTES", raising=False)
    eng = _engine("mesh4")
    assert eng.slice_axis_devices(n_slices) == devices
    assert pool_bytes(eng, n_slices) == (devices * (2 << 30), devices)
    x = eng.matrix(np.zeros((n_slices, 2, 256), dtype=np.uint32))
    assert len(x.sharding.device_set) == devices


# -- the mesh's own forms of the scatter and of the Gram ------------------------


@pytest.mark.parametrize("cells", [([5], [1]), ([0, 7], [2]), ([3], [0, 4, 6]),
                                   ([1, 2, 6], [5, 3])])
def test_mesh_set_plane_rows_writes_each_cell_on_its_own_device(cells):
    slice_idxs, slots = cells
    eng = _engine("mesh4")
    rng = np.random.default_rng(len(slice_idxs) * 10 + len(slots))
    host = rng.integers(0, 1 << 32, size=(8, 8, 256), dtype=np.uint32)
    block = rng.integers(0, 1 << 32, size=(len(slice_idxs), len(slots), 256), dtype=np.uint32)
    before = eng.matrix(host.copy())
    after = eng.set_plane_rows(before, slice_idxs, slots, block)
    host2 = host.copy()
    host2[np.asarray(slice_idxs)[:, None], np.asarray(slots)[None, :]] = block
    np.testing.assert_array_equal(np.asarray(after).reshape(8, 8, 256), host2)
    np.testing.assert_array_equal(np.asarray(before).reshape(8, 8, 256), host)  # functional
    assert after.sharding == before.sharding and len(after.sharding.device_set) == 4


@pytest.mark.parametrize("n_slices", [8, 6])
def test_mesh_pair_gram_equals_the_set_arithmetic(n_slices):
    """Sharded (8 slices over 4 devices: per-device Grams, psummed) and
    unsharded (6 slices: the parent's one program)."""
    eng = _engine("mesh4")
    rng = np.random.default_rng(n_slices)
    host = rng.integers(0, 1 << 32, size=(n_slices, 8, 256), dtype=np.uint32)
    host &= rng.integers(0, 1 << 32, size=host.shape, dtype=np.uint32)
    gram = eng.pair_gram(eng.matrix(host))
    bits = np.unpackbits(host.view(np.uint8), axis=-1).astype(np.int64)
    want = np.einsum("srb,stb->rt", bits, bits)
    np.testing.assert_array_equal(gram, want)


@pytest.mark.parametrize("n_slices", [8, 6])
def test_mesh_pool_grows_on_its_own_devices(n_slices):
    """Zero capacity is appended shard by shard (an axis the mesh cannot
    shard stays where it is): no device is handed the whole block."""
    eng = _engine("mesh4")
    host = np.arange(n_slices * 2 * 256, dtype=np.uint32).reshape(n_slices, 2, 256)
    small = eng.matrix(host)
    grown = eng.grow_rows(small, 6)
    assert grown.shape == (n_slices, 8, 2, 128) and grown.sharding == small.sharding
    out = np.asarray(grown).reshape(n_slices, 8, 256)
    np.testing.assert_array_equal(out[:, :2], host)
    assert not out[:, 2:].any()
    per_device = {s.data.nbytes for s in grown.addressable_shards}
    assert per_device == {grown.nbytes // len(grown.sharding.device_set)}


@pytest.mark.parametrize("kind", ["numpy", "jax", "mesh4"])
def test_set_plane_cells_drops_a_buckets_tail(kind):
    """One scatter for all of a repair's cells, padded to a bucket with
    (-1, -1): the tail writes nothing (not the last slice, not slot -1)."""
    eng = _engine(kind)
    rng = np.random.default_rng(7)
    host = rng.integers(0, 1 << 32, size=(8, 8, 256), dtype=np.uint32)
    cells = np.array([[6, 2], [0, 7], [3, 3], [-1, -1]], dtype=np.int32)
    planes = rng.integers(0, 1 << 32, size=(4, 256), dtype=np.uint32)
    before = eng.matrix(host.copy())
    after = eng.set_plane_cells(before, cells, planes)
    want = host.copy()
    want[cells[:3, 0], cells[:3, 1]] = planes[:3]
    np.testing.assert_array_equal(np.asarray(after).reshape(8, 8, 256), want)
    np.testing.assert_array_equal(np.asarray(before).reshape(8, 8, 256), host)


@pytest.mark.parametrize("kind", ["numpy", "jax", "mesh4"])
def test_a_burst_over_many_slices_is_one_copy_of_the_pool(kind, monkeypatch):
    """Eight written cells on eight slices: the composed repair scatters
    them in one call (a call per group would put eight copies of the pool
    in flight), and its Gram equals the rebuild's."""
    from pilosa_tpu.engine import _repair_planes_composed

    eng = _engine(kind)
    rng = np.random.default_rng(8)
    host = rng.integers(0, 1 << 32, size=(8, 8, 256), dtype=np.uint32)
    host &= rng.integers(0, 1 << 32, size=host.shape, dtype=np.uint32)
    bits = lambda m: np.unpackbits(m.view(np.uint8), axis=-1).astype(np.int64)  # noqa: E731
    gram = np.einsum("srb,stb->rt", bits(host), bits(host))
    groups = [([s], [s % 5], rng.integers(0, 1 << 32, size=(1, 1, 256), dtype=np.uint32))
              for s in range(8)]
    calls = []
    real = eng.set_plane_cells
    monkeypatch.setattr(eng, "set_plane_cells", lambda *a: calls.append(a[1].shape) or real(*a))
    matrix, finish, in_place, form = _repair_planes_composed(eng, eng.matrix(host.copy()), gram, groups)
    assert calls == [(8, 2)] and (in_place, form) == (False, "composed")
    want = host.copy()
    for (s,), (slot,), block in groups:
        want[s, slot] = block[0, 0]
    np.testing.assert_array_equal(np.asarray(matrix).reshape(8, 8, 256), want)
    np.testing.assert_array_equal(finish(), np.einsum("srb,stb->rt", bits(want), bits(want)))


# -- the repair's compiled step on every device's own shard ---------------------

# (slice, slot) cells in the burst's order; 32 slices, eight a device.
_STEP_BURSTS = {
    "one_cell": [(5, 1)],
    "two_cells_same_device": [(8, 1), (9, 2)],
    "two_cells_different_devices": [(3, 1), (20, 2)],
    "two_rows_of_one_slice": [(10, 3), (10, 4)],
    "three_cells_and_a_tail": [(0, 1), (31, 2), (17, 1)],       # bucket 4: one (-1, -1)
    "eight_cells_same_device": [(8 + i, i % 5) for i in range(8)],
    "eight_cells_different_devices": [(8 * (i % 4) + i // 4, i % 5) for i in range(8)],
    # rows of two slices on two devices, interleaved: each device's cells
    # keep the burst's order, or the deltas of a slice would not telescope
    "eight_cells_two_slices_interleaved": [(4 if i % 2 else 12, i // 2) for i in range(8)],
    "five_cells_and_a_tail_on_one_slice": [(30, i) for i in range(5)],
}


def _pool_and_gram(rng, n_slices=32, cap=16, n=12, words=256):
    """A pool matrix on the host (logical form) and the Gram of its first
    ``n`` slots: narrower than the capacity, as a pool's Gram is."""
    host = rng.integers(0, 1 << 32, size=(n_slices, cap, words), dtype=np.uint32)
    host &= rng.integers(0, 1 << 32, size=host.shape, dtype=np.uint32)
    bits = np.unpackbits(host[:, :n].view(np.uint8), axis=-1).astype(np.int64)
    return host, np.einsum("srb,stb->rt", bits, bits)


def _cells_as_groups(rng, cells):
    return [([si], [slot], rng.integers(0, 1 << 32, size=(1, 1, 256), dtype=np.uint32))
            for si, slot in cells]


def _mesh_repair_equals_numpys(host, gram, groups, donate=True):
    """``repair_planes`` of the mesh engine on ``host`` beside the numpy
    engine's (the composed form): same matrix, same Gram, exactly.
    Returns the array the mesh engine was given, the one it returned, and
    what it said of the repair: ``(in_place, form)``."""
    ref, eng = _engine("numpy"), _engine("mesh4")
    want, want_finish, _, want_form = ref.repair_planes(ref.matrix(host.copy()), gram, groups)
    assert want_form == "composed"
    given = eng.matrix(host.copy())
    matrix, finish, in_place, form = eng.repair_planes(given, gram, groups, donate=donate)
    np.testing.assert_array_equal(np.asarray(matrix).reshape(host.shape), np.asarray(want))
    if gram is None:
        assert finish is None and want_finish is None
    else:
        np.testing.assert_array_equal(finish(), want_finish())
    return given, matrix, (in_place, form)


@pytest.mark.parametrize("burst", sorted(_STEP_BURSTS))
def test_mesh_repair_step_equals_the_numpy_engines_composed_form(burst):
    """Same matrix, same Gram, exactly - and the mesh ran the step, on the
    pool's own shards: the array it was given is gone, the one it returns
    lies where that one lay."""
    rng = np.random.default_rng(len(burst))
    host, gram = _pool_and_gram(rng)
    given, matrix, said = _mesh_repair_equals_numpys(
        host, gram, _cells_as_groups(rng, _STEP_BURSTS[burst]))
    assert said == (True, "step") and given.is_deleted()
    assert len(matrix.sharding.device_set) == 4 and matrix.sharding.spec[0] == "slice"


def test_mesh_repair_step_takes_a_group_of_several_slices_and_slots():
    """A group as the pool makes them: the product of its slices (on three
    devices) and its slots, one block."""
    rng = np.random.default_rng(29)
    host, gram = _pool_and_gram(rng)
    groups = [([2, 9, 27], [1, 7], rng.integers(0, 1 << 32, size=(3, 2, 256), dtype=np.uint32)),
              ([9], [3], rng.integers(0, 1 << 32, size=(1, 1, 256), dtype=np.uint32))]
    assert _mesh_repair_equals_numpys(host, gram, groups)[2] == (True, "step")


@pytest.mark.parametrize("why,cells", [
    ("over_half_the_slices", [(s, 1) for s in range(0, 32, 2)]),
    ("wide", [(5, slot) for slot in range(6)]),
    ("no_gram", [(5, 1)]),
])
def test_mesh_repairs_the_step_does_not_take_stay_composed(why, cells):
    rng = np.random.default_rng(len(why))
    host, gram = _pool_and_gram(rng)
    given, _, said = _mesh_repair_equals_numpys(
        host, None if why == "no_gram" else gram, _cells_as_groups(rng, cells))
    assert said == (False, "composed") and not given.is_deleted()


def test_a_slice_axis_the_mesh_cannot_shard_takes_the_one_chip_step():
    rng = np.random.default_rng(30)
    host, gram = _pool_and_gram(rng, n_slices=6)
    _, matrix, said = _mesh_repair_equals_numpys(host, gram, _cells_as_groups(rng, [(4, 2)]))
    assert said == (True, "step") and len(matrix.sharding.device_set) == 1


def test_an_array_a_reader_holds_is_not_donated_on_the_mesh():
    """Without ``donate`` the step runs on a copy made first, which keeps
    the sharding: the reader's array is whole and unchanged, and
    ``in_place`` is false."""
    rng = np.random.default_rng(31)
    host, gram = _pool_and_gram(rng)
    held, matrix, said = _mesh_repair_equals_numpys(
        host, gram, _cells_as_groups(rng, [(21, 4), (2, 0)]), donate=False)
    assert said == (False, "step") and not held.is_deleted()
    assert matrix is not held and matrix.sharding == held.sharding
    np.testing.assert_array_equal(np.asarray(held).reshape(host.shape), host)
