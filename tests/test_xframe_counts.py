"""Counts whose operands come from two frames of one index, served fused.

The Star Trace index of upstream's getting-started guide has two frames over
one column per repository: ``stargazer`` (a row per user, tall, paged) and
``language`` (a row per language, short; each repository in exactly one).
Its documented three-operand query ``Count(Intersect(Bitmap(frame=
"stargazer", rowID=a), Bitmap(frame="stargazer", rowID=b),
Bitmap(frame="language", rowID=l)))`` takes the cross-frame lane: each
frame's rows from its own pool, both held at once, one kernel folding
operands of both (``gather.xcount``).  Here seeded and small - 160 users x 8
slices, a pool of 64 slots a frame by ``PILOSA_TPU_POOL_BYTES``, 6 languages
- loaded through the door, on the jax engine and on the mesh of four virtual
devices with Pallas interpreted, against a plain reference written here:
Python sets of columns per (frame, row), nothing of the program.
"""

import http.client
import json
import threading

import jax
import numpy as np
import pytest

from pilosa_tpu.config import Config
from pilosa_tpu.pilosa import SLICE_WIDTH

N_SLICES, USERS, LANGS, SLOTS, DEVICES = 8, 160, 6, 64, 4
PLANE_WORDS = SLICE_WIDTH // 32
POOL = 96           # repositories a slice that users star
HOT = 24


def _index_bits(seed=41):
    """{(frame, row): set of columns}: users star 1-5 repositories a slice
    in windows of the slice's pool; every pool column has one language."""
    rng = np.random.default_rng(seed)
    sets: dict = {}
    for s in range(N_SLICES):
        pool = rng.choice(SLICE_WIDTH, size=POOL, replace=False) + s * SLICE_WIDTH
        lang = rng.integers(0, LANGS, size=POOL)
        for c, lg in zip(pool.tolist(), lang.tolist()):
            sets.setdefault(("language", lg), set()).add(c)
        for u in range(USERS):
            at = (int(rng.integers(0, POOL)) + np.arange(1 + (u * 3) % 5)) % POOL
            sets.setdefault(("stargazer", u), set()).update(pool[at].tolist())
    return sets


def _columns(sets, frame):
    rows, cols = [], []
    for (f, r), cs in sorted(sets.items()):
        if f == frame:
            rows += [r] * len(cs)
            cols += sorted(cs)
    return np.array(rows, dtype=np.uint64), np.array(cols, dtype=np.uint64)


FOLD = {"Intersect": set.intersection, "Union": set.union, "Difference": set.difference}


def _want(sets, calls):
    """The reference: each call's count, folded left to right over sets."""
    out = []
    for op, operands in calls:
        cs = [sets.get(o, set()) for o in operands]
        out.append(len(FOLD[op](*cs)))
    return out


def _bm(o):
    return f'Bitmap(frame="{o[0]}", rowID={o[1]})'


def _body(calls):
    return " ".join(f"Count({op}({', '.join(map(_bm, operands))}))" for op, operands in calls)


def _post(host, body, trace=False):
    conn = http.client.HTTPConnection(host, timeout=300)
    try:
        conn.request("POST", "/index/repository/query", body.encode(),
                     {"X-Pilosa-Trace": "1"} if trace else {})
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        assert resp.status == 200, payload
        spans = resp.getheader("X-Pilosa-Trace-Spans")
        return payload["results"], (json.loads(spans) if spans else None)
    finally:
        conn.close()


def _vars(host):
    conn = http.client.HTTPConnection(host, timeout=60)
    try:
        conn.request("GET", "/debug/vars")
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


def _find(node, name):
    out = []
    for n in node if isinstance(node, list) else [node]:
        if n["name"] == name:
            out.append(n)
        out.extend(_find(n.get("children", []), name))
    return out


def _triples(rng, n=16, hot=HOT):
    """Half upstream's three-operand query, half a user in a language."""
    def user():
        return int(rng.integers(0, hot)) if rng.random() < 0.9 else int(rng.integers(hot, USERS))

    out = []
    for i in range(n):
        a, b, lg = user(), user(), int(rng.integers(0, LANGS))
        if i % 2:
            out.append(("Intersect", (("stargazer", a), ("language", lg))))
        else:
            out.append(("Intersect", (("stargazer", a), ("stargazer", b if b != a else a + 1),
                                      ("language", lg))))
    return out


@pytest.fixture(scope="module", params=["jax", "mesh"])
def served(request, tmp_path_factory):
    """The server over the seeded two-frame index (``engine`` jax, or mesh
    on four of the eight virtual devices), loaded through ``ingest``."""
    from pilosa_tpu import engine as engine_mod
    from pilosa_tpu import executor as executor_mod
    from pilosa_tpu.server.client import Client
    from pilosa_tpu.server.server import Server

    real = engine_mod.new_engine

    def four(name="auto"):
        if engine_mod.engine_name(name) == "mesh":
            return engine_mod.MeshEngine(devices=jax.devices()[:DEVICES])
        return real(name)

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PILOSA_TPU_PALLAS_INTERPRET", "1")
        mp.setenv("PILOSA_TPU_POOL_BYTES", str(N_SLICES * SLOTS * PLANE_WORDS * 4))
        mp.setattr(executor_mod, "new_engine", four)
        sets = _index_bits()
        server = Server(Config(data_dir=str(tmp_path_factory.mktemp(f"xframe_{request.param}")),
                               host="127.0.0.1:0", engine=request.param))
        server.open()
        try:
            c = Client(server.host, timeout=300)
            c.create_index("repository")
            for frame in ("stargazer", "language"):
                c.create_frame("repository", frame)
                c.ingest_stream("repository", frame, *_columns(sets, frame), door="ingest")
            yield {"host": server.host, "server": server, "sets": sets, "engine": request.param}
        finally:
            server.close()


def test_two_and_three_operand_cross_frame_counts_are_exact_and_fused(served):
    """Bodies of upstream's three-operand query and its two-operand form:
    every count equals the sets' arithmetic, every call went through the
    cross-frame lane, none fell back."""
    host, sets = served["host"], served["sets"]
    v0 = _vars(host)
    rng = np.random.default_rng(5)
    sent = 0
    for _ in range(12):
        calls = _triples(rng)
        assert _post(host, _body(calls))[0] == _want(sets, calls)
        sent += len(calls)
    v1 = _vars(host)
    assert v1["gather.xframe_calls"] - v0.get("gather.xframe_calls", 0) == sent
    assert v1["gather.xframe_dispatches"] > v0.get("gather.xframe_dispatches", 0)
    assert v1.get("count.unfused", 0) == v0.get("count.unfused", 0)
    if served["engine"] == "mesh":
        assert v1["gather.mesh_fetches"] > v0.get("gather.mesh_fetches", 0)


def test_union_and_difference_across_frames_and_either_frame_first(served):
    """The same fold serves Union and Difference; a call may name the short
    frame first; Difference keeps its first operand first."""
    host, sets = served["host"], served["sets"]
    calls = [
        ("Union", (("stargazer", 3), ("language", 1))),
        ("Union", (("language", 2), ("stargazer", 4), ("stargazer", 9))),
        ("Difference", (("language", 0), ("stargazer", 5), ("stargazer", 6))),
        ("Difference", (("stargazer", 7), ("language", 3))),
        ("Difference", (("stargazer", 8), ("stargazer", 11), ("stargazer", 12), ("language", 4))),
        ("Intersect", (("language", 5), ("stargazer", 13))),
    ]
    v0 = _vars(host)
    assert _post(host, _body(calls))[0] == _want(sets, calls)
    v1 = _vars(host)
    assert v1["gather.xframe_calls"] - v0["gather.xframe_calls"] == len(calls)
    assert v1.get("count.unfused", 0) == v0.get("count.unfused", 0)


def test_a_body_mixing_cross_frame_calls_with_single_frame_pairs(served):
    host, sets = served["host"], served["sets"]
    rng = np.random.default_rng(6)
    for _ in range(4):
        calls = _triples(rng, n=8)
        calls += [("Intersect", (("stargazer", int(a)), ("stargazer", int(a) + 1)))
                  for a in rng.integers(0, USERS - 1, size=4)]
        calls += [("Union", (("language", 1), ("language", 2)))]
        rng.shuffle(calls)
        assert _post(host, _body(calls))[0] == _want(sets, calls)


def test_rows_evicted_between_bodies_are_paged_back_exactly(served):
    """Bodies over every user in turn: the stargazer pool (64 slots) evicts
    between bodies and the answers stay exact."""
    host, sets = served["host"], served["sets"]
    pools = served["server"].executor._matrix_cache
    for start in range(0, USERS, 20):
        calls = [("Intersect", (("stargazer", u), ("stargazer", (u + 7) % USERS), ("language", u % LANGS)))
                 for u in range(start, start + 20)]
        assert _post(host, _body(calls))[0] == _want(sets, calls)
    by_frame = {k[1]: p for k, p in pools.items() if k[4] == ""}
    assert by_frame["stargazer"].stat_evictions > 0
    assert by_frame["stargazer"].cap == SLOTS
    assert by_frame["language"].cap <= 8 and by_frame["language"].stat_evictions == 0


def test_a_tree_over_two_frames_answers_on_the_sequential_path(served):
    """A nested tree whose leaves name two frames has no fused lane: it is
    answered exactly on the sequential path and counted as ``count.unfused``
    under ``frames``."""
    host, sets = served["host"], served["sets"]
    body = ('Count(Intersect(Union(Bitmap(frame="stargazer", rowID=1), Bitmap(frame="stargazer", rowID=2)), '
            'Bitmap(frame="language", rowID=3))) '
            'Count(Intersect(Bitmap(frame="stargazer", rowID=4), Bitmap(frame="language", rowID=0)))')
    u = sets[("stargazer", 1)] | sets[("stargazer", 2)]
    want = [len(u & sets[("language", 3)]), len(sets[("stargazer", 4)] & sets[("language", 0)])]
    v0 = _vars(host)
    results, root = _post(host, body, trace=True)
    assert results == want
    v1 = _vars(host)
    assert v1["count.unfused"] - v0.get("count.unfused", 0) == 2
    assert v1["count.unfused.frames"] - v0.get("count.unfused.frames", 0) == 2
    assert root[0]["tags"]["unfused"] == 2


def test_a_sampled_cross_frame_pass_says_what_it_held_and_gathered(served):
    host, sets = served["host"], served["sets"]
    calls = [("Intersect", (("stargazer", 40 + i), ("stargazer", 60 + i), ("language", i % LANGS)))
             for i in range(5)] + [("Intersect", (("stargazer", 80 + i), ("language", i % LANGS)))
                                   for i in range(3)]
    results, root = _post(host, _body(calls), trace=True)
    assert results == _want(sets, calls)
    assert root[0]["tags"]["unfused"] == 0
    (acq,) = _find(root, "xframe.acquire")
    assert acq["tags"]["frames"] == 2
    assert acq["tags"]["rows"] == {"language": len({i % LANGS for i in range(5)}), "stargazer": 13}
    assert set(acq["tags"]["misses"]) == {"language", "stargazer"}
    assert len(_find(acq, "pool.lock_wait")) == 2
    dev = [d for d in _find(root, "device") if d["tags"].get("lane") == "xgather"]
    # batches padded to the engine's bucket (powers of four), at most 64 calls
    assert sorted((d["tags"]["calls"], d["tags"]["bucket"]) for d in dev) == [(3, 4), (5, 16)]
    assert all(d["tags"]["frames"] == 2 for d in dev)
    assert sorted(d["tags"]["unique_rows"] for d in dev) == [3 + 3, 10 + 5]
    devices = DEVICES if served["engine"] == "mesh" else 1
    assert all(d["tags"]["devices"] == devices for d in dev)


def test_two_threads_taking_the_pools_in_opposite_orders_both_finish(served):
    """Two passes at once, bypassing the queue that runs passes one at a
    time: one names the tall frame first in every call, the other the short
    one; each holds both pools in turn.  Both finish, exactly."""
    ex, sets = served["server"].executor, served["sets"]
    slices = list(range(N_SLICES))
    rng = np.random.default_rng(9)
    orders = []
    for flip in (False, True):
        calls = _triples(rng, n=12, hot=USERS - 20)
        if flip:
            calls = [(op, tuple(reversed(ops))) for op, ops in calls]
        orders.append(calls)
    got, errors = {}, []

    def run(k):
        try:
            for _ in range(3):
                calls = orders[k]
                matched = {i: (tuple(f for f, _ in ops), "standard", "and", tuple(r for _, r in ops))
                           for i, (_op, ops) in enumerate(calls)}
                got[k] = ex._fused_local_counts("repository", matched, sorted(matched), slices)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(k,)) for k in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    assert not any(t.is_alive() for t in threads), "a pass never finished"
    assert not errors, errors
    for k in (0, 1):
        assert got[k] == _want(sets, orders[k])


def test_a_large_pass_dispatches_in_batches_of_at_most_64(served, monkeypatch):
    """80 calls of one group (a pass of five bodies) go out as batches of 64
    and 16, each at the engine's bucket: the programs a pass meets do not
    depend on how many requests it joined; the counts are exact."""
    ex, sets = served["server"].executor, served["sets"]
    shapes = []
    real = type(ex.engine).gather_count_xframe_dev

    def noted(self, op, rm_a, idx_a, rm_b, idx_b):
        shapes.append((idx_a.shape, idx_b.shape))
        return real(self, op, rm_a, idx_a, rm_b, idx_b)

    monkeypatch.setattr(type(ex.engine), "gather_count_xframe_dev", noted)
    calls = [("Intersect", (("stargazer", u % 40), ("language", u % LANGS))) for u in range(80)]
    matched = {i: (tuple(f for f, _ in ops), "standard", "and", tuple(r for _, r in ops))
               for i, (_op, ops) in enumerate(calls)}
    got = ex._fused_local_counts("repository", matched, sorted(matched), list(range(N_SLICES)))
    assert got == _want(sets, calls)
    assert sorted(shapes) == [((16, 1), (16, 1)), ((64, 1), (64, 1))]


# -- the engines' cross-frame forms against numpy ------------------------------

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


def _words(rng, shape):
    m = rng.integers(0, 1 << 32, size=shape, dtype=np.uint64).astype(np.uint32)
    return m & rng.integers(0, 1 << 32, size=shape, dtype=np.uint64).astype(np.uint32)


@pytest.mark.parametrize("kind", ENGINES)
def test_xframe_dev_and_blocking_forms_equal_numpy(kind, monkeypatch):
    """``gather_count_xframe_dev`` fetched and the blocking form equal the
    numpy ground truth, with padded operands and a padded batch, and with
    the mesh's batch cut into chunks for SMEM."""
    from pilosa_tpu.ops.bitwise import np_gather_count_xframe
    from pilosa_tpu.parallel import sharded

    monkeypatch.setattr(sharded, "_SHARDED_BATCH_MAX", 4)   # a chunk of 2 queries at 3 operands
    engine = _engine(kind, monkeypatch)
    rng = np.random.default_rng(13)
    a, b = _words(rng, (8, 12, 1024)), _words(rng, (8, 5, 1024))
    ma, mb = engine.matrix(a), engine.matrix(b)
    for op, ka, kb in (("and", 2, 1), ("and", 1, 1), ("or", 2, 2), ("andnot", 4, 1)):
        ia = rng.integers(0, 12, size=(7, ka)).astype(np.int32)
        ib = rng.integers(0, 5, size=(7, kb)).astype(np.int32)
        if op == "andnot":
            ia[:, 3] = ia[:, 1]          # a non-first operand repeated: the pad
        ia[5:], ib[5:] = ia[0], ib[0]    # padded batch rows repeat the first query
        want = np_gather_count_xframe(op, a, ia, b, ib).tolist()
        got = engine.to_numpy(engine.gather_count_xframe_dev(op, ma, ia, mb, ib)).astype(np.int64)
        blocking = engine.gather_count_xframe(op, ma, ia, mb, ib)
        assert got.tolist() == blocking.tolist() == want, (op, ka, kb)
