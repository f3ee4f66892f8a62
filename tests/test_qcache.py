"""Generation-keyed query result cache (pilosa_tpu/qcache/).

Covers: exact cache/execution equivalence under interleaved writes (a
stateful property test in the style of test_fragment_stateful.py), the
admission/eviction/error/bypass unit semantics, the X-Pilosa-No-Cache
header end to end through the HTTP handler, deletion purge hooks, the
canonical call-tree fingerprint, /debug/vars counters, and the
[cache] ranking-debounce-s promotion (satellite).
"""

import json
import tempfile

import numpy as np
import pytest

from pilosa_tpu.core.frame import FrameOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import ExecOptions, Executor
from pilosa_tpu.pilosa import SLICE_WIDTH, PilosaError
from pilosa_tpu.qcache import (
    NO_CACHE_HEADER,
    QueryCache,
    generation_vector,
    referenced_frames,
)

Q_PAIR = 'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f")))'


@pytest.fixture()
def env(tmp_path):
    h = Holder(str(tmp_path / "d"))
    h.open()
    h.create_index("i").create_frame("f", FrameOptions())
    fr = h.index("i").frame("f")
    for c in range(10):
        fr.set_bit("standard", 0, c)
    for c in range(5, 15):
        fr.set_bit("standard", 1, c)
    qc = QueryCache(min_cost_ms=0.0)
    ex = Executor(h, engine="numpy", qcache=qc)
    yield h, fr, ex, qc
    h.close()


def test_hit_serves_identical_results(env):
    h, fr, ex, qc = env
    r1 = ex.execute("i", Q_PAIR)
    r2 = ex.execute("i", Q_PAIR)
    assert r1 == r2 == [5]
    assert (qc.hits, qc.misses, qc.stores) == (1, 1, 1)
    assert len(qc) == 1 and qc.bytes > 0


def test_executor_write_invalidates(env):
    h, fr, ex, qc = env
    assert ex.execute("i", Q_PAIR) == [5]
    ex.execute("i", 'SetBit(rowID=0, frame="f", columnID=7)')  # already set: no change
    # An idempotent write that changed nothing bumps no generation, so
    # the entry stays valid.
    assert ex.execute("i", Q_PAIR) == [5] and qc.hits == 1
    ex.execute("i", 'SetBit(rowID=0, frame="f", columnID=12)')
    # Read-your-writes: the generation bump forces a miss and the fresh
    # answer reflects the write.
    assert ex.execute("i", Q_PAIR) == [6]
    assert qc.misses == 2


def test_direct_fragment_write_invalidates(env):
    """The validity token is the fragment generation, maintained inside
    the fragment's own locked mutators — so writers that never touch
    this executor (imports, sync, another executor) still invalidate."""
    h, fr, ex, qc = env
    assert ex.execute("i", Q_PAIR) == [5]
    fr.set_bit("standard", 1, 2)
    assert ex.execute("i", Q_PAIR) == [6]
    fr.import_bits(np.array([0], dtype=np.uint64), np.array([13], dtype=np.uint64))
    assert ex.execute("i", Q_PAIR) == [7]
    assert qc.hits == 0 and qc.misses == 3


def test_new_slice_invalidates(env):
    h, fr, ex, qc = env
    assert ex.execute("i", Q_PAIR) == [5]
    fr.set_bit("standard", 0, SLICE_WIDTH + 3)  # new max slice
    fr.set_bit("standard", 1, SLICE_WIDTH + 3)
    assert ex.execute("i", Q_PAIR) == [6]


def test_admission_min_cost_ms():
    """Only results whose measured cost clears min-cost-ms are stored."""
    clk = [0.0]

    def fake_clock():
        return clk[0]

    qc = QueryCache(min_cost_ms=5.0, clock=fake_clock)
    with tempfile.TemporaryDirectory() as d:
        h = Holder(d)
        h.open()
        h.create_index("i").create_frame("f", FrameOptions())
        h.index("i").frame("f").set_bit("standard", 0, 1)
        # Cheap execution (0 ms on the fake clock): not admitted.
        _, tok = qc.lookup(h, "i", Q_PAIR, None)
        assert tok is not None
        assert not qc.commit(h, tok, [1])
        assert qc.stores == 0 and len(qc) == 0
        # Expensive execution (10 ms): admitted.
        _, tok = qc.lookup(h, "i", Q_PAIR, None)
        clk[0] += 0.010
        assert qc.commit(h, tok, [1])
        assert qc.stores == 1 and len(qc) == 1
        cached, _ = qc.lookup(h, "i", Q_PAIR, None)
        assert cached == [1]
        h.close()


def test_byte_bound_eviction(env):
    h, fr, ex, qc = env
    qc.max_bytes = 2 * 560 + 10  # room for ~2 count entries
    qs = [
        f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={a}, frame="f")))'
        for a in range(6)
    ]
    for q in qs:
        ex.execute("i", q)
    assert qc.evictions > 0
    assert qc.bytes <= qc.max_bytes
    assert len(qc) >= 1
    # LRU: the most recent entry survived, the oldest was evicted.
    assert ex.execute("i", qs[-1]) == ex.execute("i", qs[-1])
    hits0 = qc.hits
    ex.execute("i", qs[-1])
    assert qc.hits == hits0 + 1
    misses0 = qc.misses
    ex.execute("i", qs[0])
    assert qc.misses == misses0 + 1


def test_oversized_result_never_stored(env):
    h, fr, ex, qc = env
    qc.max_bytes = 8  # smaller than any entry
    ex.execute("i", Q_PAIR)
    assert qc.stores == 0 and qc.bytes == 0


def test_errors_never_cached(env):
    h, fr, ex, qc = env
    bad = 'Count(Bitmap(rowID=0, frame="nope"))'
    for _ in range(2):
        with pytest.raises(PilosaError):
            ex.execute("i", bad)
    assert qc.stores == 0 and qc.hits == 0
    assert qc.misses == 2  # eligible shape, but the error aborts the commit


def test_write_and_nondeterministic_trees_ineligible(env):
    h, fr, ex, qc = env
    # Writes, TopN (rank-cache debounce timing), and top-level Bitmap
    # (attaches attrs, which mutate without a generation bump) must
    # never be cached.
    ex.execute("i", 'SetBit(rowID=0, frame="f", columnID=99)')
    ex.execute("i", 'TopN(frame="f", n=2)')
    ex.execute("i", 'Bitmap(rowID=0, frame="f")')
    # A mixed request carrying any write stays uncacheable as a whole.
    ex.execute("i", f'SetBit(rowID=0, frame="f", columnID=98) {Q_PAIR}')
    assert qc.stores == 0 and len(qc) == 0
    # Uncacheable traffic counts as INELIGIBLE, never as a bypass — the
    # bypass counter is reserved for explicit X-Pilosa-No-Cache requests
    # so the A/B hit-rate denominator stays clean.
    assert qc.ineligible == 4 and qc.bypasses == 0


def test_no_cache_exec_option(env):
    h, fr, ex, qc = env
    r1 = ex.execute("i", Q_PAIR)
    nc = ExecOptions(no_cache=True)
    r2 = ex.execute("i", Q_PAIR, opt=nc)
    assert r1 == r2
    # Bypass neither read nor stored: one store from r1, no hit for r2.
    assert qc.stores == 1 and qc.hits == 0 and qc.bypasses == 1


def test_no_cache_header_through_handler(env):
    """X-Pilosa-No-Cache: 1 threads through the HTTP handler into
    ExecOptions — the per-request A/B lever."""
    from pilosa_tpu.server.handler import Handler

    h, fr, ex, qc = env
    handler = Handler(h, ex)

    def post(headers=None):
        status, _, payload = handler.dispatch(
            "POST", "/index/i/query", {}, Q_PAIR.encode(), headers or {}
        )[:3]
        assert status == 200
        return json.loads(payload)["results"]

    assert post() == [5]
    assert post() == [5] and qc.hits == 1
    assert post({NO_CACHE_HEADER.lower(): "1"}) == [5]
    assert qc.hits == 1 and qc.bypasses == 1  # neither served nor stored


def test_client_sets_no_cache_header():
    from pilosa_tpu.server.client import Client

    captured = {}

    class _Cli(Client):
        def _request(self, method, path, body=None, **kw):
            captured.update(kw.get("headers") or {})
            from pilosa_tpu import wire

            return 200, wire.encode_query_response(results=[0])

    c = _Cli("localhost:1")
    c.execute_query("i", "Count(Bitmap(rowID=0))", no_cache=True)
    assert captured.get(NO_CACHE_HEADER) == "1"
    captured.clear()
    c.execute_query("i", "Count(Bitmap(rowID=0))")
    assert NO_CACHE_HEADER not in captured


def test_purge_on_frame_and_index_drop(env):
    h, fr, ex, qc = env
    ex.execute("i", Q_PAIR)
    assert len(qc) == 1
    ex.drop_frame_state("i", "f")
    assert len(qc) == 0 and qc.bytes == 0
    ex.execute("i", Q_PAIR)
    assert len(qc) == 1
    ex.drop_index_state("i")
    assert len(qc) == 0 and qc.bytes == 0


def test_delete_frame_route_purges(env):
    """The HTTP deletion route drives the purge, so a recreated
    namesake frame can never serve the old frame's results."""
    from pilosa_tpu.server.handler import Handler

    h, fr, ex, qc = env
    handler = Handler(h, ex)
    assert ex.execute("i", Q_PAIR) == [5]
    status, _, _ = handler.dispatch("DELETE", "/index/i/frame/f", {}, b"", {})[:3]
    assert status == 200 and len(qc) == 0
    h.index("i").create_frame("f", FrameOptions())
    fr2 = h.index("i").frame("f")
    fr2.set_bit("standard", 0, 1)
    fr2.set_bit("standard", 1, 1)
    assert ex.execute("i", Q_PAIR) == [1]


def test_canonical_fingerprint_shares_entry(env):
    h, fr, ex, qc = env
    ex.execute("i", Q_PAIR)
    # Same call tree, different formatting: one entry.  The cache learns
    # a spelling by parsing it, and parses only at a commit, so the new
    # spelling's FIRST send misses (the one hit deferral gives up: it
    # stores under the same key) and every later one is served as a hit.
    variant = 'Count(Intersect(Bitmap(rowID=0,frame="f"),Bitmap(rowID=1,frame="f")))'
    assert ex.execute("i", variant) == [5]
    assert (qc.hits, qc.misses, qc.deferred_parsed) == (0, 2, 2) and len(qc) == 1
    assert ex.execute("i", variant) == [5]
    assert ex.execute("i", Q_PAIR) == [5]
    assert (qc.hits, qc.misses) == (2, 2) and len(qc) == 1


def test_slices_key_separates_partial_requests(env):
    h, fr, ex, qc = env
    full = ex.execute("i", Q_PAIR)
    part = ex.execute("i", Q_PAIR, slices=[0])
    assert full == part == [5]  # single-slice dataset: same answer
    assert len(qc) == 2 and qc.hits == 0
    assert ex.execute("i", Q_PAIR, slices=[0]) == [5]
    assert qc.hits == 1


def test_slices_key_order_insensitive_and_empty_distinct(env):
    """The slice-set key is a SET: the same slices in a different order
    share one entry, and an explicit empty list never aliases the
    all-slices (None) request."""
    h, fr, ex, qc = env
    fr.set_bit("standard", 0, SLICE_WIDTH + 3)
    fr.set_bit("standard", 1, SLICE_WIDTH + 3)
    assert ex.execute("i", Q_PAIR, slices=[0, 1]) == [6]
    assert ex.execute("i", Q_PAIR, slices=[1, 0]) == [6]  # same entry: hit
    assert qc.hits == 1 and len(qc) == 1
    full = ex.execute("i", Q_PAIR)  # None = all slices: its own entry
    assert full == [6] and len(qc) == 2
    # An explicit empty list keys its own entry — it never aliases the
    # all-slices (None) key (execution happens to answer both the same
    # way today; the key must not bake that coincidence in).
    misses0 = qc.misses
    ex.execute("i", Q_PAIR, slices=[])
    assert qc.misses == misses0 + 1 and len(qc) == 3
    assert ex.execute("i", Q_PAIR) == [6]
    assert qc.hits == 2


def test_multi_node_cluster_scope_never_cached(tmp_path):
    """Clustered executors cache ONLY remote-scope sub-requests: a
    coordinator-scope answer covers remotely-owned slices whose writes
    never bump local generations (the coordinator forwards them without
    a local write), so caching it would serve stale reads forever."""
    from pilosa_tpu.cluster import Cluster, Node

    h = Holder(str(tmp_path / "d"))
    h.open()
    h.create_index("i").create_frame("f", FrameOptions())
    fr = h.index("i").frame("f")
    for s in range(4):
        fr.set_bit("standard", 0, s * SLICE_WIDTH + 1)
        fr.set_bit("standard", 1, s * SLICE_WIDTH + 1)

    hosts = ["h0:1", "h1:1"]
    cluster = Cluster([Node(host) for host in hosts], replica_n=2)

    class PeerClient:
        """Stand-in peer answering from the same holder, uncached."""

        def __init__(self, host):
            self.host = host

        def execute_remote(self, index, query, slices=None, **kw):
            return Executor(h, engine="numpy").execute(
                index, query, slices=slices, opt=ExecOptions(remote=True)
            )

        def execute_remote_call(self, index, call, slices, **kw):
            from pilosa_tpu import pql

            return self.execute_remote(index, pql.Query(calls=[call]), slices)[0]

    qc = QueryCache(min_cost_ms=0.0)
    ex = Executor(
        h, engine="numpy", cluster=cluster, client_factory=PeerClient,
        host="h0:1", qcache=qc,
    )
    try:
        # Coordinator scope: correct answers, but never cached.
        assert ex.execute("i", Q_PAIR) == [4]
        assert ex.execute("i", Q_PAIR) == [4]
        assert qc.ineligible == 2 and qc.stores == 0 and len(qc) == 0
        # Remote scope (what peers ask THIS node): cacheable, and a
        # local write (the forwarded-write path on an owner) invalidates.
        ropt = ExecOptions(remote=True)
        assert ex.execute("i", Q_PAIR, slices=[0], opt=ropt) == [1]
        assert ex.execute("i", Q_PAIR, slices=[0], opt=ropt) == [1]
        assert qc.hits == 1 and qc.stores == 1
        fr.set_bit("standard", 0, 2)
        fr.set_bit("standard", 1, 2)
        assert ex.execute("i", Q_PAIR, slices=[0], opt=ropt) == [2]
    finally:
        h.close()


def test_stats_counters_at_debug_vars(tmp_path):
    from pilosa_tpu.stats import ExpvarStatsClient

    stats = ExpvarStatsClient()
    h = Holder(str(tmp_path / "d"))
    h.open()
    h.create_index("i").create_frame("f", FrameOptions())
    h.index("i").frame("f").set_bit("standard", 0, 1)
    h.index("i").frame("f").set_bit("standard", 1, 1)
    qc = QueryCache(min_cost_ms=0.0, stats=stats)
    ex = Executor(h, engine="numpy", qcache=qc)
    ex.execute("i", Q_PAIR)
    ex.execute("i", Q_PAIR)
    ex.execute("i", Q_PAIR, opt=ExecOptions(no_cache=True))
    ex.execute("i", 'SetBit(rowID=2, frame="f", columnID=3)')
    pair2 = Q_PAIR + " " + Q_PAIR  # two calls: the pair matcher's to read
    assert ex.execute("i", pair2) == ex.execute("i", pair2)
    snap = stats.snapshot()
    assert snap["qcache.hit"] == 2
    assert snap["qcache.miss"] == 2
    # The one miss was a never-seen string, canonicalised at its commit.
    assert snap["qcache.deferred"] == 2
    assert snap["qcache.deferred_parsed"] == 1
    # ... and one was a body the pair matcher had read: keyed by that.
    assert snap["qcache.deferred_matched"] == 1
    assert snap["qcache.store"] == 2
    assert snap["qcache.bypass"] == 1
    assert snap["qcache.ineligible"] == 1  # the write, not a bypass
    assert snap["qcache.bytes"] > 0
    h.close()


def test_generation_vector_shape(env):
    h, fr, ex, qc = env
    v1 = generation_vector(h, "i", ("f",))
    v2 = generation_vector(h, "i", ("f",))
    assert v1 == v2
    fr.set_bit("standard", 3, 3)
    assert generation_vector(h, "i", ("f",)) != v1
    assert generation_vector(h, "missing", ("f",)) is None
    # Missing frames are distinguishable from empty ones.
    assert ("ghost", None) in generation_vector(h, "i", ("ghost",))


def test_referenced_frames():
    from pilosa_tpu import pql

    q = pql.parse(
        'Count(Intersect(Bitmap(rowID=1, frame="a"), Bitmap(rowID=2, frame="b")))'
        ' Count(Bitmap(rowID=3))'
    )
    assert referenced_frames(q) == ("a", "b", "general")


def test_executor_env_default(monkeypatch):
    """Direct Executor construction keeps pre-qcache behavior unless
    PILOSA_TPU_QCACHE opts in (the server wires [qcache] explicitly)."""
    with tempfile.TemporaryDirectory() as d:
        h = Holder(d)
        h.open()
        monkeypatch.delenv("PILOSA_TPU_QCACHE", raising=False)
        assert Executor(h, engine="numpy").qcache is None
        monkeypatch.setenv("PILOSA_TPU_QCACHE", "1")
        monkeypatch.setenv("PILOSA_TPU_QCACHE_MAX_BYTES", "1024")
        monkeypatch.setenv("PILOSA_TPU_QCACHE_MIN_COST_MS", "2.5")
        ex = Executor(h, engine="numpy")
        assert ex.qcache is not None
        assert ex.qcache.max_bytes == 1024
        assert ex.qcache.min_cost_ms == 2.5
        h.close()


def test_server_wiring_and_debug_vars(tmp_path):
    """[qcache] config reaches the real server: repeated HTTP queries
    hit, /debug/vars carries the counters, and disabling via config
    yields no cache at all."""
    import urllib.request

    from pilosa_tpu.config import Config
    from pilosa_tpu.server.server import Server

    cfg = Config(
        data_dir=str(tmp_path / "d"), host="127.0.0.1:0", engine="numpy",
        qcache_min_cost_ms=0.0,
    )
    s = Server(cfg)
    s.open()
    try:
        base = f"http://{s.host}"

        def post(path, data):
            req = urllib.request.Request(base + path, data=data.encode(), method="POST")
            return json.loads(urllib.request.urlopen(req, timeout=30).read())

        post("/index/i", "{}")
        post("/index/i/frame/f", "{}")
        post("/index/i/query", 'SetBit(rowID=0, frame="f", columnID=1)')
        post("/index/i/query", 'SetBit(rowID=1, frame="f", columnID=1)')
        r1 = post("/index/i/query", Q_PAIR)
        r2 = post("/index/i/query", Q_PAIR)
        assert r1 == r2 and r1["results"] == [1]
        assert s.qcache is not None and s.qcache.hits == 1
        with urllib.request.urlopen(base + "/debug/vars", timeout=30) as resp:
            snap = json.loads(resp.read())
        assert snap["qcache.hit"] == 1 and snap["qcache.bytes"] > 0
    finally:
        s.close()
    cfg2 = Config(data_dir=str(tmp_path / "d2"), host="127.0.0.1:0",
                  engine="numpy", qcache_enabled=False)
    s2 = Server(cfg2)
    assert s2.qcache is None and s2.executor.qcache is None


@pytest.mark.parametrize(
    "min_cost_ms,ledger_ms,stored",
    [(10.0, 1.0, False), (2.0, 15.0, True)],
    ids=["cheaper_than_the_floor_is_declined", "dearer_than_the_floor_is_stored"],
)
def test_admission_floor_is_the_configured_one_with_a_full_ledger(
    tmp_path, min_cost_ms, ledger_ms, stored
):
    """The server's query cache admits by [qcache] min-cost-ms, whatever
    the cost ledger has seen: a miss costs 5 ms here (a stepped clock)
    beside a full ledger of cheaper, or of dearer, entries."""
    import itertools
    import urllib.request

    from pilosa_tpu.config import Config
    from pilosa_tpu.server.server import Server

    cfg = Config(
        data_dir=str(tmp_path / "d"), host="127.0.0.1:0", engine="numpy",
        qcache_min_cost_ms=min_cost_ms,
    )
    s = Server(cfg)
    s.open()
    try:
        base = f"http://{s.host}"

        def post(path, data):
            req = urllib.request.Request(base + path, data=data.encode(), method="POST")
            return json.loads(urllib.request.urlopen(req, timeout=30).read())

        post("/index/i", "{}")
        post("/index/i/frame/f", "{}")
        post("/index/i/query", 'SetBit(rowID=0, frame="f", columnID=1)')
        post("/index/i/query", 'SetBit(rowID=1, frame="f", columnID=1)')
        for i in range(s.costs.cap):
            s.costs.observe(index="i", fp=f"fp{i}", lane="flat", ms=ledger_ms)
        assert len(s.costs) == s.costs.cap
        steps = itertools.count()
        s.qcache._clock = lambda: next(steps) * 0.005
        assert s.qcache.min_cost_ms == min_cost_ms
        for _ in range(2):
            assert post("/index/i/query", Q_PAIR)["results"] == [1]
        assert (s.qcache.stores, s.qcache.hits) == (int(stored), int(stored))
    finally:
        s.close()


# -- deferred canonicalisation: the repeated body, and the body never parsed --

ENGINES = ("numpy", "jax", "mesh")
_FLOOR_MS = 2.0


def _pairs(rows, pad=""):
    return pad + " ".join(
        f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={a + 1}, frame="f")))'
        for a in rows
    )


class _Clock:
    """The cache's clock in a test's hand: it moves only where told."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def during(self, fn, seconds):
        def slowed(*a, **kw):
            self.t += seconds
            return fn(*a, **kw)

        return slowed


@pytest.fixture(params=ENGINES)
def dash(request, tmp_path, monkeypatch):
    """A dashboard index of 4 slices and two frames behind each engine,
    its query cache at a 2 ms floor on a clock the test moves.  On the
    jax and the mesh engine (four of the eight virtual devices, Pallas
    interpreted) four warm-up reads arm frame f's serve state, so reads
    are answered natively from the Gram and a write is repaired."""
    kind = request.param
    monkeypatch.setenv("PILOSA_TPU_PALLAS_INTERPRET", "1")
    h = Holder(str(tmp_path / "d"))
    h.open()
    idx = h.create_index("i")
    fr, other = idx.create_frame("f", FrameOptions()), idx.create_frame("g", FrameOptions())
    rng = np.random.default_rng(31)
    for s in range(4):
        for r in range(6):
            for c in rng.choice(512, size=20, replace=False):
                fr.set_bit("standard", r, s * SLICE_WIDTH + int(c))
    other.set_bit("standard", 0, 0)
    if kind == "mesh":
        import jax

        from pilosa_tpu.engine import MeshEngine

        engine = MeshEngine(devices=jax.devices()[:4])
    else:
        engine = kind
    clock = _Clock()
    qc = QueryCache(min_cost_ms=_FLOOR_MS, clock=clock)
    ex = Executor(h, engine=engine, qcache=qc)
    fresh = Executor(h, engine="numpy", qcache=None)
    if kind != "numpy":
        for _ in range(4):
            ex.execute("i", _pairs(range(5)), opt=ExecOptions(no_cache=True))
        assert ("i", "f") in ex._serve_states
    yield h, ex, qc, clock, fresh
    h.close()


def _dear(ex, clock, seconds=0.005):
    """Make every evaluation cost ``seconds`` on the cache's clock: the
    door every string request passes once, after the lookup."""
    ex._singleton_write_fast = clock.during(ex._singleton_write_fast, seconds)


# Two bodies dearer than the floor.  "armed_lane": rows the armed serve
# state knows, which pn_serve_pairs answers where a state is armed (jax,
# mesh) - that lane hands the cache no match, so the commit parses.
# "pair_matcher": a row no state knows, which the armed lane declines,
# so on every engine native.pql_match_pairs reads the body and the
# commit keys the entry by what it read.  numpy arms nothing: both
# bodies go through the matcher there.
_DEAR_BODIES = {"armed_lane": range(5), "pair_matcher": [0, 2, 4, 8]}


@pytest.mark.parametrize("which", sorted(_DEAR_BODIES))
def test_dear_repeated_body_hits_from_its_second_send(dash, which, monkeypatch):
    """The polled dashboard (ROADMAP D13's condition): a body dearer
    than the floor is keyed once, at its first commit - by the pair
    matcher's reading where it took the body, with no Python parse, by
    a parse elsewhere - hits on its second send, and goes on hitting
    after another frame's write."""
    from pilosa_tpu.pql import parser

    h, ex, qc, clock, fresh = dash
    _dear(ex, clock)
    body = _pairs(_DEAR_BODIES[which])
    want = fresh.execute("i", body)
    matched = which == "pair_matcher" or ("i", "f") not in ex._serve_states
    keyed = (0, 1) if matched else (1, 0)
    parses = []
    monkeypatch.setattr(parser, "parse", lambda src, _p=parser.parse: parses.append(src) or _p(src))
    assert ex.execute("i", body) == want
    assert (qc.hits, qc.misses, qc.deferred, qc.stores) == (0, 1, 1, 1)
    assert (qc.deferred_parsed, qc.deferred_matched) == keyed
    assert ex.execute("i", body) == want
    assert (qc.hits, qc.misses, qc.deferred) == (1, 1, 1)
    h.index("i").frame("g").set_bit("standard", 1, 9)  # moves the epoch, not f's vector
    assert ex.execute("i", body) == want
    assert (qc.hits, qc.misses, qc.deferred, qc.stores) == (2, 1, 1, 1)
    assert (qc.deferred_parsed, qc.deferred_matched) == keyed
    # Its own frame's write: a miss on the memoized path, the fresh answer stored.
    h.index("i").frame("f").set_bit("standard", 0, 3 * SLICE_WIDTH + 999)
    want = fresh.execute("i", body)
    assert ex.execute("i", body) == want and ex.execute("i", body) == want
    assert (qc.hits, qc.misses, qc.deferred, qc.stores) == (3, 2, 1, 2)
    if matched:
        assert parses == []  # elsewhere pql's own parse cache may know the body


def test_cheap_body_is_never_parsed_by_the_cache(dash, monkeypatch):
    """Below the floor a commit returns before it canonicalises: however
    often the body is sent, the cache parses nothing and remembers
    nothing; where the armed native lane answers, nobody parses."""
    from pilosa_tpu.pql import parser

    h, ex, qc, clock, fresh = dash
    parses, canon = [], []
    monkeypatch.setattr(parser, "parse", lambda src, _p=parser.parse: parses.append(src) or _p(src))
    monkeypatch.setattr(qc, "_canonical", lambda s, _c=qc._canonical: canon.append(s) or _c(s))
    body = _pairs([4, 2, 0], pad="  ")
    want = fresh.execute("i", body)
    parses.clear()
    for send in range(1, 7):
        assert ex.execute("i", body) == want
        assert (qc.misses, qc.deferred) == (send, send)
    assert canon == [] and len(qc._canon) == 0
    assert (qc.deferred_parsed, qc.stores, qc.ineligible, qc.hits, len(qc)) == (0, 0, 0, 0, 0)
    if ("i", "f") in ex._serve_states:
        assert parses == []


@pytest.mark.parametrize("dash", ["jax", "mesh"], indirect=True)  # numpy arms no serve state
def test_queueing_for_a_repair_buys_no_admission(dash):
    """Admission is by the evaluation's cost: 10 ms inside
    _serve_state_repair (the pool's lock, another request's repair or
    its own) leave a read cheap, and it is neither parsed nor stored;
    the same 10 ms of evaluation store it."""
    h, ex, qc, clock, fresh = dash
    fr = h.index("i").frame("f")
    repairs = []
    real = ex._serve_state_repair

    def repair(key, st, span=None):
        repairs.append(key)
        return real(key, st, span)

    ex._serve_state_repair = clock.during(repair, 0.010)
    body = _pairs(range(5), pad="   ")
    fr.set_bit("standard", 2, 2 * SLICE_WIDTH + 777)
    assert ex.execute("i", body) == fresh.execute("i", body)
    assert repairs == [("i", "f")]
    assert (qc.deferred, qc.deferred_parsed, qc.stores, len(qc._canon)) == (1, 0, 0, 0)
    _dear(ex, clock, 0.010)
    fr.set_bit("standard", 2, 2 * SLICE_WIDTH + 778)
    assert ex.execute("i", body) == fresh.execute("i", body)
    assert len(repairs) == 2
    assert (qc.deferred, qc.deferred_parsed, qc.stores) == (2, 1, 1)
    assert ex.execute("i", body) == fresh.execute("i", body) and qc.hits == 1


_BETWEEN = {
    "nothing": (lambda h: None, True),
    "a_write_that_changed_nothing": (
        lambda h: h.index("i").frame("f").set_bit("standard", 0, 0), True),
    "same_frame": (lambda h: h.index("i").frame("f").set_bit("standard", 0, 70), False),
    "another_frame": (lambda h: h.index("i").frame("g").set_bit("standard", 0, 70), False),
    "a_new_fragment": (
        lambda h: h.index("i").frame("g").set_bit("standard", 0, 5 * SLICE_WIDTH), False),
    "a_cleared_bit": (lambda h: h.index("i").frame("f").clear_bit("standard", 0, 0), False),
    "frame_time_quantum": (lambda h: h.index("i").frame("f").set_time_quantum("YMD"), False),
    "index_time_quantum": (lambda h: h.index("i").set_time_quantum("YM"), False),
    "frame_options": (
        lambda h: h.index("i").frame("f").apply_options(FrameOptions(inverse_enabled=True)),
        False),
    "remote_max_slice": (lambda h: h.index("i").set_remote_max_slice(9), False),
    "remote_max_slice_unmoved": (lambda h: h.index("i").set_remote_max_slice(0), True),
    "a_frame_created": (lambda h: h.index("i").create_frame("n", FrameOptions()), False),
    "a_frame_deleted": (lambda h: h.index("i").delete_frame("g"), False),
}


@pytest.mark.parametrize("keyed_by", ["parse", "match"])
@pytest.mark.parametrize("between", sorted(_BETWEEN))
def test_write_between_deferred_lookup_and_commit_declines_the_store(env, between, keyed_by):
    """A deferred token cannot name its frames before it is keyed, so it
    holds the process's write epoch: anything that could move any
    validity vector between the lookup and the commit - a bit, on any
    frame; a fragment's creation; a schema field of the vector's header
    - declines the store, whether the key then comes from a parse or
    from the pair matcher's reading.  The memo is filled all the same,
    so the next send is judged by its own frames' vector."""
    from pilosa_tpu import native

    h, fr, ex, qc = env
    h.index("i").create_frame("g", FrameOptions()).set_bit("standard", 0, 1)
    edit, stored = _BETWEEN[between]
    body = Q_PAIR if keyed_by == "parse" else Q_PAIR + " " + Q_PAIR
    results = [5] if keyed_by == "parse" else [5, 5]
    cached, tok = qc.lookup(h, "i", body, None)
    assert cached is None and tok.deferred
    if keyed_by == "match":
        tok.match = native.pql_match_pairs(body.encode())
        assert tok.match is not None
    edit(h)
    assert qc.commit(h, tok, results) is stored
    assert (qc.stores, len(qc)) == (int(stored), int(stored))
    assert (qc.deferred_parsed, qc.deferred_matched) == (
        (1, 0) if keyed_by == "parse" else (0, 1))
    assert tok.keyed == keyed_by
    cached, tok = qc.lookup(h, "i", body, None)
    assert qc.deferred == 1  # the memo knows the string now
    assert (cached == results) if stored else (cached is None and not tok.deferred)


# -- a body the pair matcher took is keyed by what it read -------------------


def _call(op="Intersect", a=0, b=1, frame="f", key="rowID"):
    f = f', frame="{frame}"' if frame else ""
    return f"Count({op}(Bitmap({key}={a}{f}), Bitmap({key}={b}{f})))"


_BASE = [_call(), _call("Union", 2, 3)]
# One edit each of the base body: what the fingerprint has to tell apart.
_VARIANTS = {
    "one_op": [_call("Xor"), _BASE[1]],
    "one_row": [_call(b=2), _BASE[1]],
    "one_frame": [_call(frame="g"), _BASE[1]],
    "one_row_label_key": [_call(key="id"), _BASE[1]],
    "order_of_two_calls": _BASE[::-1],
    "default_against_named_frame": [_call(frame=None), _BASE[1]],
    "rows_swapped_in_a_call": [_call(a=1, b=0), _BASE[1]],
    "a_call_more": _BASE + [_call()],
}


@pytest.mark.parametrize("variant", sorted(_VARIANTS))
def test_matched_fingerprint_tells_bodies_apart(env, variant):
    """Injective where it must be: a body that differs from another in
    one op, row, frame, row-label key, the order of two calls, or the
    default against a named frame gets an entry of its own and hits only
    itself.  Through the cache's own door, so that a row-label key no
    frame has can be keyed too."""
    from pilosa_tpu import native

    h, fr, ex, qc = env
    bodies = {"base": " ".join(_BASE), variant: " ".join(_VARIANTS[variant])}
    for n, (name, body) in enumerate(bodies.items()):
        cached, tok = qc.lookup(h, "i", body, None)
        assert cached is None and tok.deferred
        tok.match = native.pql_match_pairs(body.encode())
        assert tok.match is not None
        assert qc.commit(h, tok, [name] * len(tok.match[0]))
        assert len(qc) == n + 1
    for name, body in bodies.items():
        cached, tok = qc.lookup(h, "i", body, None)
        assert tok is None and set(cached) == {name}
    assert (qc.deferred_matched, qc.deferred_parsed, qc.stores, qc.hits) == (2, 0, 2, 2)


@pytest.fixture()
def wide(env):
    """``env`` with rows 0-3 of frames f, g and the default frame."""
    h, fr, ex, qc = env
    for name in ("g", "general"):
        h.index("i").create_frame(name, FrameOptions())
    rng = np.random.default_rng(5)
    for name in ("f", "g", "general"):
        for r in range(4):
            for c in rng.choice(64, size=24, replace=False):
                h.index("i").frame(name).set_bit("standard", r, int(c))
    return h, ex, qc, Executor(h, engine="numpy", qcache=None)


def test_matched_bodies_hit_only_themselves_through_the_executor(wide, monkeypatch):
    from pilosa_tpu.pql import parser

    h, ex, qc, fresh = wide
    bodies = [" ".join(_BASE)] + [
        " ".join(calls) for name, calls in sorted(_VARIANTS.items())
        if name != "one_row_label_key"  # no frame of the index has that label
    ]
    want = [fresh.execute("i", b) for b in bodies]
    assert len({tuple(w) for w in want}) > 4  # the data tells most of them apart too
    monkeypatch.setattr(parser, "parse", lambda src: pytest.fail(f"parsed {src!r}"))
    assert [ex.execute("i", b) for b in bodies] == want
    assert (qc.stores, len(qc), qc.deferred_matched, qc.hits) == (len(bodies),) * 3 + (0,)
    assert [ex.execute("i", b) for b in bodies] == want
    assert (qc.hits, qc.misses, qc.deferred_parsed) == (len(bodies), len(bodies), 0)


def test_two_spellings_of_a_matched_body_share_an_entry(wide, monkeypatch):
    """What the matcher reads alike is one entry: whitespace, the
    quotes round a frame's name, the order of a leaf's two arguments.
    A spelling's first send is a deferred miss, as ever (it stores under
    the same key); its second hits."""
    from pilosa_tpu.pql import parser

    h, ex, qc, fresh = wide
    monkeypatch.setattr(parser, "parse", lambda src: pytest.fail(f"parsed {src!r}"))
    base = " ".join(_BASE)
    spellings = [
        base,
        "  " + base.replace(", ", " ,\n\t").replace("(", "( ") + "\n",
        base.replace('"f"', "'f'"),
        base.replace('"f"', "f"),
        base.replace('Bitmap(rowID=0, frame="f")', 'Bitmap(frame="f", rowID=0)'),
    ]
    assert len(set(spellings)) == len(spellings)
    want = fresh.execute("i", base)
    for n, body in enumerate(spellings, 1):
        assert ex.execute("i", body) == want
        assert (len(qc), qc.deferred_matched, qc.hits) == (1, n, 0)
    for n, body in enumerate(spellings, 1):
        assert ex.execute("i", body) == want and qc.hits == n
    assert (qc.misses, qc.deferred_parsed) == (len(spellings), 0)


_UNMATCHED = {
    "three_operands": " ".join(
        f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID=1, frame="f"), '
        'Bitmap(rowID=2, frame="g")))' for a in (0, 3)),
    "nested_tree": 'Count(Union(Intersect(Bitmap(rowID=0, frame="f"), '
        f'Bitmap(rowID=1, frame="g")), Bitmap(rowID=2, frame="f"))) {_call()}',
    "single_call": _call(),
    "bitmap_result": f'Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f")) {_call()}',
}


@pytest.mark.parametrize("shape", sorted(_UNMATCHED))
def test_body_no_matcher_takes_is_keyed_by_the_parse(wide, shape):
    h, ex, qc, fresh = wide
    body = _UNMATCHED[shape]
    want = [getattr(r, "bits", lambda: r)() for r in fresh.execute("i", body)]
    got = lambda: [getattr(r, "bits", lambda: r)() for r in ex.execute("i", body)]
    assert got() == want
    assert (qc.deferred_parsed, qc.deferred_matched, qc.stores, qc.hits) == (1, 0, 1, 0)
    assert isinstance(next(iter(qc._store))[1], str)
    assert got() == want
    assert (qc.deferred_parsed, qc.deferred_matched, qc.stores, qc.hits) == (1, 0, 1, 1)


def test_topn_beside_pair_counts_is_judged_by_the_parse(wide):
    h, ex, qc, fresh = wide
    body = f'{_call()} TopN(frame="f", n=2)'
    for sends in (1, 2):
        assert ex.execute("i", body) == fresh.execute("i", body)
        assert (qc.deferred_parsed, qc.deferred_matched, qc.ineligible, len(qc)) == (1, 0, sends, 0)


@pytest.mark.parametrize("again", ["by_the_matcher", "by_the_parse"])
def test_matched_body_dropped_from_the_memo_and_keyed_again(wide, again, monkeypatch):
    """The memo is a 512-entry LRU: a body it dropped is a deferred miss
    on its next send and is keyed anew.  By the matcher again, it finds
    its old entry's key and replaces it; by the parse (another lane
    answered this time), it stores beside it - the key spaces never
    meet - which costs a miss and never a wrong count."""
    h, ex, qc, fresh = wide
    body, other = " ".join(_BASE), " ".join(_VARIANTS["one_op"])
    want = fresh.execute("i", body)
    assert ex.execute("i", body) == want and ex.execute("i", body) == want
    assert (qc.hits, qc.deferred_matched, len(qc)) == (1, 1, 1)
    qc._canon_max = 1
    assert ex.execute("i", other) == fresh.execute("i", other)
    assert list(qc._canon) == [other]
    if again == "by_the_parse":
        monkeypatch.setenv("PILOSA_TPU_NO_FASTLANE", "1")
    assert ex.execute("i", body) == want
    assert (qc.hits, qc.misses, qc.deferred) == (1, 3, 3)
    if again == "by_the_matcher":
        assert (qc.deferred_matched, qc.deferred_parsed, len(qc)) == (3, 0, 2)
    else:
        assert (qc.deferred_matched, qc.deferred_parsed, len(qc)) == (2, 1, 3)
        assert sorted(type(k[1]).__name__ for k in qc._store) == ["str", "tuple", "tuple"]
    h.index("i").frame("f").set_bit("standard", 0, 60)
    h.index("i").frame("f").set_bit("standard", 1, 60)
    assert ex.execute("i", body) == fresh.execute("i", body) != want


@pytest.mark.parametrize("route", ["match", "parse"])
def test_body_over_the_fingerprint_bound_is_keyed_by_neither_route(wide, route, monkeypatch):
    import pilosa_tpu.qcache as qcache_mod
    from pilosa_tpu.pql import parser

    h, ex, qc, fresh = wide
    body = " ".join(_BASE)
    monkeypatch.setattr(qcache_mod, "_FINGERPRINT_MAX_LEN", len(body) - 1)
    monkeypatch.setattr(qcache_mod, "_matched_info", lambda m: pytest.fail("keyed by the match"))
    monkeypatch.setattr(qcache_mod, "_parsed_info", lambda s: pytest.fail("keyed by the parse"))
    if route == "parse":
        monkeypatch.setenv("PILOSA_TPU_NO_FASTLANE", "1")
    for sends in (1, 2):
        assert ex.execute("i", body) == fresh.execute("i", body)
        assert (qc.stores, len(qc), qc.ineligible, qc.hits) == (0, 0, sends, 0)
    assert qc._canon[body] is None
    assert (qc.deferred_matched, qc.deferred_parsed) == ((1, 0) if route == "match" else (0, 1))


def test_commit_span_says_what_keyed_the_entry(wide):
    from pilosa_tpu.trace import Span

    h, ex, qc, fresh = wide
    tags = []
    for body in (" ".join(_BASE), _UNMATCHED["three_operands"]):
        for _ in range(2):
            root = Span("root")
            ex.execute("i", body, opt=ExecOptions(span=root))
            commit = [c for c in root.children if c.name == "qcache.commit"]
            tags.append(commit[0].tags if commit else root.tags["qcache"])
    assert tags == [{"keyed": "match"}, "hit", {"keyed": "parse"}, "hit"]
    h.index("i").frame("f").set_bit("standard", 0, 61)
    root = Span("root")
    ex.execute("i", " ".join(_BASE), opt=ExecOptions(span=root))
    assert root.children[-1].tags == {"keyed": "memo"}
    qc.min_cost_ms = 1e9
    root = Span("root")
    ex.execute("i", " ".join(_VARIANTS["one_op"]), opt=ExecOptions(span=root))
    assert root.children[-1].name == "qcache.commit"
    assert root.children[-1].tags == {}  # under the floor: keyed by nothing


def test_non_cacheable_body_counts_ineligible_once_at_commit(env):
    h, fr, ex, qc = env
    topn = 'TopN(frame="f", n=2)'
    cached, tok = qc.lookup(h, "i", topn, None)
    assert cached is None and tok.deferred
    assert (qc.ineligible, qc.misses, qc.deferred) == (0, 1, 1)  # not judged yet
    assert not qc.commit(h, tok, [[]])
    assert (qc.ineligible, qc.deferred_parsed, qc.stores) == (1, 1, 0)
    # The memo holds the verdict: later sends are ineligible at lookup.
    assert qc.lookup(h, "i", topn, None) == (None, None)
    assert (qc.ineligible, qc.misses, qc.deferred) == (2, 1, 1)
    # Below the floor nobody asks: the body stays a deferred miss.
    qc.min_cost_ms = 1e9
    other = 'TopN(frame="f", n=3)'
    for _ in range(3):
        _, tok = qc.lookup(h, "i", other, None)
        assert not qc.commit(h, tok, [[]])
    assert (qc.ineligible, qc.deferred, qc.deferred_parsed) == (2, 4, 1)


def test_deferred_request_is_tagged_on_its_root_span(env):
    from pilosa_tpu.trace import Span

    h, fr, ex, qc = env
    tags = []
    for _ in range(2):
        root = Span("root")
        ex.execute("i", Q_PAIR, opt=ExecOptions(span=root))
        tags.append(root.tags["qcache"])
        assert [c.name for c in root.children][0] == "qcache.lookup"
    assert tags == ["deferred", "hit"]
    fr.set_bit("standard", 0, 40)
    root = Span("root")
    ex.execute("i", Q_PAIR, opt=ExecOptions(span=root))
    assert root.tags["qcache"] == "miss"
    assert [c.name for c in root.children][-1] == "qcache.commit"


def test_write_epoch_read_twice_alike_means_no_generation_moved():
    """The epoch's contract under threads: every stamp is a distinct
    generation, and an epoch that reads the same before and after two
    looks at the fragments' generations saw them unchanged.  More
    writers than cores, a short switch interval."""
    import sys
    import threading

    from pilosa_tpu.core.fragment import _WriteEpoch

    class Frag:
        generation = 0

    epoch = _WriteEpoch()
    frags = [Frag() for _ in range(16)]
    seen = [[] for _ in frags]
    torn, quiet = [], [0]
    stop = threading.Event()

    def writer(i):
        for _ in range(1500):
            epoch.stamp(frags[i])
            seen[i].append(frags[i].generation)

    def reader():
        while not stop.is_set():
            e0 = epoch.read()
            a = [f.generation for f in frags]
            b = [f.generation for f in frags]
            if epoch.read() == e0:
                quiet[0] += 1
                if a != b:
                    torn.append((a, b))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        readers = [threading.Thread(target=reader) for _ in range(2)]
        writers = [threading.Thread(target=writer, args=(i,)) for i in range(len(frags))]
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join(timeout=60)
        stop.set()
        for t in readers:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in readers + writers)
    finally:
        sys.setswitchinterval(interval)
    drawn = [g for gens in seen for g in gens]
    assert len(set(drawn)) == len(drawn) == 16 * 1500
    assert epoch.read() == max(drawn) == 16 * 1500
    assert torn == []


# -- config surface ---------------------------------------------------------


def test_qcache_config_toml_and_env(monkeypatch):
    from pilosa_tpu.config import Config

    cfg = Config.from_dict(
        {"qcache": {"enabled": False, "max-bytes": 4096, "min-cost-ms": 7.5}}
    )
    assert cfg.qcache_enabled is False
    assert cfg.qcache_max_bytes == 4096
    assert cfg.qcache_min_cost_ms == 7.5
    monkeypatch.setenv("PILOSA_TPU_QCACHE", "true")
    monkeypatch.setenv("PILOSA_TPU_QCACHE_MAX_BYTES", "8192")
    monkeypatch.setenv("PILOSA_TPU_QCACHE_MIN_COST_MS", "0.5")
    cfg.apply_env()
    assert cfg.qcache_enabled is True
    assert cfg.qcache_max_bytes == 8192
    assert cfg.qcache_min_cost_ms == 0.5


def test_ranking_debounce_promotion(tmp_path, monkeypatch):
    """[cache] ranking-debounce-s: Config resolves TOML + env ONCE
    (apply_env), the value threads through Holder -> Index -> Frame ->
    View -> Fragment construction (no module global — two holders in
    one process keep independent settings), and the debounce moves."""
    from pilosa_tpu.config import Config
    from pilosa_tpu.core.cache import RankCache

    cfg = Config.from_dict({"cache": {"ranking-debounce-s": "2s"}})
    assert cfg.ranking_debounce_s == 2.0
    monkeypatch.setenv("PILOSA_TPU_RANKING_DEBOUNCE_S", "3.5")
    cfg.apply_env()
    assert cfg.ranking_debounce_s == 3.5

    now = [100.0]
    rc = RankCache(4, _now=lambda: now[0], debounce_s=2.0)
    assert rc.debounce_s == 2.0
    rc.add(1, 10)  # first invalidate recalculates (update_time far past)
    t0 = rc._update_time
    now[0] += 1.0
    rc.add(2, 20)  # inside the 2 s debounce: no recalc
    assert rc._update_time == t0
    now[0] += 1.5
    rc.add(3, 30)  # past it: recalc
    assert rc._update_time > t0

    # RankCache itself never reads the env — Config is the only
    # resolution point, so construction is deterministic.
    assert RankCache(4, _now=lambda: now[0]).debounce_s == 10.0

    # The configured value reaches deeply-nested fragment caches through
    # holder construction, and a second holder keeps its own setting.
    ha = Holder(str(tmp_path / "a"), ranking_debounce_s=cfg.ranking_debounce_s)
    hb = Holder(str(tmp_path / "b"))
    for h in (ha, hb):
        h.open()
        h.create_index("i").create_frame(
            "f", FrameOptions(cache_type="ranked", cache_size=4)
        )
        h.index("i").frame("f").set_bit("standard", 0, 1)
    frag_a = ha.index("i").frame("f").view("standard").fragment(0)
    frag_b = hb.index("i").frame("f").view("standard").fragment(0)
    assert frag_a.cache.debounce_s == 3.5
    assert frag_b.cache.debounce_s == 10.0  # module default, not leaked
    ha.close()
    hb.close()


# -- stateful equivalence (style of test_fragment_stateful.py) ---------------

_QUERIES = [
    'Count(Bitmap(rowID=0, frame="f"))',
    'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f")))',
    'Count(Union(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f"),'
    ' Bitmap(rowID=3, frame="f")))',
    'Count(Difference(Bitmap(rowID=2, frame="f"), Bitmap(rowID=3, frame="f")))',
    'Count(Xor(Bitmap(rowID=4, frame="f"), Bitmap(rowID=5, frame="f")))',
    'Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=4, frame="f"))',
]


def _assert_equivalent(got, want):
    if hasattr(got[0], "segments"):  # QueryBitmap: compare bit sets
        assert got[0].bits() == want[0].bits()
    else:
        assert got == want


@pytest.mark.parametrize("seed", [11, 23, 47])
def test_equivalence_random_interleaving(tmp_path, seed):
    """Random interleavings of writes (executor + direct-fragment),
    clears, and repeated queries: every answer from the cached executor
    must equal a FRESH uncached execution of the same query — the
    exactness contract (read-your-writes included, since a fresh
    execution by definition sees every prior write).  Deterministic
    seeds so the suite needs no hypothesis; the machine below upgrades
    to shrinking fuzz when hypothesis is installed."""
    rng = np.random.default_rng(seed)
    h = Holder(str(tmp_path / "d"))
    h.open()
    h.create_index("i").create_frame("f", FrameOptions())
    fr = h.index("i").frame("f")
    qc = QueryCache(min_cost_ms=0.0)
    ex = Executor(h, engine="numpy", qcache=qc)
    fresh = Executor(h, engine="numpy", qcache=None)
    try:
        for _ in range(200):
            op = rng.integers(0, 5)
            r = int(rng.integers(0, 6))
            c = int(rng.integers(0, 64)) if rng.random() < 0.7 else int(
                rng.integers(SLICE_WIDTH - 8, SLICE_WIDTH + 64)
            )
            if op == 0:
                ex.execute("i", f'SetBit(rowID={r}, frame="f", columnID={c})')
            elif op == 1:
                fr.set_bit("standard", r, c)
            elif op == 2:
                fr.clear_bit("standard", r, c)
            else:  # queries twice as likely as any single write kind
                q = _QUERIES[int(rng.integers(0, len(_QUERIES)))]
                _assert_equivalent(ex.execute("i", q), fresh.execute("i", q))
        assert qc.hits > 0  # the interleaving really exercised the cache
    finally:
        h.close()


try:
    from hypothesis import settings
    from hypothesis import strategies as st
    from hypothesis.stateful import RuleBasedStateMachine, rule
except ImportError:
    pass
else:
    _ROW = st.integers(0, 5)
    _COL = st.one_of(
        st.integers(0, 64), st.integers(SLICE_WIDTH - 8, SLICE_WIDTH + 64)
    )
    _QIDX = st.integers(0, len(_QUERIES) - 1)

    class QCacheEquivalenceMachine(RuleBasedStateMachine):
        """Shrinking-fuzz upgrade of the seeded interleaving test."""

        def __init__(self):
            super().__init__()
            import shutil

            self._dir = tempfile.mkdtemp()
            self.h = Holder(self._dir)
            self.h.open()
            self.h.create_index("i").create_frame("f", FrameOptions())
            self.fr = self.h.index("i").frame("f")
            self.qc = QueryCache(min_cost_ms=0.0)
            self.ex = Executor(self.h, engine="numpy", qcache=self.qc)
            self.fresh = Executor(self.h, engine="numpy", qcache=None)
            self._shutil = shutil

        def teardown(self):
            try:
                self.h.close()
            finally:
                self._shutil.rmtree(self._dir, ignore_errors=True)

        @rule(r=_ROW, c=_COL)
        def executor_write(self, r, c):
            self.ex.execute("i", f'SetBit(rowID={r}, frame="f", columnID={c})')

        @rule(r=_ROW, c=_COL)
        def direct_write(self, r, c):
            self.fr.set_bit("standard", r, c)

        @rule(r=_ROW, c=_COL)
        def clear(self, r, c):
            self.fr.clear_bit("standard", r, c)

        @rule(k=_QIDX)
        def query(self, k):
            _assert_equivalent(
                self.ex.execute("i", _QUERIES[k]),
                self.fresh.execute("i", _QUERIES[k]),
            )

    QCacheEquivalenceMachine.TestCase.settings = settings(
        max_examples=20, stateful_step_count=30, deadline=None
    )
    TestQCacheEquivalence = QCacheEquivalenceMachine.TestCase
