"""Executor tests (reference analog: executor_test.go, local paths)."""

import numpy as np
import pytest

from pilosa_tpu.core.frame import FrameOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core.index import IndexOptions
from pilosa_tpu.executor import ExecOptions, Executor, QueryBitmap
from pilosa_tpu.pilosa import PilosaError, ErrTooManyWrites, SLICE_WIDTH


@pytest.fixture
def env(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("general", FrameOptions())
    idx.create_frame("f", FrameOptions(inverse_enabled=True, time_quantum="YMDH"))
    e = Executor(h, engine="numpy")
    yield h, e
    h.close()


def test_setbit_bitmap_roundtrip(env):
    h, e = env
    (changed,) = e.execute("i", 'SetBit(rowID=10, frame="f", columnID=100)')
    assert changed is True
    (changed,) = e.execute("i", 'SetBit(rowID=10, frame="f", columnID=100)')
    assert changed is False
    (bm,) = e.execute("i", 'Bitmap(rowID=10, frame="f")')
    assert bm.bits() == [100]
    # inverse view was maintained
    (inv,) = e.execute("i", 'Bitmap(columnID=100, frame="f")')
    assert inv.bits() == [10]


def test_multi_slice_count_intersect(env):
    h, e = env
    cols_a = [1, 2, 3, SLICE_WIDTH + 1, SLICE_WIDTH + 2, 3 * SLICE_WIDTH + 7]
    cols_b = [2, 3, SLICE_WIDTH + 2, 2 * SLICE_WIDTH + 5]
    for c in cols_a:
        e.execute("i", f'SetBit(rowID=1, frame="f", columnID={c})')
    for c in cols_b:
        e.execute("i", f'SetBit(rowID=2, frame="f", columnID={c})')
    (n,) = e.execute("i", 'Count(Intersect(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f")))')
    assert n == 3  # {2, 3, W+2}
    (bm,) = e.execute("i", 'Intersect(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f"))')
    assert bm.bits() == [2, 3, SLICE_WIDTH + 2]


def test_union_difference_xor(env):
    h, e = env
    for c in [1, 2]:
        e.execute("i", f'SetBit(rowID=1, frame="f", columnID={c})')
    for c in [2, 3]:
        e.execute("i", f'SetBit(rowID=2, frame="f", columnID={c})')
    (u,) = e.execute("i", 'Union(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f"))')
    assert u.bits() == [1, 2, 3]
    (d,) = e.execute("i", 'Difference(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f"))')
    assert d.bits() == [1]
    (x,) = e.execute("i", 'Xor(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f"))')
    assert x.bits() == [1, 3]


def test_range_time_views(env):
    h, e = env
    e.execute("i", 'SetBit(rowID=1, frame="f", columnID=7, timestamp="2017-03-02T15:00")')
    e.execute("i", 'SetBit(rowID=1, frame="f", columnID=8, timestamp="2017-05-01T00:00")')
    (bm,) = e.execute(
        "i", 'Range(rowID=1, frame="f", start="2017-03-01T00:00", end="2017-04-01T00:00")'
    )
    assert bm.bits() == [7]
    (bm2,) = e.execute(
        "i", 'Range(rowID=1, frame="f", start="2017-01-01T00:00", end="2018-01-01T00:00")'
    )
    assert bm2.bits() == [7, 8]


def test_topn_two_phase(env):
    h, e = env
    idx = h.index("i")
    idx.create_frame("r", FrameOptions(cache_type="ranked"))
    # row 1: bits in slices 0 and 1; row 2: fewer bits.
    bits = [(1, c) for c in range(20)] + [(1, SLICE_WIDTH + c) for c in range(15)]
    bits += [(2, c) for c in range(10)] + [(3, 2 * SLICE_WIDTH + 1)]
    frame = h.frame("i", "r")
    rows, cols = zip(*bits)
    frame.import_bits(rows, cols)
    (pairs,) = e.execute("i", 'TopN(frame="r", n=2)')
    assert [(p.id, p.count) for p in pairs] == [(1, 35), (2, 10)]


def test_topn_with_src(env):
    h, e = env
    idx = h.index("i")
    idx.create_frame("r", FrameOptions(cache_type="ranked"))
    frame = h.frame("i", "r")
    frame.import_bits([1] * 10 + [2] * 10, list(range(10)) + list(range(5, 15)))
    # src = row 1 of frame f
    for c in range(8):
        e.execute("i", f'SetBit(rowID=9, frame="f", columnID={c})')
    (pairs,) = e.execute("i", 'TopN(Bitmap(rowID=9, frame="f"), frame="r", n=5)')
    assert [(p.id, p.count) for p in pairs] == [(1, 8), (2, 3)]


def test_topn_ids_and_threshold(env):
    h, e = env
    idx = h.index("i")
    idx.create_frame("r", FrameOptions(cache_type="ranked"))
    frame = h.frame("i", "r")
    frame.import_bits([1] * 5 + [2] * 3 + [3] * 1, list(range(5)) + list(range(3)) + [0])
    (pairs,) = e.execute("i", 'TopN(frame="r", ids=[2,3])')
    assert {(p.id, p.count) for p in pairs} == {(2, 3), (3, 1)}
    (pairs2,) = e.execute("i", 'TopN(frame="r", n=10, threshold=3)')
    assert {(p.id, p.count) for p in pairs2} == {(1, 5), (2, 3)}


def test_attrs(env):
    h, e = env
    e.execute("i", 'SetBit(rowID=1, frame="f", columnID=2)')
    (res,) = e.execute("i", 'SetRowAttrs(rowID=1, frame="f", name="alice", active=true)')
    assert res is None
    (bm,) = e.execute("i", 'Bitmap(rowID=1, frame="f")')
    assert bm.attrs == {"name": "alice", "active": True}
    e.execute("i", 'SetColumnAttrs(columnID=2, info="x")')
    (inv,) = e.execute("i", 'Bitmap(columnID=2, frame="f")')
    assert inv.attrs == {"info": "x"}
    # exclude_attrs opt
    (bm2,) = e.execute("i", 'Bitmap(rowID=1, frame="f")', opt=ExecOptions(exclude_attrs=True))
    assert bm2.attrs == {}


def test_errors(env):
    h, e = env
    with pytest.raises(PilosaError):
        e.execute("i", "Bogus(x=1)")
    with pytest.raises(PilosaError):
        e.execute("i", 'Bitmap(rowID=1, frame="nope")')
    with pytest.raises(PilosaError):
        e.execute("i", 'Bitmap(frame="f")')  # neither row nor col
    with pytest.raises(PilosaError):
        e.execute("i", 'Count(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f"))')
    e2 = Executor(h, engine="numpy", max_writes_per_request=1)
    with pytest.raises(ErrTooManyWrites):
        e2.execute("i", 'SetBit(rowID=1, frame="f", columnID=1) SetBit(rowID=1, frame="f", columnID=2)')


def test_count_on_general_default_frame(env):
    h, e = env
    e.execute("i", "SetBit(rowID=5, frame=general, columnID=9)")
    (n,) = e.execute("i", "Count(Bitmap(rowID=5))")
    assert n == 1


def test_jax_engine_matches_numpy(env, tmp_path):
    # Same queries through the JaxEngine (CPU backend under conftest).
    h, e = env
    for c in [1, 2, 3, SLICE_WIDTH + 4]:
        e.execute("i", f'SetBit(rowID=1, frame="f", columnID={c})')
    for c in [2, SLICE_WIDTH + 4]:
        e.execute("i", f'SetBit(rowID=2, frame="f", columnID={c})')
    ej = Executor(h, engine="jax")
    q = 'Count(Intersect(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f")))'
    assert e.execute("i", q) == ej.execute("i", q)
    (bm_np,) = e.execute("i", 'Union(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f"))')
    (bm_j,) = ej.execute("i", 'Union(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f"))')
    assert bm_np.bits() == bm_j.bits()


def test_mapreduce_node_failure_retry(tmp_path):
    """A remote node erroring mid-query re-maps its slices onto the
    remaining replica owners instead of failing the query
    (executor.go:1147-1159)."""
    from pilosa_tpu.cluster import Cluster, Node

    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    # Bits in 4 slices, all stored locally (this host holds every replica's
    # data so the fallback path can answer).
    for s in range(4):
        idx.frame("f").set_bit("standard", 1, s * SLICE_WIDTH + 3)

    hosts = ["h0:1", "h1:1"]
    cluster = Cluster([Node(host) for host in hosts], replica_n=2)

    calls = []

    class FailingClient:
        def __init__(self, host):
            self.host = host

        def execute_remote_call(self, index, call, slices, deadline=None):
            calls.append((self.host, list(slices)))
            raise ConnectionError("node down")

    e = Executor(
        h, engine="numpy", cluster=cluster, client_factory=FailingClient, host="h0:1"
    )
    (n,) = e.execute("i", 'Count(Bitmap(rowID=1, frame="f"))')
    assert n == 4  # all slices answered locally after h1 failed
    assert any(host == "h1:1" for host, _ in calls)  # remote was tried
    # With NO replicas (replica_n=1) the same failure surfaces an error.
    cluster1 = Cluster([Node(host) for host in hosts], replica_n=1)
    e1 = Executor(
        h, engine="numpy", cluster=cluster1, client_factory=FailingClient, host="h0:1"
    )
    with pytest.raises(Exception):
        e1.execute("i", 'Count(Bitmap(rowID=1, frame="f"))')
    h.close()


@pytest.mark.parametrize("engine", ["numpy", "jax"])
def test_count_intersect_batch_fusion(tmp_path, engine):
    """A request carrying several Count(Intersect(Bitmap,Bitmap)) calls runs
    through the fused gather path and matches per-call execution."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("f")
    rng = np.random.default_rng(5)
    for r in range(6):
        for c in rng.choice(2 * SLICE_WIDTH, size=50, replace=False):
            fr.set_bit("standard", r, int(c))
    e = Executor(h, engine=engine)

    batch_q = "\n".join(
        f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
        for a, b in [(0, 1), (2, 3), (4, 5), (0, 5)]
    )
    fused = e.execute("i", batch_q)
    singles = [
        e.execute("i", f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))')[0]
        for a, b in [(0, 1), (2, 3), (4, 5), (0, 5)]
    ]
    assert fused == singles

    # Mutation invalidates the device row cache: counts update.
    before = e.execute("i", batch_q)[0]
    col = 123456
    fr.set_bit("standard", 0, col)
    fr.set_bit("standard", 1, col)
    after = e.execute("i", batch_q)[0]
    assert after == before + 1

    # The fused path generalizes across pair ops — a mixed batch of
    # Count(Intersect/Union/Difference/Xor) matches per-call execution.
    mixed = " ".join(
        f'Count({op}(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
        for op, a, b in [
            ("Intersect", 0, 1), ("Union", 0, 1), ("Difference", 0, 1),
            ("Xor", 0, 1), ("Union", 2, 3), ("Difference", 4, 5),
        ]
    )
    fused_mixed = e.execute("i", mixed)
    singles_mixed = [
        e.execute("i", f'Count({op}(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))')[0]
        for op, a, b in [
            ("Intersect", 0, 1), ("Union", 0, 1), ("Difference", 0, 1),
            ("Xor", 0, 1), ("Union", 2, 3), ("Difference", 4, 5),
        ]
    ]
    assert fused_mixed == singles_mixed
    h.close()


def test_fusion_respects_preceding_writes(tmp_path):
    """A write earlier in the same request must be visible to later Counts —
    mixed requests take the sequential path, not the fused one."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("f")
    fr.set_bit("standard", 0, 1)
    fr.set_bit("standard", 1, 1)
    e = Executor(h, engine="numpy")
    q = (
        'SetBit(rowID=0, frame="f", columnID=5) '
        'SetBit(rowID=1, frame="f", columnID=5) '
        'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f"))) '
        'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=0, frame="f")))'
    )
    res = e.execute("i", q)
    assert res == [True, True, 2, 2]  # counts observe the writes
    h.close()


def test_set_bit_batch_fusion_matches_sequential(tmp_path):
    """An all-SetBit request runs through the batched write path and
    returns the same per-call changed bools as sequential execution —
    including inverse + time-quantum views and in-request duplicates."""
    def build(d):
        h = Holder(str(tmp_path / d))
        h.open()
        idx = h.create_index("i")
        idx.create_frame("f", FrameOptions(inverse_enabled=True, time_quantum="YMD"))
        return h, Executor(h, engine="numpy")

    calls = [
        'SetBit(rowID=1, frame="f", columnID=100)',
        'SetBit(rowID=1, frame="f", columnID=%d)' % (SLICE_WIDTH + 7),
        'SetBit(rowID=2, frame="f", columnID=100, timestamp="2017-03-02T15:00")',
        'SetBit(rowID=1, frame="f", columnID=100)',  # duplicate -> False
        'SetBit(rowID=3, frame="f", columnID=200)',
    ]
    h1, e1 = build("seq")
    want = [e1.execute("i", q)[0] for q in calls]
    h2, e2 = build("batch")
    got = e2.execute("i", " ".join(calls))
    assert got == want == [True, True, True, False, True]
    # Data identical on both paths, all views.
    for q in (
        'Bitmap(rowID=1, frame="f")',
        'Bitmap(columnID=100, frame="f")',  # inverse view
        'Count(Range(rowID=2, frame="f", start="2017-03-01T00:00", end="2017-04-01T00:00"))',
    ):
        assert _norm(e1.execute("i", q)) == _norm(e2.execute("i", q))
    h1.close()
    h2.close()


def _norm(results):
    return [r.bits() if hasattr(r, "bits") else r for r in results]


def test_set_bit_batch_remote_forwarding(tmp_path):
    """In a 2-node cluster an all-SetBit request sends ONE batched request
    per remote owner instead of one per call, and merges changed bools."""
    from pilosa_tpu.cluster import Cluster, Node

    h = Holder(str(tmp_path / "data"))
    h.open()
    h.create_index("i").create_frame("f", FrameOptions())
    hosts = ["h0:1", "h1:1"]
    cluster = Cluster([Node(host) for host in hosts], replica_n=1)
    requests = []

    class RecordingClient:
        def __init__(self, host):
            self.host = host

        def execute_remote(self, index, query, slices=None, deadline=None):
            requests.append((self.host, len(query.calls)))
            return [True] * len(query.calls)

    e = Executor(
        h, engine="numpy", cluster=cluster, client_factory=RecordingClient, host="h0:1"
    )
    # Spread bits over slices so both nodes own some.
    calls = [
        'SetBit(rowID=1, frame="f", columnID=%d)' % (s * SLICE_WIDTH + 5)
        for s in range(8)
    ]
    got = e.execute("i", " ".join(calls))
    assert got == [True] * len(calls)
    assert requests and all(host == "h1:1" for host, _ in requests)
    assert len(requests) == 1  # one batched forward, not one per call
    n_remote = requests[0][1]
    assert 0 < n_remote < len(calls)  # split ownership
    # Locally-owned slices actually wrote.
    owned = sum(
        1
        for s in range(8)
        if any(n.host == "h0:1" for n in cluster.fragment_nodes("i", s))
    )
    assert owned == len(calls) - n_remote
    h.close()


def test_set_bit_batch_bad_timestamp_partial_commit(env):
    """A malformed timestamp mid-batch follows sequential semantics: calls
    before it commit, the error surfaces."""
    h, e = env
    q = (
        'SetBit(rowID=1, frame="f", columnID=5) '
        'SetBit(rowID=2, frame="f", columnID=6, timestamp="garbage")'
    )
    with pytest.raises(ValueError):
        e.execute("i", q)
    assert e.execute("i", 'Count(Bitmap(rowID=1, frame="f"))') == [1]


def test_fused_matrix_cache_survives_frame_recreate(env):
    """The fused-path row-matrix cache must not serve a deleted frame's
    data after the frame is recreated with a mutation history that lands
    on a look-alike state (generations are process-global, so an object
    swap can never repeat a cached generation tuple)."""
    h, e = env
    idx = h.index("i")
    fr = idx.frame("general")
    for c in range(10):
        fr.set_bit("standard", 0, c)
        fr.set_bit("standard", 1, c)
    q = " ".join(
        ['Count(Intersect(Bitmap(rowID=0, frame="general"), Bitmap(rowID=1, frame="general")))'] * 2
    )
    assert e.execute("i", q) == [10, 10]  # populates the matrix cache
    idx.delete_frame("general")
    idx.create_frame("general", FrameOptions())
    fr2 = idx.frame("general")
    for c in range(10):
        fr2.set_bit("standard", 0, c)
    fr2.set_bit("standard", 1, 0)
    assert e.execute("i", q) == [1, 1]


def test_fused_matrix_cache_sees_writes(env):
    """Mutations between fused requests invalidate the cached matrix."""
    h, e = env
    fr = h.index("i").frame("general")
    for c in range(5):
        fr.set_bit("standard", 0, c)
        fr.set_bit("standard", 1, c)
    q = " ".join(
        ['Count(Intersect(Bitmap(rowID=0, frame="general"), Bitmap(rowID=1, frame="general")))'] * 2
    )
    assert e.execute("i", q) == [5, 5]
    e.execute("i", 'SetBit(rowID=0, frame="general", columnID=100) '
                   'SetBit(rowID=1, frame="general", columnID=100)')
    assert e.execute("i", q) == [6, 6]


@pytest.mark.parametrize("engine", ["numpy", "jax", "mesh"])
def test_fused_matrix_incremental_refresh(tmp_path, engine):
    """The cached matrix is patched per-slice after writes and extended
    per-row for new rowIDs, staying correct across both paths — on both
    the numpy and jax (device scatter/concat) engines."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("general", FrameOptions())
    e = Executor(h, engine=engine)
    fr = h.index("i").frame("general")
    # Two slices, rows 0/1 in both.
    for base in (0, SLICE_WIDTH):
        for c in range(5):
            fr.set_bit("standard", 0, base + c)
            fr.set_bit("standard", 1, base + c)
    q01 = " ".join(
        ['Count(Intersect(Bitmap(rowID=0, frame="general"), Bitmap(rowID=1, frame="general")))'] * 2
    )
    assert e.execute("i", q01) == [10, 10]  # seeds the cache
    # Write to slice 1 only -> patch path (stale plane re-densified).
    fr.set_bit("standard", 0, SLICE_WIDTH + 100)
    fr.set_bit("standard", 1, SLICE_WIDTH + 100)
    assert e.execute("i", q01) == [11, 11]
    # New rows in the same frame -> append path.
    fr.set_bit("standard", 7, 0)
    fr.set_bit("standard", 8, 0)
    q78 = " ".join(
        ['Count(Intersect(Bitmap(rowID=7, frame="general"), Bitmap(rowID=8, frame="general")))'] * 2
    )
    assert e.execute("i", q78) == [1, 1]
    # Patched + appended entry still serves the original rows correctly.
    assert e.execute("i", q01) == [11, 11]
    h.close()


def test_fused_batch_pages_past_pool_capacity(env):
    """A request whose unique row set exceeds the pool capacity is served
    by CHUNKING the batch and paging rows through the device pool (the
    old design fell back to an uncached one-shot matrix; the row ceiling
    is gone)."""
    h, e = env
    fr = h.index("i").frame("general")
    for r in range(8):
        fr.set_bit("standard", r, r)
        fr.set_bit("standard", r, 100)
    q = " ".join(
        f'Count(Intersect(Bitmap(rowID={r}, frame="general"), Bitmap(rowID={(r + 1) % 8}, frame="general")))'
        for r in range(8)
    )
    pool = e._pool_for("i", "general", "standard", [0])
    pool.cap_max = 4  # force the paging regime for this 8-row batch
    assert e.execute("i", q) == [1] * 8
    assert pool.stat_evictions > 0  # rows actually paged out and back
    assert pool.cap <= 4
    # Repeat request stays correct while still paging.
    assert e.execute("i", q) == [1] * 8
    # A small request afterwards is served resident (no new evictions
    # once its rows are in).
    small = (
        'Count(Intersect(Bitmap(rowID=0, frame="general"), Bitmap(rowID=1, frame="general"))) '
        'Count(Intersect(Bitmap(rowID=2, frame="general"), Bitmap(rowID=3, frame="general")))'
    )
    assert e.execute("i", small) == [1, 1]
    ev = pool.stat_evictions
    assert e.execute("i", small) == [1, 1]
    assert pool.stat_evictions == ev


def test_fused_batch_distributed_one_request_per_node(tmp_path):
    """In a cluster, a fused batch forwards ONE Query per remote node
    (not one request per call), sums per-call counts across nodes, and
    fails over to replicas when the remote dies."""
    from pilosa_tpu.cluster import Cluster, Node

    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("f")
    # All data locally resident (this host holds every replica's data).
    for s in range(4):
        for c in range(10):
            fr.set_bit("standard", 0, s * SLICE_WIDTH + c)
            fr.set_bit("standard", 1, s * SLICE_WIDTH + c + 5)

    hosts = ["h0:1", "h1:1"]
    cluster = Cluster([Node(host) for host in hosts], replica_n=2)
    remote_batches = []

    class SpyClient:
        def __init__(self, host):
            self.host = host

        def execute_remote(self, index, query, slices=None, deadline=None):
            remote_batches.append((self.host, len(query.calls), list(slices)))
            # Answer from the same holder (stand-in for the peer's data).
            peer = Executor(h, engine="numpy")
            return peer.execute(
                index, query, slices=slices, opt=ExecOptions(remote=True)
            )

    e = Executor(h, engine="numpy", cluster=cluster, client_factory=SpyClient, host="h0:1")
    q = " ".join(
        ['Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f")))'] * 3
    )
    got = e.execute("i", q)
    assert got == [20, 20, 20]  # 5 per slice x 4 slices... verified below
    single = Executor(h, engine="numpy").execute(
        "i", 'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f")))'
    )
    assert got == single * 3
    # Exactly one remote batch request carrying all 3 calls.
    assert len(remote_batches) == 1
    host_seen, n_calls, slices_seen = remote_batches[0]
    assert host_seen == "h1:1" and n_calls == 3 and slices_seen

    # Failover: a dying remote re-maps its slices locally; counts intact.
    class DyingClient(SpyClient):
        def execute_remote(self, index, query, slices=None, deadline=None):
            raise ConnectionError("node down")

    e2 = Executor(h, engine="numpy", cluster=cluster, client_factory=DyingClient, host="h0:1")
    assert e2.execute("i", q) == got
    h.close()


def test_fused_gram_upgrade_and_invalidation(tmp_path):
    """Repeated fused requests against an unchanged matrix upgrade to the
    cached Gram (host lookups); any write invalidates it with the entry."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("f")
    for r in range(4):
        for c in range(10 + r):
            fr.set_bit("standard", r, c)
    e = Executor(h, engine="jax")
    q = (
        'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f"))) '
        'Count(Union(Bitmap(rowID=2, frame="f"), Bitmap(rowID=3, frame="f")))'
    )
    first = e.execute("i", q)
    boxes = [pool.box for pool in e._matrix_cache.values()]
    assert boxes and all("gram" not in b for b in boxes)  # cold: direct kernels
    second = e.execute("i", q)
    assert second == first
    boxes = [pool.box for pool in e._matrix_cache.values()]
    assert any("gram" in b for b in boxes)  # upgraded on 2nd hit
    third = e.execute("i", q)  # served from Gram lookups
    assert third == first
    # A write invalidates the entry (and its Gram); counts update.
    fr.set_bit("standard", 0, 500)
    fr.set_bit("standard", 1, 500)
    after = e.execute("i", q)
    assert after[0] == first[0] + 1 and after[1] == first[1]
    h.close()


def test_flat_fast_lane_matches_slow_path(tmp_path):
    """The AST-free compiled-query lane must agree with the parse path on
    results, fall back for out-of-shape requests, and preserve errors."""
    import os

    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("f")
    rng = np.random.default_rng(2)
    for r in range(5):
        for c in rng.choice(2 * SLICE_WIDTH, size=60, replace=False):
            fr.set_bit("standard", r, int(c))
    e = Executor(h, engine="numpy")
    batch = " ".join(
        f'Count({op}(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
        for op, a, b in [("Intersect", 0, 1), ("Union", 1, 2), ("Difference", 3, 4), ("Xor", 2, 4)]
    )
    fast = e.execute("i", batch)
    os.environ["PILOSA_TPU_NO_FASTLANE"] = "1"
    try:
        slow = e.execute("i", batch)
    finally:
        del os.environ["PILOSA_TPU_NO_FASTLANE"]
    assert fast == slow

    # Out-of-shape requests fall back and still work.
    mixed = 'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f"))) Bitmap(rowID=2, frame="f")'
    res = e.execute("i", mixed)
    assert res[0] == slow[0] and res[1].bits()
    # Unknown frame: identical error through the fallback.
    with pytest.raises(PilosaError):
        e.execute("i", 'Count(Intersect(Bitmap(rowID=0, frame="nope"), Bitmap(rowID=1, frame="nope"))) '
                       'Count(Intersect(Bitmap(rowID=0, frame="nope"), Bitmap(rowID=1, frame="nope")))')
    # Parse errors surface identically (fast lane defers to slow path).
    with pytest.raises(Exception):
        e.execute("i", "Count(Intersect(Bitmap(rowID=0")
    h.close()


def test_flat_fast_lane_rejects_conflicting_args(env):
    """Bitmap(columnID=.., rowID=..) must raise through the slow path, not
    be silently answered by the fast lane (arg-conflict parity)."""
    h, e = env
    fr = h.index("i").frame("general")
    for c in range(5):
        fr.set_bit("standard", 0, c)
        fr.set_bit("standard", 1, c)
    bad = (
        'Count(Intersect(Bitmap(columnID=2, rowID=0), Bitmap(rowID=1))) '
        'Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))'
    )
    with pytest.raises(PilosaError):
        e.execute("i", bad)


@pytest.mark.parametrize("engine", ["numpy", "jax"])
def test_inverse_view_fused_batch(tmp_path, engine):
    """A batch of Count(op(Bitmap(columnID=..), ...)) calls (inverse view)
    fuses like the standard view and matches per-call execution; a batch
    mixing views falls back and stays correct."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions(inverse_enabled=True))
    e = Executor(h, engine=engine)
    rng = np.random.default_rng(6)
    for r in range(4):
        for c in rng.choice(300, size=40, replace=False):
            e.execute("i", f'SetBit(rowID={r}, frame="f", columnID={int(c)})')
    inv_batch = " ".join(
        f'Count({op}(Bitmap(columnID={a}, frame="f"), Bitmap(columnID={b}, frame="f")))'
        for op, a, b in [("Intersect", 5, 6), ("Union", 7, 8), ("Xor", 5, 8)]
    )
    fused = e.execute("i", inv_batch)
    singles = [
        e.execute("i", f'Count({op}(Bitmap(columnID={a}, frame="f"), Bitmap(columnID={b}, frame="f")))')[0]
        for op, a, b in [("Intersect", 5, 6), ("Union", 7, 8), ("Xor", 5, 8)]
    ]
    assert fused == singles
    # Mixed views in one request: sequential path, still correct.
    mixed = (
        'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f"))) '
        'Count(Intersect(Bitmap(columnID=5, frame="f"), Bitmap(columnID=6, frame="f")))'
    )
    got = e.execute("i", mixed)
    want = [
        e.execute("i", 'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f")))')[0],
        e.execute("i", 'Count(Intersect(Bitmap(columnID=5, frame="f"), Bitmap(columnID=6, frame="f")))')[0],
    ]
    assert got == want
    h.close()


@pytest.mark.parametrize("engine", ["numpy", "jax"])
def test_count_range_batch_fusion(tmp_path, engine):
    """An all-Count(Range(...)) request runs through the fused multi-view
    OR kernel and matches per-call execution, across frames and covers."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions(time_quantum="YMDH"))
    idx.create_frame("g", FrameOptions(time_quantum="YM"))
    idx.create_frame("plain", FrameOptions())  # no quantum: Range counts 0
    e = Executor(h, engine=engine)
    rng = np.random.default_rng(9)
    stamps = [
        "2017-01-05T10:00", "2017-02-14T00:00", "2017-03-02T15:00",
        "2017-06-30T23:00", "2017-12-31T12:00",
    ]
    for fr_name in ("f", "g"):
        for r in (1, 2):
            for t in stamps:
                for c in rng.choice(2 * SLICE_WIDTH, size=5, replace=False):
                    e.execute(
                        "i",
                        f'SetBit(rowID={r}, frame="{fr_name}", columnID={int(c)}, timestamp="{t}")',
                    )
    ranges = [
        ("f", 1, "2017-01-01T00:00", "2018-01-01T00:00"),
        ("f", 2, "2017-03-01T00:00", "2017-04-01T00:00"),
        ("f", 1, "2017-02-01T00:00", "2017-07-01T00:00"),
        ("g", 1, "2017-01-01T00:00", "2017-07-01T00:00"),
        ("g", 2, "2017-06-01T00:00", "2017-06-02T00:00"),
        ("plain", 1, "2017-01-01T00:00", "2018-01-01T00:00"),
        ("f", 1, "2017-05-01T00:00", "2017-05-01T00:00"),  # empty cover
    ]
    calls = [
        f'Count(Range(rowID={r}, frame="{fr}", start="{s}", end="{en}"))'
        for fr, r, s, en in ranges
    ]
    fused = e.execute("i", " ".join(calls))
    singles = [e.execute("i", q)[0] for q in calls]  # len<2: no fusion
    assert fused == singles
    assert fused[0] > 0 and fused[5] == 0 and fused[6] == 0

    # Writes invalidate the cached multi-view matrix (generation check).
    before = e.execute("i", " ".join(calls))
    e.execute(
        "i",
        'SetBit(rowID=1, frame="f", columnID=999999, timestamp="2017-03-15T00:00")',
    )
    after = e.execute("i", " ".join(calls))
    assert after[0] == before[0] + 1  # year cover sees the new bit
    assert after[2] == before[2] + 1  # Feb-Jul cover too
    assert after[1] == before[1]      # row 2 unchanged
    h.close()


def test_fused_range_batch_distributed(tmp_path):
    """Fused Count(Range) batches forward ONE Query per remote node and
    sum per-call counts across the slice split, with replica failover."""
    from pilosa_tpu.cluster import Cluster, Node

    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions(time_quantum="YMD"))
    e0 = Executor(h, engine="numpy")
    for s in range(4):
        for c in range(8):
            e0.execute(
                "i",
                f'SetBit(rowID=1, frame="f", columnID={s * SLICE_WIDTH + c}, '
                'timestamp="2017-03-02T00:00")',
            )

    hosts = ["h0:1", "h1:1"]
    cluster = Cluster([Node(host) for host in hosts], replica_n=2)
    remote_batches = []

    class SpyClient:
        def __init__(self, host):
            self.host = host

        def execute_remote(self, index, query, slices=None, deadline=None):
            remote_batches.append((self.host, len(query.calls), list(slices)))
            peer = Executor(h, engine="numpy")
            return peer.execute(index, query, slices=slices, opt=ExecOptions(remote=True))

    e = Executor(h, engine="numpy", cluster=cluster, client_factory=SpyClient, host="h0:1")
    q = " ".join(
        ['Count(Range(rowID=1, frame="f", start="2017-03-01T00:00", end="2017-04-01T00:00"))'] * 3
    )
    got = e.execute("i", q)
    single = e0.execute(
        "i", 'Count(Range(rowID=1, frame="f", start="2017-03-01T00:00", end="2017-04-01T00:00"))'
    )
    assert got == single * 3 == [32, 32, 32]
    assert len(remote_batches) == 1 and remote_batches[0][1] == 3

    class DyingClient(SpyClient):
        def execute_remote(self, index, query, slices=None, deadline=None):
            raise ConnectionError("node down")

    e2 = Executor(h, engine="numpy", cluster=cluster, client_factory=DyingClient, host="h0:1")
    assert e2.execute("i", q) == got
    h.close()


@pytest.mark.parametrize("engine", ["numpy", "jax"])
def test_fused_range_matrix_grow_alignment(tmp_path, engine):
    """Growing the cached multi-view matrix past its capacity must keep
    id_pos aligned with physical rows (regression: append after spare
    zero rows shifted every new cover onto the wrong plane and poisoned
    the memo)."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions(time_quantum="YMD"))
    e = Executor(h, engine=engine)
    # One Y-covering span per row: each (row, span) is exactly one
    # (view, row) combo, so combo counts are easy to control.
    span = ('start="2017-01-01T00:00", end="2018-01-01T00:00"')
    for r in range(8):
        e.execute(
            "i",
            f'SetBit(rowID={r}, frame="f", columnID={100 + r}, '
            'timestamp="2017-06-15T00:00")',
        )
        e.execute(
            "i",
            f'SetBit(rowID={r}, frame="f", columnID={200 + r}, '
            'timestamp="2017-06-16T00:00")',
        )

    def counts(rows_):
        q = " ".join(
            f'Count(Range(rowID={r}, frame="f", {span}))' for r in rows_
        )
        return e.execute("i", q)

    # 3 combos -> capacity pow2(3)=4; then +2 new combos forces a grow
    # (one into spare capacity, one appended).
    assert counts([0, 1, 2]) == [2, 2, 2]
    assert counts([0, 1, 2, 3, 4]) == [2, 2, 2, 2, 2]
    # Re-query only the grown rows: the memo must hold correct values.
    assert counts([3, 4, 5, 6, 7]) == [2] * 5
    h.close()


def test_topn_src_scoring_engine_parity(tmp_path):
    """TopN(src) candidate scoring through the engine-backed device
    scorer must match the numpy host path exactly (threshold pruning,
    tanimoto band, two-phase refetch included)."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("r", FrameOptions(cache_type="ranked"))
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("r")
    rng = np.random.default_rng(21)
    rows, cols = [], []
    for r in range(40):
        n_bits = int(rng.integers(5, 200))
        rows.extend([r] * n_bits)
        cols.extend(rng.choice(2 * SLICE_WIDTH, size=n_bits, replace=False).tolist())
    fr.import_bits(rows, cols)
    e_np = Executor(h, engine="numpy")
    for c in range(0, 600, 3):
        e_np.execute("i", f'SetBit(rowID=9, frame="f", columnID={c})')
    e_jx = Executor(h, engine="jax")
    for q in (
        'TopN(Bitmap(rowID=9, frame="f"), frame="r", n=5)',
        'TopN(Bitmap(rowID=9, frame="f"), frame="r", n=25)',
        'TopN(Bitmap(rowID=9, frame="f"), frame="r")',
        'TopN(Bitmap(rowID=9, frame="f"), frame="r", n=3, tanimotoThreshold=10)',
        'TopN(Bitmap(rowID=9, frame="f"), frame="r", ids=[1,5,11,33])',
    ):
        got_np = [(p.id, p.count) for p in e_np.execute("i", q)[0]]
        got_jx = [(p.id, p.count) for p in e_jx.execute("i", q)[0]]
        assert got_np == got_jx, q
    h.close()


def test_topn_scorer_budget_crossover_parity(tmp_path):
    """When the candidate set crosses the matrix row budget mid-query,
    the scorer hands remaining chunks back to the fragment's host path;
    results must still match the numpy engine exactly."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("r", FrameOptions(cache_type="ranked"))
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("r")
    rng = np.random.default_rng(33)
    rows, cols = [], []
    # >256 candidates so chunk 1 (256 ids) scores on-device under a 280
    # budget and chunk 2 crosses it, handing back to the host path.
    for r in range(300):
        n_bits = int(rng.integers(5, 40))
        rows.extend([r] * n_bits)
        cols.extend(rng.choice(SLICE_WIDTH, size=n_bits, replace=False).tolist())
    fr.import_bits(rows, cols)
    e_np = Executor(h, engine="numpy")
    for c in range(0, 800, 2):
        e_np.execute("i", f'SetBit(rowID=7, frame="f", columnID={c})')
    e_jx = Executor(h, engine="jax")
    e_jx._matrix_rows_max = 280  # crossover between chunk 1 and chunk 2
    q = 'TopN(Bitmap(rowID=7, frame="f"), frame="r", n=8)'
    got_np = [(p.id, p.count) for p in e_np.execute("i", q)[0]]
    got_jx = [(p.id, p.count) for p in e_jx.execute("i", q)[0]]
    assert got_np == got_jx
    # Also cover the decline-from-the-first-chunk shape.
    e_jx2 = Executor(h, engine="jax")
    e_jx2._matrix_rows_max = 16
    got_jx2 = [(p.id, p.count) for p in e_jx2.execute("i", q)[0]]
    assert got_np == got_jx2
    h.close()


def test_topn_does_not_evict_count_lane_pool(tmp_path):
    """TopN candidate streaming pages through its OWN pool lane, leaving
    the Count lane's pool residency and Gram untouched (regression:
    alternating TopN/Count traffic must not ping-pong either lane)."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("r", FrameOptions(cache_type="ranked"))
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("r")
    rng = np.random.default_rng(44)
    rows, cols = [], []
    for r in range(30):
        n_bits = int(rng.integers(10, 60))
        rows.extend([r] * n_bits)
        cols.extend(rng.choice(SLICE_WIDTH, size=n_bits, replace=False).tolist())
    fr.import_bits(rows, cols)
    e = Executor(h, engine="jax")
    for c in range(0, 500, 2):
        e.execute("i", f'SetBit(rowID=5, frame="f", columnID={c})')
    # Count lane populates its pool with 20 rows (and a Gram on repeat).
    pair_q = " ".join(
        f'Count(Intersect(Bitmap(rowID={i}, frame="r"), Bitmap(rowID={i+1}, frame="r")))'
        for i in range(0, 20, 2)
    )
    want_counts = e.execute("i", pair_q)
    assert e.execute("i", pair_q) == want_counts  # builds the Gram
    count_pool = e._pool_for("i", "r", "standard", [0])
    box0 = count_pool.box
    n0 = len(count_pool.slot_of)
    assert n0 >= 10
    # TopN over 30 candidates pages through the "topn" lane only.
    topn_q = 'TopN(Bitmap(rowID=5, frame="f"), frame="r", n=5)'
    got_np = [(p.id, p.count) for p in Executor(h, engine="numpy").execute("i", topn_q)[0]]
    got = [(p.id, p.count) for p in e.execute("i", topn_q)[0]]
    assert got == got_np
    assert e._pool_for("i", "r", "standard", [0], lane="topn") is not count_pool
    assert count_pool.box is box0  # count lane box (and Gram) untouched
    assert len(count_pool.slot_of) == n0  # residency preserved
    assert e.execute("i", pair_q) == want_counts  # still served correctly
    h.close()


@pytest.mark.parametrize("engine", ["numpy", "jax"])
def test_count_multi_operand_batch_fusion(tmp_path, engine):
    """Requests of Count over 3+-operand Intersect/Union/Difference trees
    fuse into multi-fold kernel dispatches and match per-call results,
    including mixed-arity batches (pairs share the same matrix/Gram)."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("f")
    rng = np.random.default_rng(6)
    for r in range(8):
        for c in rng.choice(2 * SLICE_WIDTH, size=60, replace=False):
            fr.set_bit("standard", r, int(c))
    e = Executor(h, engine=engine)

    trees = [
        "Intersect(Bitmap(rowID=0), Bitmap(rowID=1), Bitmap(rowID=2))",
        "Union(Bitmap(rowID=1), Bitmap(rowID=2), Bitmap(rowID=3), Bitmap(rowID=4))",
        "Difference(Bitmap(rowID=0), Bitmap(rowID=5), Bitmap(rowID=6))",
        "Intersect(Bitmap(rowID=3), Bitmap(rowID=4))",  # pair lane
        "Difference(Bitmap(rowID=7), Bitmap(rowID=0), Bitmap(rowID=1), Bitmap(rowID=2), Bitmap(rowID=3))",
    ]
    calls = [f"Count({t})".replace("Bitmap(", 'Bitmap(frame="f", ') for t in trees]
    fused = e.execute("i", " ".join(calls))
    singles = [e.execute("i", q)[0] for q in calls]
    assert fused == singles
    assert any(v > 0 for v in fused)

    # Mutation invalidates the shared matrix; counts update.
    before = e.execute("i", " ".join(calls))
    fr.set_bit("standard", 0, 999_999)
    fr.set_bit("standard", 1, 999_999)
    fr.set_bit("standard", 2, 999_999)
    after = e.execute("i", " ".join(calls))
    assert after[0] == before[0] + 1  # 3-way intersect gained the bit
    h.close()


@pytest.mark.parametrize("engine", ["numpy", "jax"])
def test_fused_batch_slice_streaming(tmp_path, monkeypatch, engine):
    """When the working set exceeds the HBM pool budget, fused count
    batches stream the SLICE axis: transient per-chunk matrices,
    accumulated counts — identical results to sequential execution.
    Tiny budgets force the regime on a small index."""
    monkeypatch.setenv("PILOSA_TPU_POOL_BYTES", str(1 << 20))
    monkeypatch.setenv("PILOSA_TPU_STREAM_BYTES", str(1 << 20))
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("f")
    rng = np.random.default_rng(9)
    n_slices, n_rows = 4, 8
    rows = rng.integers(0, n_rows, size=3000).astype(np.uint64)
    cols = rng.integers(0, n_slices * SLICE_WIDTH, size=3000).astype(np.uint64)
    fr.import_bits(rows, cols)
    e = Executor(h, engine=engine)
    pool = e._pool_for("i", "f", "standard", list(range(n_slices)))
    assert pool.cap_max < n_rows  # proves the streaming regime is forced
    pairs = rng.integers(0, n_rows, size=(24, 2))
    q = " ".join(
        f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
        for a, b in pairs
    ) + (
        # Mixed arity in the same batch: a 3-operand union streams too.
        ' Count(Union(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f"),'
        ' Bitmap(rowID=2, frame="f")))'
    )
    got = e.execute("i", q)
    # Ground truth: one call at a time (no fusion possible).
    e_seq = Executor(h, engine="numpy")
    want = [
        e_seq.execute(
            "i",
            f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))',
        )[0]
        for a, b in pairs
    ] + [
        e_seq.execute(
            "i",
            'Count(Union(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f"),'
            ' Bitmap(rowID=2, frame="f")))',
        )[0]
    ]
    assert got == want
    h.close()


def test_map_reduce_slice_chunking(tmp_path, monkeypatch):
    """Non-fused calls fold local slice chunks through reduce_fn — a
    Count/Bitmap/TopN over many slices never materializes them all at
    once, and results match the unchunked evaluation."""
    monkeypatch.setenv("PILOSA_TPU_SLICE_CHUNK", "3")
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions(cache_type="ranked"))
    fr = idx.frame("f")
    rng = np.random.default_rng(10)
    n_slices = 10
    rows = rng.integers(0, 5, size=2000).astype(np.uint64)
    cols = rng.integers(0, n_slices * SLICE_WIDTH, size=2000).astype(np.uint64)
    fr.import_bits(rows, cols)
    e = Executor(h, engine="numpy")
    got_count = e.execute(
        "i", 'Count(Union(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f")))'
    )
    got_bits = e.execute("i", 'Bitmap(rowID=2, frame="f")')[0].bits()
    got_top = [(p.id, p.count) for p in e.execute("i", 'TopN(frame="f", n=3)')[0]]
    monkeypatch.setenv("PILOSA_TPU_SLICE_CHUNK", "2048")
    e2 = Executor(h, engine="numpy")
    assert got_count == e2.execute(
        "i", 'Count(Union(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f")))'
    )
    assert got_bits == e2.execute("i", 'Bitmap(rowID=2, frame="f")')[0].bits()
    assert got_top == [(p.id, p.count) for p in e2.execute("i", 'TopN(frame="f", n=3)')[0]]
    h.close()


@pytest.mark.parametrize("engine", ["numpy", "jax"])
def test_single_wide_count_streams_instead_of_raising(tmp_path, monkeypatch, engine):
    """One Count(Union(...)) whose operand rows exceed the pool row cap
    must stream the slice axis, not fail the request."""
    monkeypatch.setenv("PILOSA_TPU_POOL_BYTES", str(1 << 20))
    monkeypatch.setenv("PILOSA_TPU_STREAM_BYTES", str(1 << 21))
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("f")
    n_rows = 10
    for r in range(n_rows):
        fr.set_bit("standard", r, r)
        fr.set_bit("standard", r, SLICE_WIDTH + 2 * r)
    e = Executor(h, engine=engine)
    pool = e._pool_for("i", "f", "standard", [0, 1])
    assert pool.cap_max < n_rows
    operands = ", ".join(f'Bitmap(rowID={r}, frame="f")' for r in range(n_rows))
    # Two fusable calls so the fused lane (not the sequential path) runs.
    q = f"Count(Union({operands})) Count(Union({operands}))"
    assert e.execute("i", q) == [2 * n_rows, 2 * n_rows]
    h.close()


def test_write_queue_group_commit(tmp_path):
    """Concurrent singleton SetBit requests group-commit through the
    ingest queue: results match the sequential path, acks are durable
    (bits persisted), and batching actually happened under contention."""
    from concurrent.futures import ThreadPoolExecutor

    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    e = Executor(h, engine="numpy", write_queue=True)
    n = 600
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 20, size=n).tolist()
    cols = rng.integers(0, 3 * SLICE_WIDTH, size=n).tolist()
    queries = [
        f'SetBit(rowID={r}, frame="f", columnID={c})' for r, c in zip(rows, cols)
    ]
    with ThreadPoolExecutor(8) as pool:
        results = list(pool.map(lambda q: e.execute("i", q), queries))
    # Every submission acked with a bool; uniqueness: exactly the distinct
    # (row, col) pairs were "changed" True.
    changed = sum(1 for r in results if r[0])
    assert changed == len({(r, c) for r, c in zip(rows, cols)})
    # Duplicate write now reports unchanged (read-your-writes).
    assert e.execute("i", queries[0]) == [False]
    # Count agrees with an independent sequential executor.
    got = e.execute("i", 'Count(Union(%s))' % ", ".join(
        f'Bitmap(rowID={r}, frame="f")' for r in range(20)))
    want = Executor(h, engine="numpy").execute("i", 'Count(Union(%s))' % ", ".join(
        f'Bitmap(rowID={r}, frame="f")' for r in range(20)))
    assert got == want
    h.close()


def test_write_queue_invalid_call_does_not_poison_batch(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    e = Executor(h, engine="numpy", write_queue=True)
    with pytest.raises(PilosaError):
        e.execute("i", 'SetBit(rowID=1, frame="nope", columnID=1)')
    assert e.execute("i", 'SetBit(rowID=1, frame="f", columnID=1)') == [True]
    h.close()


def test_read_coalescing_queue_matches_sequential(tmp_path):
    """Concurrent flat-lane count requests coalesce through the serve
    queue into one vectorized evaluation; results match per-request
    sequential execution exactly."""
    from concurrent.futures import ThreadPoolExecutor

    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("f")
    rng = np.random.default_rng(6)
    for r in range(12):
        for c in rng.integers(0, 2 * SLICE_WIDTH, size=40).tolist():
            fr.set_bit("standard", r, c)
    e = Executor(h, engine="numpy", write_queue=True)
    e_seq = Executor(h, engine="numpy")
    queries = []
    for _ in range(40):
        pairs = rng.integers(0, 12, size=(8, 2))
        queries.append(" ".join(
            f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
            for a, b in pairs))
    with ThreadPoolExecutor(8) as pool:
        got = list(pool.map(lambda q: e.execute("i", q), queries))
    want = [e_seq.execute("i", q) for q in queries]
    assert got == want
    assert e._serve_queue.stat_items == 40
    # Reads after writes stay correct through the queue (gens refresh).
    fr.set_bit("standard", 0, 5)
    fr.set_bit("standard", 1, 5)
    q = ('Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f"))) '
         'Count(Intersect(Bitmap(rowID=2, frame="f"), Bitmap(rowID=3, frame="f")))')
    assert e.execute("i", q) == e_seq.execute("i", q)
    h.close()


def test_rowmajor_pool_lane(tmp_path, monkeypatch):
    """Tall working sets page through the ROW-MAJOR pool lane (one
    contiguous DMA descriptor per operand row on TPU); forced on here so
    the CPU suite exercises the row-major fetch/scatter/paging plumbing
    and its parity with the numpy engine.  Covers miss paging, the
    write-invalidation (stale plane) refresh, and mixed pair/3-operand
    groups."""
    import pilosa_tpu.engine as engine_mod

    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("f")
    rng = np.random.default_rng(9)
    n_rows = 160
    rows = np.repeat(np.arange(n_rows, dtype=np.uint64), 12)
    for s in range(2):
        cols = rng.integers(0, SLICE_WIDTH, size=len(rows)).astype(
            np.uint64
        ) + np.uint64(s * SLICE_WIDTH)
        fr.import_bits(rows, cols)

    monkeypatch.setattr(
        engine_mod.JaxEngine, "supports_row_major_gather", property(lambda self: True)
    )
    # The Gram outranks the rm lane when eligible (it would serve this
    # 160-row set); disable it so the test drives the rm plumbing.
    monkeypatch.setenv("PILOSA_TPU_NO_GRAM", "1")
    e = Executor(h, engine="jax")
    if e.engine.name == "numpy":
        pytest.skip("jax engine unavailable")
    e_np = Executor(h, engine="numpy")

    # All-distinct pair operands: want == 2 * n_pairs, exactly the
    # boundary where the resident-kernel predicate hands over to the
    # gather kernels (and so the row-major lane).
    perm = rng.permutation(n_rows)
    prs = [[int(perm[2 * i]), int(perm[2 * i + 1])] for i in range(64)]
    tris = rng.integers(0, n_rows, size=(8, 3)).tolist()
    q = " ".join(
        f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
        for a, b in prs
    ) + " " + " ".join(
        f'Count(Union(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f"), '
        f'Bitmap(rowID={c}, frame="f")))'
        for a, b, c in tris
    )
    assert e.execute("i", q) == e_np.execute("i", q)
    pool = e._pool_for("i", "f", "standard", [0, 1], lane="rmgather")
    assert pool.row_major and pool.matrix is not None
    assert pool.matrix.shape[0] >= len({x for p in prs for x in p})
    # Write invalidation: the stale-plane refresh path in row-major layout.
    fr.set_bit("standard", int(prs[0][0]), 5)
    assert e.execute("i", q) == e_np.execute("i", q)
    # Eviction paging in the row-major pool.  The batch chunker consults
    # the default lane's capacity, so shrink both pools together (in
    # production they share the same budget formula).
    pool.cap_max = 64
    e._pool_for("i", "f", "standard", [0, 1]).cap_max = 64
    pool._reset()
    assert e.execute("i", q) == e_np.execute("i", q)
    assert pool.stat_evictions > 0 or pool.stat_resets > 0
    h.close()


# The resident layout rule (Executor._resident_row_major): two slices of
# 128 KiB planes, so the row-major kernels buffer a widest group of 16
# (rowmajor_ok: 2 * k * slices * 128 KiB <= 8 MiB) and the resident
# kernel takes a pair batch whose rows are fewer than twice its pairs.
_TALL = dict(n_rows=160, n_parts=2, n_pairs=64, max_k=2, has_tree=False, pool_cap=160)
_LADDER = {
    # An engine without row-major support never picks it.
    "numpy_engine_never": ("numpy", {}, None, _TALL, False),
    "mesh_engine_never": ("mesh", {}, None, _TALL, False),
    # Gram-eligible (bucket 256 <= 4096 rows) and unpaged: the Gram keeps it.
    "gram_eligible_single_part": ("jax", {}, None, {**_TALL, "n_parts": 1}, False),
    # The same working set paged: the Gram can never warm, so no veto.
    "paging_regime_two_parts": ("jax", {}, None, _TALL, True),
    "above_gram_rows_max": ("jax", {"gram_rows_max": 128}, None, {**_TALL, "n_parts": 1}, True),
    "no_gram_set": ("jax", {"no_gram": True}, None, {**_TALL, "n_parts": 1}, True),
    # 8 rows under 64 pairs: dispatch keeps the resident kernel.
    "prefer_rowmajor_false": ("jax", {}, None, {**_TALL, "n_rows": 8, "pool_cap": 8}, False),
    # ... unless the slice-major pool has grown: dispatch sees its full cap.
    "grown_pool_forces_gather": ("jax", {}, None, {**_TALL, "n_rows": 8}, True),
    "tree_group": ("jax", {}, None, {**_TALL, "has_tree": True}, False),
    "rows_past_the_rowmajor_pools_cap": ("jax", {}, 64, _TALL, False),
    "widest_group_fits_the_row_buffers": ("jax", {}, None, {**_TALL, "n_pairs": 0, "max_k": 16}, True),
    "widest_group_past_rowmajor_ok": ("jax", {}, None, {**_TALL, "n_pairs": 0, "max_k": 32}, False),
}


@pytest.mark.parametrize("case", sorted(_LADDER))
def test_resident_layout_rule(tmp_path, monkeypatch, case):
    """One function picks the layout of a resident working set from what
    the code can observe: the engine's support, the Gram's gates, the
    part count, dispatch's own kernel predicate and the pools' caps."""
    import jax

    import pilosa_tpu.engine as engine_mod

    kind, ctor, rm_cap, args, want = _LADDER[case]
    monkeypatch.delenv("PILOSA_TPU_NO_GRAM", raising=False)
    monkeypatch.delenv("PILOSA_TPU_GRAM_ROWS_MAX", raising=False)
    monkeypatch.delenv("PILOSA_TPU_POOL_BYTES", raising=False)
    monkeypatch.setattr(
        engine_mod.JaxEngine, "supports_row_major_gather", property(lambda self: True)
    )
    engine = engine_mod.MeshEngine(devices=jax.devices()[:4]) if kind == "mesh" else kind
    h = Holder(str(tmp_path / "data"))
    h.open()
    e = Executor(h, engine=engine, **ctor)
    assert e.engine.name == kind
    if rm_cap is not None:
        e._pool_for("i", "f", "standard", [0, 1], lane="rmgather").cap_max = rm_cap
    assert e._resident_row_major("i", "f", "standard", [0, 1], **args) is want
    # The probe instantiates no pool for a lane it may not take.
    assert len(e._matrix_cache) == (0 if rm_cap is None else 1)
    h.close()


@pytest.mark.parametrize(
    "n_pairs,hot_rows,no_gram,cap_max,want_rm",
    [
        (64, None, True, None, True),    # tall, distinct operands, no Gram: row-major
        (64, 8, True, None, False),      # 8 hot rows under 64 pairs: resident kernel
        (64, None, False, None, False),  # Gram-eligible and unpaged: the Gram keeps it
        (64, None, False, 64, True),     # the same, paged in two parts: no veto
    ],
    ids=["tall", "hot_rows", "gram_eligible", "gram_eligible_paged"],
)
def test_arrays_and_ast_paths_pick_one_layout(
    tmp_path, monkeypatch, n_pairs, hot_rows, no_gram, cap_max, want_rm
):
    """The compiled-query (arrays) path and the AST path ask the same rule:
    for one batch they page through the same pool layout and count what
    the numpy engine counts (row-major support forced on, as in
    test_rowmajor_pool_lane)."""
    import pilosa_tpu.engine as engine_mod
    from pilosa_tpu.native import PQL_PAIR_OPS

    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("f")
    rng = np.random.default_rng(30)
    n_rows, slices = 160, [0, 1]
    rows = np.repeat(np.arange(n_rows, dtype=np.uint64), 12)
    for s in slices:
        cols = rng.integers(0, SLICE_WIDTH, size=len(rows)).astype(np.uint64)
        fr.import_bits(rows, cols + np.uint64(s * SLICE_WIDTH))
    monkeypatch.setattr(
        engine_mod.JaxEngine, "supports_row_major_gather", property(lambda self: True)
    )
    monkeypatch.delenv("PILOSA_TPU_NO_GRAM", raising=False)

    perm = rng.permutation(hot_rows or n_rows)
    r1 = np.array([perm[(2 * i) % len(perm)] for i in range(n_pairs)], dtype=np.int64)
    r2 = np.array([perm[(2 * i + 1) % len(perm)] for i in range(n_pairs)], dtype=np.int64)
    op_ids = (np.arange(n_pairs) % len(PQL_PAIR_OPS)).astype(np.uint8)
    names = {"and": "Intersect", "or": "Union", "xor": "Xor", "andnot": "Difference"}
    want = Executor(h, engine="numpy").execute("i", " ".join(
        f'Count({names[PQL_PAIR_OPS[o]]}(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
        for o, a, b in zip(op_ids, r1, r2)
    ))

    def fresh():
        e = Executor(h, engine="jax", no_gram=no_gram)
        if cap_max is not None:
            # Both lanes' pools share one budget formula in production.
            for lane in ("", "rmgather"):
                e._pool_for("i", "f", "standard", slices, lane=lane).cap_max = cap_max
        return e

    def layouts(e):
        return {k[4] for k, pool in e._matrix_cache.items() if pool.matrix is not None}

    arrays, ast = fresh(), fresh()
    frame_ids = np.zeros(n_pairs, dtype=np.int64)
    assert arrays._fused_local_counts_arrays(
        "i", ["f"], op_ids, frame_ids, r1, r2, slices) == want
    matched = {
        i: ("f", "standard", PQL_PAIR_OPS[op_ids[i]], (int(r1[i]), int(r2[i])))
        for i in range(n_pairs)
    }
    assert ast._fused_local_counts("i", matched, list(range(n_pairs)), slices) == want
    assert layouts(arrays) == layouts(ast) == {"rmgather" if want_rm else ""}
    h.close()


def test_gram_eligibility_covers_tall_row_sets(env, monkeypatch):
    """The chunked Gram builder (bitwise.pair_gram word-axis subdivision)
    removed the per-slice unpack ceiling: eligibility is now a rows gate
    (PILOSA_TPU_GRAM_ROWS_MAX, default 4096 = a 64 MiB Gram) plus the
    int32 slice bound — the round-3 gather-regime shapes (1024/4096
    distinct rows) are Gram-served product paths."""
    _, e = env
    monkeypatch.delenv("PILOSA_TPU_NO_GRAM", raising=False)
    e._gram_env_cache = None  # env settings are cached once per Executor
    assert e._gram_could_serve(1024, 4)
    assert e._gram_could_serve(4096, 4)       # round-3 regression shape
    assert not e._gram_could_serve(4097, 4)   # bucket 8192 > rows max
    assert e._gram_could_serve(64, 2047)
    assert not e._gram_could_serve(64, 2048)  # int32 count bound
    monkeypatch.setenv("PILOSA_TPU_GRAM_ROWS_MAX", "8192")
    e._gram_env_cache = None
    assert e._gram_could_serve(8192, 4)
    monkeypatch.setenv("PILOSA_TPU_NO_GRAM", "1")
    e._gram_env_cache = None
    assert not e._gram_could_serve(64, 4)


def test_count_exact_past_int32_full_density(tmp_path, monkeypatch):
    """A >=2.2B-column full-density Count must return the EXACT value:
    device kernels accumulate in int32, so the executor must never span
    more than _INT32_SAFE_SLICES in one dispatch (the pooled branch
    falls back to slice streaming, chunks clamp to the bound, and the
    partials sum in int64 host-side).  BASELINE.md round-3 addendum 3
    measured the raw overflow; this pins the engine-level guard."""
    from pilosa_tpu.executor import _INT32_SAFE_SLICES, _WORDS

    n_slices = 2112  # > _INT32_SAFE_SLICES; full density = 2.2e9 > int32
    monkeypatch.setenv("PILOSA_TPU_STREAM_BYTES", str(32 * 1024 * 1024))
    h = Holder(str(tmp_path / "data"))
    h.open()
    h.create_index("i").create_frame("f", FrameOptions())
    fr = h.index("i").frame("f")
    # One real bit per slice per row establishes max_slice and fragments;
    # density is injected below (4.3B real bit writes would dwarf CI).
    for row in (0, 1):
        fr.import_bits(
            np.full(n_slices, row, dtype=np.uint64),
            (np.arange(n_slices, dtype=np.uint64) * np.uint64(SLICE_WIDTH)),
        )
    e = Executor(h, engine="jax")
    if not getattr(e.engine, "wants_static_shapes", False):
        pytest.skip("jax engine unavailable")

    def dense_block(index, frame, view, chunk_slices, rows, row_major=False):
        shape = (
            (len(rows), len(chunk_slices), _WORDS)
            if row_major
            else (len(chunk_slices), len(rows), _WORDS)
        )
        return np.full(shape, 0xFFFFFFFF, dtype=np.uint32)

    monkeypatch.setattr(
        Executor,
        "_densify_block",
        lambda self, index, frame, view, chunk_slices, rows, row_major=False:
            dense_block(index, frame, view, chunk_slices, rows, row_major),
    )
    want = n_slices * SLICE_WIDTH  # 2,214,592,512 > 2^31-1
    q = (
        'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f"))) '
        'Count(Union(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f")))'
    )
    got = e.execute("i", q)
    assert got == [want, want]
    # The chunk clamp itself: a huge byte budget must still cap at the
    # int32-safe slice span.
    monkeypatch.setenv("PILOSA_TPU_STREAM_BYTES", str(1 << 62))
    assert e._slice_chunk(2) == _INT32_SAFE_SLICES
    h.close()


def test_singleton_write_fast_lane_parity(tmp_path, monkeypatch):
    """The singleton SetBit/ClearBit fast lane must be observably
    identical to the general path: changed semantics, label validation
    (declining non-matching arg names), inverse-frame decline, and
    interleaving with reads."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    idx.create_frame("inv", FrameOptions(inverse_enabled=True))
    e = Executor(h, engine="numpy")

    # fast lane serves the canonical shape
    assert e.execute("i", 'SetBit(rowID=3, frame="f", columnID=9)') == [True]
    assert e.execute("i", 'SetBit(rowID=3, frame="f", columnID=9)') == [False]
    assert e.execute("i", 'Count(Bitmap(rowID=3, frame="f"))') == [1]
    assert e.execute("i", 'ClearBit(rowID=3, frame="f", columnID=9)') == [True]
    assert e.execute("i", 'ClearBit(rowID=3, frame="f", columnID=9)') == [False]
    # wrong arg label: declines to the general path, which raises the
    # same error as before the lane existed
    with pytest.raises(PilosaError):
        e.execute("i", 'SetBit(wrongID=3, frame="f", columnID=9)')
    # inverse frames decline (dual-view write handled by the general path)
    assert e.execute("i", 'SetBit(rowID=1, frame="inv", columnID=5)') == [True]
    assert e.execute("i", 'Count(Bitmap(rowID=5, frame="inv", inverse=true))')[0] >= 0
    inv_fr = h.frame("i", "inv")
    assert inv_fr.views.get("inverse") is not None, "inverse view must be written"
    # frame recreation invalidates the identity cache
    idx.delete_frame("f")
    idx.create_frame("f", FrameOptions())
    assert e.execute("i", 'SetBit(rowID=3, frame="f", columnID=9)') == [True]
    assert e.execute("i", 'Count(Bitmap(rowID=3, frame="f"))') == [1]
    h.close()


def test_effective_max_opn_scaling(tmp_path, monkeypatch):
    """Snapshot-trigger scaling: DEFAULT-tuned fragments scale the
    threshold with container count (bounded); explicit max_opn and the
    env kill switch keep exact reference behavior."""
    from pilosa_tpu.core.fragment import DEFAULT_MAX_OPN, Fragment

    f = Fragment(str(tmp_path / "0"), "i", "f", "standard", 0)
    f.open()
    assert f._effective_max_opn() >= DEFAULT_MAX_OPN
    # explicit max_opn: honored exactly
    g = Fragment(str(tmp_path / "1"), "i", "f", "standard", 1, max_opn=5)
    g.open()
    assert g._effective_max_opn() == 5
    for i in range(7):
        g.set_bit(0, i)
    assert g.storage.op_n < 5  # snapshot fired at the explicit threshold
    # env kill switch restores the fixed default
    monkeypatch.setenv("PILOSA_TPU_MAX_OPN_SCALE", "0")
    k = Fragment(str(tmp_path / "2"), "i", "f", "standard", 2)
    k.open()
    assert k._effective_max_opn() == DEFAULT_MAX_OPN
    f.close(); g.close(); k.close()


@pytest.mark.parametrize("engine", ["numpy", "jax"])
def test_fused_tree_lane_matches_sequential(tmp_path, engine):
    """Nested mixed trees and multi-operand Xor fuse into the tree lane
    and agree exactly with the sequential path (executor.go:261-276's
    uniform any-depth evaluation, fused)."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("f")
    rng = np.random.default_rng(5)
    fr.import_bits(rng.integers(0, 10, 600), rng.integers(0, 3 * SLICE_WIDTH, 600))
    e = Executor(h, engine=engine)
    qs = [
        'Count(Intersect(Union(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f")), Bitmap(rowID=2, frame="f")))',
        'Count(Xor(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f")))',
        'Count(Difference(Union(Bitmap(rowID=3, frame="f"), Bitmap(rowID=4, frame="f")), Bitmap(rowID=5, frame="f"), Bitmap(rowID=6, frame="f")))',
        'Count(Union(Intersect(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f")), Intersect(Bitmap(rowID=3, frame="f"), Bitmap(rowID=4, frame="f"))))',
        'Count(Xor(Union(Bitmap(rowID=0, frame="f"), Bitmap(rowID=7, frame="f")), Bitmap(rowID=8, frame="f"), Bitmap(rowID=9, frame="f"), Bitmap(rowID=1, frame="f")))',
        # flat shapes mixed in: pair + multi lanes coexist with tree groups
        'Count(Intersect(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f")))',
        'Count(Union(Bitmap(rowID=3, frame="f"), Bitmap(rowID=4, frame="f"), Bitmap(rowID=5, frame="f")))',
    ]
    seq = [e.execute("i", q)[0] for q in qs]
    # The batch must actually take the fused lane.
    from pilosa_tpu.pql.parser import parse

    fused = e._fuse_count_pair_batch(
        "i", parse(" ".join(qs)).calls, list(range(3)), None, ExecOptions()
    )
    assert fused is not None and len(fused) == len(qs)
    assert [fused[i] for i in range(len(qs))] == seq
    assert e.execute("i", " ".join(qs)) == seq
    h.close()


def test_fused_tree_lane_depth_cap_falls_back(tmp_path):
    """Trees past _TREE_DEPTH_MAX decline the fused lane but still
    answer correctly through the sequential path."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("f")
    fr.import_bits(np.arange(6) % 3, np.arange(6) * 1000)
    e = Executor(h, engine="numpy")
    deep = 'Bitmap(rowID=0, frame="f")'
    for _ in range(6):  # depth 6 > _TREE_DEPTH_MAX
        deep = f'Union({deep}, Bitmap(rowID=1, frame="f"))'
    q = f"Count({deep})"
    assert e._compile_count_tree("i", parse_query(q).calls[0].children[0]) is None
    assert e.execute("i", f"{q} {q}") == [e.execute("i", q)[0]] * 2
    h.close()


def parse_query(src):
    from pilosa_tpu.pql.parser import parse

    return parse(src)


class TestServeLane:
    """The single-call native serve lane (pn_serve_pairs + cached state):
    parity with the general path, and every invalidation edge."""

    def _setup(self, tmp_path, engine="jax"):
        h = Holder(str(tmp_path / "data"))
        h.open()
        h.create_index("p").create_frame("f", FrameOptions())
        fr = h.index("p").frame("f")
        rng = np.random.default_rng(7)
        fr.import_bits(
            rng.integers(0, 32, 3000), rng.integers(0, 3 * SLICE_WIDTH, 3000)
        )
        ex = Executor(h, engine=engine)
        rng2 = np.random.default_rng(1)
        batch = " ".join(
            f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
            for a, b in rng2.integers(0, 32, size=(64, 2))
        )
        return h, ex, batch

    def _arm(self, ex, batch):
        ex.execute("p", batch)
        ex.execute("p", batch)  # Gram arms on the second request
        assert ex._serve_states, "serve state did not arm"

    def test_parity_and_all_ops(self, tmp_path):
        h, ex, batch = self._setup(tmp_path)
        self._arm(ex, batch)
        e_np = Executor(h, engine="numpy")
        ops_batch = " ".join(
            f'Count({op}(Bitmap(rowID=3, frame="f"), Bitmap(rowID=9, frame="f")))'
            for op in ("Intersect", "Union", "Xor", "Difference")
        )
        got = ex.execute("p", ops_batch)  # through pn_serve_pairs
        assert got == e_np.execute("p", ops_batch)
        h.close()

    def test_write_invalidates(self, tmp_path):
        h, ex, batch = self._setup(tmp_path)
        self._arm(ex, batch)
        before = ex.execute("p", batch)
        ex.execute("p", 'SetBit(rowID=3, frame="f", columnID=12345678)')
        after = ex.execute("p", batch)
        want = Executor(h, engine="numpy").execute("p", batch)
        assert after == want
        # the state re-arms and still serves correct counts
        again = ex.execute("p", batch)
        assert again == want
        del before  # counts may or may not change; correctness is vs `want`
        h.close()

    def test_new_slice_invalidates(self, tmp_path):
        h, ex, batch = self._setup(tmp_path)
        self._arm(ex, batch)
        # a write in a NEW slice extends max_slice: state must not serve
        # stale slice ranges
        ex.execute("p", f'SetBit(rowID=3, frame="f", columnID={5 * SLICE_WIDTH + 1})')
        got = ex.execute("p", batch)
        assert got == Executor(h, engine="numpy").execute("p", batch)
        h.close()

    def test_unknown_rows_and_other_frames_fall_back(self, tmp_path):
        h, ex, batch = self._setup(tmp_path)
        h.index("p").create_frame("g", FrameOptions())
        h.index("p").frame("g").import_bits(
            np.arange(4, dtype=np.uint64), np.arange(4, dtype=np.uint64) * 100
        )
        self._arm(ex, batch)
        e_np = Executor(h, engine="numpy")
        # rows outside the captured table
        q1 = (
            'Count(Intersect(Bitmap(rowID=500, frame="f"), Bitmap(rowID=501, frame="f"))) '
            'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f")))'
        )
        assert ex.execute("p", q1) == e_np.execute("p", q1)
        # a different frame than the armed one
        q2 = (
            'Count(Intersect(Bitmap(rowID=0, frame="g"), Bitmap(rowID=1, frame="g"))) '
            'Count(Union(Bitmap(rowID=2, frame="g"), Bitmap(rowID=3, frame="g")))'
        )
        assert ex.execute("p", q2) == e_np.execute("p", q2)
        h.close()

    def test_threaded_parity(self, tmp_path):
        import threading

        h, ex, batch = self._setup(tmp_path)
        self._arm(ex, batch)
        want = ex.execute("p", batch)
        errs = []

        def client():
            try:
                for _ in range(20):
                    if ex.execute("p", batch) != want:
                        errs.append("mismatch")
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))

        ts = [threading.Thread(target=client) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs, errs[:3]
        h.close()

    def test_single_bit_write_repairs_warm_state(self, tmp_path):
        """Read-your-writes through the PATCH lane: a single-bit write
        below the repair budget must be served with updated counts by a
        REPAIRED warm state (matrix row rewrite + rank-k Gram update),
        not by dropping the state and rebuilding."""
        from pilosa_tpu.core.view import VIEW_STANDARD

        h, ex, batch = self._setup(tmp_path)
        self._arm(ex, batch)
        key = ("p", "f")
        st0 = ex._serve_states[key]
        pool = ex._matrix_cache[("p", "f", VIEW_STANDARD, (0, 1, 2), "")]
        # Deterministic delta: clear then set the same bit, counting the
        # row-3 diagonal through the warm lane around each write.
        q = 'Count(Intersect(Bitmap(rowID=3, frame="f"), Bitmap(rowID=3, frame="f")))'
        col = 2 * SLICE_WIDTH + 99
        ex.execute("p", f'ClearBit(rowID=3, frame="f", columnID={col})')
        before = ex.execute("p", q)[0]
        ex.execute("p", f'SetBit(rowID=3, frame="f", columnID={col})')
        after = ex.execute("p", q)[0]
        assert after == before + 1
        # The state was re-captured (patched), never dropped, and the
        # pool took the repair lane — no reset, no blind plane refresh.
        # (The ClearBit is usually a no-op on the random import — no
        # generation bump — so only the SetBit is guaranteed to repair.)
        st1 = ex._serve_states.get(key)
        assert st1 is not None and st1 is not st0
        assert pool.stat_repairs >= 1 and pool.stat_resets == 0
        # Full-batch parity with the sequential numpy path after repair.
        assert ex.execute("p", batch) == Executor(h, engine="numpy").execute("p", batch)
        h.close()

    def test_write_burst_over_budget_falls_back_to_rebuild(
        self, tmp_path, monkeypatch
    ):
        """A burst touching more rows than the repair budget must take
        the full invalidate-and-rebuild path — and still satisfy
        read-your-writes, then re-arm."""
        from pilosa_tpu.core.view import VIEW_STANDARD

        monkeypatch.setenv("PILOSA_TPU_REPAIR_ROWS_MAX", "4")
        h, ex, batch = self._setup(tmp_path)  # Executor reads the env at init
        self._arm(ex, batch)
        pool = ex._matrix_cache[("p", "f", VIEW_STANDARD, (0, 1, 2), "")]
        burst = " ".join(
            f'SetBit(rowID={r}, frame="f", columnID={SLICE_WIDTH + 777 + r})'
            for r in range(10)  # 10 distinct rows > budget 4
        )
        ex.execute("p", burst)
        want = Executor(h, engine="numpy").execute("p", batch)
        assert ex.execute("p", batch) == want
        assert pool.stat_repairs == 0  # over budget: no patch attempted
        # The lane re-arms and keeps serving correct counts.
        assert ex.execute("p", batch) == want
        assert ex._serve_states, "serve lane did not re-arm after rebuild"
        h.close()

    def test_repair_disabled_env_forces_rebuild(self, tmp_path, monkeypatch):
        """PILOSA_TPU_REPAIR_ROWS_MAX=0 is the A/B lever bench_mixed
        uses: every write must invalidate, none may patch."""
        from pilosa_tpu.core.view import VIEW_STANDARD

        monkeypatch.setenv("PILOSA_TPU_REPAIR_ROWS_MAX", "0")
        h, ex, batch = self._setup(tmp_path)
        self._arm(ex, batch)
        pool = ex._matrix_cache[("p", "f", VIEW_STANDARD, (0, 1, 2), "")]
        ex.execute("p", 'SetBit(rowID=3, frame="f", columnID=98765)')
        assert ex.execute("p", batch) == Executor(h, engine="numpy").execute("p", batch)
        assert pool.stat_repairs == 0
        h.close()

    def test_frame_recreate_never_serves_stale(self, tmp_path):
        """Deleting and recreating a frame of the same name must drop the
        old warm state (identity/generation tokens) — counts come from
        the NEW frame's bits."""
        h, ex, batch = self._setup(tmp_path)
        self._arm(ex, batch)
        h.index("p").delete_frame("f")
        h.index("p").create_frame("f", FrameOptions())
        fr = h.index("p").frame("f")
        fr.import_bits(np.array([3, 9], dtype=np.uint64), np.array([5, 5], dtype=np.uint64))
        got = ex.execute("p", batch)
        assert got == Executor(h, engine="numpy").execute("p", batch)
        h.close()

    def test_drop_frame_state_hook(self, tmp_path):
        """The deletion hook reclaims every cached artifact for the
        frame: serve states, row pools, fast-write pins, dirty ledger."""
        h, ex, batch = self._setup(tmp_path)
        self._arm(ex, batch)
        ex.execute("p", 'SetBit(rowID=1, frame="f", columnID=424242)')
        assert any(k[:2] == ("p", "f") for k in ex._matrix_cache)
        epoch_before = ex._lane_epoch
        ex.drop_frame_state("p", "f")
        assert ("p", "f") not in ex._serve_states
        assert not any(k[:2] == ("p", "f") for k in ex._matrix_cache)
        # The per-thread armed lane tables invalidate via the epoch: the
        # drop bumps it, and the calling thread's table resets empty at
        # next access.
        assert ex._lane_epoch == epoch_before + 1
        fastwrite, writelane = ex._lane_tables()
        assert ("p", "f") not in fastwrite and ("p", "f") not in writelane
        assert ("p", "f") not in ex._dirty_rows
        # Still serves correctly from scratch afterwards.
        assert ex.execute("p", batch) == Executor(h, engine="numpy").execute("p", batch)
        # Index-level drop clears every frame's artifacts too.
        ex.execute("p", batch)
        ex.drop_index_state("p")
        assert not ex._serve_states
        assert not any(k[0] == "p" for k in ex._matrix_cache)
        h.close()

    def test_serve_state_cache_size_configurable(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PILOSA_SERVE_STATE_CACHE", "2")
        h, ex, _ = self._setup(tmp_path)
        assert ex._serve_states_max == 2
        ex2 = Executor(h, serve_state_cache=7)  # explicit arg wins
        assert ex2._serve_states_max == 7
        h.close()

    def test_repair_and_gram_budgets_configurable(self, tmp_path, monkeypatch):
        """config.py plumbing for the repair/Gram budgets: constructor
        arg (server passes Config values) > PILOSA_TPU_* env > default,
        with 0 meaning 'disabled' for repair (so None is the
        not-configured sentinel)."""
        from pilosa_tpu.config import Config

        monkeypatch.setenv("PILOSA_TPU_REPAIR_ROWS_MAX", "9")
        monkeypatch.setenv("PILOSA_TPU_GRAM_ROWS_MAX", "512")
        h, ex, _ = self._setup(tmp_path)
        assert ex._repair_rows_max == 9
        assert ex._gram_rows_max() == 512
        ex2 = Executor(h, repair_rows_max=0, gram_rows_max=128)  # args win
        assert ex2._repair_rows_max == 0
        assert ex2._gram_rows_max() == 128
        # TOML -> Config -> env precedence mirrors serve-state-cache.
        cfg = Config.from_dict({"repair-rows-max": 5, "gram-rows-max": 2048})
        assert cfg.repair_rows_max == 5 and cfg.gram_rows_max == 2048
        cfg.apply_env({"PILOSA_TPU_REPAIR_ROWS_MAX": "0",
                       "PILOSA_TPU_GRAM_ROWS_MAX": "64"})
        assert cfg.repair_rows_max == 0 and cfg.gram_rows_max == 64
        h.close()

    def test_ledger_skipped_when_repair_disabled(self, tmp_path, monkeypatch):
        """With PILOSA_TPU_REPAIR_ROWS_MAX=0 the dirty-row ledger must
        stay empty even while serve state is warm — its only consumer
        (the repair precheck) can never use it."""
        monkeypatch.setenv("PILOSA_TPU_REPAIR_ROWS_MAX", "0")
        h, ex, batch = self._setup(tmp_path)
        self._arm(ex, batch)
        ex.execute("p", 'SetBit(rowID=3, frame="f", columnID=424242)')
        assert not ex._dirty_rows
        h.close()

    def test_ledger_saturation_forces_rebuild(self, tmp_path, monkeypatch):
        """A burst past 4x the budget saturates the ledger (value None);
        the repair lane must refuse without walking journals, the state
        rebuilds, and counts stay read-your-writes correct."""
        from pilosa_tpu.core.view import VIEW_STANDARD

        monkeypatch.setenv("PILOSA_TPU_REPAIR_ROWS_MAX", "2")  # cap = 24
        h, ex, batch = self._setup(tmp_path)
        self._arm(ex, batch)
        pool = ex._matrix_cache[("p", "f", VIEW_STANDARD, (0, 1, 2), "")]
        burst = " ".join(
            f'SetBit(rowID={r}, frame="f", columnID={2 * SLICE_WIDTH + 600 + r})'
            for r in range(30)  # 30 distinct rows > 4*2+16
        )
        ex.execute("p", burst)
        assert ex._dirty_rows[("p", "f")] is None  # saturated
        walks = {"n": 0}
        orig = ex._journal_dirty_rows

        def counting(*a, **kw):
            walks["n"] += 1
            return orig(*a, **kw)

        ex._journal_dirty_rows = counting
        want = Executor(h, engine="numpy").execute("p", batch)
        assert ex.execute("p", batch) == want
        assert pool.stat_repairs == 0
        # The serve-lane repair precheck declined BEFORE the journal
        # walk; the only walks come from the pool acquire path (which
        # rebuilds because the delta is over budget anyway).  The lane
        # re-arms on the second post-write read (Gram warms on hit 2).
        assert ex.execute("p", batch) == want
        assert ex._serve_states, "lane did not re-arm"
        h.close()

    def test_over_budget_precheck_declines_without_journal_walk(
        self, tmp_path, monkeypatch
    ):
        """A ledger clearly over budget (but not saturated) must make
        _serve_state_repair decline before touching the fragment
        journals."""
        monkeypatch.setenv("PILOSA_TPU_REPAIR_ROWS_MAX", "4")
        h, ex, batch = self._setup(tmp_path)
        self._arm(ex, batch)
        st = ex._serve_states[("p", "f")]
        with ex._dirty_mu:
            ex._dirty_rows[("p", "f")] = {1, 2, 3, 4, 5, 6}  # 6 > budget 4

        def boom(*a, **kw):
            raise AssertionError("journal walk after precheck decline")

        ex._journal_dirty_rows = boom
        assert ex._serve_state_repair(("p", "f"), st) is None
        h.close()

    def test_repair_bails_on_replaced_fragment(self, tmp_path):
        """A fragment deleted/recreated since capture fails the identity
        check: the repair lane returns None (rebuild path)."""
        h, ex, batch = self._setup(tmp_path)
        self._arm(ex, batch)
        st = ex._serve_states[("p", "f")]
        h.index("p").delete_frame("f")
        h.index("p").create_frame("f", FrameOptions())
        h.index("p").frame("f").import_bits(
            np.array([1], dtype=np.uint64),
            np.array([2 * SLICE_WIDTH + 5], dtype=np.uint64),
        )
        assert ex._serve_state_repair(("p", "f"), st) is None
        h.close()

    def test_repair_bails_on_slice_growth(self, tmp_path):
        """A write extending max_slice makes the state's span wrong: the
        repair lane must decline (the general lane rebuilds wider)."""
        h, ex, batch = self._setup(tmp_path)
        self._arm(ex, batch)
        st = ex._serve_states[("p", "f")]
        ex.execute("p", f'SetBit(rowID=3, frame="f", columnID={7 * SLICE_WIDTH + 1})')
        assert ex._serve_state_repair(("p", "f"), st) is None
        # And the general path still serves correct post-growth counts.
        assert ex.execute("p", batch) == Executor(h, engine="numpy").execute("p", batch)
        h.close()

    def test_write_burst_coalesces_into_one_repair(self, tmp_path):
        """Batched write->repair dispatch: a burst of N singleton writes
        with no interleaved reads must be repaired by ONE deferred patch
        dispatch on the next read (not one per write), touching only the
        written slice's planes."""
        from pilosa_tpu.core.view import VIEW_STANDARD

        h, ex, batch = self._setup(tmp_path)
        self._arm(ex, batch)
        pool = ex._matrix_cache[("p", "f", VIEW_STANDARD, (0, 1, 2), "")]
        repairs0 = pool.stat_repairs
        # 8 writes to distinct rows, all landing in slice 1.
        for r in range(8):
            ex.execute(
                "p", f'SetBit(rowID={r}, frame="f", columnID={SLICE_WIDTH + 4000 + r})'
            )
        want = Executor(h, engine="numpy").execute("p", batch)
        assert ex.execute("p", batch) == want
        assert pool.stat_repairs == repairs0 + 1  # one repair for the burst
        # Per-(row, slice) granularity: 8 rows x ONE slice, not x3.
        assert pool.stat_patch_planes == 8
        h.close()


def test_serve_lane_multi_frame_alternation(tmp_path):
    """Two frames' dashboards alternating must BOTH stay armed (the
    serve-state LRU holds one entry per (index, frame)) and keep serving
    natively without thrash."""
    from pilosa_tpu import native

    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("p")
    rng = np.random.default_rng(9)
    for fname in ("f", "g"):
        idx.create_frame(fname, FrameOptions())
        idx.frame(fname).import_bits(
            rng.integers(0, 16, 400), rng.integers(0, 2 * SLICE_WIDTH, 400)
        )
    ex = Executor(h, engine="jax")

    def batch(fname):
        return " ".join(
            f'Count(Intersect(Bitmap(rowID={a}, frame="{fname}"), Bitmap(rowID={b}, frame="{fname}")))'
            for a, b in np.random.default_rng(1).integers(0, 16, size=(16, 2))
        )

    qf, qg = batch("f"), batch("g")
    want_f, want_g = ex.execute("p", qf), ex.execute("p", qg)
    for q, w in ((qf, want_f), (qg, want_g)):  # arm both (Gram on 2nd hit)
        assert ex.execute("p", q) == w
    assert set(ex._serve_states) == {("p", "f"), ("p", "g")}
    calls = {"n": 0}
    orig = native.serve_pairs

    def counting(*a, **kw):
        r = orig(*a, **kw)
        if r is not None:
            calls["n"] += 1
        return r

    native.serve_pairs = counting
    try:
        for _ in range(5):  # alternate: both frames keep serving natively
            assert ex.execute("p", qf) == want_f
            assert ex.execute("p", qg) == want_g
    finally:
        native.serve_pairs = orig
    assert calls["n"] == 10, f"only {calls['n']}/10 alternating requests served natively"
    h.close()


@pytest.mark.parametrize("engine", ["numpy", "jax"])
def test_count_bitmap_singles_fuse_with_pairs(tmp_path, engine):
    """Plain Count(Bitmap(r)) calls ride the pair lane as (r, r): a
    dashboard mixing row counts, pair counts, and nested trees stays ONE
    fused batch instead of falling to sequential per-call evaluation."""
    from pilosa_tpu.pql.parser import parse

    h = Holder(str(tmp_path / "data"))
    h.open()
    h.create_index("i").create_frame("f", FrameOptions())
    fr = h.index("i").frame("f")
    rng = np.random.default_rng(6)
    fr.import_bits(rng.integers(0, 12, 500), rng.integers(0, 3 * SLICE_WIDTH, 500))
    e = Executor(h, engine=engine)
    qs = [
        'Count(Bitmap(rowID=3, frame="f"))',
        'Count(Intersect(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f")))',
        'Count(Bitmap(rowID=7, frame="f"))',
        'Count(Union(Intersect(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f")), Bitmap(rowID=3, frame="f")))',
    ]
    seq = [e.execute("i", q)[0] for q in qs]
    fused = e._fuse_count_pair_batch(
        "i", parse(" ".join(qs)).calls, list(range(3)), None, ExecOptions()
    )
    assert fused is not None and [fused[i] for i in range(4)] == seq
    assert e.execute("i", " ".join(qs)) == seq
    h.close()


class TestServeLaneBreadth:
    """The serve-lane breadth kernels (pn_serve_multi / pn_serve_tree /
    pn_pql_match_range): seeded differential parity with the Python
    lane, lane-selection proof (the native entry actually fires), the
    A/B env levers, and every decline edge falling back byte-identical."""

    def _pair_holder(self, tmp_path):
        h = Holder(str(tmp_path / "data"))
        h.open()
        idx = h.create_index("p")
        idx.create_frame("f", FrameOptions())
        idx.create_frame("g", FrameOptions())
        rng = np.random.default_rng(7)
        h.index("p").frame("f").import_bits(
            rng.integers(0, 32, 3000), rng.integers(0, 3 * SLICE_WIDTH, 3000))
        h.index("p").frame("g").import_bits(
            rng.integers(0, 16, 2000), rng.integers(0, 3 * SLICE_WIDTH, 2000))
        parts = []
        for a, b in np.random.default_rng(1).integers(0, 16, size=(32, 2)):
            parts.append(f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))')
            parts.append(f'Count(Union(Bitmap(rowID={a}, frame="g"), Bitmap(rowID={b}, frame="g")))')
        return h, " ".join(parts)

    def _count_native(self, monkeypatch, name):
        """Wrap a pilosa_tpu.native entry to count successful serves."""
        from pilosa_tpu import native

        hits = {"n": 0}
        orig = getattr(native, name)

        def counting(*a, **k):
            r = orig(*a, **k)
            if r is not None:
                hits["n"] += 1
            return r

        monkeypatch.setattr(native, name, counting)
        return hits

    def test_multiframe_parity_and_lever(self, tmp_path, monkeypatch):
        h, multi = self._pair_holder(tmp_path)
        ex = Executor(h, engine="jax")
        e_np = Executor(h, engine="numpy")
        want = e_np.execute("p", multi)
        r1 = ex.execute("p", multi)
        r2 = ex.execute("p", multi)  # Gram warms; per-frame states arm
        assert len(ex._serve_states) == 2, "both frames should arm"
        hits = self._count_native(monkeypatch, "serve_multi")
        r3 = ex.execute("p", multi)
        assert hits["n"] == 1, "pn_serve_multi did not serve the batch"
        assert r1 == r2 == r3 == want
        # the A/B lever routes the identical batch off the native lane
        monkeypatch.setenv("PILOSA_TPU_NO_SERVEMULTI", "1")
        assert ex.execute("p", multi) == want
        h.close()

    def test_multiframe_write_invalidates(self, tmp_path):
        h, multi = self._pair_holder(tmp_path)
        ex = Executor(h, engine="jax")
        ex.execute("p", multi)
        ex.execute("p", multi)
        ex.execute("p", 'SetBit(rowID=3, frame="g", columnID=12345678)')
        assert ex.execute("p", multi) == Executor(h, engine="numpy").execute("p", multi)
        h.close()

    def _tree_holder(self, tmp_path, slices=1):
        h = Holder(str(tmp_path / "data"))
        h.open()
        h.create_index("t").create_frame("f", FrameOptions())
        rng = np.random.default_rng(3)
        h.index("t").frame("f").import_bits(
            rng.integers(0, 12, 4000), rng.integers(0, slices * SLICE_WIDTH, 4000))
        body = (
            'Count(Intersect(Union(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f")), '
            'Difference(Bitmap(rowID=3, frame="f"), Bitmap(rowID=4, frame="f"), Bitmap(rowID=5, frame="f")))) '
            'Count(Xor(Bitmap(rowID=1, frame="f"), Bitmap(rowID=6, frame="f"), Bitmap(rowID=7, frame="f"))) '
            'Count(Bitmap(rowID=2, frame="f"))'
        )
        return h, body

    def test_tree_parity_and_lever(self, tmp_path, monkeypatch):
        h, body = self._tree_holder(tmp_path)
        ex = Executor(h, engine="numpy")
        hits = self._count_native(monkeypatch, "serve_tree")
        got = ex.execute("t", body)
        assert hits["n"] == 1, "pn_serve_tree did not serve the batch"
        monkeypatch.setenv("PILOSA_TPU_NO_SERVETREE", "1")
        assert got == ex.execute("t", body)
        h.close()

    def test_tree_direct_fragment_call(self, tmp_path):
        h, body = self._tree_holder(tmp_path)
        frag = h.fragment("t", "f", "standard", 0)
        counts = frag.serve_tree(body.encode(), b"f", False, b"rowID")
        assert counts is not None
        assert list(counts) == Executor(h, engine="numpy").execute("t", body)
        h.close()

    def test_tree_after_native_write_stays_correct(self, tmp_path):
        """Interleaved writes: the tree lane reads the same armed
        container table the native write lane mutates in place."""
        h, body = self._tree_holder(tmp_path)
        ex = Executor(h, engine="numpy")
        before = ex.execute("t", body)
        ex.execute("t", 'SetBit(rowID=2, frame="f", columnID=777777)')
        after = ex.execute("t", body)
        want = Executor(h, engine="numpy").execute("t", body)
        assert after == want and after[2] == before[2] + 1
        h.close()

    def test_tree_declines_multislice_index(self, tmp_path, monkeypatch):
        """The tree lane is single-slice only: a 2-slice index must fall
        back to the Python path with identical answers."""
        h, body = self._tree_holder(tmp_path, slices=2)
        ex = Executor(h, engine="numpy")
        hits = self._count_native(monkeypatch, "serve_tree")
        got = ex.execute("t", body)
        assert hits["n"] == 0, "tree lane must decline multi-slice indexes"
        monkeypatch.setenv("PILOSA_TPU_NO_SERVETREE", "1")
        assert got == ex.execute("t", body)
        h.close()

    def test_tree_depth_and_unknown_frame_fall_back(self, tmp_path, monkeypatch):
        h, _ = self._tree_holder(tmp_path)
        ex = Executor(h, engine="numpy")
        deep = 'Bitmap(rowID=1, frame="f")'
        for _ in range(8):  # depth past PN_TREE_MAX_DEPTH
            deep = f'Union({deep}, Bitmap(rowID=2, frame="f"))'
        q = f"Count({deep}) Count(Bitmap(rowID=1, frame=\"f\"))"
        got = ex.execute("t", q)
        monkeypatch.setenv("PILOSA_TPU_NO_SERVETREE", "1")
        assert got == ex.execute("t", q)
        monkeypatch.delenv("PILOSA_TPU_NO_SERVETREE")
        from pilosa_tpu.pilosa import ErrFrameNotFound

        bad = 'Count(Bitmap(rowID=1, frame="nope")) Count(Bitmap(rowID=1, frame="f"))'
        with pytest.raises(ErrFrameNotFound, match="nope"):
            ex.execute("t", bad)
        h.close()

    def _range_holder(self, tmp_path):
        h = Holder(str(tmp_path / "data"))
        h.open()
        idx = h.create_index("r")
        idx.create_frame("f", FrameOptions(time_quantum="YMDH"))
        idx.create_frame("g", FrameOptions(time_quantum="YM"))
        idx.create_frame("plain", FrameOptions())
        e = Executor(h, engine="jax")
        rng = np.random.default_rng(9)
        stamps = ["2017-01-05T10:00", "2017-02-14T00:00", "2017-03-02T15:00",
                  "2017-06-30T23:00", "2017-12-31T12:00"]
        for fr_name in ("f", "g"):
            for r in (1, 2):
                for t in stamps:
                    for c in rng.choice(2 * SLICE_WIDTH, size=5, replace=False):
                        e.execute("r", f'SetBit(rowID={r}, frame="{fr_name}", columnID={int(c)}, timestamp="{t}")')
        body = " ".join(
            f'Count(Range(rowID={r}, frame="{fr}", start="{s}", end="{en}"))'
            for fr, r, s, en in [
                ("f", 1, "2017-01-01T00:00", "2018-01-01T00:00"),
                ("f", 2, "2017-03-01T00:00", "2017-04-01T00:00"),
                ("f", 1, "2017-02-01T00:00", "2017-07-01T00:00"),
                ("g", 1, "2017-01-01T00:00", "2017-07-01T00:00"),
                ("g", 2, "2017-06-01T00:00", "2017-06-02T00:00"),
                ("plain", 1, "2017-01-01T00:00", "2018-01-01T00:00"),
                ("f", 1, "2017-05-01T00:00", "2017-05-01T00:00"),
            ])
        return h, e, body

    def test_range_parity_and_lever(self, tmp_path, monkeypatch):
        h, ex, body = self._range_holder(tmp_path)
        hits = self._count_native(monkeypatch, "pql_match_range")
        got = ex.execute("r", body)
        assert hits["n"] == 1, "native Range matcher did not fire"
        want = Executor(h, engine="numpy").execute("r", body)
        monkeypatch.setenv("PILOSA_TPU_NO_RANGELANE", "1")
        py = ex.execute("r", body)
        assert got == want == py
        assert got[0] > 0 and got[5] == 0 and got[6] == 0
        h.close()

    def test_range_write_invalidates(self, tmp_path):
        h, ex, body = self._range_holder(tmp_path)
        before = ex.execute("r", body)
        ex.execute("r", 'SetBit(rowID=1, frame="f", columnID=999999, timestamp="2017-03-15T00:00")')
        after = ex.execute("r", body)
        assert after[0] == before[0] + 1 and after[2] == before[2] + 1
        assert after[1] == before[1]
        h.close()

    @pytest.mark.parametrize("q", [
        # unknown frame -> ErrFrameNotFound, identical through both lanes
        'Count(Range(rowID=1, frame="nope", start="2017-01-01T00:00", end="2017-02-01T00:00")) ' * 2,
        # month 13 -> "cannot parse Range() time" (calendar checks stay in Python)
        'Count(Range(rowID=1, frame="f", start="2017-13-01T00:00", end="2017-14-01T00:00")) ' * 2,
        # non-padded time declines the native matcher; Python still serves it
        'Count(Range(rowID=1, frame="f", start="2017-1-01T00:00", end="2017-02-01T00:00")) ' * 2,
    ])
    def test_range_edges_byte_identical(self, tmp_path, monkeypatch, q):
        h, ex, _ = self._range_holder(tmp_path)

        def run(e):
            try:
                return e.execute("r", q), None
            except Exception as exc:  # noqa: BLE001 — comparing error text
                return None, f"{type(exc).__name__}: {exc}"

        r_native, err_native = run(ex)
        monkeypatch.setenv("PILOSA_TPU_NO_RANGELANE", "1")
        r_py, err_py = run(ex)
        assert (r_native, err_native) == (r_py, err_py)
        h.close()
