"""Device-first bulk build + Arrow egress (pilosa_tpu/bulk).

Pins the build kernels (composite-key sort, CSR word lane, jax/numpy
parity on ragged shapes), the fragment overlay commit (dense planes and
sparse word OR, edge cases: empty input, slice growth mid-batch,
overlap with existing storage), the lazy materialization ledger (debt
on commit, pay-on-touch, budgeted drain, close-with-debt persistence),
the seeded differential contract (bulk-built fragments digest-identical
to streamed), and both front doors end to end (HTTP server and the
lockstep service) including the Arrow export -> re-ingest round trip.

Arrow-dependent tests carry the reason-logged skip contract: a host
without pyarrow skips them by name (the packed-PI64 lanes still run),
it does not fail tier-1.
"""

import json
import tempfile
import threading
import time
import urllib.request
import zlib

import numpy as np
import pytest

from pilosa_tpu import ingest
from pilosa_tpu.bulk import build as bulk_build
from pilosa_tpu.bulk import ingress
from pilosa_tpu.bulk.build import (
    WORDS_PER_PLANE,
    build_planes_numpy,
    build_words_numpy,
    group_pairs,
    plane_positions,
)
from pilosa_tpu.bulk.lazy import LEDGER, MaterializationLedger
from pilosa_tpu.config import Config
from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.core.frame import FrameOptions
from pilosa_tpu.ops import bitwise as bw
from pilosa_tpu.pilosa import SLICE_WIDTH
from pilosa_tpu.qos import CLASS_WRITE, classify_request
from pilosa_tpu.server.client import Client
from pilosa_tpu.server.server import Server

requires_pyarrow = pytest.mark.skipif(
    not ingest.arrow_available(),
    reason="pyarrow unavailable on this host: arrow bulk/egress lanes "
    "skipped (packed-PI64 lanes still covered)",
)


# -- reference ---------------------------------------------------------------

def _reference_planes(rows, cols):
    """Brute-force ground truth: {(slice, row): set(local cols)}."""
    ref: dict = {}
    for r, c in zip(np.asarray(rows).tolist(), np.asarray(cols).tolist()):
        ref.setdefault((c // SLICE_WIDTH, r), set()).add(c % SLICE_WIDTH)
    return ref


def _planes_to_sets(slice_ids, row_ids, planes):
    out = {}
    for s, r, plane in zip(slice_ids.tolist(), row_ids.tolist(), planes):
        out[(s, r)] = set(plane_positions(plane).tolist())
    return out


# -- build kernels -----------------------------------------------------------

def test_group_pairs_empty():
    s, r, gid, local = group_pairs([], [])
    assert len(s) == len(r) == len(gid) == len(local) == 0


def test_group_pairs_orders_and_segments():
    rows = np.array([5, 1, 5, 1, 5], dtype=np.uint64)
    cols = np.array([3, SLICE_WIDTH + 1, 3, 2, 1], dtype=np.uint64)
    s, r, gid, local = group_pairs(rows, cols)
    # groups sorted by (slice, row); within a group locals nondecreasing
    assert list(zip(s.tolist(), r.tolist())) == [(0, 1), (0, 5), (1, 1)]
    assert gid.tolist() == sorted(gid.tolist())
    for g in set(gid.tolist()):
        ll = local[gid == g]
        assert ll.tolist() == sorted(ll.tolist())


def test_group_pairs_bigid_fallback_matches_fastpath():
    """Slice/row ids past the 44-bit composite budget take the lexsort
    lane; both lanes produce the identical group table on data that
    fits either."""
    rng = np.random.default_rng(7)
    rows = rng.integers(0, 100, size=2000).astype(np.uint64)
    cols = rng.integers(0, 8 * SLICE_WIDTH, size=2000).astype(np.uint64)
    fast = group_pairs(rows, cols)
    # Force the fallback by planting one huge row id, then restricting
    # the comparison to the shared groups' shape via the reference.
    big_rows = np.concatenate([rows, np.array([1 << 50], dtype=np.uint64)])
    big_cols = np.concatenate([cols, np.array([3], dtype=np.uint64)])
    slow = group_pairs(big_rows, big_cols)
    ref = _reference_planes(big_rows, big_cols)
    assert len(slow[0]) == len(ref)
    # and the fast lane alone matches ITS reference exactly
    assert _planes_to_sets(*build_planes_numpy(rows, cols)) == \
        _reference_planes(rows, cols)
    assert len(fast[0]) == len(_reference_planes(rows, cols))


def test_build_planes_numpy_matches_reference():
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 16, size=5000).astype(np.uint64)
    cols = rng.integers(0, 3 * SLICE_WIDTH, size=5000).astype(np.uint64)
    s, r, planes = build_planes_numpy(rows, cols)
    assert _planes_to_sets(s, r, planes) == _reference_planes(rows, cols)


def test_build_words_matches_dense_planes():
    """The sparse CSR lane is the SAME build as the dense lane, in
    nonzero-word form: reassembling its words reproduces the planes."""
    rng = np.random.default_rng(4)
    rows = rng.integers(0, 8, size=4000).astype(np.uint64)
    cols = rng.integers(0, 2 * SLICE_WIDTH, size=4000).astype(np.uint64)
    ds, dr, planes = build_planes_numpy(rows, cols)
    ws, wr, counts, widx, wvals = build_words_numpy(rows, cols)
    assert ds.tolist() == ws.tolist() and dr.tolist() == wr.tolist()
    assert int(counts.sum()) == len(widx) == len(wvals)
    offs = np.concatenate([[0], np.cumsum(counts)])
    for g in range(len(ws)):
        lo, hi = offs[g], offs[g + 1]
        rebuilt = np.zeros(WORDS_PER_PLANE, dtype=np.uint32)
        rebuilt[widx[lo:hi]] = wvals[lo:hi]
        assert np.array_equal(rebuilt, planes[g])
        # word indices unique + ascending within the group (the
        # fancy-indexed OR in bulk_or_words depends on it)
        assert np.all(np.diff(widx[lo:hi]) > 0)


def test_build_words_empty():
    s, r, counts, widx, wvals = build_words_numpy([], [])
    assert len(s) == len(r) == len(counts) == len(widx) == len(wvals) == 0


def test_build_jax_matches_numpy_ragged_last_slice():
    """Device lane parity on a ragged shape: the last slice holds a
    single pair, duplicates included (the jax dedup makes scatter-add
    equal scatter-or)."""
    jax = pytest.importorskip("jax")  # noqa: F841
    rng = np.random.default_rng(5)
    rows = rng.integers(0, 6, size=3000).astype(np.uint64)
    cols = rng.integers(0, 2 * SLICE_WIDTH, size=3000).astype(np.uint64)
    rows = np.concatenate([rows, rows[:100],  # duplicates
                           np.array([2], dtype=np.uint64)])
    cols = np.concatenate([cols, cols[:100],
                           np.array([5 * SLICE_WIDTH + 17], dtype=np.uint64)])
    ns, nr, nplanes = build_planes_numpy(rows, cols)
    js, jr, jplanes = bulk_build.build_planes_jax(rows, cols)
    assert ns.tolist() == js.tolist() and nr.tolist() == jr.tolist()
    assert np.array_equal(nplanes, jplanes)


def test_build_jax_matches_numpy_sparse_many_groups(monkeypatch):
    """A sparse chunk touches thousands of (slice, row) groups: past 2048
    of them a composite position key no longer fits int32 (jax runs
    without x64), so the device lane sorts two int32 keys and builds at
    most _GROUPS_PER_CALL planes per kernel call (cap lowered here so the
    block loop runs)."""
    pytest.importorskip("jax")
    monkeypatch.setattr(bulk_build, "_GROUPS_PER_CALL", 1024)
    rng = np.random.default_rng(6)
    g = 2500
    rows = np.repeat(np.arange(g), 2).astype(np.uint64)
    cols = rng.integers(0, 2 * SLICE_WIDTH, size=2 * g).astype(np.uint64)
    rows = np.concatenate([rows, rows[:50]])  # duplicates
    cols = np.concatenate([cols, cols[:50]])
    ns, nr, nplanes = build_planes_numpy(rows, cols)
    js, jr, jplanes = bulk_build.build_planes_jax(rows, cols)
    assert ns.tolist() == js.tolist() and nr.tolist() == jr.tolist()
    assert np.array_equal(nplanes, jplanes)


def test_plane_positions_matches_roaring_bit_order():
    from pilosa_tpu.roaring import Bitmap

    pos = np.array([0, 1, 31, 32, 63, 1000, SLICE_WIDTH - 1], dtype=np.uint64)
    plane = np.zeros(WORDS_PER_PLANE, dtype=np.uint32)
    for p in pos.tolist():
        plane[p // 32] |= np.uint32(1) << np.uint32(p % 32)
    assert plane_positions(plane).tolist() == pos.tolist()
    b = Bitmap()
    b.add_many(pos)
    assert plane_positions(plane, base=0).tolist() == list(b)


def test_count_words_matches_reference():
    rng = np.random.default_rng(6)
    x = rng.integers(0, 1 << 32, size=999, dtype=np.uint64).astype(np.uint32)
    assert bw.count_words(x) == bw.np_count(x)
    assert bw.count_words(np.zeros(0, dtype=np.uint32)) == 0


# -- fragment overlay commit -------------------------------------------------

@pytest.fixture
def frag(tmp_path):
    f = Fragment(str(tmp_path / "0"), "i", "f", "standard", 0,
                 cache_type="ranked")
    f.open()
    yield f
    if f._open:
        f.close()


def _commit_words(f, rows, cols):
    s, r, counts, widx, wvals = build_words_numpy(rows, cols)
    offs = np.concatenate([[0], np.cumsum(counts)])
    assert set(s.tolist()) <= {0}
    return f.bulk_or_words(r, counts, widx, wvals)


def test_bulk_or_words_serves_merged_and_materializes_on_touch(frag):
    frag.set_bit(3, 10)  # pre-existing roaring bit overlapping the bulk rows
    rows = np.array([3, 3, 4], dtype=np.uint64)
    cols = np.array([10, 11, 99], dtype=np.uint64)
    _commit_words(frag, rows, cols)
    # merged read-your-writes before any materialization
    assert frag.row_count(3) == 2  # {10, 11}: overlap deduplicated
    assert frag.row_count(4) == 1
    assert frag._bulk_planes  # still lazy
    # roaring-shaped touch pays the debt and converges
    assert frag.contains(3, 11)
    csum = frag.checksum()
    assert not frag._bulk_planes
    # equal to the same bits set directly
    g = Fragment(frag.path + ".b", "i", "f2", "standard", 0)
    g.open()
    g.set_bit(3, 10), g.set_bit(3, 11), g.set_bit(4, 99)
    assert g.checksum() == csum
    g.close()


def test_bulk_or_words_validates_csr():
    with tempfile.TemporaryDirectory() as d:
        f = Fragment(d + "/0", "i", "f", "standard", 0)
        f.open()
        try:
            with pytest.raises(ValueError):
                f.bulk_or_words(np.array([1]), np.array([1, 2]),
                                np.array([0]), np.array([1], dtype=np.uint32))
            with pytest.raises(ValueError):
                f.bulk_or_words(np.array([1]), np.array([2]),  # sum != len
                                np.array([0]), np.array([1], dtype=np.uint32))
            with pytest.raises(ValueError):
                f.bulk_or_words(np.array([1]), np.array([1]),
                                np.array([WORDS_PER_PLANE]),  # out of range
                                np.array([1], dtype=np.uint32))
        finally:
            f.close()


def test_apply_bulk_empty_and_slice_growth(tmp_path):
    """Edge cases via the full ingress path: a zero-pair chunk commits
    nothing, and a later chunk touching NEW slices grows the fragment
    set mid-batch."""
    from pilosa_tpu.core.holder import Holder

    h = Holder(str(tmp_path / "d"))
    h.open()
    try:
        idx = h.create_index("i")
        fr = idx.create_frame("f", FrameOptions())
        assert ingress.apply_bulk(fr, [], []) == 0
        std = fr.view("standard")
        assert std is None or not std.fragments
        # chunk 1: slice 0 only
        ingress.apply_bulk(fr, np.array([1, 2], dtype=np.uint64),
                           np.array([5, 6], dtype=np.uint64))
        assert sorted(fr.view("standard").fragments) == [0]
        # chunk 2: grows to slice 2 (slice 1 stays absent — sparse)
        ingress.apply_bulk(fr, np.array([1], dtype=np.uint64),
                           np.array([2 * SLICE_WIDTH + 7], dtype=np.uint64))
        assert sorted(fr.view("standard").fragments) == [0, 2]
        assert fr.view("standard").fragment(2).row_count(1) == 1
    finally:
        h.close()


def test_close_with_debt_persists(tmp_path):
    """A fragment closed while carrying overlay debt materializes on
    close: reopening serves the bulk bits from storage."""
    f = Fragment(str(tmp_path / "0"), "i", "f", "standard", 0)
    f.open()
    _commit_words(f, np.array([7, 7], dtype=np.uint64),
                  np.array([100, 200], dtype=np.uint64))
    assert f._bulk_planes
    f.close()
    g = Fragment(f.path, "i", "f", "standard", 0)
    g.open()
    try:
        assert g.contains(7, 100) and g.contains(7, 200)
    finally:
        g.close()


# -- lazy ledger -------------------------------------------------------------

def test_ledger_tracks_debt_and_budget_drain(frag):
    """Budget semantics on the process ledger (fragments report their
    own materialization back to it, so the drain must run against the
    same registry the commit noted debt in)."""
    _commit_words(frag, np.array([1], dtype=np.uint64),
                  np.array([5], dtype=np.uint64))
    assert LEDGER.pending_count() >= 1
    assert LEDGER.materialize_some(0) == 0  # <=0 budget: fully lazy
    assert frag._bulk_planes
    assert LEDGER.materialize_some(5000) >= 1
    assert not frag._bulk_planes
    assert LEDGER.pending_count() == 0
    # debt already paid: the drain is a no-op
    assert LEDGER.materialize_some(5000) == 0


def test_ledger_weakref_never_pins_fragments():
    led = MaterializationLedger()

    class _F:  # minimal stand-in with the materialize hook
        def materialize_bulk(self):
            pass

    f = _F()
    led.note_pending(f)
    assert led.pending_count() == 1
    del f
    import gc

    gc.collect()
    assert led.pending_count() == 0


def test_global_ledger_pays_on_touch(frag):
    before = LEDGER.pending_count()
    _commit_words(frag, np.array([2], dtype=np.uint64),
                  np.array([9], dtype=np.uint64))
    assert LEDGER.pending_count() == before + 1
    frag.checksum()  # storage-shaped touch
    assert LEDGER.pending_count() == before


# -- seeded differential: bulk-built == streamed -----------------------------

@pytest.mark.parametrize("inverse", [False, True])
def test_bulk_differential_digest_vs_streamed(tmp_path, inverse):
    """The tentpole contract: the SAME seeded pairs through the bulk
    build and through the streamed set_bits door produce digest-
    identical fragments, standard and inverse views both."""
    from pilosa_tpu.core.holder import Holder

    rng = np.random.default_rng(11)
    rows = rng.integers(0, 40, size=20000).astype(np.uint64)
    cols = rng.integers(0, 3 * SLICE_WIDTH, size=20000).astype(np.uint64)
    h = Holder(str(tmp_path / "d"))
    h.open()
    try:
        idx = h.create_index("i")
        fb = idx.create_frame("b", FrameOptions(inverse_enabled=inverse))
        fs = idx.create_frame("s", FrameOptions(inverse_enabled=inverse))
        # bulk door applies in chunks (exercises overlay accumulation)
        for i in range(0, len(rows), 4096):
            ingress.apply_bulk(fb, rows[i:i + 4096], cols[i:i + 4096])
        ingress.complete_bulk(fb)
        ingest.apply_columnar(fs, rows, cols)
        ingest.recalc_frame_caches(fs)
        views = ["standard"] + (["inverse"] if inverse else [])
        for vname in views:
            vb, vs = fb.view(vname), fs.view(vname)
            assert sorted(vb.fragments) == sorted(vs.fragments)
            for s in vb.fragments:
                assert vb.fragment(s).checksum() == vs.fragment(s).checksum(), (
                    f"{vname}/{s} diverged"
                )
    finally:
        h.close()


# -- HTTP front door ---------------------------------------------------------

def test_bulk_route_classifies_as_write():
    assert classify_request("POST", "/index/i/frame/f/bulk", b"") == CLASS_WRITE


@pytest.fixture
def srv():
    with tempfile.TemporaryDirectory() as d:
        cfg = Config(data_dir=d, host="127.0.0.1:0", engine="numpy",
                     stats="expvar", qcache_enabled=False)
        s = Server(cfg)
        s.open()
        try:
            c = Client(s.host)
            c.create_index("i")
            c.create_frame("i", "f")
            yield s, c
        finally:
            s.close()


def test_bulk_end_to_end_http(srv):
    s, c = srv
    rng = np.random.default_rng(12)
    rows = rng.integers(0, 30, size=20000).astype(np.uint64)
    cols = rng.integers(0, 2 * SLICE_WIDTH, size=20000).astype(np.uint64)
    out = c.bulk_stream("i", "f", rows, cols, chunk_pairs=4096)
    assert out["done"] and out["ops"] == 20000
    # served reads merge the overlay; TopN fresh at completion
    r = c.execute_query("i", 'Count(Bitmap(rowID=7, frame="f"))')
    assert r["results"][0]["n"] == len(np.unique(cols[rows == 7]))
    uniq = {int(x): len(np.unique(cols[rows == x])) for x in np.unique(rows)}
    top = c.execute_query("i", 'TopN(frame="f", n=1)')["results"][0]["pairs"]
    assert top[0]["count"] == max(uniq.values())
    # streamed twin digest parity through the OTHER door
    c.create_frame("i", "g")
    assert c.ingest_stream("i", "g", rows, cols, chunk_pairs=4096)["done"]
    idx = s.holder.index("i")
    for sl in sorted(idx.frame("g").view("standard").fragments):
        assert idx.frame("f").view("standard").fragment(sl).checksum() == \
            idx.frame("g").view("standard").fragment(sl).checksum()
    # bulk.* counters registered and moving (fragment-level counters
    # carry index/frame tags, so match on the flat dump)
    v = json.loads(
        urllib.request.urlopen(f"http://{s.host}/debug/vars").read()
    )
    assert v["bulk.pairs"] >= 20000
    flat = json.dumps(v)
    assert "bulk.commit_rows" in flat and "bulk.build" in flat


@requires_pyarrow
def test_arrow_export_reingest_roundtrip(srv):
    s, c = srv
    rng = np.random.default_rng(13)
    rows = rng.integers(0, 20, size=5000).astype(np.uint64)
    cols = rng.integers(0, SLICE_WIDTH, size=5000).astype(np.uint64)
    assert c.bulk_stream("i", "f", rows, cols)["done"]
    a = c.export_arrow("i", "f", "standard", 0)
    c.create_frame("i", "rt")
    crc = zlib.crc32(a)
    status, out = c.ingest_chunk("i", "rt", 0, len(a), crc, a, ccrc=crc,
                                 door="bulk", arrow=True)
    assert status == 200 and out["done"]
    b = c.export_arrow("i", "rt", "standard", 0)
    assert a == b  # deterministic egress: byte-identical round trip
    r2, c2 = ingest.decode_arrow(a)
    ref = sorted(zip(rows.tolist(), cols.tolist()))
    got = sorted(set(zip(r2.tolist(), c2.tolist())))
    assert got == sorted(set(ref))


@requires_pyarrow
def test_arrow_ingest_hardening_http(srv):
    """Producer-variety arrow chunks through the HTTP bulk door: extra
    columns and dictionary-encoded ids apply; schema mistakes answer
    pointed 400s."""
    import io

    import pyarrow as pa

    _, c = srv
    rows = np.array([1, 1, 2], dtype=np.uint64)
    cols = np.array([10, 11, 12], dtype=np.uint64)
    t = pa.table({
        "row": pa.array(rows.tolist(), type=pa.int32()).dictionary_encode(),
        "col": cols,
        "extra": ["a", "b", "c"],
    })
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, t.schema) as w:
        w.write_table(t)
    body = sink.getvalue()
    crc = zlib.crc32(body)
    status, out = c.ingest_chunk("i", "f", 0, len(body), crc, body, ccrc=crc,
                                 door="bulk", arrow=True)
    assert status == 200 and out["done"]
    assert c.execute_query("i", 'Count(Bitmap(rowID=1, frame="f"))')[
        "results"][0]["n"] == 2
    # missing required column: pointed 400 naming it
    t2 = pa.table({"row": rows})
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, t2.schema) as w:
        w.write_table(t2)
    body = sink.getvalue()
    from pilosa_tpu.server.client import ClientError

    with pytest.raises(ClientError) as ei:
        c.ingest_chunk("i", "f", 0, len(body), zlib.crc32(body), body,
                       ccrc=zlib.crc32(body), door="bulk", arrow=True)
    assert ei.value.status == 400 and "col" in str(ei.value)


# -- lockstep front door -----------------------------------------------------

def _lockstep_svc(tmp_path):
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.parallel.service import LockstepService

    h = Holder(str(tmp_path / "d"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    svc = LockstepService(
        h, control_addr=("127.0.0.1", 0), http_addr=("127.0.0.1", 0)
    )
    threading.Thread(target=svc.serve_forever, daemon=True).start()
    deadline = time.monotonic() + 10
    while svc._httpd is None and time.monotonic() < deadline:
        time.sleep(0.02)
    assert svc._httpd is not None
    return h, svc, f"http://{svc.http_addr[0]}:{svc.http_addr[1]}"


def _post(base, path, data, timeout=30):
    rq = urllib.request.Request(base + path, data=data, method="POST")
    with urllib.request.urlopen(rq, timeout=timeout) as resp:
        return json.loads(resp.read())


def test_lockstep_front_end_bulk(tmp_path):
    """The lockstep front end serves the bulk wire: rank 0 decodes each
    chunk once and replays the pairs through the replicated total order;
    every rank runs the build kernel; the completion recalc rides its
    own reserved entry — reads right after are fresh and digest-equal
    to the streamed door."""
    h, svc, base = _lockstep_svc(tmp_path)
    try:
        rng = np.random.default_rng(14)
        rows = rng.integers(0, 12, size=6000).astype(np.uint64)
        cols = rng.integers(0, 2 * SLICE_WIDTH, size=6000).astype(np.uint64)
        frames = [
            ingest.encode_packed(rows[i:i + 2048], cols[i:i + 2048])
            for i in range(0, len(rows), 2048)
        ]
        total = sum(len(f) for f in frames)
        crc = 0
        for fb in frames:
            crc = zlib.crc32(fb, crc)
        off = 0
        for fb in frames:
            out = _post(
                base,
                f"/index/i/frame/f/bulk?off={off}&total={total}"
                f"&crc={crc}&ccrc={zlib.crc32(fb)}", fb,
            )
            off += len(fb)
            assert out["staged"] == off
        assert out["done"]
        got = _post(base, "/index/i/query",
                    b'Count(Bitmap(rowID=3, frame="f"))')["results"][0]
        assert got == len(np.unique(cols[rows == 3]))
        # digest + TopN parity vs the streamed door on the same service
        # (TopN is per-fragment-approximate by design, so the streamed
        # twin — not brute-force ground truth — is the correctness bar)
        h.index("i").create_frame("g", FrameOptions())
        off = 0
        for fb in frames:
            out = _post(
                base,
                f"/index/i/frame/g/ingest?off={off}&total={total}"
                f"&crc={crc}&ccrc={zlib.crc32(fb)}", fb,
            )
            off += len(fb)
        assert out["done"]
        top_b = _post(base, "/index/i/query",
                      b'TopN(frame="f", n=3)')["results"][0]
        top_g = _post(base, "/index/i/query",
                      b'TopN(frame="g", n=3)')["results"][0]
        assert top_b == top_g and top_b[0]["count"] > 0
        idx = h.index("i")
        for sl in sorted(idx.frame("g").view("standard").fragments):
            assert idx.frame("f").view("standard").fragment(sl).checksum() \
                == idx.frame("g").view("standard").fragment(sl).checksum()
    finally:
        svc.shutdown()
        h.close()


@requires_pyarrow
def test_lockstep_front_end_bulk_arrow(tmp_path):
    """Arrow chunks through the lockstep bulk door: rank 0's decode is
    the only pyarrow touch — replicated replay carries decoded pairs."""
    import io

    import pyarrow as pa

    h, svc, base = _lockstep_svc(tmp_path)
    try:
        rows = np.array([1, 2, 2], dtype=np.uint64)
        cols = np.array([7, 8, 9], dtype=np.uint64)
        t = pa.table({"row": rows, "col": cols, "noise": [0.1, 0.2, 0.3]})
        sink = io.BytesIO()
        with pa.ipc.new_stream(sink, t.schema) as w:
            w.write_table(t)
        body = sink.getvalue()
        crc = zlib.crc32(body)
        rq = urllib.request.Request(
            base + f"/index/i/frame/f/bulk?off=0&total={len(body)}"
            f"&crc={crc}&ccrc={crc}",
            data=body, method="POST",
            headers={"Content-Type": ingest.ARROW_CONTENT_TYPE},
        )
        with urllib.request.urlopen(rq, timeout=30) as resp:
            out = json.loads(resp.read())
        assert out["done"]
        got = _post(base, "/index/i/query",
                    b'Count(Bitmap(rowID=2, frame="f"))')["results"][0]
        assert got == 2
    finally:
        svc.shutdown()
        h.close()
