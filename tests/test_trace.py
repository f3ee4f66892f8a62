"""Request-tracing tests: span trees, head sampling, the slow-query
log, wire propagation, executor integration, and the client-retry
satellites (one retry = one trace identity; deadline expiry during
backoff aborts the retry).

End-to-end HTTP coverage (/debug/traces, slow log through a real
server, two-node remote sub-spans) lives in test_server.py; the 2-rank
lockstep sampling-determinism test lives in test_multihost.py.
"""

import json
import logging
import time

import pytest

from pilosa_tpu.config import Config
from pilosa_tpu.trace import (
    TRACE_HEADER,
    TRACE_SPANS_HEADER,
    Span,
    Trace,
    Tracer,
    fingerprint,
)


# -- spans --------------------------------------------------------------------


def test_span_tree_offsets_and_tags():
    root = Span("root", trace_id="t1")
    a = root.child("parse")
    a.finish()
    b = root.child("call.Count").annotate(slices=4)
    b.finish()
    root.finish()
    assert a.trace_id == "t1"  # children inherit the trace identity
    js = root.to_json()
    assert js["name"] == "root" and js["ms"] >= 0
    names = [c["name"] for c in js["children"]]
    assert names == ["parse", "call.Count"]
    assert js["children"][1]["tags"] == {"slices": 4}
    # Offsets are relative to the root's own start — no wall clock.
    assert all(c["start_ms"] >= 0 for c in js["children"])


def test_span_finish_idempotent_and_unfinished_serializes():
    sp = Span("x")
    sp.finish()
    ms1 = sp.ms
    time.sleep(0.002)
    sp.finish()
    assert sp.ms == ms1  # idempotent
    live = Span("still-running")
    js = live.to_json()
    assert js["ms"] >= 0  # measured at serialization, not an error


def test_span_graft_keeps_remote_payload_verbatim():
    root = Span("root")
    remote = root.child("remote")
    payload = [{"name": "POST /index/i/query", "start_ms": 0.0, "ms": 3.2,
                "children": [{"name": "parse", "start_ms": 0.1, "ms": 0.2}]}]
    remote.graft(payload)
    remote.finish()
    js = root.to_json()
    grafted = js["children"][0]["children"][0]
    assert grafted["name"] == "POST /index/i/query"
    assert grafted["children"][0]["name"] == "parse"


def test_stage_breakdown_sums_duplicate_names():
    root = Span("root")
    for ms in (1.0, 2.0):
        c = root.child("slice_chunk")
        c.ms = ms
    c = root.child("parse")
    c.ms = 0.5
    bd = root.stage_breakdown()
    assert bd == {"slice_chunk": 3.0, "parse": 0.5}


# -- sampling -----------------------------------------------------------------


def test_head_sampling_rate_zero_only_forced():
    t = Tracer(sample_rate=0.0)
    assert t.begin({}) is None  # never sampled
    tr = t.begin({TRACE_HEADER.lower(): "1"})
    assert tr is not None and tr.forced and tr.propagate
    # A bare override gets a fresh id; a propagated id is adopted.
    assert len(tr.id) == 16
    tr2 = t.begin({TRACE_HEADER.lower(): "abc123def"})
    assert tr2.id == "abc123def"


def test_head_sampling_rate_one_and_decide():
    t = Tracer(sample_rate=1.0)
    tr = t.begin({})
    assert tr is not None and not tr.forced and not tr.propagate
    assert t.decide() is True
    t0 = Tracer(sample_rate=0.0)
    assert t0.decide() is False and t0.decide(force=True) is True


def test_ring_bounded_newest_first_min_ms_filter():
    t = Tracer(sample_rate=1.0, ring=4)
    for i in range(8):
        tr = Trace(f"q{i}")
        tr.root.ms = float(i)
        t.record(tr)
    snap = t.traces_json()
    assert len(snap) == 4  # bounded
    assert [e["name"] for e in snap] == ["q7", "q6", "q5", "q4"]  # newest-first
    assert [e["name"] for e in t.traces_json(min_ms=6.0)] == ["q7", "q6"]
    assert len(t.traces_json(limit=1)) == 1


# -- slow-query log -----------------------------------------------------------


def test_slow_request_bypasses_sampling_and_logs(caplog):
    t = Tracer(sample_rate=0.0, slow_ms=5.0)
    # Fast + unsampled: nothing recorded, nothing logged.
    assert t.finish_request(None, name="POST /q", dt_ms=1.0, body=b"x") is None
    assert len(t) == 0
    with caplog.at_level(logging.WARNING, logger="pilosa_tpu.slowquery"):
        t.finish_request(None, name="POST /q", dt_ms=72.0,
                         body=b'Count(Bitmap(rowID=1, frame="f"))')
    assert len(t) == 1 and t.stat_slow == 1
    entry = t.traces_json()[0]
    assert entry["slow"] and entry["ms"] == 72.0
    assert entry["spans"]["tags"]["unsampled"] is True
    rec = json.loads(caplog.records[-1].message.split("slow-query ", 1)[1])
    assert rec["ms"] == 72.0 and rec["fp"] and "Count(" in rec["snippet"]


def test_slow_sampled_trace_logs_stage_breakdown(caplog):
    t = Tracer(sample_rate=1.0, slow_ms=1.0)
    tr = t.begin({}, name="POST /q")
    tr.root.tags["qcache"] = "miss"
    sp = tr.root.child("parse")
    sp.ms = 0.4
    sp = tr.root.child("call.Count")
    sp.ms = 9.0
    with caplog.at_level(logging.WARNING, logger="pilosa_tpu.slowquery"):
        t.finish_request(tr, name="POST /q", dt_ms=10.0, body=b"Count(...)")
    rec = json.loads(caplog.records[-1].message.split("slow-query ", 1)[1])
    assert rec["stages"] == {"parse": 0.4, "call.Count": 9.0}
    assert rec["tags"]["qcache"] == "miss"  # cache disposition surfaced


def test_propagate_returns_header_and_truncates_oversize():
    class Counts:
        def __init__(self):
            self.n = {}

        def count(self, name, value=1):
            self.n[name] = self.n.get(name, 0) + value

    stats = Counts()
    t = Tracer(sample_rate=0.0, stats=stats)
    tr = t.begin({TRACE_HEADER.lower(): "deadbeef"}, name="POST /q")
    extra = t.finish_request(tr, name="POST /q", dt_ms=1.0)
    payload = json.loads(extra[TRACE_SPANS_HEADER])
    assert payload[0]["name"] == "POST /q"
    # Oversize trees degrade to the root rather than breaking the header.
    tr2 = t.begin({TRACE_HEADER.lower(): "deadbeef"}, name="POST /q")
    for i in range(3000):
        tr2.root.child(f"span-{i}").finish()
    extra2 = t.finish_request(tr2, name="POST /q", dt_ms=1.0)
    raw = extra2[TRACE_SPANS_HEADER]
    assert len(raw) < 32000
    slim = json.loads(raw)[0]
    assert slim.get("truncated") and "children" not in slim
    # Every reader of the header has lost that request: it is counted.
    assert stats.n == {"trace.sampled": 2, "trace.spans_truncated": 1}


def test_fingerprint_stable_and_bounded():
    a = fingerprint(b"Count(Bitmap(rowID=1))" * 100)
    b = fingerprint(b"Count(Bitmap(rowID=1))" * 100)
    assert a == b and len(a["snippet"]) <= 120 and len(a["fp"]) == 12
    assert fingerprint(b"") == {"fp": "", "snippet": ""}


# -- config promotion ---------------------------------------------------------


def test_config_trace_toml_and_env(tmp_path):
    toml = tmp_path / "c.toml"
    toml.write_text(
        """
[trace]
  sample-rate = 0.25
  slow-ms = 150.0
  ring = 64
"""
    )
    cfg = Config.from_toml(str(toml))
    assert cfg.trace_sample_rate == 0.25
    assert cfg.trace_slow_ms == 150.0
    assert cfg.trace_ring == 64
    cfg.apply_env({
        "PILOSA_TPU_TRACE_SAMPLE_RATE": "0.5",
        "PILOSA_TPU_TRACE_SLOW_MS": "75",
        "PILOSA_TPU_TRACE_RING": "32",
    })
    assert cfg.trace_sample_rate == 0.5
    assert cfg.trace_slow_ms == 75.0
    assert cfg.trace_ring == 32
    # Defaults: tracing off (only the force header samples).
    assert Config().trace_sample_rate == 0.0 and Config().trace_slow_ms == 0.0


# -- executor integration -----------------------------------------------------


@pytest.fixture
def holder(tmp_path):
    from pilosa_tpu.core.frame import FrameOptions
    from pilosa_tpu.core.holder import Holder

    h = Holder(str(tmp_path / "d"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("f")
    for r in range(3):
        for c in range(r, 30 + r):
            fr.set_bit("standard", r, c)
    yield h
    h.close()


def test_executor_spans_sequential_path(holder):
    from pilosa_tpu.executor import ExecOptions, Executor

    ex = Executor(holder, engine="numpy")
    root = Span("root")
    res = ex.execute("i", 'TopN(frame="f", n=2) Bitmap(rowID=1, frame="f")',
                     opt=ExecOptions(span=root))
    assert len(res) == 2
    names = [c.name for c in root.children]
    assert "parse" in names
    assert "call.TopN" in names and "call.Bitmap" in names
    # Fan-out spans nest under the calls.
    topn = next(c for c in root.children if c.name == "call.TopN")
    assert any(c.name in ("slices", "slice_chunk") for c in topn.children)
    assert all(c.ms is not None for c in root.children)


def test_executor_spans_fused_and_lanes(holder):
    import os

    from pilosa_tpu.executor import ExecOptions, Executor

    os.environ["PILOSA_TPU_NO_FASTLANE"] = "1"  # land in the AST fused lane
    try:
        ex = Executor(holder, engine="numpy")
        root = Span("root")
        q = ('Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f"))) '
             'Count(Union(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f")))')
        ex.execute("i", q, opt=ExecOptions(span=root))
        assert root.tags.get("lane") == "fused"
        fsp = next(c for c in root.children if c.name == "fused")
        assert fsp.tags["calls"] == 2 and fsp.tags["slices"] >= 1
    finally:
        del os.environ["PILOSA_TPU_NO_FASTLANE"]
    # Fast lanes tag the root; the write lane's one child is its apply.
    ex2 = Executor(holder, engine="numpy")
    root2 = Span("root")
    ex2.execute("i", 'SetBit(rowID=9, frame="f", columnID=3)',
                opt=ExecOptions(span=root2))
    assert root2.tags.get("lane") == "write_fast"
    assert [c.name for c in root2.children] == ["write.apply"]
    assert root2.children[0].tags == {"changed": 1} and root2.children[0].ms is not None


def test_executor_qcache_span_outcomes(holder):
    from pilosa_tpu.executor import ExecOptions, Executor
    from pilosa_tpu.qcache import QueryCache

    ex = Executor(holder, engine="numpy", qcache=QueryCache(min_cost_ms=0.0))
    q = 'Count(Bitmap(rowID=1, frame="f"))'
    r1 = Span("r1")
    ex.execute("i", q, opt=ExecOptions(span=r1))
    assert r1.tags["qcache"] == "deferred"  # a never-seen string: a miss, parsed at its commit
    names1 = [c.name for c in r1.children]
    assert names1.index("qcache.lookup") < names1.index("qcache.commit")  # the miss's admission
    r2 = Span("r2")
    ex.execute("i", q, opt=ExecOptions(span=r2))
    assert r2.tags["qcache"] == "hit"
    assert [c.name for c in r2.children] == ["qcache.lookup"]  # a hit commits nothing
    r3 = Span("r3")
    ex.execute("i", q, opt=ExecOptions(span=r3, no_cache=True))
    assert r3.tags["qcache"] == "bypass"
    holder.index("i").frame("f").set_bit("standard", 1, 77)
    r4 = Span("r4")
    ex.execute("i", q, opt=ExecOptions(span=r4))
    assert r4.tags["qcache"] == "miss"  # the memo knows the string; its frame moved


def test_executor_untraced_requests_build_no_spans(holder):
    """The off path: no span objects anywhere (opt.span=None and the
    default ExecOptions) — guard against accidental always-on costs."""
    from pilosa_tpu.executor import ExecOptions, Executor

    ex = Executor(holder, engine="numpy")
    opt = ExecOptions()
    assert opt.span is None
    res = ex.execute("i", 'Count(Bitmap(rowID=1, frame="f"))', opt=opt)
    assert res and opt.span is None


# -- client satellites: retry keeps ONE trace/request identity ----------------


class _StubHTTP:
    """Minimal scripted HTTP stub (same shape as test_qos's)."""

    def __init__(self, script):
        import http.server
        import threading

        self.requests = []
        stub = self

        class H(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def _serve(self):
                n = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(n) if n else b""
                stub.requests.append(
                    {"path": self.path, "headers": dict(self.headers), "body": body}
                )
                status, headers, payload = (
                    script[min(len(stub.requests), len(script)) - 1]
                )
                self.send_response(status)
                for k, v in headers.items():
                    self.send_header(k, v)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            do_GET = do_POST = _serve

            def log_message(self, *a):
                pass

        self.httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.host = f"127.0.0.1:{self.httpd.server_address[1]}"
        t = threading.Thread(target=self.httpd.serve_forever, daemon=True)
        t.start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_client_retry_reuses_trace_identity():
    """One capped Retry-After retry must reuse the SAME trace/request
    identity: the retried attempt carries the identical X-Pilosa-Trace
    id, and the hop span grafts exactly ONE peer payload — never a
    duplicate root span per attempt."""
    from pilosa_tpu import wire
    from pilosa_tpu.server.client import Client

    ok = wire.encode_query_response(results=[1])
    peer_spans = json.dumps([{"name": "POST /index/i/query", "start_ms": 0.0,
                              "ms": 1.5}])
    stub = _StubHTTP([
        (429, {"Retry-After": "0.05", "Content-Type": "application/json"},
         b'{"error": "shed"}'),
        (200, {"Content-Type": "application/x-protobuf",
               TRACE_SPANS_HEADER: peer_spans}, ok),
    ])
    try:
        c = Client(stub.host)
        hop = Span("remote", trace_id="feedface12345678")
        resp = c.execute_query("i", "Count(Bitmap(rowID=1))", trace_span=hop)
        assert resp["results"]
        assert len(stub.requests) == 2  # one retry happened
        ids = [r["headers"].get(TRACE_HEADER) for r in stub.requests]
        assert ids == ["feedface12345678", "feedface12345678"]  # same identity
        # Exactly one grafted peer payload (from the final response).
        assert len(hop.children) == 1
        assert hop.children[0]["name"] == "POST /index/i/query"
    finally:
        stub.close()


def test_client_deadline_expiry_during_backoff_aborts_retry():
    """Deadline expiry during the Retry-After backoff must abort the
    retry: the client returns the shed answer after ONE attempt instead
    of sleeping past the budget."""
    from pilosa_tpu.qos import Deadline
    from pilosa_tpu.server.client import Client, ClientError

    stub = _StubHTTP([
        (429, {"Retry-After": "1.5"}, b'{"error": "shed"}'),
        (200, {}, b"never reached"),
    ])
    try:
        c = Client(stub.host)
        t0 = time.monotonic()
        with pytest.raises(ClientError) as e:
            c.execute_query(
                "i", "Count(Bitmap(rowID=1))", deadline=Deadline(200),
                trace_span=Span("remote", trace_id="aa"),
            )
        assert e.value.status == 429
        assert len(stub.requests) == 1  # the retry was aborted, not slept
        assert time.monotonic() - t0 < 1.0
    finally:
        stub.close()


# -- the served path's layers: door, serve state, pool, write lane, encode ----
#
# One real server on the jax engine (CPU backend): the host Gram lanes
# serve the reads, a SetBit makes the serve state stale, and the next
# read repairs the pool's planes and Gram under the pool's lock.

_PAIRS = " ".join(
    f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
    for a in range(4) for b in range(a + 1, 4)
)


def _post(host, body, trace=True):
    import urllib.request

    req = urllib.request.Request(
        f"http://{host}/index/i/query", data=body.encode(), method="POST"
    )
    req.add_header("X-Pilosa-No-Cache", "1")
    if trace:
        req.add_header(TRACE_HEADER, "1")
    with urllib.request.urlopen(req, timeout=120) as r:
        out = json.loads(r.read())["results"]
        tree = r.headers.get(TRACE_SPANS_HEADER)
    return out, (json.loads(tree)[0] if tree else None)


def _find(node, name):
    """Every span of that name in a serialized tree, depth first."""
    out = [node] if node["name"] == name else []
    for c in node.get("children", []):
        out.extend(_find(c, name))
    return out


def _fits(node, outer_start, outer_end, slack=0.05):
    end = node["start_ms"] + node["ms"]
    ok = node["start_ms"] >= outer_start - slack and end <= outer_end + slack
    return ok and all(_fits(c, node["start_ms"], end, slack) for c in node.get("children", []))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from pilosa_tpu.server.client import Client
    from pilosa_tpu.server.server import Server

    cfg = Config(data_dir=str(tmp_path_factory.mktemp("served")), host="127.0.0.1:0",
                 engine="jax")
    s = Server(cfg)
    s.open()
    c = Client(s.host)
    c.create_index("i")
    c.create_frame("i", "f")
    for r in range(4):
        c.execute_query("i", " ".join(
            f'SetBit(rowID={r}, frame="f", columnID={k * 3 + r})' for k in range(40)))
    for _ in range(4):  # page the rows in, build the Gram, arm the serve state
        _post(s.host, _PAIRS, trace=False)
    yield s
    s.close()


def _pool(served):
    (pool,) = served.executor._matrix_cache.values()
    return pool


def test_served_flat_read_has_a_span_for_each_layer(served):
    sent = time.perf_counter()
    res, root = _post(served.host, _PAIRS)
    # The root says when it began, on the clock this process reads too.
    assert sent <= root["tags"]["t0_s"] <= time.perf_counter() - root["ms"] / 1e3
    assert len(res) == 6 and root["tags"]["lane"] == "flat"
    names = [c["name"] for c in root["children"]]
    for want in ("door.read", "qos.admit", "serve.validate", "device", "encode"):
        assert want in names, names
    assert root["children"][0]["name"] == "door.read"
    assert root["children"][0]["start_ms"] == 0.0  # the root starts at take-up
    assert root["children"][0]["tags"]["bytes"] == len(_PAIRS)
    assert _find(root, "serve.validate")[0]["tags"] == {"valid": True}
    assert _find(root, "encode")[0]["tags"]["bytes"] > 0
    assert _fits(root, 0.0, root["ms"])
    assert 0 < root["tags"]["cpu_ms"] <= root["ms"]
    # One after another: the layers' spans never overlap.
    flat = sorted(root["children"], key=lambda c: c["start_ms"])
    for a, b in zip(flat, flat[1:]):
        assert a["start_ms"] + a["ms"] <= b["start_ms"] + 0.05


def test_read_after_a_setbit_shows_the_repair_under_the_pools_lock(served):
    host = served.host
    res0, _ = _post(host, _PAIRS, trace=False)
    wres, _ = _post(host, 'SetBit(rowID=1, frame="f", columnID=1000)', trace=False)
    assert wres == [True]
    res, root = _post(host, _PAIRS)
    assert _find(root, "serve.validate")[0]["tags"] == {"valid": False}
    (rep,) = _find(root, "serve.repair")
    assert rep["tags"] == {"repaired": True}
    assert [c["name"] for c in rep["children"]] == ["pool.lock_wait", "pool.repair"]
    (prep,) = _find(rep, "pool.repair")
    assert [c["name"] for c in prep["children"]] == ["pool.fetch", "pool.scatter", "pool.gram"]
    assert prep["tags"]["planes"] == 1 and prep["tags"]["slices"] == 1
    assert prep["tags"]["upload_bytes"] >= 131072  # one 128 KiB plane
    assert sum(c["ms"] for c in prep["children"]) <= prep["ms"] + 0.05
    assert _fits(root, 0.0, root["ms"])
    assert "device" in [c["name"] for c in root["children"]]  # then served natively
    assert res == res0  # column 1000 is in no other row
    # One slice, so no clean one: the composed repair, which copies.
    assert prep["tags"]["in_place"] is False and _pool(served).stat_repairs_in_place == 0


def test_a_repair_of_an_array_no_reader_holds_is_in_place(tmp_path):
    """Four slices: a write to one is a repair the jax engine's compiled
    step takes.  The first patches a copy (the warm-up's readers hold the
    pool's array); the new array never left the pool, so the second
    updates it in place; tag, counter and /debug/vars say so, and the
    stages keep their names and order."""
    import urllib.request

    from pilosa_tpu.server.client import Client
    from pilosa_tpu.server.server import Server

    s = Server(Config(data_dir=str(tmp_path / "s4"), host="127.0.0.1:0", engine="jax"))
    s.open()
    try:
        c = Client(s.host)
        c.create_index("i")
        c.create_frame("i", "f")
        for r in range(4):
            c.execute_query("i", " ".join(
                f'SetBit(rowID={r}, frame="f", columnID={(sl << 20) + k * 3 + r})'
                for sl in range(4) for k in range(10)))
        for _ in range(4):
            res0, _ = _post(s.host, _PAIRS, trace=False)
        pool = _pool(s)
        for nth, in_place in ((1, False), (2, True), (3, True)):
            _post(s.host, f'SetBit(rowID=1, frame="f", columnID={(2 << 20) + 1000 + nth})',
                  trace=False)
            res, root = _post(s.host, _PAIRS)
            (prep,) = _find(root, "pool.repair")
            assert [c["name"] for c in prep["children"]] == [
                "pool.fetch", "pool.scatter", "pool.gram"]
            assert prep["tags"]["in_place"] is in_place and prep["tags"]["planes"] == 1
            assert prep["tags"]["upload_bytes"] == 131072
            assert res == res0  # those columns are in no other row
        assert (pool.stat_repairs, pool.stat_repairs_in_place) == (3, 2)
        snap = json.loads(urllib.request.urlopen(f"http://{s.host}/debug/vars", timeout=30).read())
        assert snap["rowpool.repairs"] == 3 and snap["rowpool.repairs_in_place"] == 2
    finally:
        s.close()


def test_a_reader_behind_a_held_pool_lock_shows_the_wait(served):
    import threading

    host, pool = served.host, _pool(served)
    _post(host, 'SetBit(rowID=2, frame="f", columnID=2000)', trace=False)
    trees = []

    def reader():
        trees.append(_post(host, _PAIRS)[1])

    with pool.mu:
        threads = [threading.Thread(target=reader) for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.4)  # both have found the state stale and queue for the lock
        t_hold = time.perf_counter()
        time.sleep(0.15)
        held_ms = (time.perf_counter() - t_hold) * 1e3
    for t in threads:
        t.join(60)
    assert len(trees) == 2
    repairs = [_find(t, "serve.repair")[0] for t in trees]
    for rep in repairs:
        (wait,) = _find(rep, "pool.lock_wait")
        assert wait["ms"] >= held_ms
        assert rep["ms"] >= wait["ms"]
    # One of the two repaired; the other found it done when the lock came.
    assert sorted(r["tags"]["repaired"] for r in repairs) == [False, True]
    idle = next(r for r in repairs if not r["tags"]["repaired"])
    assert [c["name"] for c in idle["children"]] == ["pool.lock_wait"]
    for t in trees:  # waiting holds no core
        assert t["ms"] - t["tags"]["cpu_ms"] >= held_ms


@pytest.mark.parametrize("body,lane,changed", [
    ('SetBit(rowID=3, frame="f", columnID=3000)', "write_fast", 1),
    ('SetBit(rowID=3, frame="f", columnID=3000)', "write_fast", 0),
    ('SetBit(rowID=3, frame="f", columnID=3001) SetBit(rowID=3, frame="f", columnID=3002)',
     "write_native", 2),
    ('ClearBit(rowID=3, frame="f", columnID=3001)', "write_fast", 1),
])
def test_a_write_has_write_apply(served, body, lane, changed):
    _res, root = _post(served.host, body)
    assert root["tags"]["lane"] == lane
    (wsp,) = _find(root, "write.apply")
    assert wsp["tags"]["changed"] == changed
    assert _fits(root, 0.0, root["ms"])
    assert not _find(root, "serve.validate")  # a read's span


def test_an_unsampled_request_builds_no_span_and_no_annotation(served, monkeypatch):
    from pilosa_tpu import trace as trace_mod

    made = {"span": 0, "annotation": 0, "reply": 0}
    real_init = Span.__init__

    def counting_init(self, *a, **kw):
        made["span"] += 1
        real_init(self, *a, **kw)

    def counting_annotation(name):
        if name != "interp.gc":   # a collection may fall anywhere: not a request's doing
            made["reply" if name == "door.reply" else "annotation"] += 1
        return None

    monkeypatch.setattr(Span, "__init__", counting_init)
    monkeypatch.setattr(trace_mod, "_open_annotation", counting_annotation)
    host = served.host
    _post(host, 'SetBit(rowID=0, frame="f", columnID=4000)', trace=False)
    res, tree = _post(host, _PAIRS, trace=False)  # repairs, then serves
    _post(host, _PAIRS, trace=False)
    assert len(res) == 6 and tree is None
    assert made == {"span": 0, "annotation": 0, "reply": 0}
    # The same three sampled: every span opens one annotation, but for
    # door.read, which is made after the fact; and each traced response
    # goes out under door.reply, an annotation with no span.
    _post(host, 'SetBit(rowID=0, frame="f", columnID=4001)')
    _res, tree = _post(host, _PAIRS)
    assert made["span"] > 10
    assert made["span"] - made["annotation"] == 2  # two requests' door.read
    assert made["reply"] == 2


def test_spans_are_annotations_while_open(monkeypatch):
    from pilosa_tpu import trace as trace_mod

    log = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            if self.name != "interp.gc":   # a collection may fall anywhere
                log.append(("enter", self.name))

        def __exit__(self, *exc):
            if self.name != "interp.gc":
                log.append(("exit", self.name))

    monkeypatch.setattr(trace_mod, "_annotation_cls", Ann)
    root = Span("root")
    a = root.child("a")
    a.finish()
    a.finish()  # idempotent: leaves once
    root.record("before", root.t0 - 0.002, root.t0 - 0.001)
    root.finish()
    assert log == [("enter", "root"), ("enter", "a"), ("exit", "a"), ("exit", "root")]
    monkeypatch.setattr(trace_mod, "_annotation_cls", False)  # no jax: spans alone
    assert Span("x").finish().ms is not None


def test_root_starts_at_the_doors_stamp_and_record_backdates():
    tr = Tracer(sample_rate=1.0)
    t0 = time.perf_counter() - 0.010
    trace = tr.begin({}, name="POST /x", t0=t0)
    sp = trace.root.record("door.read", t0, t0 + 0.004)
    trace.root.finish()
    assert trace.root.ms >= 10.0
    js = trace.root.to_json()
    assert js["children"][0] == {"name": "door.read", "start_ms": 0.0, "ms": 4.0}
    assert sp.ms == pytest.approx(4.0)
    # An unsampled-but-slow request synthesizes a root that opens nothing.
    assert Trace("slow", annotate=False).root._ann is None


def test_forced_trace_ids_are_distinct_without_uuid():
    import sys

    tr = Tracer()
    ids = {tr.begin({TRACE_HEADER.lower(): "1"}, name="r").id for _ in range(10000)}
    assert len(ids) == 10000
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids)
    assert len({i[:8] for i in ids}) == 1  # one process, one prefix
    assert "uuid" not in vars(sys.modules["pilosa_tpu.trace"])


def test_cost_ledger_and_slow_stages_read_a_tree_with_the_new_children(caplog):
    from pilosa_tpu.costs import CostLedger

    ledger = CostLedger()
    tr = Tracer(sample_rate=1.0, slow_ms=0.0001, costs=ledger)
    trace = tr.begin({}, name="POST /index/i/query")
    root = trace.root
    root.tags.update(index="i", frame="f", lane="flat")
    root.record("door.read", root.t0, root.t0 + 0.001)
    root.child("serve.validate").finish()
    rep = root.child("serve.repair")
    rep.child("pool.lock_wait").finish()
    prep = rep.child("pool.repair")
    for name in ("pool.fetch", "pool.scatter", "pool.gram"):
        prep.child(name).finish()
    prep.finish()
    rep.finish()
    dev = root.child("device")
    dev.ms = 0.7
    dev.tags.update(lane="native", bytes=437)
    wsp = root.child("write.apply")
    nested = wsp.child("device")  # the write lane's crossing nests one deeper
    nested.ms = 0.3
    nested.tags.update(lane="native", bytes=40)
    wsp.finish()
    root.child("encode").finish()
    with caplog.at_level(logging.WARNING, logger="pilosa_tpu.slowquery"):
        tr.finish_request(trace, name=root.name, dt_ms=5.0, body=b"Count(x)")
    (entry,) = ledger.entries()
    assert entry["lane"] == "flat" and entry["frame"] == "f"
    assert entry["ewma_device_ms"] == pytest.approx(1.0)  # both crossings, each once
    rec = json.loads(caplog.records[-1].getMessage().split("slow-query ", 1)[1])
    assert set(rec["stages"]) == {"door.read", "serve.validate", "serve.repair", "device",
                                  "write.apply", "encode"}
    assert rec["stages"]["device"] == 0.7 and rec["stages"]["door.read"] == 1.0


def test_pool_and_engine_counters_reach_debug_vars(served):
    import urllib.request

    host = served.host
    snap0 = json.loads(urllib.request.urlopen(f"http://{host}/debug/vars", timeout=30).read())
    _post(host, 'SetBit(rowID=1, frame="f", columnID=5000)', trace=False)
    _post(host, _PAIRS, trace=False)
    snap = json.loads(urllib.request.urlopen(f"http://{host}/debug/vars", timeout=30).read())
    pool = _pool(served)
    assert snap["rowpool.misses"] == pool.stat_misses == 4
    assert snap["rowpool.repairs"] == pool.stat_repairs == snap0["rowpool.repairs"] + 1
    assert snap["rowpool.patch_planes"] == pool.stat_patch_planes
    assert snap["engine.upload_bytes"] == served.executor.engine.stat_upload_bytes
    assert snap["engine.upload_bytes"] - snap0["engine.upload_bytes"] >= 131072
    metrics = urllib.request.urlopen(f"http://{host}/metrics", timeout=30).read().decode()
    assert "rowpool_repairs" in metrics and "engine_upload_bytes" in metrics
    # A traced response's reply is timed; an untraced one's is not.
    def replies():
        typed = served.handler.stats.snapshot_typed()["timings"]
        return typed.get("http.reply_ms", {"count": 0})["count"]

    n0 = replies()
    _post(host, _PAIRS)
    _post(host, _PAIRS, trace=False)
    assert replies() == n0 + 1
    snap = json.loads(urllib.request.urlopen(f"http://{host}/debug/vars", timeout=30).read())
    assert 0 < snap["http.reply_ms.avg_ms"] < 1000
    # The process's full collections, as gauges at scrape time.
    assert snap["gc.full_collections"] == served.handler.tracer.gc_watch.collections
    assert "gc_full_pause_ms" in metrics


def test_a_cold_read_through_the_coalescing_queue_owns_its_pool_spans(tmp_path):
    """The unarmed flat lane hands its arrays to the serve queue; the
    shared pass's pool spans go to the group's first sampled request."""
    import urllib.request

    from pilosa_tpu.server.client import Client
    from pilosa_tpu.server.server import Server

    s = Server(Config(data_dir=str(tmp_path / "cold"), host="127.0.0.1:0", engine="jax"))
    s.open()
    try:
        c = Client(s.host)
        c.create_index("i")
        c.create_frame("i", "f")
        c.execute_query("i", " ".join(
            f'SetBit(rowID={r}, frame="f", columnID={r + 7})' for r in range(4)))
        _res, root = _post(s.host, _PAIRS)
        assert root["tags"]["coalesced"] == 1
        (miss,) = _find(root, "pool.miss")
        assert miss["tags"]["rows"] == 4 and miss["tags"]["evicted"] == 0
        # one bit a row: the chunk went up as four (slice, slot, word) cells and their values
        assert (miss["tags"]["sparse"], miss["tags"]["words"]) == (1, 4)
        assert miss["tags"]["upload_bytes"] == 4 * 16
        assert _find(root, "pool.lock_wait")
        # The coalescer's wait and pass: alone in its batch, this thread ran it.
        (queued,), (ran,) = _find(root, "serve.queue"), _find(root, "serve.pass")
        assert ran["tags"] == {"leader": True, "batch": 1}
        assert queued["start_ms"] + queued["ms"] == pytest.approx(ran["start_ms"], abs=0.002)
        assert _fits(miss, ran["start_ms"], ran["start_ms"] + ran["ms"])
        # One chip: the host's wait for each dispatch's counts is a span inside the pass.
        fetches = _find(root, "device.fetch")
        assert fetches and all(_fits(f, ran["start_ms"], ran["start_ms"] + ran["ms"])
                               for f in fetches)
        snap = json.loads(urllib.request.urlopen(f"http://{s.host}/debug/vars", timeout=30).read())
        assert snap["gather.fetches"] == len(fetches) and "gather.mesh_fetches" not in snap
    finally:
        s.close()


def test_pool_spans_and_counters_for_paging_refresh_and_eviction():
    """The pool alone, on the numpy engine: a miss pages rows in
    (``pool.miss``), a write with no journal takes the blind refresh
    (``pool.refresh``), a full pool evicts; each count also goes out
    through the stats client where it is incremented."""
    import numpy as np

    from pilosa_tpu.engine import NumpyEngine
    from pilosa_tpu.rowpool import DeviceRowPool

    class Counts:
        def __init__(self):
            self.n = {}

        def count(self, name, value=1):
            self.n[name] = self.n.get(name, 0) + value

        def gauge(self, name, value):
            self.g = dict(getattr(self, "g", {}), **{name: value})

    def fetch(row_ids, slice_idxs):
        return np.zeros((len(slice_idxs), len(row_ids), 8), dtype=np.uint32)

    stats = Counts()
    pool = DeviceRowPool(NumpyEngine(), 2, 8, fetch, cap_max=2, stats=stats)
    root = Span("root")
    pool.acquire([1, 2], (0, 0), span=root)
    pool.acquire([1, 2], (0, 1), span=root)            # slice 1 written, delta unknown
    pool.acquire([3], (0, 1), span=root)               # full: row 1 goes
    pool.acquire([3], (0, 1))                          # unsampled: no span
    names = [c.name for c in root.children]
    assert names == ["pool.lock_wait", "pool.miss", "pool.lock_wait", "pool.refresh",
                     "pool.lock_wait", "pool.miss"]
    assert all(c.ms is not None for c in root.children)
    # a pool built with ``fetch`` alone pages dense: no chunk went sparse, no words shipped
    assert root.children[1].tags == {"rows": 2, "bucket": 2, "evicted": 0, "sparse": 0,
                                     "words": 0, "upload_bytes": 0, "devices": 1}
    assert [c.name for c in root.children[1].children] == ["pool.miss.fetch", "pool.miss.scatter"]
    assert root.children[3].tags == {"rows": 2, "upload_bytes": 0}
    assert root.children[5].tags == {"rows": 1, "bucket": 1, "evicted": 1, "sparse": 0,
                                     "words": 0, "upload_bytes": 0, "devices": 1}
    # two block sizes met (the numpy engine's are the chunks' row counts themselves)
    assert stats.n == {"rowpool.misses": 3, "rowpool.evictions": 1, "rowpool.miss_buckets": 2,
                       "rowpool.miss_chunks_dense": 2}
    # what the pool was budgeted when it took device memory: the default per device, its own cap
    assert stats.g == {"rowpool.budget_bytes_per_device": 2 << 30, "rowpool.capacity_slots": 2}
    assert (pool.stat_misses, pool.stat_evictions, pool.stat_repairs) == (3, 1, 0)
    pool._reset()
    assert stats.n["rowpool.resets"] == pool.stat_resets == 1


# -- the read coalescer's wait and pass, the one-chip wait, the collector -------


def test_a_follower_of_a_coalesced_pass_holds_the_queues_wait_and_the_pass():
    """Three sampled requests: the first runs alone and blocks; the two that
    arrive meanwhile wait for it, then share one batch that one of them runs."""
    import threading

    from pilosa_tpu.ingest import WriteQueue

    hold, entered = threading.Event(), threading.Event()

    def apply(items):
        if items == ["first"]:
            entered.set()
            assert hold.wait(30)
        return [it.upper() for it in items]

    q = WriteQueue(apply)
    roots = {name: Span(name) for name in ("first", "b", "c")}
    out = {}

    def go(name):
        out[name] = q.submit(name, span=roots[name])

    threads = [threading.Thread(target=go, args=(n,)) for n in ("first", "b", "c")]
    threads[0].start()
    assert entered.wait(30)
    threads[1].start()
    threads[2].start()
    deadline = time.monotonic() + 30
    while len(q._items) < 2 and time.monotonic() < deadline:
        time.sleep(0.001)
    time.sleep(0.02)   # what the two wait for: the batch ahead of theirs
    hold.set()
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    assert out == {"first": "FIRST", "b": "B", "c": "C"} and q.stat_batches == 2
    for root in roots.values():
        assert [c.name for c in root.children] == ["serve.queue", "serve.pass"]
        assert all(c.ms is not None and c._ann is None for c in root.children)  # after the fact
        queued, ran = root.children
        assert queued.t0 + queued.ms / 1e3 == pytest.approx(ran.t0)
    assert roots["first"].children[1].tags == {"leader": True, "batch": 1}
    assert roots["first"].children[0].ms < 15.0
    # One pass for the two: the same stamps, one leader, and a wait behind the first's batch.
    (qb, pb), (qc, pc) = roots["b"].children, roots["c"].children
    assert (pb.t0, pb.ms) == (pc.t0, pc.ms) and pb.tags["batch"] == pc.tags["batch"] == 2
    assert sorted([pb.tags["leader"], pc.tags["leader"]]) == [False, True]
    assert qb.ms >= 15.0 and qc.ms >= 15.0
    assert pb.t0 >= roots["first"].children[1].t0 + roots["first"].children[1].ms / 1e3


def test_submit_without_a_span_reads_no_clock(monkeypatch):
    from pilosa_tpu import ingest

    class Clock:
        reads = 0

        def perf_counter(self):
            self.reads += 1
            return time.perf_counter()

    clock = Clock()
    monkeypatch.setattr(ingest, "time", clock)
    q = ingest.WriteQueue(lambda items: [i + 1 for i in items])
    assert [q.submit(i) for i in range(3)] == [1, 2, 3] and clock.reads == 0
    root = Span("root")
    assert q.submit(7, span=root) == 8
    assert clock.reads == 3  # the append, the batch's start, its end
    with pytest.raises(ValueError):   # a batch that fails still closes its spans
        ingest.WriteQueue(lambda items: [ValueError("no")]).submit(0, span=root)
    assert [c.name for c in root.children] == ["serve.queue", "serve.pass"] * 2


def test_the_jax_engines_fetch_is_a_span_only_under_one(monkeypatch):
    import numpy as np

    from pilosa_tpu.engine import JaxEngine

    eng = JaxEngine()
    x = eng.asarray(np.arange(6, dtype=np.uint32))
    made = []
    real_init = Span.__init__
    monkeypatch.setattr(Span, "__init__",
                        lambda self, *a, **kw: (made.append(a[0]), real_init(self, *a, **kw))[1])
    assert eng.to_numpy(x).tolist() == [0, 1, 2, 3, 4, 5] and made == []
    root = Span("root")
    assert eng.to_numpy(x, root).tolist() == [0, 1, 2, 3, 4, 5]
    assert made == ["root", "device.fetch"]
    (sp,) = root.children
    assert sp.name == "device.fetch" and sp.ms is not None and sp._ann is None


def test_a_full_collection_inside_a_traced_request_is_its_interp_gc_child():
    import gc

    from pilosa_tpu import trace as trace_mod

    watch = trace_mod.gc_watch()
    assert trace_mod.gc_watch() is watch and gc.callbacks.count(watch) == 1
    tr = trace_mod.from_config(Config())       # the server's: a second tracer adds no entry
    assert tr.gc_watch is watch and gc.callbacks.count(watch) == 1
    assert Tracer().gc_watch is None
    was = gc.isenabled()
    gc.disable()     # no collection but the ones made here
    try:
        n0, ms0 = watch.collections, watch.pause_ms
        before = tr.begin({TRACE_HEADER.lower(): "1"}, name="POST /a")
        tr.finish_request(before, name="POST /a", dt_ms=0.1)
        trace = tr.begin({TRACE_HEADER.lower(): "1"}, name="POST /b")
        t_in = time.perf_counter()
        gc.collect(0)
        gc.collect(1)
        assert (watch.collections, watch.pause_ms) == (n0, ms0)   # young generations: nothing
        gc.collect()
        t_out = time.perf_counter()
        other = tr.begin({TRACE_HEADER.lower(): "1"}, name="POST /c")  # began after the pause
        extra = tr.finish_request(trace, name="POST /b", dt_ms=1.0)
        tr.finish_request(other, name="POST /c", dt_ms=0.1)
    finally:
        if was:
            gc.enable()
    assert watch.collections == n0 + 1 and watch.pause_ms > ms0
    (pause,) = [c for c in trace.root.children if c.name == "interp.gc"]
    assert t_in <= pause.t0 and pause.t0 + pause.ms / 1e3 <= t_out and pause._ann is None
    assert pause.tags == {"collected": pause.tags["collected"], "t0_s": round(pause.t0, 6)}
    assert pause.tags["collected"] >= 0
    assert not before.root.children and not other.root.children
    (child,) = json.loads(extra[TRACE_SPANS_HEADER])[0]["children"]
    assert child["name"] == "interp.gc" and child["tags"]["t0_s"] == pause.tags["t0_s"]
    assert watch.overlapping(t_out + 1.0, t_out + 2.0) == []
    assert len(watch.pauses) <= watch.KEPT


def test_an_unsampled_root_has_no_t0_s_and_no_span(served, monkeypatch):
    made = []
    real_init = Span.__init__
    monkeypatch.setattr(Span, "__init__",
                        lambda self, *a, **kw: (made.append(a[0]), real_init(self, *a, **kw))[1])
    out = served.handler.dispatch("POST", "/index/i/query", {}, _PAIRS.encode(), {},
                                  taken=(time.perf_counter(), time.thread_time()))
    assert out[0] == 200 and made == []
    assert TRACE_SPANS_HEADER not in (out[3] if len(out) > 3 else {})
    out = served.handler.dispatch("POST", "/index/i/query", {}, _PAIRS.encode(),
                                  {TRACE_HEADER.lower(): "1"},
                                  taken=(time.perf_counter(), time.thread_time()))
    root = json.loads(out[3][TRACE_SPANS_HEADER])[0]
    assert made[0] == "POST /index/i/query" and isinstance(root["tags"]["t0_s"], float)
