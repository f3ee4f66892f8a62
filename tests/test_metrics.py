"""PR 14 observability plane: Prometheus exposition at /metrics, the
cluster-wide fleet view at /debug/fleet, and the trace-derived
per-fingerprint cost ledger at /debug/costs.

The invariants pinned:

- The registry -> Prometheus name mapping is MECHANICAL (prom_name), so
  /metrics covers every series the stats client holds — asserted here
  by diffing the exposition's families against snapshot_typed().
- parse_exposition is STRICT (the bench preflight's contract): any
  malformed line raises with its line number.
- /debug/fleet over a 3-group cluster with one group DOWN serves a
  PARTIAL aggregate: the dead group stays present with an error and a
  staleness stamp, the survivors scrape live.
- The cost ledger folds recorded traces into bounded EWMA entries
  keyed (index, frame, fingerprint, lane); /debug/costs serves them
  cost-descending.
- ?min-ms=/?limit= on the debug endpoints CLAMP malformed values
  instead of answering 400.
- Spans and slow-query log lines carry qos_class + tenant tags.
"""

import json
import logging
import tempfile
import threading
import urllib.error
import urllib.request

import pytest

from pilosa_tpu import metrics
from pilosa_tpu.config import Config
from pilosa_tpu.costs import CostLedger, DispatchMeter
from pilosa_tpu.stats import NOP_STATS, ExpvarStatsClient
from pilosa_tpu.trace import Trace, Tracer, fingerprint


# -- name mapping -------------------------------------------------------------


def test_prom_name_mechanical_mapping():
    assert metrics.prom_name("qcache.hit", "counter") == "pilosa_qcache_hit_total"
    assert metrics.prom_name("qos.latency_ms.read") == "pilosa_qos_latency_ms_read"
    # Registry placeholder segments stay valid names for the drift gate.
    assert metrics.valid_metric_name(metrics.prom_name("engine.dispatch_ms.<lane>"))
    assert metrics.prom_name("replica.healthy.g-0") == "pilosa_replica_healthy_g_0"


def test_split_key_tags_to_labels():
    assert metrics.split_key("index.query") == ("index.query", {})
    base, labels = metrics.split_key("index.query[index:foo,frame:f]")
    assert base == "index.query"
    assert labels == {"index": "foo", "frame": "f"}
    # A bare tag with no colon becomes a `tag` label.
    assert metrics.split_key("x[solo]")[1] == {"tag": "solo"}


def test_registry_collisions_invalid_and_colliding():
    # Clean set: no findings.
    assert metrics.registry_collisions({"a.b": "counter", "c.d": "gauge"}) == []
    # Two distinct series mangling onto one name (the _total rename).
    bad = metrics.registry_collisions({"a.b": "counter", "a.b.total": "gauge"})
    assert bad and bad[0][2] == "pilosa_a_b_total"
    # A name that mangles to nothing is invalid.
    assert metrics.registry_collisions({"!!!": "gauge"})[0][1] == ""


def test_clamp_float_and_int():
    assert metrics.clamp_float("2.5", 0.0) == 2.5
    assert metrics.clamp_float("bogus", 0.0) == 0.0
    assert metrics.clamp_float(None, 7.0) == 7.0
    assert metrics.clamp_float("nan", 3.0) == 3.0
    assert metrics.clamp_float("-4", 0.0) == 0.0  # lo clamp
    assert metrics.clamp_int("12", 64) == 12
    assert metrics.clamp_int("junk", 64) == 64
    assert metrics.clamp_int("-3", 64) == 0
    assert metrics.clamp_int("1e99", 64) == 1 << 30  # hi clamp


# -- render + strict parse ----------------------------------------------------


def _loaded_client() -> ExpvarStatsClient:
    c = ExpvarStatsClient()
    c.count("index.query", 3)
    c.with_tags("index:foo").count("index.query", 2)
    c.gauge("replica.wal_bytes", 123)
    c.set("node.state", "up")
    for v in (1.0, 2.0, 50.0):
        c.histogram("qos.latency_ms.read", v)
    c.timing("snapshot", 0.25)
    return c


def test_render_covers_every_series_and_parses():
    c = _loaded_client()
    text = metrics.render(c)
    fams = metrics.parse_exposition(text)
    # MECHANICAL coverage: every series the client holds appears as a
    # family in the exposition under its prom_name.
    typed = c.snapshot_typed()
    for key in typed["counters"]:
        base, _ = metrics.split_key(key)
        assert metrics.prom_name(base, "counter") in fams, (key, fams)
    for kind in ("gauges", "sets"):
        for key in typed[kind]:
            base, _ = metrics.split_key(key)
            assert metrics.prom_name(base) in fams, key
    for key in typed["histograms"]:
        base, _ = metrics.split_key(key)
        assert fams[metrics.prom_name(base)]["type"] == "summary"
    for key in typed["timings"]:
        base, _ = metrics.split_key(key)
        assert fams[metrics.prom_name(base) + "_seconds"]["type"] == "summary"
    # Tagged counter rendered with labels; summary carries its quantile
    # rows plus _count/_sum (5 samples toward the base family).
    assert 'pilosa_index_query_total{index="foo"} 2' in text
    assert fams["pilosa_qos_latency_ms_read"]["samples"] == 5
    assert 'pilosa_node_state{value="up"} 1' in text


def test_render_nop_stats_is_empty_valid_exposition():
    assert metrics.render(NOP_STATS) == ""
    assert metrics.parse_exposition("") == {}


def test_parse_exposition_rejects_malformed():
    with pytest.raises(ValueError, match="line 1"):
        metrics.parse_exposition("not a metric line!")
    with pytest.raises(ValueError, match="malformed TYPE"):
        metrics.parse_exposition("# TYPE pilosa_x")
    with pytest.raises(ValueError, match="bad sample value"):
        metrics.parse_exposition("pilosa_x twelve")
    with pytest.raises(ValueError, match="malformed labels"):
        metrics.parse_exposition('pilosa_x{a=unquoted} 1')
    # Label values holding commas/spaces inside the quotes are legal.
    fams = metrics.parse_exposition('pilosa_x{a="b, c d",e="f"} 1')
    assert fams["pilosa_x"]["samples"] == 1


# -- cost ledger --------------------------------------------------------------


def test_cost_ledger_ewma_and_lru_eviction():
    stats = ExpvarStatsClient()
    led = CostLedger(cap=2, alpha=0.5, stats=stats)
    led.observe(index="i", fp="a", lane="gram", ms=10.0, bytes_moved=1_000_000)
    led.observe(index="i", fp="a", lane="gram", ms=20.0)
    led.observe(index="i", fp="b", lane="gather", ms=5.0)
    e = led.snapshot()["entries"][0]
    assert e["fp"] == "a" and e["n"] == 2
    assert e["ewma_ms"] == pytest.approx(15.0)  # 10 + 0.5*(20-10)
    # Transfer-free second hit did not decay the bandwidth estimate.
    assert e["ewma_mbps"] == pytest.approx(100.0)  # 1 MB in 10 ms
    # Third key over cap=2 evicts the least-recently-touched ("a" was
    # last touched before "b" was inserted).
    led.observe(index="i", fp="c", lane="flat", ms=1.0)
    fps = {x["fp"] for x in led.snapshot()["entries"]}
    assert fps == {"b", "c"} and len(led) == 2
    snap = stats.snapshot()
    assert snap["costs.fold"] == 4 and snap["costs.evict"] == 1
    assert snap["costs.entries"] == 2


def test_cost_ledger_folds_device_spans_from_trace():
    led = CostLedger()
    tr = Trace("POST /index/foo/query")
    tr.root.tags.update({"tenant": "foo", "lane": "flat", "frame": "f"})
    d = tr.root.child("device")
    d.ms = 2.0
    d.tags.update({"lane": "flat", "bytes": 4096})
    led.fold(tr, dt_ms=9.0, body=b'Count(Bitmap(rowID=1, frame="f"))')
    e = led.snapshot()["entries"][0]
    assert (e["index"], e["frame"], e["lane"]) == ("foo", "f", "flat")
    assert e["fp"] and e["ewma_ms"] == 9.0 and e["ewma_device_ms"] == 2.0
    assert e["ewma_mbps"] > 0


def test_ledger_lru_eviction_under_fingerprint_churn():
    led = CostLedger(cap=8)
    for i in range(100):
        led.observe(index="i", fp=f"fp{i}", lane="gram", ms=1.0, wall_ts=0.0)
    assert len(led) == 8
    assert led.peek(index="i", fp="fp0", lane="gram") is None
    assert led.peek(index="i", fp="fp99", lane="gram") is not None
    # observe() bumps recency; peek() is a pure read and must NOT.
    led.observe(index="i", fp="fp92", lane="gram", ms=1.0, wall_ts=0.0)
    led.peek(index="i", fp="fp93", lane="gram")
    for i in range(100, 106):
        led.observe(index="i", fp=f"fp{i}", lane="gram", ms=1.0, wall_ts=0.0)
    assert led.peek(index="i", fp="fp92", lane="gram") is not None
    assert led.peek(index="i", fp="fp93", lane="gram") is None
    assert led.peek(index="i", fp="fp99", lane="gram") is not None


def test_ledger_ewma_fold_deterministic_across_state_restore():
    obs = [
        (f"fp{i % 5}", ("gram", "gather")[i % 2], 1.0 + 0.37 * i, 1000 * i)
        for i in range(40)
    ]
    a = CostLedger(cap=16, alpha=0.25)
    for fp, lane, ms, b in obs[:20]:
        a.observe(index="i", fp=fp, lane=lane, ms=ms, bytes_moved=b, wall_ts=1.0)
    b2 = CostLedger()
    b2.restore(a.state())
    assert b2.cap == 16 and b2.alpha == 0.25
    # Folding the same tail into the restored ledger yields
    # bit-identical state — EWMA folds carry no hidden host state.
    for fp, lane, ms, by in obs[20:]:
        a.observe(index="i", fp=fp, lane=lane, ms=ms, bytes_moved=by, wall_ts=2.0)
        b2.observe(index="i", fp=fp, lane=lane, ms=ms, bytes_moved=by, wall_ts=2.0)
    assert a.state() == b2.state()


def test_ledger_snapshot_consistent_under_concurrent_folds():
    led = CostLedger(cap=32)
    errors = []
    done = threading.Event()

    def folder(tid):
        try:
            for i in range(400):
                led.observe(index="i", fp=f"fp{tid}-{i % 40}", lane="gram",
                            ms=1.0 + (i % 7), wall_ts=0.0)
        except Exception as exc:  # pragma: no cover - failure path
            errors.append(exc)

    threads = [threading.Thread(target=folder, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    # Read every public surface while folds churn the LRU.
    for _ in range(200):
        snap = led.snapshot(limit=16)
        assert len(snap["entries"]) <= 16
        for e in snap["entries"]:
            assert e["n"] >= 1 and e["ewma_ms"] > 0
        assert len(led.entries()) <= 32
        st = led.state()
        assert len(st["entries"]) <= 32
    for t in threads:
        t.join()
    done.set()
    assert not errors
    assert len(led) <= 32


def test_dispatch_meter_emits_tagged_series_and_device_span():
    class FakeEngine:
        stat_upload_bytes = 0

    stats = ExpvarStatsClient()
    eng = FakeEngine()
    meter = DispatchMeter(stats, engine=eng)
    tr = Trace("q")
    with meter.measure("stream", tr.root) as m:
        eng.stat_upload_bytes += 1 << 20  # the upload-ledger delta
        m.add_bytes(512)
    snap = stats.snapshot()
    assert snap["engine.dispatch_ms.stream"]["count"] == 1
    assert snap["engine.dispatch_bytes.stream"] == (1 << 20) + 512
    dev = tr.root.children[0]
    assert dev.name == "device" and dev.tags["lane"] == "stream"
    assert dev.tags["bytes"] == (1 << 20) + 512 and dev.ms >= 0
    meter.resident(123456)
    assert stats.snapshot()["engine.hbm_bytes"] == 123456


# -- server integration: /metrics, /debug/costs, clamp, span tags -------------


@pytest.fixture
def server(tmp_path):
    from pilosa_tpu.server.server import Server

    cfg = Config(
        data_dir=str(tmp_path / "d"), host="127.0.0.1:0", engine="numpy",
        stats="expvar", trace_sample_rate=1.0,
    )
    s = Server(cfg)
    s.open()
    try:
        yield s
    finally:
        s.close()


def _get(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read(), dict(resp.headers)


def _post(url, body, headers=None, timeout=30):
    rq = urllib.request.Request(url, data=body, method="POST")
    for k, v in (headers or {}).items():
        rq.add_header(k, v)
    with urllib.request.urlopen(rq, timeout=timeout) as resp:
        return resp.status, resp.read()


def test_server_metrics_endpoint_valid_and_complete(server):
    s = server
    base = f"http://{s.host}"
    _post(base + "/index/i", b"{}")
    _post(base + "/index/i/frame/f", b"{}")
    _post(base + "/index/i/query", b'SetBit(rowID=1, frame="f", columnID=2)')
    _post(base + "/index/i/query", b'Count(Bitmap(rowID=1, frame="f"))')
    st, body, hdrs = _get(base + "/metrics")
    assert st == 200
    assert hdrs["Content-Type"].startswith("text/plain; version=0.0.4")
    fams = metrics.parse_exposition(body.decode())
    assert fams, "server exposition is empty after serving requests"
    # Every series emitted during the run is covered by the exposition.
    typed = s.stats.snapshot_typed()
    kinds = [("counters", "counter"), ("gauges", ""), ("sets", "")]
    for field, kind in kinds:
        for key in typed[field]:
            base_name, _ = metrics.split_key(key)
            assert metrics.prom_name(base_name, kind) in fams, key
    for key in typed["histograms"]:
        base_name, _ = metrics.split_key(key)
        assert metrics.prom_name(base_name) in fams, key
    # The QoS door's latency histogram made it through as a summary.
    assert fams["pilosa_qos_latency_ms_read"]["type"] == "summary"


def test_server_debug_costs_per_fingerprint_lanes(server):
    s = server
    base = f"http://{s.host}"
    _post(base + "/index/i", b"{}")
    _post(base + "/index/i/frame/f", b"{}")
    _post(base + "/index/i/query", b'SetBit(rowID=1, frame="f", columnID=2)')
    q = b'Count(Bitmap(rowID=1, frame="f"))'
    for _ in range(3):
        _post(base + "/index/i/query", q)
    st, body, _ = _get(base + "/debug/costs")
    assert st == 200
    out = json.loads(body)
    assert out["cap"] > 0 and out["entries"]
    # The repeated Count folded under ONE fingerprint, tagged with the
    # tenant index.  The ledger keys by lane too, and a body need not
    # take one lane every time (a query-cache hit is answered before
    # any strategy lane runs), so the three sends may split over
    # entries: they sum to 3 and each names its lane.
    fp = fingerprint(q)["fp"]
    counts = [e for e in out["entries"] if e["index"] == "i" and e["fp"] == fp]
    assert sum(e["n"] for e in counts) == 3, out["entries"]
    assert all(e["lane"] and e["ewma_ms"] > 0 for e in counts)
    # ?limit= caps the payload (and clamps malformed values).
    st, body, _ = _get(base + "/debug/costs?limit=1")
    assert len(json.loads(body)["entries"]) == 1
    st, body, _ = _get(base + "/debug/costs?limit=bogus")
    assert st == 200


def test_debug_traces_clamps_malformed_filters(server):
    s = server
    base = f"http://{s.host}"
    _post(base + "/index/i", b"{}")
    _post(base + "/index/i/frame/f", b"{}")
    _post(base + "/index/i/query", b'Count(Bitmap(rowID=1, frame="f"))')
    # Malformed/out-of-range values clamp to defaults — never 400.
    for qs in ("?min-ms=bogus", "?min-ms=nan", "?limit=-5", "?min-ms=&limit="):
        st, body, _ = _get(base + "/debug/traces" + qs)
        assert st == 200, qs
        json.loads(body)
    # Valid filters still filter, newest-first.
    for t in s.tracer.traces_json():
        pass
    st, body, _ = _get(base + "/debug/traces?min-ms=999999")
    assert json.loads(body)["traces"] == []
    st, body, _ = _get(base + "/debug/traces?limit=1")
    traces = json.loads(body)["traces"]
    assert len(traces) <= 1
    all_traces = json.loads(_get(base + "/debug/traces")[1])["traces"]
    if len(all_traces) > 1:
        assert all_traces[0]["ms"] is not None  # newest first entry intact
        assert traces[0]["name"] == all_traces[0]["name"]


def test_span_and_slowlog_carry_qos_class_and_tenant(server, caplog):
    s = server
    base = f"http://{s.host}"
    _post(base + "/index/i", b"{}")
    _post(base + "/index/i/frame/f", b"{}")
    _post(base + "/index/i/query", b'Count(Bitmap(rowID=1, frame="f"))')
    entry = s.tracer.traces_json(limit=1)[0]
    tags = entry["spans"]["tags"]
    assert tags["qos_class"] == "read" and tags["tenant"] == "i"
    # Slow-query bypass: unsampled request over slow-ms synthesizes a
    # root-only trace and exactly ONE structured log line, both carrying
    # the QoS class + tenant tags.
    s.tracer.sample_rate = 0.0
    s.tracer.slow_ms = 1e-6
    before = len(s.tracer)
    with caplog.at_level(logging.WARNING, logger="pilosa_tpu.slowquery"):
        _post(base + "/index/i/query", b'Count(Bitmap(rowID=1, frame="f"))')
    slow = [r for r in caplog.records if r.name == "pilosa_tpu.slowquery"]
    assert len(slow) == 1, "expected exactly one slow-query line"
    rec = json.loads(slow[0].message.split("slow-query ", 1)[1])
    assert rec["tags"]["qos_class"] == "read" and rec["tags"]["tenant"] == "i"
    assert len(s.tracer) == before + 1
    entry = s.tracer.traces_json(limit=1)[0]
    assert entry["slow"] and entry["spans"]["tags"]["unsampled"] is True
    assert entry["spans"]["tags"]["qos_class"] == "read"
    assert "children" not in entry["spans"]  # root-only: synthesized late


# -- fleet view ---------------------------------------------------------------


class _FleetRig:
    """Three in-process group servers + a router (the test_replica rig
    shape, sized for the fleet view)."""

    def __init__(self, tmp, n_groups=3):
        from pilosa_tpu.replica import ReplicaRouter
        from pilosa_tpu.server.server import Server

        self.servers = []
        for i in range(n_groups):
            cfg = Config(
                data_dir=f"{tmp}/g{i}", host="127.0.0.1:0", engine="numpy",
                stats="expvar", qcache_enabled=False, replica_group=f"g{i}",
            )
            srv = Server(cfg)
            srv.open()
            self.servers.append(srv)
        self.stats = ExpvarStatsClient()
        self.router = ReplicaRouter(
            [f"g{i}={srv.host}" for i, srv in enumerate(self.servers)],
            probe_interval_s=0.1, stats=self.stats,
            tracer=Tracer(sample_rate=1.0),
        ).serve()
        self.base = f"http://127.0.0.1:{self.router.port}"
        self.closed = set()

    def close(self):
        self.router.close()
        for i, s in enumerate(self.servers):
            if i not in self.closed:
                s.close()

    def kill(self, i):
        self.servers[i].close()
        self.closed.add(i)


@pytest.fixture
def fleet():
    with tempfile.TemporaryDirectory() as tmp:
        r = _FleetRig(tmp)
        try:
            yield r
        finally:
            r.close()


def test_fleet_aggregates_and_degrades_partially(fleet):
    base = fleet.base
    _post(base + "/index/i", b"{}")
    _post(base + "/index/i/frame/f", b"{}")
    _post(base + "/index/i/query", b'SetBit(rowID=1, frame="f", columnID=1)')
    _post(base + "/index/i/query", b'Count(Bitmap(rowID=1, frame="f"))')
    st, body, _ = _get(base + "/debug/fleet")
    assert st == 200
    fl = json.loads(body)
    assert fl["partial"] is False and len(fl["groups"]) == 3
    assert fl["quorum"] == 2 and fl["quorate"] is True
    # 3 sequenced mutations (2 schema + 1 SetBit); the Count is a read.
    assert fl["wal"]["lastSeq"] == fl["writeSeq"] == 3
    for g in fl["groups"]:
        assert g["staleScrape"] is False and g["ageMs"] is not None
        assert g["scrape"]["health"]["group"] == g["name"]
        assert g["scrape"]["appliedSeq"] == 3 and g["walDepth"] == 0
        # Latency percentiles surfaced from the group's QoS histograms
        # (every group saw the fanned-out writes at minimum).
        assert "write" in g["scrape"]["latencyMs"]
        assert g["scrape"]["latencyMs"]["write"]["p50"] >= 0
    # The one group that served the read carries its read percentiles.
    assert any("read" in g["scrape"]["latencyMs"] for g in fl["groups"])
    # Router-side progress counters ride along.
    assert fl["routerStats"]["replica.write_fanout"] == 3
    # Kill one group: the aggregate degrades to PARTIAL — the dead
    # group stays present, stamped stale with its error and the LAST
    # SUCCESSFUL scrape (aged), while the survivors scrape live.
    fleet.kill(2)
    st, body, _ = _get(base + "/debug/fleet?timeout-ms=200")
    fl = json.loads(body)
    assert st == 200 and fl["partial"] is True
    dead = next(g for g in fl["groups"] if g["name"] == "g2")
    assert dead["staleScrape"] is True and dead["error"]
    assert dead["scrape"] is not None  # cached from the earlier scrape
    assert dead["ageMs"] >= 0
    live = [g for g in fl["groups"] if g["name"] != "g2"]
    assert all(not g["staleScrape"] for g in live)
    # The router's own exposition stays scrapeable throughout.
    st, body, hdrs = _get(base + "/metrics")
    assert st == 200
    fams = metrics.parse_exposition(body.decode())
    assert "pilosa_replica_write_fanout_total" in fams


def test_router_debug_traces_clamp(fleet):
    base = fleet.base
    _post(base + "/index/i", b"{}")
    st, body, _ = _get(base + "/debug/traces?min-ms=bogus&limit=junk")
    assert st == 200
    json.loads(body)
