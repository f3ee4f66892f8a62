"""HTTP API + server tests (reference analogs: handler_test.go,
server/server_test.go — real in-process servers on ephemeral ports)."""

import json
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.config import ClusterConfig, Config
from pilosa_tpu.server.client import Client, ClientError
from pilosa_tpu.server.server import Server
from pilosa_tpu.pilosa import SLICE_WIDTH


def make_server(tmp_path, name="s0", **cfg_kwargs):
    cfg = Config(data_dir=str(tmp_path / name), host="127.0.0.1:0", engine="numpy", **cfg_kwargs)
    s = Server(cfg)
    s.open()
    return s


@pytest.fixture
def srv(tmp_path):
    s = make_server(tmp_path)
    yield s
    s.close()


@pytest.fixture
def client(srv):
    return Client(srv.host)


def test_version_hosts_status(client):
    assert client.version().startswith("0.")
    assert client.status()["state"] == "UP"
    assert len(client.hosts()) == 1


def test_index_frame_lifecycle(client):
    client.create_index("i", {"columnLabel": "col"})
    client.create_frame("i", "f", {"rowLabel": "row", "inverseEnabled": True})
    schema = client.schema()
    assert schema[0]["name"] == "i"
    assert schema[0]["frames"][0]["name"] == "f"
    with pytest.raises(ClientError) as e:
        client.create_index("i")
    assert e.value.status == 409
    client.delete_frame("i", "f")
    client.delete_index("i")
    assert client.schema() == []


def test_query_json_and_protobuf(srv, client):
    client.create_index("i")
    client.create_frame("i", "f")
    # protobuf query path
    resp = client.execute_query("i", 'SetBit(rowID=1, frame="f", columnID=100)')
    assert resp["results"][0]["changed"] is True
    resp = client.execute_query("i", 'Bitmap(rowID=1, frame="f")')
    assert resp["results"][0]["bitmap"]["bits"] == [100]
    # JSON query path
    req = urllib.request.Request(
        f"http://{srv.host}/index/i/query",
        data=b'Count(Bitmap(rowID=1, frame="f"))',
        method="POST",
    )
    body = json.loads(urllib.request.urlopen(req).read())
    assert body == {"results": [1]}


def test_query_column_attrs(client):
    client.create_index("i")
    client.create_frame("i", "f")
    client.execute_query("i", 'SetBit(rowID=1, frame="f", columnID=7)')
    client.execute_query("i", 'SetColumnAttrs(columnID=7, tag="x")')
    resp = client.execute_query("i", 'Bitmap(rowID=1, frame="f")', column_attrs=True)
    assert resp["columnAttrSets"] == [{"id": 7, "attrs": {"tag": "x"}}]


def test_query_errors(srv, client):
    client.create_index("i")
    with pytest.raises(ClientError):
        client.execute_query("i", "Bogus(")
    # GET on query endpoint → 405
    req = urllib.request.Request(f"http://{srv.host}/index/i/query", method="GET")
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req)
    assert e.value.code == 405


def test_import_and_export(client):
    client.create_index("i")
    client.create_frame("i", "f")
    bits = [(1, 10), (1, SLICE_WIDTH + 3), (2, 20)]
    client.import_bits("i", "f", bits)
    resp = client.execute_query("i", 'Bitmap(rowID=1, frame="f")')
    assert resp["results"][0]["bitmap"]["bits"] == [10, SLICE_WIDTH + 3]
    csv0 = client.export_csv("i", "f", "standard", 0)
    assert "1,10" in csv0 and "2,20" in csv0
    csv1 = client.export_csv("i", "f", "standard", 1)
    assert f"1,{SLICE_WIDTH + 3}" in csv1


def test_slices_max_and_views(client):
    client.create_index("i")
    client.create_frame("i", "f", {"timeQuantum": "YM"})
    client.execute_query(
        "i", f'SetBit(rowID=1, frame="f", columnID={2 * SLICE_WIDTH}, timestamp="2017-05-01T00:00")'
    )
    assert client.max_slices() == {"i": 2}
    views = client.frame_views("i", "f")
    assert "standard" in views and "standard_2017" in views


def test_fragment_data_roundtrip_and_blocks(client):
    client.create_index("i")
    client.create_frame("i", "f")
    client.execute_query("i", 'SetBit(rowID=1, frame="f", columnID=3)')
    client.execute_query("i", 'SetBit(rowID=150, frame="f", columnID=9)')
    blocks = client.fragment_blocks("i", "f", "standard", 0)
    assert [b for b, _ in blocks] == [0, 1]
    rows, cols = client.block_data("i", "f", "standard", 0, 1)
    assert rows.tolist() == [150] and cols.tolist() == [9]
    data = client.fragment_data("i", "f", "standard", 0)
    assert data[:4] == (12346).to_bytes(4, "little")
    # restore into a fresh frame
    client.create_frame("i", "g")
    client.restore_fragment("i", "g", "standard", 0, data)
    resp = client.execute_query("i", 'Bitmap(rowID=150, frame="g")')
    assert resp["results"][0]["bitmap"]["bits"] == [9]


def test_attr_diff_endpoints(client):
    client.create_index("i")
    client.create_frame("i", "f")
    client.execute_query("i", 'SetRowAttrs(rowID=5, frame="f", name="x")')
    client.execute_query("i", 'SetColumnAttrs(columnID=2, tag="y")')
    # empty local blocks → server returns everything it has
    assert client.row_attr_diff("i", "f", []) == {5: {"name": "x"}}
    assert client.column_attr_diff("i", []) == {2: {"tag": "y"}}


def test_persistence_across_restart(tmp_path):
    s = make_server(tmp_path, "p")
    c = Client(s.host)
    c.create_index("i")
    c.create_frame("i", "f")
    c.execute_query("i", 'SetBit(rowID=1, frame="f", columnID=42)')
    s.close()
    s2 = make_server(tmp_path, "p")
    c2 = Client(s2.host)
    resp = c2.execute_query("i", 'Bitmap(rowID=1, frame="f")')
    assert resp["results"][0]["bitmap"]["bits"] == [42]
    s2.close()


def test_two_node_cluster_distributed_query(tmp_path):
    """Two real servers; fan-out + reduce across both (executor_test.go
    TestExecutor_Execute_Remote_* analog with real processes)."""
    # Start both on fixed free ports so the shared host list is consistent.
    import socket

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    p0, p1 = free_port(), free_port()
    hosts = [f"127.0.0.1:{p0}", f"127.0.0.1:{p1}"]
    servers = []
    for i, p in enumerate((p0, p1)):
        cfg = Config(
            data_dir=str(tmp_path / f"n{i}"),
            host=hosts[i],
            engine="numpy",
            cluster=ClusterConfig(type="static", hosts=list(hosts)),
        )
        s = Server(cfg)
        s.open()
        servers.append(s)
    try:
        c0, c1 = Client(hosts[0]), Client(hosts[1])
        # schema must exist on both nodes (static cluster: no broadcast)
        for c in (c0, c1):
            c.create_index("i")
            c.create_frame("i", "f")
        # import routes each slice to its owner; set bits across 4 slices
        bits = [(1, s * SLICE_WIDTH + 7) for s in range(4)]
        cluster = servers[0].cluster
        c0.import_bits("i", "f", bits, fragment_nodes=cluster.fragment_nodes)
        # force both nodes to know the global max slice
        servers[0]._monitor_max_slices()
        servers[1]._monitor_max_slices()
        resp = c0.execute_query("i", 'Count(Bitmap(rowID=1, frame="f"))')
        assert resp["results"][0]["n"] == 4
        resp = c1.execute_query("i", 'Bitmap(rowID=1, frame="f")')
        assert resp["results"][0]["bitmap"]["bits"] == [s * SLICE_WIDTH + 7 for s in range(4)]
        # distributed write: send SetBit to the non-owner; it must forward
        owner = cluster.fragment_nodes("i", 0)[0].host
        non_owner = hosts[1] if owner == hosts[0] else hosts[0]
        resp = Client(non_owner).execute_query("i", 'SetBit(rowID=9, frame="f", columnID=1)')
        assert resp["results"][0]["changed"] is True
        resp = Client(owner).execute_query("i", 'Count(Bitmap(rowID=9, frame="f"))')
        assert resp["results"][0]["n"] == 1
    finally:
        for s in servers:
            s.close()


def test_two_node_cluster_qcache_invalidation(tmp_path):
    """qcache in a multi-node HTTP cluster: a write to a REMOTELY-owned
    slice must be visible through the coordinator's very next read.
    Cluster writes apply only on slice-owner nodes, so the coordinator's
    local generation vector can never see them — coordinator-scope
    results are therefore never cached (counted ineligible); only each
    node's remote sub-requests are, and those invalidate locally."""
    import socket

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    p0, p1 = free_port(), free_port()
    hosts = [f"127.0.0.1:{p0}", f"127.0.0.1:{p1}"]
    servers = []
    for i, p in enumerate((p0, p1)):
        cfg = Config(
            data_dir=str(tmp_path / f"n{i}"),
            host=hosts[i],
            engine="numpy",
            cluster=ClusterConfig(type="static", hosts=list(hosts)),
            # Admit every eligible result: any unsafely-keyed entry
            # WOULD be stored and served, so staleness can't hide
            # behind cost-based admission.
            qcache_min_cost_ms=0.0,
        )
        s = Server(cfg)
        s.open()
        servers.append(s)
    try:
        c0, c1 = Client(hosts[0]), Client(hosts[1])
        for c in (c0, c1):
            c.create_index("i")
            c.create_frame("i", "f")
        bits = [(1, s * SLICE_WIDTH + 7) for s in range(4)]
        cluster = servers[0].cluster
        c0.import_bits("i", "f", bits, fragment_nodes=cluster.fragment_nodes)
        servers[0]._monitor_max_slices()
        servers[1]._monitor_max_slices()

        q = 'Count(Bitmap(rowID=1, frame="f"))'
        assert c0.execute_query("i", q)["results"][0]["n"] == 4
        assert c0.execute_query("i", q)["results"][0]["n"] == 4
        # The coordinator never cached its global answers.
        assert servers[0].qcache.stores == 0
        assert servers[0].qcache.ineligible >= 2

        # Write a NEW bit into a slice node 0 does NOT own: the
        # coordinator only forwards it, so no local generation moves —
        # exactly the write a coordinator-scope cache entry would miss.
        remote_slice = next(
            s for s in range(4)
            if all(n.host != hosts[0] for n in cluster.fragment_nodes("i", s))
        )
        col = remote_slice * SLICE_WIDTH + 99
        r = c0.execute_query("i", f'SetBit(rowID=1, frame="f", columnID={col})')
        assert r["results"][0]["changed"] is True
        # Read-your-writes THROUGH the coordinator, immediately.
        assert c0.execute_query("i", q)["results"][0]["n"] == 5
        # And through the other node too (it owns the written slice).
        assert c1.execute_query("i", q)["results"][0]["n"] == 5

        # Per-node remote sub-requests DID use the cache: the repeated
        # coordinator reads hit on the peer's remote-scope entries.
        assert (servers[0].qcache.hits + servers[1].qcache.hits) > 0
    finally:
        for s in servers:
            s.close()


def test_debug_traces_and_slow_query_log(tmp_path):
    """Tracing end to end through a real server: sampled requests land
    in /debug/traces (newest-first, min-ms filterable) with executor
    stage spans, requests past [trace] slow-ms emit one structured
    slow-query log line, and the X-Pilosa-Trace force override samples
    even at rate 0."""
    import logging

    s = make_server(
        tmp_path, name="tr0",
        trace_sample_rate=1.0, trace_slow_ms=0.0001, qcache_min_cost_ms=0.0,
    )
    records = []
    h = logging.Handler()
    h.emit = lambda rec: records.append(rec.getMessage())
    logging.getLogger("pilosa_tpu.slowquery").addHandler(h)
    try:
        c = Client(s.host)
        c.create_index("i")
        c.create_frame("i", "f")
        c.execute_query("i", 'SetBit(rowID=1, frame="f", columnID=3)')
        q = 'Count(Bitmap(rowID=1, frame="f"))'
        c.execute_query("i", q)  # miss
        c.execute_query("i", q)  # hit

        with urllib.request.urlopen(f"http://{s.host}/debug/traces", timeout=30) as r:
            traces = json.loads(r.read())["traces"]
        assert traces, "sampled requests never reached the ring"
        # Newest-first: the LAST query (the cache hit) leads.
        query_traces = [t for t in traces if t["name"].endswith("/index/i/query")]
        assert len(query_traces) >= 3
        hit = query_traces[0]
        assert hit["ms"] > 0 and hit["spans"]["tags"]["status"] == 200
        assert hit["spans"]["tags"]["qcache"] == "hit"
        names = [c_["name"] for c_ in hit["spans"]["children"]]
        assert "qos.admit" in names and "qcache.lookup" in names
        # The miss before it carried the execution stages.
        miss = query_traces[1]
        assert miss["spans"]["tags"]["qcache"] == "deferred"  # a miss on a never-seen string
        # min-ms filter: an impossible floor returns nothing.
        with urllib.request.urlopen(
            f"http://{s.host}/debug/traces?min-ms=1e9", timeout=30
        ) as r:
            assert json.loads(r.read())["traces"] == []

        # Slow-query log: slow-ms is microscopic, so every request
        # logged — structured JSON with fingerprint + stage breakdown.
        assert records, "no slow-query log lines emitted"
        recs = [json.loads(r.split("slow-query ", 1)[1]) for r in records]
        qrecs = [r for r in recs if r["name"].endswith("/index/i/query")]
        assert qrecs, recs
        rec = qrecs[-1]
        assert rec["ms"] > 0 and rec["fp"] and rec["trace_id"]
        assert "Count(" in rec["snippet"]
        # The miss's breakdown attributed the execution: the compiled
        # serve lane (lane=flat) times its single native crossing as a
        # "device" stage; the general lane emits fused/per-call spans.
        miss_rec = next(r for r in qrecs if r["tags"].get("qcache") == "deferred")
        if miss_rec["tags"].get("lane") == "flat":
            assert "device" in miss_rec["stages"]
        else:
            assert "call.Count" in miss_rec["stages"] or "fused" in miss_rec["stages"]

        # Force override: a zero-rate tracer still samples on demand.
        s.tracer.sample_rate = 0.0
        before = len(s.tracer.traces_json(limit=1000))
        req = urllib.request.Request(
            f"http://{s.host}/index/i/query", data=q.encode(), method="POST"
        )
        urllib.request.urlopen(req, timeout=30).read()  # unsampled
        req.add_header("X-Pilosa-Trace", "1")
        urllib.request.urlopen(req, timeout=30).read()  # forced
        after = s.tracer.traces_json(limit=1000)
        # The unsampled request appears only if slow (root-only); the
        # forced one definitely appears with forced=True.
        assert any(t["forced"] for t in after[: len(after) - before])
        # /debug/vars carries the tracer counters.
        snap = json.loads(
            urllib.request.urlopen(f"http://{s.host}/debug/vars", timeout=30).read()
        )
        assert snap.get("trace.sampled", 0) >= 3
        assert snap.get("trace.slow", 0) >= 1
    finally:
        logging.getLogger("pilosa_tpu.slowquery").removeHandler(h)
        s.close()


def test_two_node_cluster_trace_remote_subspans(tmp_path):
    """Cross-node propagation: a force-traced coordinator query fans out
    to the peer with the trace id in X-Pilosa-Trace; the peer's span
    tree comes back in X-Pilosa-Trace-Spans and lands grafted under the
    coordinator's remote span — ONE trace shows both sides of the hop."""
    import socket

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    hosts = [f"127.0.0.1:{free_port()}" for _ in range(2)]
    servers = []
    for i, h in enumerate(hosts):
        cfg = Config(
            data_dir=str(tmp_path / f"n{i}"),
            host=h,
            engine="numpy",
            cluster=ClusterConfig(type="static", hosts=list(hosts)),
        )
        s = Server(cfg)
        s.open()
        servers.append(s)
    try:
        c0 = Client(hosts[0])
        for c in (c0, Client(hosts[1])):
            c.create_index("i")
            c.create_frame("i", "f")
        bits = [(1, s * SLICE_WIDTH + 7) for s in range(4)]
        cluster = servers[0].cluster
        c0.import_bits("i", "f", bits, fragment_nodes=cluster.fragment_nodes)
        servers[0]._monitor_max_slices()
        servers[1]._monitor_max_slices()

        req = urllib.request.Request(
            f"http://{hosts[0]}/index/i/query",
            data=b'Count(Bitmap(rowID=1, frame="f"))',
            method="POST",
        )
        req.add_header("X-Pilosa-Trace", "1")
        resp = urllib.request.urlopen(req, timeout=60)
        assert json.loads(resp.read())["results"] == [4]
        # The coordinator returned its own span tree too (propagation).
        assert resp.headers.get("X-Pilosa-Trace-Spans")

        traces = servers[0].tracer.traces_json(limit=10)
        tr = next(t for t in traces if t["name"].endswith("/index/i/query"))

        def walk(span, out):
            out.append(span)
            for ch in span.get("children", []):
                walk(ch, out)
            return out

        spans = walk(tr["spans"], [])
        remotes = [sp for sp in spans if sp["name"] == "remote"]
        assert remotes, f"no remote hop span in {tr}"
        assert remotes[0]["tags"]["host"] == hosts[1]
        # The peer's own root span (its handler door) was grafted under
        # the hop — with the same trace id having forced it.
        peer_roots = [
            sp for sp in spans if sp["name"].startswith("POST /index/i/query")
            and sp is not tr["spans"]
        ]
        assert peer_roots, f"peer sub-spans missing from {tr}"
        # And the peer recorded the hop under the SAME trace id.
        peer_traces = servers[1].tracer.traces_json(limit=10)
        assert any(t["id"] == tr["id"] for t in peer_traces)
    finally:
        for s in servers:
            s.close()


def test_webui_served_to_browsers(srv):
    """`/` serves the console to Accept: text/html clients and the plain
    banner to API clients; /assets/* serves the bundle (handler.go:132-145)."""
    def get(path, accept=None):
        req = urllib.request.Request(f"http://{srv.host}{path}")
        if accept:
            req.add_header("Accept", accept)
        with urllib.request.urlopen(req, timeout=5) as r:
            return r.status, r.headers.get("Content-Type", ""), r.read()

    st, ct, body = get("/", accept="text/html,application/xhtml+xml")
    assert st == 200 and ct.startswith("text/html")
    assert b"pilosa-tpu console" in body

    st, ct, body = get("/")
    assert st == 200 and ct.startswith("text/plain")

    st, ct, body = get("/assets/main.js")
    assert st == 200 and ct == "application/javascript" and b"runQuery" in body
    st, ct, body = get("/assets/style.css")
    assert st == 200 and ct == "text/css"

    with pytest.raises(urllib.error.HTTPError) as e:
        get("/assets/nope.js")
    assert e.value.code == 404
    # path traversal is rejected, not served
    with pytest.raises(urllib.error.HTTPError) as e:
        get("/assets/..%2Findex.html")
    assert e.value.code == 404


def test_profile_endpoints(client):
    """JAX trace start/stop round trip (aux tracing subsystem)."""
    status, body = client._request("POST", "/debug/profile/start")
    if status == 500:
        pytest.skip("jax profiler unavailable in this environment")
    assert status == 200 and b"tracing" in body
    # double start conflicts
    status2, _ = client._request("POST", "/debug/profile/start")
    assert status2 == 409
    status3, body3 = client._request("POST", "/debug/profile/stop")
    assert status3 == 200 and b"written" in body3
    status4, _ = client._request("POST", "/debug/profile/stop")
    assert status4 == 409


def test_profile_door_traces_the_device_and_the_spans_not_python(client, monkeypatch):
    """The door hands jax a ProfileOptions with the Python tracer off (the
    host tracer stays at its default, which keeps annotations)."""
    import jax

    seen = {}

    def start_trace(log_dir, *a, profiler_options=None, **kw):
        seen["dir"], seen["options"] = log_dir, profiler_options

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: seen.setdefault("stopped", True))
    status, _ = client._request("POST", "/debug/profile/start")
    assert status == 200
    assert isinstance(seen["options"], jax.profiler.ProfileOptions)
    assert seen["options"].python_tracer_level == 0
    assert seen["options"].host_tracer_level == jax.profiler.ProfileOptions().host_tracer_level
    status, _ = client._request("POST", "/debug/profile/stop")
    assert status == 200 and seen["stopped"]


def test_profile_holds_the_programs_spans_on_the_traces_clock(tmp_path):
    """A traced request between start and stop leaves host events named
    after its spans in the .xplane.pb, between the door's two markers,
    and no Python frame."""
    import glob

    from jax.profiler import ProfileData

    s = Server(Config(data_dir=str(tmp_path / "prof"), host="127.0.0.1:0", engine="jax"))
    s.open()
    try:
        c = Client(s.host)
        c.create_index("i")
        c.create_frame("i", "f")
        for r in range(3):
            c.execute_query("i", " ".join(
                f'SetBit(rowID={r}, frame="f", columnID={k * 3 + r})' for k in range(20)))
        q = " ".join(
            f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
            for a, b in ((0, 1), (0, 2), (1, 2)))

        def post(body, trace):
            req = urllib.request.Request(
                f"http://{s.host}/index/i/query", data=body.encode(), method="POST")
            req.add_header("X-Pilosa-No-Cache", "1")
            if trace:
                req.add_header("X-Pilosa-Trace", "1")
            return urllib.request.urlopen(req, timeout=120).read()

        for _ in range(4):  # rows resident, Gram built, serve state armed
            post(q, trace=False)
        trace_dir = str(tmp_path / "xplane")
        status, _ = c._request("POST", f"/debug/profile/start?dir={trace_dir}")
        if status == 500:
            pytest.skip("jax profiler unavailable in this environment")
        post('SetBit(rowID=1, frame="f", columnID=900)', trace=True)
        post(q, trace=True)     # finds the state stale: repairs under the pool's lock
        post(q, trace=False)    # an unsampled request leaves nothing
        status, _ = c._request("POST", "/debug/profile/stop")
        assert status == 200
        (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
        events = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name == "/host:CPU":
                for line in plane.lines:
                    events += [(ev.name, ev.start_ns, ev.duration_ns) for ev in line.events]
        names = [n for n, _, _ in events]
        for want in ("profile_door start_trace", "profile_door stop_trace", "POST /index/i/query",
                     "serve.repair", "pool.lock_wait", "pool.repair", "pool.gram", "write.apply",
                     "device", "encode"):
            assert want in names, (want, sorted(set(names))[:40])
        assert names.count("profile_door start_trace") == names.count("profile_door stop_trace") == 1
        assert names.count("encode") == 2 and "door.read" not in names
        assert not any(".py:" in n or n.startswith("$") for n in names)  # no Python frame
        at = {n: (t0, t0 + d) for n, t0, d in events}
        lo, hi = at["profile_door start_trace"][1], at["profile_door stop_trace"][0]
        assert lo <= at["pool.repair"][0] <= at["pool.repair"][1] <= hi
        # The stages sit inside the repair on the trace's clock too.
        assert at["pool.repair"][0] <= at["pool.gram"][0] <= at["pool.gram"][1] <= at["pool.repair"][1]
    finally:
        s.close()


def test_set_quick_property(tmp_path):
    """Full-stack property test (server_test.go:42-121 TestMain_Set_Quick):
    random SetBits over HTTP, Bitmap() must match a model dict, and state
    must survive a restart."""
    rng = np.random.default_rng(1234)
    s = make_server(tmp_path)
    try:
        c = Client(s.host)
        c.create_index("q")
        c.create_frame("q", "f")
        model: dict[int, set[int]] = {}
        for _ in range(120):
            row = int(rng.integers(0, 5))
            col = int(rng.integers(0, 3 * SLICE_WIDTH))
            resp = c.execute_query("q", f'SetBit(rowID={row}, frame="f", columnID={col})')
            changed = resp["results"][0]["changed"]
            assert changed == (col not in model.setdefault(row, set()))
            model[row].add(col)
        for row, cols in model.items():
            resp = c.execute_query("q", f'Bitmap(rowID={row}, frame="f")')
            assert resp["results"][0]["bitmap"]["bits"] == sorted(cols)
    finally:
        s.close()
    # restart on the same data dir; all bits must come back
    s2 = make_server(tmp_path)
    try:
        c2 = Client(s2.host)
        for row, cols in model.items():
            resp = c2.execute_query("q", f'Bitmap(rowID={row}, frame="f")')
            assert resp["results"][0]["bitmap"]["bits"] == sorted(cols)
    finally:
        s2.close()


def test_stats_wired_through_data_path(tmp_path):
    """Counters flow holder->index->frame->view->fragment with tags and
    surface at /debug/vars (stats.go + holder.go:113/252, fragment.go:410)."""
    s = make_server(tmp_path, name="stats0")
    try:
        c = Client(s.host)
        c.create_index("st")
        c.create_frame("st", "f")
        c.execute_query("st", 'SetBit(rowID=1, frame="f", columnID=5) '
                              'SetBit(rowID=1, frame="f", columnID=6)')
        c.execute_query("st", 'ClearBit(rowID=1, frame="f", columnID=6)')
        with urllib.request.urlopen(f"http://{s.host}/debug/vars") as resp:
            vars_ = json.loads(resp.read())
        flat = json.dumps(vars_)
        assert "indexN" in flat
        assert "setN" in flat and "clearN" in flat
        assert "index:st" in flat and "frame:f" in flat  # tag propagation
    finally:
        s.close()


def test_two_node_fused_batch_query(tmp_path):
    """A batch of Count(pair-op) calls against a 2-node cluster runs
    through the distributed fused path (one forwarded batch per node) and
    matches per-call execution."""
    import socket

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    hosts = [f"127.0.0.1:{free_port()}" for _ in range(2)]
    servers = []
    for i, h in enumerate(hosts):
        cfg = Config(
            data_dir=str(tmp_path / f"n{i}"),
            host=h,
            engine="numpy",
            cluster=ClusterConfig(type="static", hosts=list(hosts)),
        )
        s = Server(cfg)
        s.open()
        servers.append(s)
    try:
        c0 = Client(hosts[0])
        for c in (c0, Client(hosts[1])):
            c.create_index("i")
            c.create_frame("i", "f")
        cluster = servers[0].cluster
        rng = np.random.default_rng(9)
        bits = []
        for r in range(4):
            for s_i in range(4):
                for c_i in rng.choice(1000, size=40, replace=False):
                    bits.append((r, s_i * SLICE_WIDTH + int(c_i)))
        c0.import_bits("i", "f", bits, fragment_nodes=cluster.fragment_nodes)
        servers[0]._monitor_max_slices()
        servers[1]._monitor_max_slices()

        combos = [("Intersect", 0, 1), ("Union", 1, 2), ("Difference", 2, 3), ("Xor", 0, 3)]
        batch = " ".join(
            f'Count({op}(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
            for op, a, b in combos
        )
        fused = c0.execute_query("i", batch)["results"]
        singles = [
            c0.execute_query(
                "i", f'Count({op}(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
            )["results"][0]
            for op, a, b in combos
        ]
        assert fused == singles
        # Both nodes agree (the batch coordinated from node 1 too).
        assert Client(hosts[1]).execute_query("i", batch)["results"] == fused
    finally:
        for s in servers:
            s.close()


def test_status_merge_skips_bad_items(tmp_path):
    """A peer-advertised frame with invalid options (e.g. persisted by an
    older node) must not abort the rest of the status merge."""
    s = make_server(tmp_path, name="m0")
    try:
        indexes = [
            {"name": "a", "meta": {}, "maxSlice": 3,
             "frames": [{"name": "bad", "meta": {"cacheType": "bogus"}},
                        {"name": "good", "meta": {}}]},
            {"name": "b", "meta": {}, "maxSlice": 1, "frames": []},
        ]
        from pilosa_tpu import wire

        s.handle_remote_status(wire.encode_node_status(s.host, "UP", indexes))
        # bad frame skipped; everything after it still merged.
        assert s.holder.index("a") is not None
        assert s.holder.index("a").frame("bad") is None
        assert s.holder.index("a").frame("good") is not None
        assert s.holder.index("a").max_slice() == 3
        assert s.holder.index("b") is not None
    finally:
        s.close()


def test_status_merge_survives_malformed_items(tmp_path):
    """Structurally-malformed peer items (missing keys, wrong types — a
    different-version peer) are skipped per item, not merge-aborting."""
    s = make_server(tmp_path, name="mm0")
    try:
        indexes = [
            {"name": "a", "meta": {}, "maxSlice": 0,
             "frames": [{"meta": {}},                      # no "name"
                        {"name": "ok", "meta": {}}]},
            {"name": "b", "meta": {}, "maxSlice": 2, "frames": []},
        ]
        from pilosa_tpu import wire

        s.handle_remote_status(wire.encode_node_status(s.host, "UP", indexes))
        assert s.holder.index("a") is not None
        assert s.holder.index("a").frame("ok") is not None
        assert s.holder.index("b") is not None
        assert s.holder.index("b").max_slice() == 2
    finally:
        s.close()


def test_http_surface_survives_garbage(srv, client):
    """Random paths/methods/bodies must yield clean HTTP errors, never
    kill the server or leak tracebacks as responses."""
    import random
    import urllib.error

    rng = random.Random(5)
    client.create_index("z")
    client.create_frame("z", "f")
    paths = [
        "/", "/index", "/index/", "/index/%ff", "/index/z/query", "/index/z/frame/f",
        "/schema", "/status", "/fragment/data?index=z&frame=f&view=standard&slice=0",
        "/fragment/data?index=z&frame=f&view=standard&slice=notanumber",
        "/fragment/nodes?index=z", "/fragment/nodes", "/export", "/nope/deep/path",
        "/index/z/query?slices=a,b", "/debug/vars", "/index/z/time-quantum",
    ]
    bodies = [b"", b"\x00\x01\x02" * 40, b"{", b'{"options": 5}', b"Count(", b"A" * 5000,
              bytes(rng.randrange(256) for _ in range(64))]
    for _ in range(120):
        path = rng.choice(paths)
        method = rng.choice(["GET", "POST", "DELETE", "PATCH", "PUT"])
        body = rng.choice(bodies) if method in ("POST", "PATCH", "PUT") else None
        req = urllib.request.Request(f"http://{srv.host}{path}", data=body, method=method)
        if rng.random() < 0.3:
            req.add_header("Content-Type", "application/x-protobuf")
            req.add_header("Accept", "application/x-protobuf")
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                resp.read()
        except urllib.error.HTTPError as e:
            assert 400 <= e.code < 600
            e.read()
        except urllib.error.URLError as e:  # pragma: no cover
            raise AssertionError(f"server died on {method} {path}: {e}")
    # Server is still fully functional afterwards.
    assert client.status()["state"] == "UP"
    resp = client.execute_query("z", 'SetBit(rowID=1, frame="f", columnID=1)')
    assert resp["results"][0]["changed"] is True


def test_json_and_protobuf_codecs_agree(srv, client):
    """The same query answered over JSON and protobuf negotiation must
    carry identical data (handler.go content-negotiation parity)."""
    client.create_index("cp")
    client.create_frame("cp", "f", {"cacheType": "ranked"})
    bits = [(r, c) for r in range(3) for c in range(r, 40 + r)]
    client.import_bits("cp", "f", bits)
    client.execute_query("cp", 'SetRowAttrs(rowID=1, frame="f", name="x", n=3)')
    queries = [
        'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f")))',
        'Bitmap(rowID=1, frame="f")',
        'TopN(frame="f", n=2)',
        'Union(Bitmap(rowID=0, frame="f"), Bitmap(rowID=2, frame="f"))',
    ]
    for q in queries:
        pb = client.execute_query("cp", q)  # protobuf path
        req = urllib.request.Request(
            f"http://{srv.host}/index/cp/query", data=q.encode(), method="POST"
        )
        js = json.loads(urllib.request.urlopen(req).read())  # JSON path

        def norm(results):
            out = []
            for r in results:
                if isinstance(r, dict) and "bitmap" in r:
                    out.append(("bm", tuple(r["bitmap"]["bits"]),
                                tuple(sorted(r["bitmap"].get("attrs", {}).items()))))
                elif isinstance(r, dict) and "pairs" in r:
                    out.append(("pairs", tuple((p["id"], p["count"]) for p in r["pairs"])))
                elif isinstance(r, dict) and "n" in r:
                    out.append(("n", r["n"]))
                elif isinstance(r, dict) and "attrs" in r and "bits" in r:
                    out.append(("bm", tuple(r["bits"]), tuple(sorted(r["attrs"].items()))))
                elif isinstance(r, list):
                    out.append(("pairs", tuple((p["id"], p["count"]) for p in r)))
                elif isinstance(r, int):
                    out.append(("n", r))  # JSON carries counts as numbers
                else:
                    out.append(("v", r))
            return out

        assert norm(pb["results"]) == norm(js["results"]), q


def test_crash_durability_sigkill(tmp_path):
    """Acknowledged single-bit writes survive a SIGKILL: each SetBit's
    WAL record reaches the kernel (unbuffered append) before the HTTP
    response, so a crashed server replays them on reopen
    (roaring.go:590-611 + fragment.go WAL semantics)."""
    import os
    import signal
    import socket
    import subprocess
    import sys

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    data_dir = str(tmp_path / "crash")
    port = free_port()
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PILOSA_TPU_ENGINE"] = "numpy"
    proc = subprocess.Popen(
        [sys.executable, "-m", "pilosa_tpu.cli", "server",
         "--data-dir", data_dir, "--host", f"127.0.0.1:{port}"],
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
        cwd=repo,
        env=env,
    )
    try:
        deadline = time.monotonic() + 60
        c = Client(f"127.0.0.1:{port}")
        while True:
            try:
                c.create_index("i")
                break
            except OSError:
                assert time.monotonic() < deadline, "server never came up"
                time.sleep(0.2)
        c.create_frame("i", "f")
        # Individual SetBits: each is one durable WAL append (no snapshot
        # for most of them), including time-view and inverse fan-out.
        rng = np.random.default_rng(3)
        cols = sorted(set(rng.integers(0, 2 * SLICE_WIDTH, size=120).tolist()))
        for col in cols:
            resp = c.execute_query("i", f'SetBit(rowID=5, frame="f", columnID={col})')
            assert resp["results"] in ([True], [{"changed": True}])
        # Hard kill: no close(), no flush hooks, no snapshot.
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()

    # Reopen the same data dir in-process: WAL replay must restore every
    # acknowledged bit.
    s2 = Server(Config(data_dir=data_dir, host="127.0.0.1:0", engine="numpy"))
    s2.open()
    try:
        c2 = Client(s2.host)
        got = c2.execute_query("i", 'Bitmap(rowID=5, frame="f")')
        assert got["results"][0]["bitmap"]["bits"] == cols
    finally:
        s2.close()


def test_pprof_proto_endpoints(srv):
    """/debug/pprof serves REAL pprof payloads (gzipped profile.proto,
    handler.go:99 net/http/pprof semantics): goroutine-analog thread
    profile, sampling CPU profile, text form at ?debug=1.  Structure
    validated by decoding the protobuf with the wire codec (the encoder
    was additionally cross-checked against a protoc-compiled official
    parser when authored)."""
    import gzip
    import threading
    import time as time_mod

    from pilosa_tpu import wire

    stop = threading.Event()

    def busy():  # a sampleable workload thread
        while not stop.wait(0.001):
            sum(range(200))

    t = threading.Thread(target=busy, name="busy-worker", daemon=True)
    t.start()
    try:
        def get(path):
            with urllib.request.urlopen(f"http://{srv.host}{path}", timeout=30) as r:
                return r.status, r.read()

        def parse_profile(body):
            raw = gzip.decompress(body)  # gzip magic implied
            strings, sample_types, samples, locs, fns = [], [], [], {}, {}
            for f, w, v in wire.iter_fields(raw):
                if f == 6:
                    strings.append(v.decode())
                elif f == 1:
                    d = dict((f2, v2) for f2, _, v2 in wire.iter_fields(v))
                    sample_types.append((d.get(1, 0), d.get(2, 0)))
                elif f == 2:
                    d = {}
                    for f2, _, v2 in wire.iter_fields(v):
                        d[f2] = wire.decode_packed_uint64(v2)
                    samples.append(d)
                elif f == 4:
                    d = dict((f2, v2) for f2, _, v2 in wire.iter_fields(v))
                    locs[d[1]] = d
                elif f == 5:
                    d = dict((f2, v2) for f2, _, v2 in wire.iter_fields(v))
                    fns[d[1]] = d
            return strings, sample_types, samples, locs, fns

        st, body = get("/debug/pprof/goroutine")
        assert st == 200 and body[:2] == b"\x1f\x8b"
        strings, stypes, samples, locs, fns = parse_profile(body)
        assert strings[0] == ""
        assert [(strings[a], strings[b]) for a, b in stypes] == [("threads", "count")]
        assert samples and all(s[2] == [1] for s in samples)
        # every referenced location resolves to a named function
        for s in samples:
            for lid in s[1]:
                line = dict(
                    (f2, v2) for f2, _, v2 in wire.iter_fields(locs[lid][4])
                )
                assert strings[fns[line[1]][2]]
        # one sample's root frame is the busy worker thread
        roots = set()
        for s in samples:
            lid = s[1][-1]
            line = dict((f2, v2) for f2, _, v2 in wire.iter_fields(locs[lid][4]))
            roots.add(strings[fns[line[1]][2]])
        assert any("busy-worker" in r for r in roots), roots

        st, body = get("/debug/pprof/profile?seconds=0.4")
        assert st == 200 and body[:2] == b"\x1f\x8b"
        strings, stypes, samples, _, _ = parse_profile(body)
        assert [(strings[a], strings[b]) for a, b in stypes] == [
            ("samples", "count"), ("cpu", "nanoseconds")
        ]
        assert samples, "CPU sampler collected nothing with a busy thread live"

        st, body = get("/debug/pprof/goroutine?debug=1")
        assert st == 200 and b"--- thread" in body
    finally:
        stop.set()
        t.join(timeout=5)


def test_serve_lane_through_http_server(tmp_path):
    """The single-call native serve lane must engage through the REAL
    threaded HTTP server: after the Gram warms, concurrent clients'
    batched Count requests are answered by pn_serve_pairs (executor
    serve state armed) with results identical to a cold numpy oracle."""
    import os
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu import native
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.server.server import Server

    # qcache OFF: this test proves the layer BELOW it (the native serve
    # lane) engages; with the query result cache on, byte-identical
    # repeats are answered above the executor and never arm the lane.
    cfg = Config(
        data_dir=str(tmp_path / "d"), host="127.0.0.1:0", engine="jax",
        qcache_enabled=False,
    )
    s = Server(cfg)
    s.open()
    try:
        base = f"http://{s.host}"

        def post(path, data):
            req = urllib.request.Request(
                base + path, data=data.encode(), method="POST"
            )
            return json.loads(urllib.request.urlopen(req, timeout=60).read())

        post("/index/i", "{}")
        post("/index/i/frame/f", "{}")
        rng = np.random.default_rng(4)
        s.holder.frame("i", "f").import_bits(
            rng.integers(0, 24, 800), rng.integers(0, 2 * (1 << 20), 800)
        )
        batch = " ".join(
            f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
            for a, b in rng.integers(0, 24, size=(32, 2))
        )
        first = post("/index/i/query", batch)["results"]
        post("/index/i/query", batch)  # second request arms the Gram/state
        assert s.executor._serve_states, "serve lane did not arm over HTTP"
        # Count actual native serve calls: the concurrent requests must
        # ride pn_serve_pairs, not silently fall to the general lane.
        calls = {"n": 0}
        orig = native.serve_pairs

        def counting(*a, **kw):
            r = orig(*a, **kw)
            if r is not None:
                calls["n"] += 1
            return r

        native.serve_pairs = counting
        try:
            with ThreadPoolExecutor(6) as pool:
                outs = list(
                    pool.map(
                        lambda _: post("/index/i/query", batch)["results"], range(12)
                    )
                )
        finally:
            native.serve_pairs = orig
        assert calls["n"] == 12, f"only {calls['n']}/12 requests served natively"
        oracle = Executor(s.holder, engine="numpy")
        os.environ["PILOSA_TPU_NO_FASTLANE"] = "1"
        try:
            want = oracle.execute("i", batch)
        finally:
            del os.environ["PILOSA_TPU_NO_FASTLANE"]
        assert first == want
        assert all(o == want for o in outs)
    finally:
        s.close()
