"""Multi-tenant isolation (pilosa_tpu/tenancy/, ROADMAP item 5).

Covers: the single tenant-resolution seam (header > map > index name >
default) and its config parsers; weighted fair-share admission inside
the QoS class doors (a hostile tenant sheds at its share while a polite
tenant keeps clearing the SAME door); per-tenant qcache byte quotas
(self-first reclamation — one tenant's store flood never flushes
another's working set); the per-tenant ingest bandwidth pacer (token
buckets, weighted shares, idle reclaim); the cost-ledger tenant
dimension (5-tuple keys, tenant-agnostic peek fallback, legacy snapshot
restore); the ``[tenancy]`` config section + env overrides; and the
/debug/tenants endpoint end to end through the HTTP server — including
the isolation-OFF contract (no TenancyState, pre-tenancy behavior).
"""

import json
import threading
import time
import urllib.error
import urllib.request
import zlib

import pytest

from pilosa_tpu import tenancy
from pilosa_tpu.config import Config
from pilosa_tpu.qos import CLASS_READ, CLASS_WRITE, AdmissionController, ShedError

# -- resolution seam --------------------------------------------------------


def test_index_of():
    assert tenancy.index_of("/index/foo/query") == "foo"
    assert tenancy.index_of("/index/foo") == "foo"
    assert tenancy.index_of("/status") == ""
    assert tenancy.index_of("") == ""


def test_resolve_precedence():
    # Header beats everything (handler dicts are lowercased).
    assert (
        tenancy.resolve(
            "/index/i/query",
            {"x-pilosa-tenant": "acme"},
            index_map={"i": "gold"},
        )
        == "acme"
    )
    # Map beats the index name.
    assert tenancy.resolve("/index/i/query", {}, index_map={"i": "gold"}) == "gold"
    # Index name beats the default.
    assert tenancy.resolve("/index/i/query", {}) == "i"
    # Admin routes with no index fall to the default.
    assert tenancy.resolve("/status", {}) == tenancy.DEFAULT_TENANT
    # Whitespace-only headers are absent, not a tenant named "  ".
    assert tenancy.resolve("/index/i/query", {"x-pilosa-tenant": "  "}) == "i"


def test_parse_helpers():
    assert tenancy.parse_weights("gold=4, free=1") == {"gold": 4.0, "free": 1.0}
    assert tenancy.parse_weights("") == {}
    assert tenancy.parse_weights("bad,x=notanumber") == {}
    # Weights are floored away from zero: a zero weight would divide
    # the shares by zero, not exclude the tenant.
    assert tenancy.parse_weights("z=0")["z"] == pytest.approx(1e-3)
    assert tenancy.parse_map("a=gold, b=free") == {"a": "gold", "b": "free"}
    assert tenancy.parse_map("") == {}
    # Bare fraction: one default share for every tenant.
    assert tenancy.parse_shares("0.5") == (0.5, {})
    assert tenancy.parse_shares("2.0") == (1.0, {})  # clamped
    d, per = tenancy.parse_shares("gold=0.75,free=0.1")
    assert d == 0.0 and per == {"gold": 0.75, "free": 0.1}
    assert tenancy.parse_shares("") == (0.0, {})


def test_tenancy_state_resolution():
    st = tenancy.TenancyState(
        weights="gold=4", index_map="i=gold", qcache_share="0.5"
    )
    assert st.resolve("/index/i/query", {}) == "gold"
    assert st.resolve_for_index("i", {}) == "gold"
    assert st.resolve_for_index("i", {"x-pilosa-tenant": "acme"}) == "acme"
    assert st.tenant_of_index("other") == "other"
    assert st.tenant_of_index("") == tenancy.DEFAULT_TENANT
    assert st.qcache_quota("anyone", 1000) == 500
    # 0.0 share = unquoted.
    st2 = tenancy.TenancyState(qcache_share="gold=0.5")
    assert st2.qcache_quota("free", 1000) == 0
    assert st2.qcache_quota("gold", 1000) == 500


# -- weighted fair-share admission ------------------------------------------


def _door(depth=2, queue_wait_ms=40.0, **kw):
    st = tenancy.TenancyState(**kw)
    adm = AdmissionController(
        depths={CLASS_READ: depth},
        queue_wait_ms=queue_wait_ms,
        retry_after_ms=100.0,
        tenancy=st,
    )
    return adm, st


def test_fair_share_work_conserving_alone():
    """A tenant ALONE at the door gets the whole depth — tenancy on
    with one tenant present costs no throughput."""
    adm, _ = _door(depth=3)
    for _ in range(3):
        adm.acquire(CLASS_READ, tenant="hostile")
    # Slot 4: over depth, waits, then sheds.
    with pytest.raises(ShedError):
        adm.acquire(CLASS_READ, tenant="hostile")
    for _ in range(3):
        adm.release(CLASS_READ, tenant="hostile")


def test_fair_share_presence_hysteresis():
    """A tenant's share survives the instant between its closed-loop
    requests: a flooder cannot seize the whole door during a momentary
    gap — the departed tenant's share is reclaimed only PRESENCE_S
    after its last door activity."""
    clk = _Clock()
    fs = tenancy.FairShare(weights={"polite": 7, "hostile": 1}, clock=clk)
    fs.note_admit(CLASS_READ, "polite")
    fs.note_release(CLASS_READ, "polite")
    # No polite inflight or waiting — but inside the presence window
    # the flooder still sees polite's share standing.
    assert fs.cap(CLASS_READ, "hostile", 8) == 1
    clk.t += tenancy.FairShare.PRESENCE_S / 2
    assert fs.cap(CLASS_READ, "hostile", 8) == 1
    # Past the horizon the polite tenant is gone: work conservation
    # hands the flooder the whole depth.
    clk.t += tenancy.FairShare.PRESENCE_S
    assert fs.cap(CLASS_READ, "hostile", 8) == 8


def test_fair_share_hostile_sheds_polite_clears():
    """The isolation property at the unit scale: with the door FULL of
    hostile inflight, a polite tenant's request still clears on the next
    release — the freed slot goes to the under-share tenant, never back
    to the flooder."""
    adm, _ = _door(depth=2, queue_wait_ms=2000.0)
    adm.acquire(CLASS_READ, tenant="hostile")
    adm.acquire(CLASS_READ, tenant="hostile")

    admitted = []

    def polite():
        adm.acquire(CLASS_READ, tenant="polite")
        admitted.append(True)

    def hostile_waiter():
        try:
            adm.acquire(CLASS_READ, tenant="hostile")
            admitted.append("hostile!")
        except ShedError:
            pass

    tp = threading.Thread(target=polite)
    th = threading.Thread(target=hostile_waiter)
    tp.start()
    th.start()
    # Both parked in the wait lane (visible in the snapshot) before the
    # release decides who gets the slot.
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        snap = adm.tenants_snapshot()
        if (
            snap.get("polite", {}).get("waiting", {}).get(CLASS_READ)
            and snap.get("hostile", {}).get("waiting", {}).get(CLASS_READ)
        ):
            break
        time.sleep(0.005)
    # One hostile slot frees: present = {hostile, polite}, so the
    # hostile cap is now 1 and its remaining inflight (1) fills it —
    # only the polite waiter is eligible for the freed slot.
    adm.release(CLASS_READ, tenant="hostile")
    tp.join(timeout=5)
    th.join(timeout=5)
    assert admitted == [True]

    snap = adm.tenants_snapshot()
    assert snap["polite"]["shed"] == 0 and snap["polite"]["admitted"] == 1
    assert snap["hostile"]["shed"] == 1 and snap["hostile"]["admitted"] == 2
    adm.release(CLASS_READ, tenant="hostile")
    adm.release(CLASS_READ, tenant="polite")


def test_fair_share_weights_split_share():
    """weights gold=3 free=1 over depth 4: gold's cap is 3, free's 1 —
    and debt grows per-admit at 1/w, so equal debt means
    weight-proportional admission."""
    adm, st = _door(depth=4, weights="gold=3,free=1")
    fair = st.fair
    adm.acquire(CLASS_READ, tenant="gold")
    adm.acquire(CLASS_READ, tenant="free")
    assert fair.cap(CLASS_READ, "gold", 4) == 3
    assert fair.cap(CLASS_READ, "free", 4) == 1
    # free is AT its cap: its next request waits/sheds, gold's clears.
    adm.acquire(CLASS_READ, tenant="gold")
    with pytest.raises(ShedError):
        adm.acquire(CLASS_READ, tenant="free")
    snap = adm.tenants_snapshot()
    assert snap["gold"]["debt"] == pytest.approx(2 / 3.0, abs=1e-3)
    assert snap["free"]["debt"] == pytest.approx(1.0)
    for _ in range(2):
        adm.release(CLASS_READ, tenant="gold")
    adm.release(CLASS_READ, tenant="free")


def test_fair_share_unbounded_class_accounts_only():
    """depth <= 0 stays unbounded with tenancy on — the accounting
    rides along but nothing sheds (the pre-QoS contract)."""
    adm, _ = _door(depth=0)
    for _ in range(16):
        adm.acquire(CLASS_READ, tenant="t")
    snap = adm.tenants_snapshot()
    assert snap["t"]["admitted"] == 16 and snap["t"]["shed"] == 0
    for _ in range(16):
        adm.release(CLASS_READ, tenant="t")


def test_tenancy_off_door_unchanged():
    """tenant=None (isolation off) takes the pre-tenancy body: no
    per-tenant state is ever created."""
    adm = AdmissionController(depths={CLASS_READ: 1}, queue_wait_ms=20.0)
    adm.acquire(CLASS_READ)
    with pytest.raises(ShedError):
        adm.acquire(CLASS_READ)
    adm.release(CLASS_READ)
    assert adm.tenants_snapshot() == {}


# -- per-tenant ingest bandwidth pacing -------------------------------------


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_pacer_tokens_and_retry_after():
    clk = _Clock()
    p = tenancy.BandwidthPacer(1000, clock=clk)
    # A fresh bucket starts full (burst_s * rate = 2000 bytes).
    assert p.admit("a", 1500) == 0.0
    # 1000 more: 500 tokens left -> retry-after (1000-500)/1000 = 0.5s.
    wait = p.admit("a", 1000)
    assert wait == pytest.approx(0.5, abs=0.05)
    clk.t += wait
    assert p.admit("a", 1000) == 0.0
    assert "a" in p.snapshot()


def test_pacer_share_rebalances_and_idle_reclaims():
    clk = _Clock()
    p = tenancy.BandwidthPacer(1000, clock=clk)
    # Drain a's bucket while it is ALONE: full rate (1000 B/s).
    assert p.admit("a", 2000) == 0.0
    assert p.admit("a", 1000) == pytest.approx(1.0, abs=0.05)
    # b shows up: equal weights halve a's refill rate.
    p.admit("b", 1)
    assert p.admit("a", 1000) == pytest.approx(2.0, abs=0.1)
    # b idle past the window: its share returns to a.
    clk.t += tenancy.BandwidthPacer.IDLE_S + 1
    assert p.admit("a", 1000) == 0.0  # refilled at >= half rate for 11s
    assert "b" not in p.snapshot()


def test_pacer_single_chunk_always_eventually_clears():
    clk = _Clock()
    p = tenancy.BandwidthPacer(100, burst_s=0.5, clock=clk)
    # A chunk far above rate*burst still fits the cap floor.
    assert p.admit("a", 5000) == 0.0


# -- per-tenant qcache byte quotas ------------------------------------------


@pytest.fixture()
def qc_env(tmp_path):
    from pilosa_tpu.core.frame import FrameOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.qcache import QueryCache

    h = Holder(str(tmp_path / "d"))
    h.open()
    for name in ("i", "j"):
        h.create_index(name).create_frame("f", FrameOptions())
        fr = h.index(name).frame("f")
        for r in range(16):
            fr.set_bit("standard", r, r)
    st = tenancy.TenancyState(qcache_share="0.5")
    qc = QueryCache(min_cost_ms=0.0, tenancy=st)
    ex = Executor(h, engine="numpy", qcache=qc)
    yield h, ex, qc
    h.close()


def test_qcache_quota_self_reclaim_spares_neighbor(qc_env):
    """Tenant i floods the cache: its own LRU entries reclaim at its
    50% byte share while tenant j's resident entry survives untouched —
    then j still HITS."""
    h, ex, qc = qc_env
    q_j = 'Count(Bitmap(rowID=0, frame="f"))'
    assert ex.execute("j", q_j) == [1]  # j's working set: one entry
    # Size the budget so only a few entries fit: measure one entry.
    entry_bytes = qc.bytes - qc.tenant_bytes_snapshot().get("i", 0)
    assert entry_bytes > 0
    qc.max_bytes = entry_bytes * 4  # quota: 2 entries per tenant
    for r in range(12):
        ex.execute("i", f'Count(Bitmap(rowID={r}, frame="f"))')
    snap = qc.tenant_bytes_snapshot()
    assert snap["i"] <= qc.max_bytes // 2
    # j's entry never paid for i's flood.
    assert snap["j"] == entry_bytes
    hits0 = qc.hits
    assert ex.execute("j", q_j) == [1]
    assert qc.hits == hits0 + 1
    assert qc.evictions > 0


def test_qcache_purge_and_clear_return_tenant_bytes(qc_env):
    h, ex, qc = qc_env
    ex.execute("i", 'Count(Bitmap(rowID=0, frame="f"))')
    ex.execute("j", 'Count(Bitmap(rowID=0, frame="f"))')
    assert set(qc.tenant_bytes_snapshot()) == {"i", "j"}
    qc.purge_index("i")
    assert set(qc.tenant_bytes_snapshot()) == {"j"}
    qc.clear()
    assert qc.tenant_bytes_snapshot() == {}


def test_qcache_no_tenancy_no_tenant_accounting(tmp_path):
    """Isolation off: entries carry no tenant and the byte map stays
    empty — the pre-tenancy cache, byte for byte."""
    from pilosa_tpu.core.frame import FrameOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.qcache import QueryCache

    h = Holder(str(tmp_path / "d"))
    h.open()
    h.create_index("i").create_frame("f", FrameOptions())
    h.index("i").frame("f").set_bit("standard", 0, 1)
    qc = QueryCache(min_cost_ms=0.0)
    ex = Executor(h, engine="numpy", qcache=qc)
    assert ex.execute("i", 'Count(Bitmap(rowID=0, frame="f"))') == [1]
    assert qc.tenant_bytes_snapshot() == {}
    h.close()


# -- cost-ledger tenant dimension -------------------------------------------


def test_costs_five_tuple_keys():
    from pilosa_tpu.costs import CostLedger

    led = CostLedger()
    led.observe(tenant="gold", index="i", frame="f", fp="fp",
                lane="exec", ms=10.0)
    # Exact peek with the tenant.
    e = led.peek(tenant="gold", index="i", frame="f", fp="fp", lane="exec")
    assert e is not None and e["ewma_ms"] == pytest.approx(10.0)
    # The tenant is part of the key: a peek that names none finds none.
    assert led.peek(index="i", frame="f", fp="fp", lane="exec") is None
    # A different tenant, same 4-tuple: separate entries.
    led.observe(tenant="free", index="i", frame="f", fp="fp",
                lane="exec", ms=30.0)
    e = led.peek(tenant="free", index="i", frame="f", fp="fp", lane="exec")
    assert e["ewma_ms"] == pytest.approx(30.0)
    e = led.peek(tenant="gold", index="i", frame="f", fp="fp", lane="exec")
    assert e["ewma_ms"] == pytest.approx(10.0)
    rows = led.entries()
    assert {r["tenant"] for r in rows} == {"gold", "free"}
    by = led.by_tenant()
    assert by["gold"]["entries"] == 1 and by["free"]["entries"] == 1
    # /debug/costs keeps emitting index/frame/fp/lane and now tenant.
    snap = led.snapshot()
    assert {r["tenant"] for r in snap["entries"]} == {"gold", "free"}
    assert all(r["index"] == "i" for r in snap["entries"])


class _FakeSpan:
    def __init__(self, name="root", tags=None):
        self.name = name
        self.tags = tags or {}
        self.children = []
        self.ms = 0.0


class _FakeTrace:
    def __init__(self, tags):
        self.root = _FakeSpan(tags=tags)
        self.wall_ts = 1000.0


def test_costs_fold_separates_tenant_from_index():
    """The PR-13 conflation fix: a trace tagged with BOTH tenant and
    index folds into a key carrying each in its own dimension."""
    from pilosa_tpu.costs import CostLedger

    led = CostLedger()
    led.fold(_FakeTrace({"tenant": "gold", "index": "i", "frame": "f",
                         "lane": "exec"}), 5.0)
    rows = led.entries()
    assert rows[0]["tenant"] == "gold" and rows[0]["index"] == "i"
    # Embedders that only tagged "tenant" (the pre-tenancy handler wrote
    # the index name there) keep their index keying.
    led.fold(_FakeTrace({"tenant": "solo", "frame": "f", "lane": "exec"}),
             5.0)
    rows = {(r["tenant"], r["index"]) for r in led.entries()}
    assert ("solo", "solo") in rows


def test_costs_restore_legacy_four_tuple_snapshot():
    from pilosa_tpu.costs import CostLedger

    led = CostLedger()
    led.observe(index="i", frame="f", fp="fp", lane="exec", ms=7.0)
    st = led.state()
    # Age the state to the pre-tenancy 4-tuple key shape.
    for row in st["entries"]:
        assert row[0][0] == ""
        row[0] = row[0][1:]
    led2 = CostLedger()
    led2.restore(st)
    e = led2.peek(index="i", frame="f", fp="fp", lane="exec")
    assert e is not None and e["ewma_ms"] == pytest.approx(7.0)


# -- config section ---------------------------------------------------------


def test_config_tenancy_section_and_env(monkeypatch):
    cfg = Config.from_dict({
        "tenancy": {
            "enabled": True,
            "weights": "gold=4,free=1",
            "default-weight": 2.0,
            "map": "i=gold",
            "qcache-share": "0.5",
            "ingest-bytes-per-s": 1 << 20,
        }
    })
    assert cfg.tenancy_enabled and cfg.tenancy_weights == "gold=4,free=1"
    assert cfg.tenancy_default_weight == 2.0
    assert cfg.tenancy_map == "i=gold"
    assert cfg.tenancy_qcache_share == "0.5"
    assert cfg.tenancy_ingest_bytes_per_s == 1 << 20
    st = tenancy.from_config(cfg)
    assert st is not None and st.weights == {"gold": 4.0, "free": 1.0}
    assert st.pacer is not None

    # Env wins over TOML; disabled builds no state at all.
    monkeypatch.setenv("PILOSA_TPU_TENANCY", "0")
    assert tenancy.from_config(Config.from_dict({
        "tenancy": {"enabled": True},
    }).apply_env()) is None
    monkeypatch.setenv("PILOSA_TPU_TENANCY", "1")
    monkeypatch.setenv("PILOSA_TPU_TENANCY_WEIGHTS", "a=9")
    st = tenancy.from_config(Config().apply_env())
    assert st is not None and st.weights == {"a": 9.0}


def test_from_config_default_off():
    assert tenancy.from_config(Config()) is None


# -- /debug/tenants through the server --------------------------------------


def _make_server(tmp_path, **cfg_kwargs):
    from pilosa_tpu.server.server import Server

    cfg = Config(data_dir=str(tmp_path / "s"), host="127.0.0.1:0",
                 engine="numpy", **cfg_kwargs)
    s = Server(cfg)
    s.open()
    return s


def _http(host, method, path, body=None, headers=None):
    req = urllib.request.Request(
        f"http://{host}{path}", data=body, method=method
    )
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.status, dict(resp.headers), resp.read()


def test_debug_tenants_endpoint(tmp_path):
    srv = _make_server(
        tmp_path,
        tenancy_enabled=True,
        tenancy_weights="gold=4",
        tenancy_map="i=gold",
    )
    try:
        _http(srv.host, "POST", "/index/i")
        _http(srv.host, "POST", "/index/i/frame/f")
        _http(srv.host, "POST", "/index/i/query",
              b'SetBit(rowID=1, frame="f", columnID=3)')
        # A read billed to the mapped tenant, one to a header override.
        _http(srv.host, "POST", "/index/i/query",
              b'Count(Bitmap(rowID=1, frame="f"))')
        _http(srv.host, "POST", "/index/i/query",
              b'Count(Bitmap(rowID=1, frame="f"))',
              headers={"X-Pilosa-Tenant": "acme"})
        st, _, payload = _http(srv.host, "GET", "/debug/tenants")
        out = json.loads(payload)
        assert st == 200 and out["enabled"] is True
        assert out["tenants"]["gold"]["weight"] == 4.0
        assert out["tenants"]["gold"]["admitted"] >= 2
        assert out["tenants"]["acme"]["admitted"] == 1
        # Per-tenant latency series landed in /debug/vars too.
        _, _, vars_payload = _http(srv.host, "GET", "/debug/vars")
        vars_snap = json.loads(vars_payload)
        assert any(k.startswith("tenancy.latency_ms.gold") for k in vars_snap)
    finally:
        srv.close()


def test_debug_tenants_endpoint_off(tmp_path):
    srv = _make_server(tmp_path)
    try:
        st, _, payload = _http(srv.host, "GET", "/debug/tenants")
        out = json.loads(payload)
        assert st == 200 and out == {"enabled": False, "tenants": {}}
    finally:
        srv.close()


def test_ingest_door_pacer_sheds_429_with_retry_after(tmp_path):
    """A chunk past the tenant's bandwidth share answers 429 +
    Retry-After BEFORE staging; honoring the hint clears it."""
    from pilosa_tpu import ingest as ingest_mod
    import numpy as np

    srv = _make_server(
        tmp_path,
        tenancy_enabled=True,
        tenancy_ingest_bytes_per_s=2048,
    )
    try:
        _http(srv.host, "POST", "/index/i")
        _http(srv.host, "POST", "/index/i/frame/f")
        rows = np.arange(600, dtype=np.uint64) % 8
        cols = np.arange(600, dtype=np.uint64)
        half = 300
        frames = [
            ingest_mod.encode_packed(rows[:half], cols[:half]),
            ingest_mod.encode_packed(rows[half:], cols[half:]),
        ]
        total = sum(len(f) for f in frames)
        crc = 0
        for f in frames:
            crc = zlib.crc32(f, crc)
        # First chunk rides the initial burst; the second overdraws the
        # 2 KiB/s bucket (each chunk is ~4.8 KB).
        url = (
            f"/index/i/frame/f/ingest?off=0&total={total}"
            f"&crc={crc}&ccrc={zlib.crc32(frames[0])}"
        )
        st, _, _ = _http(srv.host, "POST", url, frames[0])
        assert st == 200
        url2 = (
            f"/index/i/frame/f/ingest?off={len(frames[0])}&total={total}"
            f"&crc={crc}&ccrc={zlib.crc32(frames[1])}"
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            _http(srv.host, "POST", url2, frames[1])
        assert e.value.code == 429
        retry = float(e.value.headers["Retry-After"])
        assert retry > 0
        time.sleep(min(retry, 5.0))
        st, _, payload = _http(srv.host, "POST", url2, frames[1])
        assert st == 200 and json.loads(payload)["done"]
    finally:
        srv.close()


def test_tenancy_off_query_path_unchanged(tmp_path):
    """Isolation OFF end to end: queries serve, no tenancy.* series
    appear, and traces keep the PR-13 tenant=index attribution."""
    srv = _make_server(tmp_path)
    try:
        _http(srv.host, "POST", "/index/i")
        _http(srv.host, "POST", "/index/i/frame/f")
        _http(srv.host, "POST", "/index/i/query",
              b'SetBit(rowID=1, frame="f", columnID=3)')
        st, _, payload = _http(srv.host, "POST", "/index/i/query",
                               b'Count(Bitmap(rowID=1, frame="f"))')
        assert st == 200 and json.loads(payload)["results"] == [1]
        _, _, vars_payload = _http(srv.host, "GET", "/debug/vars")
        assert not any(
            k.startswith("tenancy.") for k in json.loads(vars_payload)
        )
    finally:
        srv.close()
