"""The benchmark's pieces for the ``ranked1.topn_src`` cell, on small data:
the plain TopN reference (``benchmark/references/topn.py``) against a
two-phase TopN written out here from each slice's rows, the ``powerlaw``
data rule and the ``topn_src`` generator.  Imports only ``benchmark/``."""

import json
import os
import sys
import time

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import byname, schema  # noqa: E402
from lib.records import SLICE_WIDTH  # noqa: E402

topn_ref = byname.load("references", "topn")
powerlaw = byname.load("datagens", "powerlaw")
topn_src = byname.load("generators", "topn_src")


def _frame(rows, cols, cache_size, name="stargazer"):
    f = schema.Frame(name, int(max(rows)) + 1, {"cacheType": "ranked", "cacheSize": cache_size}, {})
    return schema.FrameBits(f, np.asarray(rows, np.uint64), np.asarray(cols, np.uint64), None)


def _brute(bits: dict, cache_size: int, src: int, n: int) -> list:
    """Upstream's two phases from {(slice, row): set of columns}."""
    slices = sorted({s for s, _ in bits})
    merged = {}
    for s in slices:
        rows = sorted((r for t, r in bits if t == s), key=lambda r: (-len(bits[s, r]), r))[:cache_size]
        srcs = bits.get((s, src), set())
        scored = sorted((-len(bits[s, r] & srcs), r) for r in rows if bits[s, r] & srcs)[:n]
        for c, r in scored:
            merged[r] = merged.get(r, 0) - c
    total = {r: sum(len(bits.get((s, r), set()) & bits.get((s, src), set())) for s in slices) for r in merged}
    return [{"id": r, "count": c} for r, c in sorted(total.items(), key=lambda t: (-t[1], t[0])) if c][:n]


def _small(seed, n_rows=60, slices=3, max_bits=40):
    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for r in range(n_rows):
        k = int(rng.integers(1, max_bits))
        rows += [r] * k
        # a narrow pool of columns, so that rows meet and counts tie
        cols += (rng.choice(slices * 64, size=k, replace=False) // 64 * SLICE_WIDTH
                 + rng.choice(64, size=k)).tolist()
    pairs = sorted(set(zip(rows, cols)))
    return [p[0] for p in pairs], [p[1] for p in pairs]


def _as_sets(rows, cols):
    bits = {}
    for r, c in zip(rows, cols):
        bits.setdefault((c // SLICE_WIDTH, r), set()).add(c)
    return bits


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("cache_size,n", [(8, 3), (20, 5), (100, 10)])
def test_reference_matches_a_plain_two_phase_topn(seed, cache_size, n):
    rows, cols = _small(seed)
    ref = topn_ref.build([_frame(rows, cols, cache_size)])
    bits = _as_sets(rows, cols)
    for src in range(0, 60, 7):
        assert ref.topn("stargazer", src, n) == _brute(bits, cache_size, src, n)


def test_a_write_moves_the_references_candidates_and_counts():
    rows, cols = _small(9)
    ref = topn_ref.build([_frame(rows, cols, 10)])
    bits = _as_sets(rows, cols)
    src_cols = sorted(c for (s, r), cs in bits.items() if r == 3 for c in cs)
    outside = next(r for r in range(60) if r != 3 and r not in
                   [p["id"] for p in ref.topn("stargazer", 3, 60)])
    for c in src_cols:           # a row outside the cache takes every column of the src
        ref.set_bit(outside, c)
        bits.setdefault((c // SLICE_WIDTH, outside), set()).add(c)
    got = ref.topn("stargazer", 3, 5)
    assert got == _brute(bits, 10, 3, 5)
    assert got[0]["id"] in (3, outside)


def test_powerlaw_rows_and_the_cache_edge():
    frame = {"rows": 2000, "top_bits": 512}
    rows, cols = powerlaw.make_frame(3000039001, 1, frame)
    r, c = rows.astype(np.int64), cols.astype(np.int64)
    assert len(np.unique(r * SLICE_WIDTH + c)) == len(r)          # distinct within a row
    assert ((np.diff(r) > 0) | ((np.diff(r) == 0) & (np.diff(c) > 0))).all()   # sorted by (row, col)
    counts = np.sort(np.bincount(r, minlength=2000))[::-1]
    assert counts.tolist() == powerlaw.row_bits(frame).tolist()
    assert counts[255] == 2 and counts[256] == 1                   # a cache of 256: no tie at its edge
    again = powerlaw.make_frame(3000039001, 1, frame)
    assert (again[0] == rows).all() and (again[1] == cols).all()


def test_topn_src_streams_and_expected():
    p = {"clients": 8, "n": 10}
    frames = [schema.Frame("stargazer", 50, {"cacheType": "ranked", "cacheSize": 8}, {})]
    fill = topn_src.fill_requests(p, frames, SLICE_WIDTH, 7)
    assert len(fill) == topn_src.FILL_SENDS and all(r.body.startswith("TopN(Bitmap(") for r in fill)
    s = topn_src.Stream(p, frames, SLICE_WIDTH, 7, client=2, phase=0)
    bodies = [s.next().body for _ in range(50)]
    assert len(set(bodies)) == 50                                   # every row once ...
    assert len(set(s.next().body for _ in range(10))) == 10          # ... then again, never twice in a row's round
    again = topn_src.Stream(p, frames, SLICE_WIDTH, 7, client=2, phase=0)
    assert [again.next().body for _ in range(50)] == bodies
    req = fill[0]
    assert topn_src.n_calls(req) == 1

    class Oracle:
        def topn(self, frame, src_row, n):
            return [{"id": src_row, "count": n}]

    assert topn_src.expected(Oracle(), req) == [[{"id": req.calls[0][2], "count": 10}]]


def test_topn_src_warm_up_deadline(monkeypatch):
    p = {"clients": 8, "n": 10}
    frames = [schema.Frame("stargazer", 50, {}, {})]
    monkeypatch.setattr(topn_src, "WARM_BUDGET_S", -1.0)
    topn_src.fill_requests(p, frames, SLICE_WIDTH, 7)
    with pytest.raises(TimeoutError):
        topn_src.Stream(p, frames, SLICE_WIDTH, 7, client=0, phase=1)
    monkeypatch.setattr(topn_src, "WARM_BUDGET_S", 60.0)
    topn_src.fill_requests(p, frames, SLICE_WIDTH, 7)
    topn_src._fill["t0"] -= 2 * 10.0          # a fill of 10 s a request ...
    with pytest.raises(TimeoutError):         # ... so 8 in flight end past the deadline
        topn_src.Stream(p, frames, SLICE_WIDTH, 7, client=0, phase=1)
    topn_src.fill_requests(p, frames, SLICE_WIDTH, 7)
    warm = topn_src.Stream(p, frames, SLICE_WIDTH, 7, client=0, phase=1)
    window = topn_src.Stream(p, frames, SLICE_WIDTH, 7, client=0, phase=0)   # ends the deadline
    topn_src._deadline[0] = time.perf_counter() - 1
    with pytest.raises(TimeoutError):
        warm.next()
    topn_src._deadline[0] = None
    assert window.next().kind == "read"


def test_ranked1_configuration_and_its_cell():
    root = os.path.dirname(BENCH)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == "ranked1.topn_src")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == "ranked1")
    with open(os.path.join(root, cfg_entry["file"])) as f:
        cfg = json.load(f)
    assert cell["chips"] == cfg["chips"] == 1 and cfg["slices"] == 1
    assert cfg["reference"] == "topn" and set(cfg_entry["reduced"]) == {"rows"}
    assert cfg["server"]["env"] == {"PILOSA_ENGINE": "jax"}
    (f0,) = schema.frames(cfg)
    assert f0.rows == 500000 and f0.options == {"cacheType": "ranked", "cacheSize": 50000}
    assert f0.entry["top_bits"] == 2 * f0.options["cacheSize"]
    per_layer = [m for m in bench["per_layer"] if "ranked1.topn_src" in m.get("workloads", [])]
    assert {m["name"] for m in per_layer} == {
        "topn_candidates_ms", "topn_score_ms", "topn_hbm_share", "topn_device_idle_share"}
    for m in per_layer:
        assert m["workloads"] == ["ranked1.topn_src"]
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"] + ".py"))


def test_topn_span_readers():
    """``topn_candidates_ms`` / ``topn_score_ms``: their spans' ms over the
    TopN calls of the traced reads; nothing to read from trees without them
    (a program whose TopN takes no such span)."""
    from lib.records import Record, Request

    def rec(children):
        tree = [{"name": "root", "ms": 9.0, "children": [
            {"name": "call.TopN", "ms": 8.0, "children": children}]}]
        return Record(0, Request("read", "TopN(...)", [("TopN", "stargazer", 1, 10)]), 0.0, 1.0, [[]], spans=tree)

    phase = [{"name": "topn.phase", "ms": 7.0, "children": [
        {"name": "topn.candidates", "ms": 0.5}, {"name": "topn.candidates", "ms": 0.25, "tags": {"built": False}},
        {"name": "topn.score", "ms": 2.0, "tags": {"rows": 5, "words": 9, "bucket": 16}},
        {"name": "topn.select", "ms": 0.25}]}]
    ctx = {"records": [rec(phase), rec(phase), rec([])]}
    cand = byname.load("metrics", "topn_candidates_ms")
    score = byname.load("metrics", "topn_score_ms")
    assert cand.read(ctx) == pytest.approx(2 * 1.0 / 3)
    assert score.read(ctx) == pytest.approx(2 * 2.0 / 3)
    bare = {"records": [rec([])]}
    assert cand.read(bare) is None and score.read(bare) is None
    topn_work = __import__("lib.topn_work", fromlist=["topn_min_bytes"])
    assert topn_work.topn_min_bytes(975000, SLICE_WIDTH // 32) == 4 * (975000 + 32768)
