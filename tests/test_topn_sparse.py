"""TopN of a ranked frame taller than its rank cache: on the jax engine a
fragment's candidates are scored from their words held sparse on the
device (``Executor._topn_form``, ``ops.bitwise.topn_sparse_counts``), one
dispatch a phase, and the top n is one selection over the counts
(``core.fragment.select_top``).  Held here to the numpy engine, whose
fragments score every candidate with their own host path, and to a plain
two-phase TopN written out below; every TopOptions case, writes on a
candidate and on a row that enters the cache, the tie rule, the dense lane
where a form would be too large, and the spans and counters of a traced
TopN."""

import heapq
import math

import numpy as np
import pytest

from pilosa_tpu import trace
from pilosa_tpu.core import cache as cache_mod
from pilosa_tpu.core.fragment import TopOptions, select_top
from pilosa_tpu.core.frame import FrameOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import ExecOptions, Executor
from pilosa_tpu.pilosa import SLICE_WIDTH
from pilosa_tpu.stats import ExpvarStatsClient

ROWS, CACHE = 300, 64
SLICES = 2
SRC = 900  # the src rows, in frame "f"
FEW_SRC, FEW_WORDS = 1000, (1, 2, 15, 16, 17)  # src rows FEW_SRC + k: k words a slice, each a candidate's


def _power_law(rng, n_rows, top_bits, n_cols):
    """Row of rank r holds max(1, top_bits // r) bits, columns distinct
    within a row, ranks dealt by a permutation."""
    rows, cols = [], []
    for rank, row in enumerate(rng.permutation(n_rows), start=1):
        n = max(1, top_bits // rank)
        rows.extend([int(row)] * n)
        cols.extend(rng.choice(n_cols, size=n, replace=False).tolist())
    return rows, cols


@pytest.fixture
def ranked(tmp_path):
    """A ranked frame "r" of ROWS rows (cache CACHE) over SLICES slices -
    row sizes falling as 1/rank, a row with a bitmap container (5,000 bits
    in one container of slice 0) - and src rows SRC.. built from the
    candidates' own columns, so that intersections are large and tie, and
    FEW_SRC + k of k such words a slice."""
    h = Holder(str(tmp_path / "data"), ranking_debounce_s=0)
    h.open()
    idx = h.create_index("i")
    idx.create_frame("r", FrameOptions(cache_type="ranked", cache_size=CACHE))
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("r")
    rng = np.random.default_rng(39)
    rows, cols = _power_law(rng, ROWS, 2 * CACHE * 3, SLICES * SLICE_WIDTH)
    rows += [7] * 5000
    cols += (SLICE_WIDTH + rng.choice(1 << 16, size=5000, replace=False)).tolist()
    taken = np.unique(cols[: len(cols) // 2])
    src_rows, src_cols = [], []
    for k in range(4):
        pick = rng.choice(taken, size=40 + 60 * k, replace=False)
        src_rows += [SRC + k] * len(pick)
        src_cols += pick.tolist()
    few = np.random.default_rng(40)
    for k in FEW_WORDS:              # k words that are not zero in each slice
        for s in range(SLICES):
            here = taken[(taken // SLICE_WIDTH) == s]
            _, first = np.unique(here // 32, return_index=True)
            pick = few.choice(here[first], size=k, replace=False)
            src_rows += [FEW_SRC + k] * k
            src_cols += pick.tolist()
    fr.import_bits(rows, cols)
    idx.frame("f").import_bits(src_rows, src_cols)
    for r in range(0, ROWS, 3):
        fr.row_attr_store.set_attrs(r, {"kind": r % 2})
    yield h
    h.close()


def _pairs(res):
    return [(p.id, p.count) for p in res]


QUERIES = [
    'TopN(Bitmap(rowID={s}, frame="f"), frame="r", n=5)',
    'TopN(Bitmap(rowID={s}, frame="f"), frame="r", n=1)',
    'TopN(Bitmap(rowID={s}, frame="f"), frame="r", n=40)',
    'TopN(Bitmap(rowID={s}, frame="f"), frame="r")',
    'TopN(Bitmap(rowID={s}, frame="f"), frame="r", n=8, threshold=3)',
    'TopN(Bitmap(rowID={s}, frame="f"), frame="r", n=4, tanimotoThreshold=2)',
    'TopN(Bitmap(rowID={s}, frame="f"), frame="r", n=6, field="kind", filters=[1])',
    'TopN(Bitmap(rowID={s}, frame="f"), frame="r", ids=[0, 7, 11, 250, 299])',
    'TopN(frame="r", n=7)',
    'TopN(frame="r", n=5, threshold=4)',
]


@pytest.mark.parametrize("src", [SRC, SRC + 3] + [FEW_SRC + k for k in FEW_WORDS])
@pytest.mark.parametrize("q", QUERIES)
def test_sparse_scorer_matches_the_host_path(ranked, q, src):
    stats = ExpvarStatsClient()
    e_jx = Executor(ranked, engine="jax", stats=stats)
    e_np = Executor(ranked, engine="numpy")
    q = q.format(s=src)
    want = _pairs(e_np.execute("i", q)[0])
    assert _pairs(e_jx.execute("i", q)[0]) == want
    assert _pairs(e_jx.execute("i", q)[0]) == want   # the forms reused
    if "Bitmap" in q and "ids=" not in q:
        snap = stats.snapshot()
        assert snap["topn.sparse_builds"] == SLICES      # one form a fragment, built once
        assert snap.get("topn.dense_fallbacks", 0) == 0
        assert snap["topn.rows_scored"] > 0


@pytest.mark.parametrize("nsrc", [1, 2, 15, 16, 17, 300, 32768])
def test_the_kernel_counts_every_candidate(nsrc):
    """``topn_sparse_counts`` against a numpy popcount, candidate by
    candidate, for a src of ``nsrc`` words that are not zero (half of
    them at candidates' words), the padded candidates counting 0."""
    from pilosa_tpu.engine import JaxEngine

    rng = np.random.default_rng(nsrc)
    words = SLICE_WIDTH // 32
    lens = rng.integers(0, 40, size=1000)
    widx = np.concatenate([np.sort(rng.choice(words, size=n, replace=False)) for n in lens])
    seg = np.repeat(np.arange(len(lens)), lens)
    vals = rng.integers(1, 2**32, size=len(widx), dtype=np.uint64).astype(np.uint32)
    cand = np.unique(widx)
    hits = rng.choice(cand, size=min((nsrc + 1) // 2, len(cand)), replace=False)
    rest = rng.choice(np.setdiff1d(np.arange(words), hits), size=nsrc - len(hits), replace=False)
    at = np.concatenate([hits, rest])
    src = np.zeros(words, dtype=np.uint32)
    src[at] = rng.integers(1, 2**32, size=len(at), dtype=np.uint64).astype(np.uint32)
    assert np.count_nonzero(src) == nsrc
    ones = np.unpackbits((src[widx] & vals).view(np.uint8)).reshape(len(widx), 32).sum(axis=1)
    want = np.bincount(seg, weights=ones, minlength=len(lens)).astype(np.int64)
    e = JaxEngine()
    form, bucket = e.topn_sparse_form(widx, vals, seg, len(lens))
    got = e.topn_sparse_counts(src, form)
    assert bucket >= len(widx) and len(got) >= len(lens)
    assert got[: len(lens)].tolist() == want.tolist()
    assert not got[len(lens):].any()


def _brute_topn(ranked, src_row, n):
    """Upstream's two phases written out over each fragment's rank cache
    and dense rows: phase 1's top n by (count desc, id asc) a slice,
    phase 2's exact counts of the merged ids over every slice."""
    frags = [ranked.fragment("i", "r", "standard", s) for s in range(SLICES)]
    srcs = [ranked.fragment("i", "f", "standard", s).row_dense(src_row) for s in range(SLICES)]
    merged = {}
    for f, src in zip(frags, srcs):
        scored = []
        for p in f.cache.top():
            c = int(np.unpackbits((f.row_dense(p.id) & src).view(np.uint8)).sum())
            if c:
                scored.append((-c, p.id))
        for c, i in sorted(scored)[:n]:
            merged[i] = merged.get(i, 0) - c
    total = {}
    for f, src in zip(frags, srcs):
        for i in merged:
            c = int(np.unpackbits((f.row_dense(i) & src).view(np.uint8)).sum())
            total[i] = total.get(i, 0) + c
    return sorted(((i, c) for i, c in total.items() if c), key=lambda t: (-t[1], t[0]))[:n]


@pytest.mark.parametrize("n", [1, 3, 10])
def test_two_phases_match_a_plain_topn(ranked, n):
    e = Executor(ranked, engine="jax")
    for src in range(SRC, SRC + 4):
        got = _pairs(e.execute("i", f'TopN(Bitmap(rowID={src}, frame="f"), frame="r", n={n})')[0])
        assert got == _brute_topn(ranked, src, n)


@pytest.mark.parametrize("write", ["set_candidate", "clear_candidate", "enter_cache"])
def test_a_write_is_seen_by_the_next_topn(ranked, write):
    """A write moves the fragment's generation (the form is rebuilt) and,
    where it changes a row's rank, the cache's version: the next TopN
    reflects it in the candidates and in the counts."""
    stats = ExpvarStatsClient()
    e = Executor(ranked, engine="jax", stats=stats)
    e_np = Executor(ranked, engine="numpy")
    q = f'TopN(Bitmap(rowID={SRC + 2}, frame="f"), frame="r", n=6)'
    e.execute("i", q)
    frag = ranked.fragment("i", "r", "standard", 0)
    src_cols = [s * SLICE_WIDTH + int(c) for s in range(SLICES) for c in np.flatnonzero(np.unpackbits(
        ranked.fragment("i", "f", "standard", s).row_dense(SRC + 2).view(np.uint8), bitorder="little"))]
    top = frag.cache.top()
    if write == "set_candidate":
        row = top[len(top) // 2].id      # a candidate: all the src's columns make it win
        for c in src_cols:
            e.execute("i", f'SetBit(rowID={row}, frame="r", columnID={int(c)})')
    elif write == "clear_candidate":
        row = _pairs(e.execute("i", q)[0])[0][0]
        for c in src_cols:
            e.execute("i", f'ClearBit(rowID={row}, frame="r", columnID={int(c)})')
    else:
        ranked_ids = {p.id for p in top}
        row = next(r for r in range(ROWS) if r not in ranked_ids)   # outside the cache
        for c in src_cols:
            e.execute("i", f'SetBit(rowID={row}, frame="r", columnID={int(c)})')
        frag.recalculate_cache()
        assert row in {p.id for p in frag.cache.top()}
    got = _pairs(e.execute("i", q)[0])
    assert got == _pairs(e_np.execute("i", q)[0])
    if write == "clear_candidate":
        assert row not in [i for i, _ in got]
    else:
        assert got[0][0] == row
    assert stats.snapshot()["topn.sparse_builds"] > SLICES


def test_ties_at_the_nth_count_go_to_the_lowest_ids(tmp_path):
    """Rows 10..19 each meet the src in 2 columns and row 5 in 3: n = 4
    takes row 5 and, of the ten tied at 2, rows 10, 11, 12 - on both
    engines, in the fragment and through the executor."""
    h = Holder(str(tmp_path / "d"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("r", FrameOptions(cache_type="ranked", cache_size=50))
    idx.create_frame("f", FrameOptions())
    idx.frame("f").import_bits([40] * 3, [0, 1, 2])   # the src
    fr = idx.frame("r")
    rows, cols = [5] * 4, [0, 1, 2, 100]
    for r in range(19, 9, -1):                     # ranked by size: 19 first
        rows += [r] * (3 + r)
        cols += [0, 1] + list(range(1000 * r, 1000 * r + 1 + r))
    fr.import_bits(rows, cols)
    want = [(5, 3), (10, 2), (11, 2), (12, 2)]
    frag = h.fragment("i", "r", "standard", 0)
    src = h.fragment("i", "f", "standard", 0).row_dense(40)
    assert _pairs(frag.top(TopOptions(n=4, src_dense=src))) == want
    for engine in ("numpy", "jax"):
        e = Executor(h, engine=engine)
        assert _pairs(e.execute("i", 'TopN(Bitmap(rowID=40, frame="f"), frame="r", n=4)')[0]) == want
    h.close()


def test_a_candidate_set_too_large_for_a_form_pages_dense(ranked, monkeypatch):
    """Where the candidates' bits are more than a form may hold, the
    fragment's chunks go through the pool's "topn" lane, with the same
    answers."""
    from pilosa_tpu import executor as executor_mod

    monkeypatch.setattr(executor_mod, "_TOPN_FORM_WORDS_MAX", 10)
    stats = ExpvarStatsClient()
    e = Executor(ranked, engine="jax", stats=stats)
    e_np = Executor(ranked, engine="numpy")
    for q in QUERIES[:6]:
        q = q.format(s=SRC + 1)
        assert _pairs(e.execute("i", q)[0]) == _pairs(e_np.execute("i", q)[0])
    snap = stats.snapshot()
    assert snap["topn.dense_fallbacks"] > 0 and snap.get("topn.sparse_builds", 0) == 0


def test_a_traced_topn_has_its_spans_and_counters(ranked):
    stats = ExpvarStatsClient()
    e = Executor(ranked, engine="jax", stats=stats)
    root = trace.Span("root")
    e.execute("i", f'TopN(Bitmap(rowID={SRC + 1}, frame="f"), frame="r", n=5)', opt=ExecOptions(span=root))
    root.finish()

    def walk(node):
        yield node
        for c in node.get("children", []):
            yield from walk(c)

    spans = list(walk(root.to_json()))
    phases = [s for s in spans if s["name"] == "topn.phase"]
    assert [p["tags"]["phase"] for p in phases] == [1, 2]
    ranked_rows = sum(len(ranked.fragment("i", "r", "standard", s).cache.top()) for s in range(SLICES))
    assert phases[0]["tags"]["candidates"] == ranked_rows == phases[0]["tags"]["scored"]
    assert phases[0]["tags"]["n"] == 5
    names = {s["name"] for p in phases for s in walk(p)}
    assert {"topn.candidates", "topn.score", "topn.select", "device.fetch"} <= names
    built = [s["tags"]["built"] for s in spans if s["name"] == "topn.candidates" and "built" in s.get("tags", {})]
    assert built == [True] * SLICES
    score = [s for s in spans if s["name"] == "topn.score"]
    assert len(score) == SLICES   # phase 2 reads phase 1's counts
    assert sum(s["tags"]["rows"] for s in score) == ranked_rows
    for s in score:
        assert 0 < s["tags"]["words"] <= s["tags"]["bucket"]
    snap = stats.snapshot()
    assert snap["topn.calls"] == 1 and snap["topn.sparse_builds"] == SLICES
    assert snap["topn.rows_scored"] == phases[0]["tags"]["scored"] + phases[1]["tags"]["scored"]
    assert snap.get("topn.dense_fallbacks", 0) == 0


def _heap_top(ids, own, counts, n, min_threshold, tanimoto, src_count):
    """The reference's heap (fragment.go:553-606) over scored candidates,
    as the fragment ran it before: the counts it keeps."""
    results = []
    for i, o, c in zip(ids.tolist(), own.tolist(), counts.tolist()):
        if n and len(results) >= n:
            threshold = results[0][0]
            if o < threshold:
                break
            if c >= threshold:
                heapq.heapreplace(results, (c, i))
            continue
        if c == 0:
            continue
        if tanimoto:
            if math.ceil(c * 100.0 / (o + src_count - c)) <= tanimoto:
                continue
        elif c < min_threshold:
            continue
        heapq.heappush(results, (c, i))
    return sorted(c for c, _ in results)


@pytest.mark.parametrize("seed", range(6))
def test_select_top_keeps_the_heaps_counts(seed):
    rng = np.random.default_rng(seed)
    k = 300
    own = np.sort(rng.integers(1, 40, size=k))[::-1].copy()
    ids = np.arange(k, dtype=np.int64)
    rng.shuffle(ids[:])
    order = np.lexsort((ids, -own))
    ids, own = ids[order], own[order]
    counts = np.minimum(own, rng.integers(0, 12, size=k))
    for n, thr, tan in [(5, 0, 0), (10, 4, 0), (0, 2, 0), (7, 0, 30), (3, 0, 80)]:
        got_ids, got = select_top(ids, own, counts, n, thr, tan, 20)
        assert sorted(got.tolist()) == _heap_top(ids, own, counts, n, thr, tan, 20)
        assert list(zip((-got).tolist(), got_ids.tolist())) == sorted(zip((-got).tolist(), got_ids.tolist()))


def test_cache_arrays_follow_the_rankings():
    c = cache_mod.RankCache(3)
    for i, n in enumerate([5, 9, 7, 1]):
        c.bulk_add(i, n)
    c.recalculate()
    v = c.version
    ids, counts = c.top_arrays()
    assert ids.tolist() == [1, 2, 0] and counts.tolist() == [9, 7, 5]
    c.recalculate()                  # ranks the same: nothing moves
    assert c.version == v and c.top_arrays()[0] is ids
    c.bulk_add(3, 8)
    c.recalculate()
    assert c.version > v and c.top_arrays()[0].tolist() == [1, 3, 2]


def test_dropping_the_frame_drops_its_forms(ranked):
    e = Executor(ranked, engine="jax")
    e.execute("i", f'TopN(Bitmap(rowID={SRC}, frame="f"), frame="r", n=3)')
    assert sorted(e._topn_forms) == [("i", "r", s) for s in range(SLICES)]
    e.drop_frame_state("i", "f")
    assert len(e._topn_forms) == SLICES
    e.drop_frame_state("i", "r")
    assert not e._topn_forms
    e.execute("i", f'TopN(Bitmap(rowID={SRC}, frame="f"), frame="r", n=3)')
    e.drop_index_state("i")
    assert not e._topn_forms
