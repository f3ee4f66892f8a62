"""The Star Trace index whole, ``startrace256.lang_triples``: its data generator
(two frames over one pool of repositories a slice: every starred repository
has exactly one language), its request generator ``xframe_counts`` (streams
a pure function of (seed, client, phase), bodies half three-operand and half
two-operand, the users' hot share, languages by their rank in the data, the
answers equal the ``sets`` reference's), the cell at rehearsal size on four
virtual CPU devices, and its three readers on hand-made span trees."""

import numpy as np
import pytest

from conftest import read_on_cpu
from lib import byname, schema
from lib.records import SLICE_WIDTH, Record, Request

CELL = "startrace256.lang_triples"
NEW_METRICS = {"xframe_hbm_share", "xframe_acquire_ms", "xframe_unfused_share"}
END_TO_END = {"calls_per_s", "read_p50_ms", "read_p95_ms", "setup_s"}


def _plan(run_mod, rehearse=True):
    return run_mod.plan(CELL, rehearse=rehearse)


def _data(run_mod, seed=2**31 + 41, rehearse=True):
    p = _plan(run_mod, rehearse)
    return p, schema.make(p["config"], seed)


def test_the_cell_is_the_deployment_and_mix_it_names(bench_json, run_mod):
    cell = next(w for w in bench_json["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("startrace256", "lang_triples", 4)
    assert sum(w["chips"] == 4 for w in bench_json["workloads"]) == 3 == len(bench_json["workloads"]) // 2
    p = _plan(run_mod, rehearse=False)
    cfg, mix = p["config"], p["mix"]
    assert cfg["server"]["env"] == {"PILOSA_ENGINE": "mesh"} and cfg["server"]["args"] == []
    assert (cfg["engine"], cfg["chips"], cfg["slices"], cfg["reference"]) == ("mesh", 4, 256, "sets")
    assert cfg["reduced"] == ["rows", "columns"] and cfg["controls"] == ["drop_slice"]
    assert cfg["load_door"] == "ingest"
    seg256 = run_mod.plan("seg256.tall_pairs", rehearse=False)["config"]
    users, langs = cfg["frames"]
    assert {k: users[k] for k in ("name", "rows", "pool", "bits_per_row_slice")} == \
        {k: seg256["frame"][k] for k in ("name", "rows", "pool", "bits_per_row_slice")}
    assert (langs["name"], langs["rows"], langs["pool"], langs["zipf_s"]) == ("language", 64, 65536, 1.0)
    assert users["columns_seed"] == langs["columns_seed"]
    assert (mix["generator"], mix["loop"], mix["clients"], mix["read_calls"]) == ("xframe_counts", "closed", 8, 32)
    assert (mix["hot_rows"], mix["hot_share"], mix["zipf_s"], mix["write_share"]) == (192, 0.95, 1.0, 0)
    assert {m["name"] for m in p["per_layer"]} == NEW_METRICS
    assert {m["name"] for m in p["end_to_end"]} == END_TO_END
    moves = {m["name"]: (m["moves"], m["source"], m["layer"]) for m in p["per_layer"]}
    assert moves["xframe_hbm_share"] == ("calls_per_s", "device_trace", "kernels")
    assert moves["xframe_acquire_ms"] == ("read_p50_ms", "program_span", "row pool and Gram repair")
    assert moves["xframe_unfused_share"] == ("calls_per_s", "program_span", "server host path")
    # the language shares the configuration assumes: Zipf 1.0 over 64 ranks
    shares = byname.load("datagens", "startrace").label_shares(langs)
    assert round(100 * shares[0], 1) == 21.1 and round(100 * shares[-1], 2) == 0.33


def test_every_starred_repository_has_exactly_one_language(run_mod):
    p, data = _data(run_mod)
    users, langs = data
    assert (users.frame.name, langs.frame.name) == ("stargazer", "language")
    lang_cols = langs.cols.astype(np.int64)
    assert len(np.unique(lang_cols)) == len(lang_cols)             # one language a repository
    assert np.isin(users.cols.astype(np.int64), lang_cols).all()   # every starred one has it
    cfg = p["config"]
    assert len(lang_cols) == cfg["slices"] * cfg["frames"][1]["pool"]
    # users keep windows.py's rule and numbers
    bits = byname.load("datagens", "windows").bits_of_rows(cfg["frames"][0])
    assert len(users.rows) == int(bits.sum()) * cfg["slices"]


def test_data_is_a_function_of_the_seed(run_mod):
    cfg = _plan(run_mod)["config"]
    a, b, c = schema.make(cfg, 5), schema.make(cfg, 5), schema.make(cfg, 6)
    for x, y in zip(a, b):
        assert np.array_equal(x.rows, y.rows) and np.array_equal(x.cols, y.cols)
    assert not np.array_equal(a[1].rows, c[1].rows)      # other languages
    assert not np.array_equal(a[0].cols, c[0].cols)      # other windows


def _gen():
    return byname.load("generators", "xframe_counts")


def _streams(p, seed, phase, clients=None):
    gen, cfg = _gen(), p["config"]
    return [gen.Stream(p["mix"], p["frames"], cfg["slices"] * SLICE_WIDTH, seed, c, phase)
            for c in (range(p["mix"]["clients"]) if clients is None else clients)]


def test_streams_are_a_pure_function_of_seed_client_and_phase(run_mod):
    p = _plan(run_mod, rehearse=False)
    seed = 2**31 + 7
    for client, phase in ((0, 0), (3, 0), (3, 2)):
        a, b = _streams(p, seed, phase, [client]) + _streams(p, seed, phase, [client])
        assert [a.next().body for _ in range(5)] == [b.next().body for _ in range(5)]
    (x,), (y,), (z,) = _streams(p, seed, 0, [0]), _streams(p, seed, 0, [1]), _streams(p, seed, 1, [0])
    assert x.next().body != y.next().body
    assert _streams(p, seed, 0, [0])[0].next().body != z.next().body


def test_bodies_are_half_of_each_form_and_never_repeat(run_mod):
    p = _plan(run_mod, rehearse=False)
    (s,) = _streams(p, 11, 0, [2])
    bodies = set()
    for _ in range(200):
        req = s.next()
        assert req.body not in bodies
        bodies.add(req.body)
        assert len(req.calls) == 32 and _gen().n_calls(req) == 32
        three = [c for c in req.calls if len(c) == 4]
        two = [c for c in req.calls if len(c) == 3]
        assert len(three) == len(two) == 16
        for c in three:
            assert [o[0] for o in c[1:]] == ["stargazer", "stargazer", "language"] and c[1] != c[2]
        for c in two:
            assert [o[0] for o in c[1:]] == ["stargazer", "language"]
        assert req.body.count("Count(Intersect(") == 32


def test_hot_share_and_popular_languages(run_mod):
    p = _plan(run_mod, rehearse=False)
    seed = 2**31 + 3
    hot = set(byname.load("generators", "pairs_rw").ranking(seed, 8192)[:192].tolist())
    lab = _gen().label_ranking(p["mix"], p["frames"], seed)
    users, langs = [], []
    for s in _streams(p, seed, 0):
        for _ in range(40):
            for c in s.next().calls:
                users += [o[1] for o in c[1:] if o[0] == "stargazer"]
                langs += [o[1] for o in c[1:] if o[0] == "language"]
    share = np.isin(users, list(hot)).mean()
    assert 0.93 < share < 0.97, share
    counts = np.bincount(langs, minlength=64)
    assert counts[lab[0]] > counts[lab[8]] > counts[lab[63]]
    assert 0.18 < counts[lab[0]] / len(langs) < 0.24
    # the ranking is the data's: the language of rank 1 holds the most repositories
    data = schema.make(_plan(run_mod)["config"], seed)
    lab_reh = _gen().label_ranking(_plan(run_mod)["mix"], _plan(run_mod)["frames"], seed)
    held = np.bincount(data[1].rows.astype(np.int64), minlength=8)
    assert held.argmax() == lab_reh[0]


def test_fill_names_every_hot_user_and_every_language(run_mod):
    p = _plan(run_mod, rehearse=False)
    seed = 2**31 + 5
    reqs = list(_gen().fill_requests(p["mix"], p["frames"], 256 * SLICE_WIDTH, seed))
    assert len(reqs[0].calls) == 2 and all(len(r.calls) == 32 for r in reqs[1:])
    named = {o for r in reqs for c in r.calls for o in c[1:]}
    hot = byname.load("generators", "pairs_rw").ranking(seed, 8192)[:192]
    assert {("stargazer", int(u)) for u in hot} <= named
    cold = byname.load("generators", "pairs_rw").ranking(seed, 8192)[192:576]
    assert {("stargazer", int(u)) for u in cold} <= named        # more users than the pool's 256 slots
    assert {("language", lg) for lg in range(64)} <= named


def test_a_slow_program_ends_the_fill_at_its_deadline(run_mod, monkeypatch):
    """At a pace that would end the fill past ``WARM_BUDGET_S``, the fill
    stops with ``TimeoutError`` (an ``OSError``: ``run.py`` ends the run as a
    failure with no result); so does a warm-up phase's stream."""
    gen = _gen()
    p = _plan(run_mod, rehearse=False)
    clock = [1000.0]
    monkeypatch.setattr(gen, "_deadline", [None])
    monkeypatch.setattr(gen, "_pace", {"per_call": None})
    monkeypatch.setattr(gen.time, "perf_counter", lambda: clock[0])
    it = iter(gen.fill_requests(p["mix"], p["frames"], 256 * SLICE_WIDTH, 1))
    next(it)
    clock[0] += 10.0                   # the probe's two calls took 10 s: 5 s a call
    with pytest.raises(TimeoutError):
        next(it)                       # 32 calls would end 160 s on: past the budget
    assert issubclass(TimeoutError, OSError)
    it = iter(gen.fill_requests(p["mix"], p["frames"], 256 * SLICE_WIDTH, 1))
    for _ in it:
        clock[0] += 0.01               # a fast program: the whole fill
    gen.Stream(p["mix"], p["frames"], 256 * SLICE_WIDTH, 1, 0, phase=1)
    clock[0] += gen.WARM_BUDGET_S
    with pytest.raises(TimeoutError):
        gen.Stream(p["mix"], p["frames"], 256 * SLICE_WIDTH, 1, 0, phase=2)
    with pytest.raises(TimeoutError):
        gen.Stream(p["mix"], p["frames"], 256 * SLICE_WIDTH, 1, 0, phase=0)


def test_expected_equals_the_sets_reference_count(run_mod):
    p, data = _data(run_mod)
    ref = byname.load("references", "sets").build(data)
    gen = _gen()
    n = 0
    for s in _streams(p, 2**31 + 41, 0):
        for _ in range(6):
            req = s.next()
            want = [ref.count("Intersect", *c[1:]) for c in req.calls]
            assert gen.expected(ref, req) == want
            n += sum(w > 0 for w in want)
    assert n > 0        # the comparison sees counts that are not 0


def test_rehearsal_is_correct_on_a_mesh_of_four_and_reports_the_four(rehearse):
    rc, line = rehearse(CELL, seed=2**31 + 41)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == END_TO_END


def test_control_drop_slice_is_not_correct(rehearse):
    rc, line = rehearse(CELL, seed=2**31 + 42, control="drop_slice")
    assert rc == 0 and line["correct"] is False


def test_traced_rehearsal_reads_the_span_metrics(rehearse, bench_json):
    rc, line = rehearse(CELL, seed=2**31 + 43, seconds=4.0, trace=1)
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == read_on_cpu(bench_json, CELL) == {"xframe_acquire_ms", "xframe_unfused_share"}
    assert line["metrics"]["xframe_unfused_share"]["value"] == 0.0
    assert line["metrics"]["xframe_acquire_ms"]["value"] > 0


def _tree(root_tags, *children):
    return [{"name": "POST /index/bench/query", "start_ms": 0.0, "ms": 10.0, "tags": root_tags,
             "children": list(children)}]


def _span(name, ms, tags=None, *children):
    return {"name": name, "start_ms": 1.0, "ms": ms, "tags": tags or {}, "children": list(children)}


def _rec(tree, calls=32):
    return Record(0, Request("read", "", [("Intersect",)] * calls), 0.0, 1.0, [0] * calls, spans=tree)


def test_acquire_ms_is_the_wait_for_both_pools_a_request_a_pass_answered():
    read = byname.load("metrics", "xframe_acquire_ms").read
    owner = _tree({"coalesced": 3, "unfused": 0},
                  _span("xframe.acquire", 4.0, {"frames": 2}, _span("pool.miss", 3.0)))
    alone = _tree({"unfused": 0}, _span("xframe.acquire", 1.0))
    rider = _tree({"unfused": 0}, _span("serve.queue", 2.0))
    assert read({"records": [_rec(owner), _rec(alone), _rec(rider)]}) == pytest.approx((3 * 4.0 + 1.0) / 4)
    assert read({"records": [_rec(rider)]}) is None          # the parent's trees: nothing to read


def test_unfused_share_is_the_share_of_count_calls_that_fell_back():
    read = byname.load("metrics", "xframe_unfused_share").read
    fused, fell = _tree({"unfused": 0}), _tree({"unfused": 32})
    assert read({"records": [_rec(fused), _rec(fused)]}) == 0.0
    assert read({"records": [_rec(fused), _rec(fell), _rec(_tree({}))]}) == pytest.approx(100 / 3)
    assert read({"records": [_rec(_tree({}))]}) is None      # no tag anywhere: the program does not say
