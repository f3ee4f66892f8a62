"""The readers of root coverage (``benchmark/metrics/``: ``takeup_ms``,
``reply_ms``, ``queue_wait_ms``, ``seg_fetch_ms``, ``gc_pause_ms_per_s``,
``unnamed_ms``, ``idle_named_share``) on hand-made records and span trees,
and ``benchmark/lib/host_cover.py`` on hand-made intervals: the arithmetic
each states, nothing (never 0) where there is nothing to read, and nothing
raised on the trees of a program that lacks the spans (the parent commit's).
"""

import json
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import byname, host_cover  # noqa: E402
from lib.records import Record, Request  # noqa: E402

ALL = ["gram64.mixed_95_5", "mesh256.mixed_95_5", "seg64.tall_pairs", "seg256.tall_pairs"]
READERS = {"takeup_ms": ALL, "reply_ms": ALL, "queue_wait_ms": ALL, "seg_fetch_ms": ["seg64.tall_pairs"],
           "gc_pause_ms_per_s": ALL, "unnamed_ms": ALL, "idle_named_share": ALL}


def read(name, records, **ctx):
    return byname.load("metrics", name).read({"records": records, **ctx})


def span(name, start_ms, ms, *children, **tags):
    node = {"name": name, "start_ms": start_ms, "ms": ms}
    if tags:
        node["tags"] = tags
    if children:
        node["children"] = list(children)
    return node


def root(ms, *children, **tags):
    return [span("POST /index/i/query", 0.0, ms, *children, **tags)]


def rec(kind, t_send, t_recv, spans=None):
    return Record(0, Request(kind, "", []), t_send, t_recv, [1], spans=spans)


# -- BENCHMARK.json ----------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_metric_is_listed_with_its_cells_and_its_reader_is_a_file(name):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == READERS[name]
    assert callable(byname.load("metrics", name).read)
    reports = {m["name"] for m in bench["end_to_end"]}
    assert entry["moves"] in reports and set(entry) == {"name", "unit", "better", "source", "layer",
                                                        "moves", "workloads"}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_finds_nothing_and_raises_nothing_without_trees(name):
    ctx = {"config": {"name": "no-such-config"}}
    assert read(name, [], **ctx) is None
    assert read(name, [rec("read", 0.0, 1.0), rec("write", 0.0, 1.0)], **ctx) is None


@pytest.mark.parametrize("name", ["takeup_ms", "reply_ms", "queue_wait_ms", "seg_fetch_ms",
                                  "gc_pause_ms_per_s"])
def test_a_reader_finds_nothing_in_the_trees_of_a_program_without_the_spans(name):
    old = root(4.0, span("door.read", 0.0, 1.0), span("device", 1.5, 2.0, lane="gather"),
               cpu_ms=1.0, coalesced=2)
    assert read(name, [rec("read", 10.0, 10.006, old), rec("readback", 11.0, 11.005, old)]) is None


# -- take-up and reply -------------------------------------------------------------


def test_takeup_and_reply_split_client_latency_less_root():
    records = [
        # sent at 100.000, root began at 100.0015 and took 4 ms, answered at 100.0065
        rec("read", 100.0, 100.0065, root(4.0, t0_s=100.0015)),
        rec("readback", 200.0, 200.0100, root(6.0, t0_s=200.0005)),
        rec("write", 300.0, 300.5, root(1.0, t0_s=300.4)),         # a write: not a read's take-up
        rec("read", 400.0, 400.5),                                 # untraced: no tree
    ]
    assert read("takeup_ms", records) == pytest.approx((1.5 + 0.5) / 2)
    assert read("reply_ms", records) == pytest.approx((1.0 + 3.5) / 2)
    for r in records[:2]:   # the two parts and the root are the client's latency
        t0_s = r.spans[0]["tags"]["t0_s"]
        assert (t0_s - r.t_send) * 1e3 + r.spans[0]["ms"] + (r.t_recv - t0_s) * 1e3 - r.spans[0]["ms"] \
            == pytest.approx((r.t_recv - r.t_send) * 1e3)


@pytest.mark.parametrize("early_ms, reads", [(0.04, True), (0.2, False)])
def test_a_root_that_begins_before_its_request_was_sent_shows_two_clocks(early_ms, reads):
    records = [rec("read", 100.0, 100.010, root(4.0, t0_s=100.001)),
               rec("read", 200.0, 200.010, root(4.0, t0_s=200.0 - early_ms / 1e3))]
    got = read("takeup_ms", records), read("reply_ms", records)
    if reads:   # inside the slack of one clock read from two processes
        assert got[0] == pytest.approx((1.0 - early_ms) / 2) and got[1] is not None
    else:
        assert got == (None, None)


# -- the coalescer's wait, the one-chip fetch --------------------------------------


def test_queue_wait_is_a_mean_over_all_reads():
    follower = root(30.0, span("door.read", 0.0, 1.0), span("serve.queue", 1.2, 12.0),
                    span("serve.pass", 13.2, 16.0, leader=False, batch=7))
    owner = root(20.0, span("serve.queue", 1.0, 2.0), span("serve.pass", 3.0, 16.0, leader=True, batch=7),
                 span("pool.miss", 3.5, 9.0))
    armed = root(1.0, span("door.read", 0.0, 0.5))                  # never reached the queue: 0
    records = [rec("read", 0.0, 1.0, follower), rec("read", 0.0, 1.0, owner),
               rec("readback", 0.0, 1.0, armed), rec("write", 0.0, 1.0, root(9.0, span("serve.queue", 0.0, 9.0)))]
    assert read("queue_wait_ms", records) == pytest.approx((12.0 + 2.0 + 0.0) / 3)
    # a window whose reads never reach the queue waited 0.0 there, if the program stamps its queue
    assert read("queue_wait_ms", [rec("read", 0.0, 1.0, armed)]) is None
    assert read("queue_wait_ms", [rec("read", 0.0, 1.0, root(1.0, span("door.read", 0.0, 0.5), t0_s=5.0))]) == 0.0


def test_seg_fetch_sums_a_pass_and_weights_it_by_the_requests_it_answered():
    gathered = root(30.0, span("device", 5.0, 0.5, lane="gather", pairs=64),
                    span("device", 6.0, 0.5, lane="gather", pairs=16),
                    span("device.fetch", 7.0, 3.0), span("device.fetch", 10.0, 0.2), coalesced=6)
    alone = root(10.0, span("device", 1.0, 0.5, lane="gather"), span("device.fetch", 2.0, 1.0))
    native = root(2.0, span("device", 1.0, 0.5, lane="native"), span("device.fetch", 1.5, 9.0))
    follower = root(30.0, span("serve.queue", 1.0, 10.0))
    records = [rec("read", 0.0, 1.0, t) for t in (gathered, alone, native, follower)]
    assert read("seg_fetch_ms", records) == pytest.approx((6 * 3.2 + 1 * 1.0) / 7)
    # the mesh's name is another metric's
    mesh = root(10.0, span("device", 1.0, 0.5, lane="gather"), span("mesh.fetch", 2.0, 1.0))
    assert read("seg_fetch_ms", [rec("read", 0.0, 1.0, mesh)]) is None


# -- the collector ---------------------------------------------------------------


def test_one_pause_under_eight_trees_is_one_pause():
    pause = span("interp.gc", 2.0, 120.0, collected=31, t0_s=1000.25)
    held = [rec("read", 1000.0 + i / 100, 1000.4, root(150.0, span("door.read", 0.0, 1.0), dict(pause),
                                                       t0_s=1000.1)) for i in range(8)]
    later = rec("read", 1003.0, 1004.0, root(40.0, span("interp.gc", 1.0, 30.0, collected=2, t0_s=1003.5),
                                             t0_s=1003.2))
    quiet = rec("readback", 1001.0, 1001.1, root(3.0, t0_s=1001.01))
    # 120 + 30 ms of pauses over the 4 s from the first send to the last answer
    assert read("gc_pause_ms_per_s", held + [later, quiet]) == pytest.approx(150.0 / 4.0)
    assert read("gc_pause_ms_per_s", [quiet]) == 0.0       # trees came, none holds a pause


# -- what no span names --------------------------------------------------------------


@pytest.mark.parametrize("children, ms, want", [
    ([], 10.0, 10.0),
    ([span("door.read", 0.0, 1.0), span("encode", 9.0, 1.0)], 10.0, 8.0),
    # the pass and the leader's spans inside it overlap: counted once
    ([span("serve.queue", 1.0, 4.0), span("serve.pass", 5.0, 20.0), span("pool.miss", 6.0, 9.0),
      span("device", 15.5, 0.5), span("device.fetch", 24.0, 2.0)], 30.0, 1.0 + 4.0),
    # a pause that began before the root and a child that outlasts it are cut to the root
    ([span("interp.gc", -5.0, 7.0), span("encode", 8.0, 9.0)], 10.0, 6.0),
    ([span("a", 0.0, 10.0), span("b", 2.0, 3.0)], 10.0, 0.0),
])
def test_unnamed_is_the_root_less_the_union_of_its_children(children, ms, want):
    mod = byname.load("metrics", "unnamed_ms")
    assert mod.unnamed(root(ms, *children)[0]) == pytest.approx(want)


def test_unnamed_is_a_mean_over_reads_and_looks_at_direct_children_only():
    a = root(10.0, span("door.read", 0.0, 1.0), span("serve.repair", 2.0, 6.0, span("pool.lock_wait", 2.0, 1.0)))
    b = root(4.0, span("door.read", 0.0, 4.0))
    records = [rec("read", 0.0, 1.0, a), rec("readback", 0.0, 1.0, b), rec("write", 0.0, 1.0, root(50.0))]
    assert read("unnamed_ms", records) == pytest.approx((3.0 + 0.0) / 2)


# -- the device's idle time by what the host was doing --------------------------------


def test_idle_time_goes_to_the_shortest_open_span_that_is_no_root():
    spans = [(0, 100, "POST /index/bench/query"),       # a root: names no layer
             (10, 60, "pool.miss"), (20, 40, "pool.miss.fetch"),
             (120, 200, "POST /index/bench/query"), (130, 140, "encode"),
             (50, 90, "interp.gc"),                     # on another thread, over the first root's end
             (300, 300, "empty")]
    idle = [(0, 30), (35, 70), (95, 125), (135, 150), (210, 220)]
    got = host_cover.attribute(idle, spans)
    assert got == {
        host_cover.ROOT_ONLY: (10 - 0) + (100 - 95) + (125 - 120) + (150 - 140),
        "pool.miss": (20 - 10) + (50 - 40),
        "pool.miss.fetch": (30 - 20) + (40 - 35),
        "interp.gc": 70 - 50,                           # shorter than pool.miss where both are open
        host_cover.NO_SPAN: (120 - 100) + (220 - 210),
        "encode": 140 - 135,
    }
    assert sum(got.values()) == sum(e - s for s, e in idle)
    assert host_cover.attribute([], spans) == {} and host_cover.attribute(idle[:1], []) == {host_cover.NO_SPAN: 30}


@pytest.mark.parametrize("name, kind", [
    ("POST /index/bench/query", "root"), ("GET /status", "root"), ("pool.miss.fetch", "span"),
    ("interp.gc", "span"), ("call.Count", "span"), ("device", "span"), ("door.reply", "span"),
    ("PjitFunction(<lambda>)", None), ("tpu::System::Execute=>Done", None), ("Release semaphore", None),
    ("profile_door start_trace", None), ("$profiler.py:101 start_trace", None), ("ReadSyncFlag", None),
    ("shard_args", None), ("slice_chunk", "span"), ("encode", "span"),
])
def test_the_programs_annotations_are_told_from_the_runtimes_events(name, kind):
    got = "root" if host_cover.ROOT.match(name) else "span" if host_cover.SPAN.match(name) else None
    assert got == kind


def test_a_recorded_trace_with_no_program_span_names_none_of_its_idle_time():
    path = os.path.join(BENCH, "tests", "data", "small.xplane.pb")
    cover = host_cover.idle_by_span(path)
    assert cover["idle_s"] > 0 and cover["named_s"] == 0.0
    assert [k for k, _ in cover["by_span"]] == [host_cover.NO_SPAN]
