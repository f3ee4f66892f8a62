"""The seven readers that take one layer's spans out of the request trees,
each on made-up trees: the arithmetic it states, writes left out of read
metrics, nothing (never 0) where there is nothing to read, nested and
repeated spans summed once.  Then one traced rehearsal whose last line
holds every per-layer metric ``BENCHMARK.json`` lists, each with a value."""

import pytest

from lib import spantree
from lib.records import Record, Request

NEW = ["door_ms", "read_cpu_ms", "read_wait_ms", "pool_lock_wait_ms", "repair_ms",
       "repair_device_wait_ms", "write_apply_ms"]


def reader(run_mod, name):
    return lambda records: run_mod.read_metric(name, {"records": records})


def rec(kind, spans=None):
    return Record(0, Request(kind, "", []), 0.0, 1.0, [1], spans=spans)


def span(name, ms, *children, **tags):
    node = {"name": name, "start_ms": 0.0, "ms": ms}
    if tags:
        node["tags"] = tags
    if children:
        node["children"] = list(children)
    return node


def root(ms, *children, **tags):
    return [span("POST /index/i/query", ms, *children, **tags)]


def repair(wait_ms, repair_ms=None, gram_ms=None):
    """A read's ``serve.repair`` as the program nests it."""
    inner = [span("pool.lock_wait", wait_ms)]
    if repair_ms is not None:
        stages = [span("pool.fetch", 1.0), span("pool.scatter", 2.0)]
        if gram_ms is not None:
            stages.append(span("pool.gram", gram_ms))
        inner.append(span("pool.repair", repair_ms, *stages, planes=1))
    return span("serve.repair", wait_ms + (repair_ms or 0.0), *inner, repaired=repair_ms is not None)


def test_named_finds_nested_spans_and_counts_each_once():
    tree = root(50.0, span("door.read", 1.0), repair(10.0, 30.0, 20.0),
                span("device", 2.0, span("device", 1.5)),      # same name inside: part of the outer
                span("device", 3.0))                           # repeated: counted again
    assert spantree.ms_of(tree, "pool.gram") == (1, 20.0)
    assert spantree.ms_of(tree, "device") == (2, 5.0)
    assert spantree.ms_of(tree, "door.read", "pool.lock_wait") == (2, 11.0)
    assert spantree.ms_of(tree, "encode") == (0, 0)
    assert spantree.ms_of(None, "encode") == (0, 0)
    assert spantree.root_tag(root(1.0, cpu_ms=0.5), "cpu_ms") == 0.5
    assert spantree.root_tag(root(1.0), "cpu_ms") is None
    assert spantree.percentile([], 0.95) is None and spantree.mean([]) is None
    assert spantree.percentile(list(range(1, 101)), 0.95) == 95


def test_door_ms_is_the_mean_of_door_admission_and_encode_per_read(run_mod):
    read = reader(run_mod, "door_ms")
    recs = [rec("read", root(10.0, span("door.read", 0.25), span("qos.admit", 0.5), span("device", 5.0),
                             span("encode", 0.25))),
            rec("readback", root(40.0, span("door.read", 1.0), span("encode", 2.0))),
            rec("write", root(9.0, span("door.read", 100.0), span("encode", 100.0))),   # not a read
            rec("read")]                                                                   # untraced
    assert read(recs) == pytest.approx((1.0 + 3.0) / 2)
    # qos.admit alone is the parent program's tree: the door has no span there.
    assert read([rec("read", root(10.0, span("qos.admit", 0.5), span("device", 5.0)))]) is None
    assert read([rec("write", root(9.0, span("door.read", 1.0)))]) is None
    assert read([]) is None


def test_read_cpu_and_wait_split_the_root(run_mod):
    recs = [rec("read", root(10.0, span("device", 1.0), cpu_ms=2.0)),
            rec("readback", root(50.0, repair(20.0, 25.0, 15.0), cpu_ms=6.0)),
            rec("write", root(1000.0, cpu_ms=900.0)),          # not a read
            rec("read", root(7.0)),                            # a tree without the tag: left out of both
            rec("read")]
    assert reader(run_mod, "read_cpu_ms")(recs) == pytest.approx(4.0)
    assert reader(run_mod, "read_wait_ms")(recs) == pytest.approx((8.0 + 44.0) / 2)
    assert reader(run_mod, "read_cpu_ms")([rec("read", root(7.0))]) is None
    assert reader(run_mod, "read_wait_ms")([rec("read", root(7.0))]) is None
    assert reader(run_mod, "read_cpu_ms")([rec("write", root(7.0, cpu_ms=1.0))]) is None


def test_pool_lock_wait_is_the_p95_over_all_reads(run_mod):
    read = reader(run_mod, "pool_lock_wait_ms")
    quiet = [rec("read", root(5.0, span("device", 1.0))) for _ in range(17)]
    waits = [rec("readback", root(60.0, repair(12.0, 30.0, 20.0))),
             rec("read", root(60.0, repair(30.0), span("pool.lock_wait", 4.0))),   # two waits in one request
             rec("read", root(60.0, repair(8.0)))]
    # 20 reads, nearest rank 19: 0 x17, 8, 12, 34.
    assert read(quiet + waits) == pytest.approx(12.0)
    assert read(quiet + waits + [rec("write", root(99.0, span("pool.lock_wait", 99.0)))]) == pytest.approx(12.0)
    assert read(quiet[:16] + waits) == pytest.approx(34.0)     # 19 reads, rank 19
    assert read(quiet + waits[:1] + quiet + quiet) == 0.0      # a rare wait: the tail is 0, and that is a reading
    assert read(quiet) is None                                 # no read went to the pool
    assert read([]) is None


def test_repair_ms_and_its_device_wait_are_medians_over_the_windows_repairs(run_mod):
    recs = [rec("readback", root(60.0, repair(1.0, 30.0, 20.0))),
            rec("read", root(60.0, repair(2.0, 36.0, 26.0))),
            rec("read", root(60.0, repair(40.0))),             # waited, repaired nothing
            rec("read", root(60.0, repair(3.0, 24.0))),        # a repair with no Gram to patch
            rec("read", root(5.0, span("device", 1.0)))]
    assert reader(run_mod, "repair_ms")(recs) == pytest.approx(30.0)
    assert reader(run_mod, "repair_device_wait_ms")(recs) == pytest.approx(23.0)
    assert reader(run_mod, "repair_ms")(recs[2:3] + recs[4:]) is None
    assert reader(run_mod, "repair_device_wait_ms")(recs[2:]) is None


def test_write_apply_is_the_median_over_setbits(run_mod):
    read = reader(run_mod, "write_apply_ms")
    recs = [rec("write", root(14.0, span("write.apply", 0.25, changed=1))),
            rec("write", root(15.0, span("write.apply", 0.5, span("device", 0.4, lane="native")))),
            rec("write", root(90.0, span("write.apply", 2.0))),
            rec("write", root(3.0)),                           # a lane with no span: left out
            rec("read", root(5.0, span("write.apply", 77.0)))]  # not a SetBit
    assert read(recs) == pytest.approx(0.5)
    assert read(recs[3:]) is None
    assert read([]) is None


@pytest.mark.parametrize("name", NEW)
def test_the_parents_trees_read_as_nothing(run_mod, name):
    """The program before these spans: a root, ``qos.admit`` and the native
    crossing.  Every new reader finds nothing there and does not raise."""
    old = [rec("read", root(14.0, span("qos.admit", 0.02), span("device", 0.7, lane="native"),
                            qos_class="read", lane="flat")),
           rec("write", root(13.0, span("qos.admit", 0.02), lane="write_fast")),
           rec("readback", root(50.0, span("qos.admit", 0.02), span("device", 0.6)))]
    assert reader(run_mod, name)(old) is None


def test_traced_run_reports_every_per_layer_metric_listed(rehearse, bench_json):
    """``test_served.py`` pins this run's metrics to the four names the
    benchmark started with; here they are the names ``BENCHMARK.json`` lists."""
    rc, line = rehearse("gram64.mixed_95_5", seed=2**31 + 26, seconds=6.0, trace=1)
    assert rc == 0 and line["correct"] is True
    want = {m["name"] for m in bench_json["per_layer"]
            if "gram64.mixed_95_5" in m.get("workloads", ["gram64.mixed_95_5"])}
    assert len(want) == 11 and set(NEW) < want
    assert set(line["metrics"]) == want
    assert all(set(v) == {"value", "unit"} and v["value"] is not None for v in line["metrics"].values())
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["read_cpu_ms"] > 0 and m["read_wait_ms"] > 0
    assert m["read_cpu_ms"] + m["read_wait_ms"] == pytest.approx(m["exec_host_ms"], rel=1e-6)
    assert 0 < m["repair_device_wait_ms"] < m["repair_ms"]
    assert 0 < m["write_apply_ms"] < m["write_ack_ms"]
    assert 0 < m["door_ms"] < m["exec_host_ms"]
    assert 2.9 < line["device"]["window_s"] < 3.1          # between the door's own two markers
    gaps = [g[0] for g in line["breakdown"]["idle_gaps"]]
    assert gaps and not any(".py:" in g for g in gaps)     # named by spans, not by Python frames
