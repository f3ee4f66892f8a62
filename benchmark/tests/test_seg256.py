"""The four-chip paging cell ``seg256.tall_pairs`` at rehearsal size on four
virtual CPU devices (the reference agrees with the served mesh whichever rows
were resident, the sharded pool pages, its control does not pass, a traced run
reads the span metrics), and the two readers this cell brought -
``segmesh_fetch_ms`` on hand-made span trees, ``segmesh_collective_ms`` on a
small made-up ``.xplane.pb`` with two device planes."""

import os
import shutil

import pytest

from lib import byname
from lib.records import Record, Request
from test_mesh256 import _device_plane, _host_plane, _write
from test_span_metrics import root, span

CELL = "seg256.tall_pairs"
SPAN_METRICS = {"segmesh_pool_miss_ms", "segmesh_pool_miss_fetch_ms",
                "segmesh_pool_upload_bytes_per_call", "segmesh_fetch_ms"}
TRACE_METRICS = {"segmesh_device_idle_share", "segmesh_balance", "segmesh_gather_hbm_share",
                 "segmesh_collective_ms"}
END_TO_END = {"calls_per_s", "read_p50_ms", "read_p95_ms", "setup_s"}


def test_the_cell_is_what_the_issue_named(bench_json, run_mod):
    cell = next(w for w in bench_json["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("seg256", "tall_pairs", 4)
    assert len(bench_json["configs"]) >= 4 and len(bench_json["workloads"]) >= 4
    assert sum(w["chips"] == 4 for w in bench_json["workloads"][:4]) == 2      # two four-chip cells of four
    p = run_mod.plan(CELL, rehearse=False)
    cfg, mix = p["config"], p["mix"]
    assert cfg["server"]["env"] == {"PILOSA_ENGINE": "mesh"} and cfg["server"]["args"] == []
    assert (cfg["engine"], cfg["chips"], cfg["slices"], cfg["columns"]) == ("mesh", 4, 256, 256 << 20)
    assert cfg["reduced"] == ["rows", "columns"] and set(cfg["reduced_why"]) == {"rows", "columns"}
    assert cfg["rows"] == cfg["frame"]["rows"] == 8192
    assert cfg["frame"] == {"name": "stargazer", "datagen": "windows", "rows": 8192, "pool": 65536,
                            "bits_per_row_slice": {"base": 1, "step": 3, "mod": 5}}
    assert cfg["controls"] == ["drop_slice"] and cfg["load_door"] == "ingest"
    assert cfg["source_values"]["rows"] == cfg["source_values"]["columns"] == "not_at_hand"   # nothing invented
    # The data, the skew and the guarantees are seg64's; the layout is mesh256's.
    seg64 = run_mod.plan("seg64.tall_pairs", rehearse=False)["config"]
    mesh256 = run_mod.plan("mesh256.mixed_95_5", rehearse=False)["config"]
    for key in ("frame", "controls", "load_door", "index", "slice_width_bits", "rows"):
        assert cfg[key] == seg64[key], key
    assert cfg["guarantees"]["answers"] == seg64["guarantees"]["answers"]
    assert "256, 512, 768, 1,024, 1,280" in cfg["guarantees"]["what_the_comparison_catches"]
    assert cfg["assumed"]["skew"] == seg64["assumed"]["skew"]
    assert cfg["assumed"]["traffic"] == seg64["assumed"]["traffic"]
    assert cfg["assumed"]["layout"] == mesh256["assumed"]["layout"]
    assert cfg["source_values"]["cluster"] == mesh256["source_values"]["cluster"]
    assert {k: v for k, v in cfg["source_values"].items() if k != "cluster"} == seg64["source_values"]
    assert cfg["source"] != seg64["source"] and cfg["source"] != mesh256["source"]
    bits = byname.load("datagens", "windows").bits_of_rows(cfg["frame"])
    assert (bits.min(), bits.max(), round(float(bits.mean()), 2)) == (1, 5, 3.0)
    assert sorted(set((bits * cfg["slices"]).tolist())) == [256, 512, 768, 1024, 1280]    # stars a user
    assert int(bits.sum()) * cfg["slices"] == 6291200
    # the traffic file is seg64.tall_pairs', unchanged
    assert mix == run_mod.plan("seg64.tall_pairs", rehearse=False)["mix"]
    assert (mix["generator"], mix["loop"], mix["clients"], mix["read_calls"]) == ("pairs_rw", "closed", 8, 32)
    assert mix["ops"] == ["Intersect", "Union", "Difference", "Xor"]
    assert (mix["hot_rows"], mix["hot_share"], mix["zipf_s"], mix["write_share"]) == (192, 0.95, 1.0, 0)
    assert {m["name"] for m in p["per_layer"]} == SPAN_METRICS | TRACE_METRICS
    assert {m["name"] for m in p["end_to_end"]} == END_TO_END
    moves = {m["name"]: (m["moves"], m["source"], m["layer"]) for m in p["per_layer"]}
    assert moves["segmesh_fetch_ms"] == ("read_p50_ms", "program_span", "mesh collectives")
    assert moves["segmesh_collective_ms"] == ("read_p50_ms", "device_trace", "mesh collectives")
    assert moves["segmesh_pool_miss_ms"][0] == "read_p95_ms"
    assert moves["segmesh_gather_hbm_share"] == ("calls_per_s", "device_trace", "kernels")
    # the rehearsal runs the same lane on four forced host devices
    reh = run_mod.plan(CELL, rehearse=True)["config"]
    assert (reh["chips"], reh["slices"], reh["frame"]["rows"]) == (4, 8, 256)


def test_rehearsal_is_correct_on_a_mesh_of_four_and_reports_the_four(rehearse):
    rc, line = rehearse(CELL, seed=2**31 + 34)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4          # and run.py refused unless /status said engine mesh
    assert set(line["metrics"]) == END_TO_END
    assert line["checks"]["answers_checked"]["value"] > 100
    assert line["checks"]["compilations_in_window"]["value"] == 0


def test_control_drop_slice_is_not_correct(rehearse):
    rc, line = rehearse(CELL, seed=35, control="drop_slice")
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0


def test_traced_rehearsal_pages_across_the_mesh_and_reads_the_span_metrics(rehearse):
    rc, line = rehearse(CELL, seed=36, seconds=4.0, trace=1)
    assert rc == 0 and line["correct"] is True
    # No device plane on the CPU: the four device-trace readers find nothing to read.
    assert set(line["metrics"]) == SPAN_METRICS
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["segmesh_pool_miss_fetch_ms"] < m["segmesh_pool_miss_ms"]
    assert m["segmesh_pool_upload_bytes_per_call"] > 0
    assert m["segmesh_fetch_ms"] > 0             # the reads waited for the mesh, under a span


# -- segmesh_fetch_ms on hand-made span trees ----------------------------------

def _record(spans, kind="read", t_send=0.0):
    return Record(0, Request(kind, "", []), t_send, t_send + 1.0, [1], spans=spans)


def _gather(start_ms, lane="gather"):
    return dict(span("device", 0.4, lane=lane, pairs=64, unique_rows=100, devices=4), start_ms=start_ms)


def test_fetch_ms_is_the_wait_for_the_mesh_a_request():
    read = byname.load("metrics", "segmesh_fetch_ms").read
    # A pass of 7 requests: four dispatches, then four fetches (3.0 ms, then nearly nothing).
    big = root(40.0, span("pool.miss", 14.0), _gather(16.0), _gather(16.5), _gather(17.0), _gather(17.5),
               span("mesh.fetch", 3.0), span("mesh.fetch", 0.05), span("mesh.fetch", 0.05),
               span("mesh.fetch", 0.1), coalesced=7)
    # A pass of one request: one dispatch, one fetch under a deeper span.
    small = root(9.0, span("exec", 8.0, _gather(2.0), span("mesh.fetch", 1.6)), coalesced=1)
    # A request coalesced into another's pass carries no spans of the pass; one answered
    # from the Gram gathered nothing; a write is no read: none of them counts.
    rider = root(40.0, coalesced=1)
    gram = root(2.0, _gather(1.0, lane="native"), span("mesh.fetch", 50.0))
    write = root(2.0, _gather(1.0), span("mesh.fetch", 70.0))
    ctx = {"records": [_record(big), _record(small), _record(rider), _record(gram),
                       _record(write, kind="write")]}
    assert read(ctx) == pytest.approx((7 * 3.2 + 1 * 1.6) / 8)
    # A program whose gather dispatches block one by one has no such span: nothing to read.
    old = root(40.0, _gather(16.0), _gather(19.0), coalesced=7)
    assert read({"records": [_record(old)]}) is None
    assert read({"records": []}) is None


# -- segmesh_collective_ms on a made-up trace ------------------------------------

def test_collective_ms_is_the_psums_device_time_a_gather_dispatch(tmp_path):
    read = byname.load("metrics", "segmesh_collective_ms").read
    # test_mesh256's made-up ops: 3 = an all-reduce under .../psum, 4 = an all-gather known by
    # its opcode.  Device 0 is the busiest: all-reduce 1,000 us + all-gather 100 us inside the window.
    dev0 = [(1, 0, 2000), (2, 2000, 500), (3, 2500, 1000), (4, 5000, 100)]
    dev1 = [(1, 0, 2000), (2, 2000, 1400), (3, 3400, 100)]
    path = _write(tmp_path, _device_plane(0, dev0) + _device_plane(1, dev1) + _host_plane([]))
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    trace_dir = os.path.join(repo, ".bench", "made_up_seg.cell", "trace", "plugins", "profile", "t0")
    os.makedirs(trace_dir)
    # The traced span on the client's clock: 10.0 .. 13.0 s.  A request sent at 9.99 s whose
    # dispatches began 5 ms and 20 ms in (one before the span, one inside it); one inside with four
    # dispatches, one of them of another lane; one sent inside whose dispatch began after the stop.
    tree_a = root(30.0, _gather(5.0), _gather(20.0))
    tree_b = root(30.0, _gather(1.0), _gather(2.0), _gather(3.0), _gather(4.0, lane="native"))
    tree_c = root(30.0, _gather(15.0))
    ctx = {"config": {"name": "made_up_seg"}, "traced": (10.0, 13.0),
           "records": [_record(tree_a, t_send=9.99), _record(tree_b, t_send=11.0),
                       _record(tree_c, t_send=12.99), _record(None, t_send=11.5)]}
    try:
        os.replace(path, os.path.join(trace_dir, "made_up.xplane.pb"))
        assert read(ctx) == pytest.approx(1.1 / 4)          # 1.1 ms of collectives over 4 dispatches
        assert read({**ctx, "records": [_record(tree_c, t_send=12.99)]}) is None    # nothing gathered in it
    finally:
        shutil.rmtree(os.path.join(repo, ".bench", "made_up_seg.cell"))
    # A run that left no trace: nothing to read, and no error.
    assert read(ctx) is None
