"""The four-chip cell ``mesh256.mixed_95_5`` at rehearsal size on four
virtual CPU devices (the reference agrees with the served mesh, its control
does not, a traced run reads the four span metrics), and the reader of what
a cell on several chips is for (``lib/mesh_trace.py``) on a small made-up
``.xplane.pb`` with two device planes."""

import os

import pytest

from lib import mesh_trace

CELL = "mesh256.mixed_95_5"
SPAN_METRICS = {"mesh_repair_ms", "mesh_repair_device_wait_ms", "mesh_pool_lock_wait_ms",
                "mesh_exec_host_ms"}
TRACE_METRICS = {"mesh_device_idle_share", "mesh_balance", "mesh_collective_ms"}


def test_the_cell_is_what_the_issue_named(bench_json, run_mod):
    cell = next(w for w in bench_json["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("mesh256", "mixed_95_5", 4)
    p = run_mod.plan(CELL, rehearse=False)
    cfg = p["config"]
    assert cfg["server"]["env"] == {"PILOSA_ENGINE": "mesh"} and cfg["server"]["args"] == []
    assert (cfg["engine"], cfg["chips"], cfg["slices"], cfg["columns"]) == ("mesh", 4, 256, 256 << 20)
    assert cfg["reduced"] == ["columns"] and set(cfg["reduced_why"]) == {"columns"}
    gram64 = run_mod.plan("gram64.mixed_95_5", rehearse=False)["config"]
    for key in ("frame", "guarantees", "controls", "load_door", "index", "slice_width_bits"):
        assert cfg[key] == gram64[key], key          # the data and the guarantees are gram64's
    assert {k: v for k, v in cfg["assumed"].items() if k != "layout"} == gram64["assumed"]
    assert {m["name"] for m in p["per_layer"]} == SPAN_METRICS | TRACE_METRICS
    assert {m["name"] for m in p["end_to_end"]} == {
        "calls_per_s", "read_p50_ms", "read_p95_ms", "write_to_read_p95_ms", "setup_s"}


def test_rehearsal_is_correct_on_a_mesh_of_four(rehearse):
    rc, line = rehearse(CELL, seed=2**31 + 28)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4          # and run.py refused unless /status said engine mesh
    assert set(line["metrics"]) == {"calls_per_s", "read_p50_ms", "read_p95_ms",
                                    "write_to_read_p95_ms", "setup_s"}
    assert line["checks"]["answers_checked"]["value"] > 100


def test_control_stale_read_is_not_correct(rehearse):
    rc, line = rehearse(CELL, seed=29, control="stale_read")
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0


def test_traced_rehearsal_reads_the_span_metrics(rehearse):
    rc, line = rehearse(CELL, seed=30, seconds=5.0, trace=1)
    assert rc == 0 and line["correct"] is True
    # The device-trace readers find no device plane on the CPU and say nothing.
    assert set(line["metrics"]) == SPAN_METRICS
    assert all(v["value"] > 0 and v["unit"] == "ms" for v in line["metrics"].values())
    assert line["metrics"]["mesh_repair_device_wait_ms"]["value"] < line["metrics"]["mesh_repair_ms"]["value"]


# -- lib/mesh_trace.py on a made-up trace --------------------------------------

_OPS = {   # metadata id -> (HLO text, hlo_category, tf_op)
    1: ("%copy.2 = u32[64,256,256,128] copy(u32[64,256,256,128] %p0)", "data formatting",
        "jit(kernel)/shard_map/pool.set_plane_rows/scatter"),
    2: ("%fusion.7 = s32[256] fusion(u32[64,256,256,128] %p0), kind=kCustom", "custom-call",
        "jit(gram_update)/pool.gram_update/jit(kernel)/shard_map/jit(fused_resident_count2)/pallas_call"),
    3: ("%all-reduce.1 = s32[256] all-reduce(s32[256] %fusion.7), replica_groups={}", "all-reduce",
        "jit(gram_update)/pool.gram_update/jit(kernel)/shard_map/psum"),
    4: ("%all-gather-start.3 = u32[8] all-gather-start(u32[2] %x)", "", ""),   # no metadata stats
}


def _device_plane(n: int, events) -> str:
    """``events``: [(metadata id, start us, duration us)] on the plane's
    "XLA Ops" line; the line starts at 1 ms."""
    evs = "".join(f"events {{ metadata_id: {m} offset_ps: {s * 10**6} duration_ps: {d * 10**6} }} "
                  for m, s, d in events)
    meta = ""
    for k, (name, cat, tf_op) in _OPS.items():
        stats = (f'stats {{ metadata_id: 1 str_value: "{cat}" }} ' if cat else "") + (
            f"stats {{ metadata_id: 2 ref_value: {10 + k} }} " if tf_op else "")
        meta += f'event_metadata {{ key: {k} value {{ id: {k} name: "{name}" {stats}}} }} '
        if tf_op:   # a string stat by reference: its text is a stat_metadata name
            meta += f'stat_metadata {{ key: {10 + k} value {{ id: {10 + k} name: "{tf_op}" }} }} '
    return (f'planes {{ id: {n + 1} name: "/device:TPU:{n}" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000 {evs}}} '
            f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 1000000 '
            f'events {{ metadata_id: 1 offset_ps: 0 duration_ps: 1 }} }} {meta}'
            'stat_metadata { key: 1 value { id: 1 name: "hlo_category" } } '
            'stat_metadata { key: 2 value { id: 2 name: "tf_op" } } }')


def _host_plane(repairs_at_us) -> str:
    """The door's markers round 1,000 us .. 11,000 us of the trace, and a
    ``pool.repair`` annotation at each given time."""
    names = {1: "profile_door start_trace", 2: "profile_door stop_trace", 3: "pool.repair"}
    evs = ("events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000000 } "
           "events { metadata_id: 2 offset_ps: 11000000000 duration_ps: 500000000 } ")
    evs += "".join(f"events {{ metadata_id: 3 offset_ps: {t * 10**6} duration_ps: 900000000 }} "
                   for t in repairs_at_us)
    meta = "".join(f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }} '
                   for k, n in names.items())
    return f'planes {{ id: 9 name: "/host:CPU" lines {{ id: 1 name: "python3" {evs}}} {meta}}}'


def _write(tmp_path, text: str) -> str:
    from jax.profiler import ProfileData

    path = tmp_path / "made_up.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_two_device_planes_give_per_device_busy_and_collective_time(tmp_path):
    # The device lines start at 1,000 us = the end of start_trace; with the clocks' slack
    # of 1 ms a side the window is 0 .. 12,000 us of the trace.
    # Device 0: copy 0..2,000 us, kernel 2,000..2,500, all-reduce 2,500..3,500 (it waits
    # for device 1), and one all-gather-start known by its opcode alone, 5,000..5,100.
    dev0 = [(1, 0, 2000), (2, 2000, 500), (3, 2500, 1000), (4, 5000, 100)]
    # Device 1: copy 0..2,000, kernel 2,000..3,400 (the slow one), all-reduce 3,400..3,500;
    # and an op after the stop marker, outside the window.
    dev1 = [(1, 0, 2000), (2, 2000, 1400), (3, 3400, 100), (1, 11500, 1000)]
    path = _write(tmp_path, _device_plane(0, dev0) + _device_plane(1, dev1)
                  + _host_plane([1200, 6000, 12300]))
    r = mesh_trace.reduce_mesh(path)
    assert r["devices"] == ["/device:TPU:0", "/device:TPU:1"]
    assert r["window_s"] == pytest.approx(0.012)
    assert r["busy_s"] == pytest.approx([3600e-6, 3500e-6])
    assert r["busiest"] == 0 and r["repairs"] == 2               # the third began after the stop
    assert r["collective_s"] == pytest.approx(1100e-6)
    assert dict(r["collectives"]) == pytest.approx({"all-reduce": 1000e-6, "all-gather": 100e-6})
    assert dict(r["by_scope"]) == pytest.approx({
        "jit(kernel)/pool.set_plane_rows": 2000e-6,
        "jit(gram_update)/pool.gram_update": 1500e-6, "(no tf_op)": 100e-6})
    assert (r["ops_with_metadata"], r["ops"]) == (3, 4)

    def read(name):
        from lib import byname
        return byname.load("metrics", name).read

    ctx = {"config": {"name": "made_up"}}
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    trace_dir = os.path.join(root, ".bench", "made_up.cell", "trace", "plugins", "profile", "t0")
    os.makedirs(trace_dir)
    try:
        os.replace(path, os.path.join(trace_dir, "made_up.xplane.pb"))
        assert read("mesh_device_idle_share")(ctx) == pytest.approx(
            100 * (1 - 3600e-6 / r["window_s"]))
        assert read("mesh_balance")(ctx) == pytest.approx(3600 / 3500)
        assert read("mesh_collective_ms")(ctx) == pytest.approx(1.1 / 2)
    finally:
        import shutil
        shutil.rmtree(os.path.join(root, ".bench", "made_up.cell"))
    # A run that left no trace: nothing to read, and no error.
    assert all(read(n)({"config": {"name": "made_up"}}) is None for n in TRACE_METRICS)


def test_scope_is_the_programs_own_name_for_an_op():
    assert mesh_trace.scope({"tf_op": "jit(set_plane_rows)/pool.set_plane_rows/scatter"}) == (
        "jit(set_plane_rows)/pool.set_plane_rows")
    assert mesh_trace.scope({"tf_op": "jit(<lambda>)/reduce_sum:"}) == "jit(<lambda>)"
    assert mesh_trace.scope({"tf_op": "jit(repair_planes)/while/body/dynamic_slice"}) == (
        "jit(repair_planes)")
    assert mesh_trace.scope({"tf_op": "jit(k)/shard_map/pool.pair_gram/while/body/dot_general"}) == (
        "jit(k)/pool.pair_gram")
    assert mesh_trace.scope({}) == ""
    assert mesh_trace.category("%all-reduce-done.2 = s32[] all-reduce-done(%s)", {}) == "all-reduce"
    assert mesh_trace.category("%fusion.1 = u32[] fusion(%a)", {}) == ""
    assert mesh_trace.category("anything", {"hlo_category": "collective-permute"}) == "collective-permute"


def test_one_device_plane_is_nothing_to_read(tmp_path):
    path = _write(tmp_path, _device_plane(0, [(1, 0, 2000), (3, 2500, 1000)]) + _host_plane([1200]))
    assert mesh_trace.reduce_mesh(path) is None
    recorded = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")
    assert mesh_trace.reduce_mesh(recorded) is None          # the chip's own one-device trace
    assert mesh_trace.op_metadata(recorded)["/device:TPU:0"]  # ... whose metadata reads all the same


def test_a_file_that_is_no_trace_has_no_metadata(tmp_path):
    junk = tmp_path / "junk.pb"
    junk.write_bytes(b"\x0a\xff\xff\xff\xff\x0f not a protobuf")
    assert mesh_trace.op_metadata(str(junk)) == {}
