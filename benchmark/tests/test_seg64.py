"""The paging cell ``seg64.tall_pairs`` at rehearsal size on the CPU (the
reference agrees with the served answers whichever rows were resident, the
pool pages, its control does not pass, a traced run reads the span metrics),
the least bytes a gather dispatch reads against a hand count, and the reader
of device time under a trace name on a small made-up ``.xplane.pb``."""

import pytest

from lib import peaks, scope_trace, work
from test_mesh256 import _device_plane, _host_plane, _write

CELL = "seg64.tall_pairs"
SPAN_METRICS = {"pool_miss_share", "pool_miss_ms", "pool_miss_fetch_ms",
                "pool_upload_bytes_per_call", "seg_pool_lock_wait_ms"}
TRACE_METRICS = {"seg_device_idle_share", "gather_hbm_share"}
END_TO_END = {"calls_per_s", "read_p50_ms", "read_p95_ms", "setup_s"}


def test_the_cell_is_what_the_issue_named(bench_json, run_mod):
    cell = next(w for w in bench_json["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("seg64", "tall_pairs", 1)
    p = run_mod.plan(CELL, rehearse=False)
    cfg, mix = p["config"], p["mix"]
    assert cfg["server"]["env"] == {"PILOSA_ENGINE": "jax"} and cfg["server"]["args"] == []
    assert (cfg["engine"], cfg["chips"], cfg["slices"], cfg["columns"]) == ("jax", 1, 64, 64 << 20)
    assert cfg["reduced"] == ["rows", "columns"] and set(cfg["reduced_why"]) == {"rows", "columns"}
    assert cfg["rows"] == cfg["frame"]["rows"] == 8192 and cfg["frame"]["name"] == "stargazer"
    assert cfg["controls"] == ["drop_slice"] and cfg["load_door"] == "ingest"
    assert cfg["source_values"]["rows"] == "not_at_hand"        # nothing invented
    # a row's bits a slice span 1-5, mean 3
    from lib import byname
    bits = byname.load("datagens", "windows").bits_of_rows(cfg["frame"])
    assert (bits.min(), bits.max(), round(float(bits.mean()), 2)) == (1, 5, 3.0)
    assert (mix["generator"], mix["loop"], mix["clients"], mix["read_calls"]) == ("pairs_rw", "closed", 8, 32)
    assert (mix["hot_rows"], mix["hot_share"], mix["zipf_s"], mix["write_share"]) == (192, 0.95, 1.0, 0)
    assert {m["name"] for m in p["per_layer"]} == SPAN_METRICS | TRACE_METRICS
    assert {m["name"] for m in p["end_to_end"]} == END_TO_END


def test_rehearsal_is_correct_and_reports_the_four(rehearse):
    rc, line = rehearse(CELL, seed=2**31 + 32)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == END_TO_END
    assert line["checks"]["answers_checked"]["value"] > 100
    assert line["checks"]["compilations_in_window"]["value"] == 0


def test_control_drop_slice_is_not_correct(rehearse):
    rc, line = rehearse(CELL, seed=33, control="drop_slice")
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0


def test_traced_rehearsal_pages_and_reads_the_span_metrics(rehearse):
    rc, line = rehearse(CELL, seed=34, seconds=4.0, trace=1)
    assert rc == 0 and line["correct"] is True
    # No device plane on the CPU: the device's idle share is the CPU workers' (a
    # rehearsal's number), and the kernels' share finds no op to read.
    assert set(line["metrics"]) == SPAN_METRICS | {"seg_device_idle_share"}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["pool_miss_share"] <= 100
    assert 0 < m["pool_miss_fetch_ms"] < m["pool_miss_ms"]
    # whole buckets of 2 slices x 128 KiB a row, over bodies of 8 calls
    assert m["pool_upload_bytes_per_call"] > 0 and m["seg_pool_lock_wait_ms"] >= 0


def test_gather_min_bytes_against_a_hand_count():
    # 3 distinct rows over 64 slices of 32,768 words: 3 x 64 x 128 KiB
    assert work.gather_min_bytes(3, 64, 32768) == 3 * 64 * 131072 == 25165824
    assert work.gather_min_bytes(0, 64, 32768) == 0
    # 256 pairs of distinct rows at the cell's size: the issue's "at most 4 GiB"
    assert work.gather_min_bytes(512, 64, 32768) == 4 << 30
    assert peaks.hbm_bytes_per_s("TPU v5 lite") == 819e9
    with pytest.raises(KeyError):
        peaks.hbm_bytes_per_s("cpu")


def test_scope_seconds_reads_the_ops_under_a_trace_name(tmp_path):
    # test_mesh256's made-up ops: 2 = a kernel under pool.gram_update/.../pallas_call,
    # 1 = a copy under pool.set_plane_rows, 4 = an op without metadata.
    dev0 = [(1, 0, 2000), (2, 2000, 500), (2, 3000, 250), (4, 5000, 100)]
    path = _write(tmp_path, _device_plane(0, dev0) + " " + _host_plane([]))
    got = scope_trace.scope_seconds(path, "pool.gram_update")
    assert got["ops"] == 2 and got["seconds"] == pytest.approx(750e-6)
    assert scope_trace.scope_seconds(path, "pool.set_plane_rows")["seconds"] == pytest.approx(2000e-6)
    assert scope_trace.scope_seconds(path, "gather.count") is None      # no op carries it
    assert scope_trace.scope_seconds(path, "pool") is None              # a whole component, not a prefix
    # no device plane: nothing to read
    assert scope_trace.scope_seconds(_write(tmp_path, _host_plane([])), "pool.gram_update") is None
