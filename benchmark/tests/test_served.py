"""Whole runs at rehearsal size on the CPU, the look for a chip skipped:
the reference agrees with what the program serves; each configuration's
control, put in the program's place, comes out not correct; so does the
run with the timed path broken underneath (an answer altered where it is
produced; a write acknowledged and never applied) and the run whose
window compiled; and a configuration that is only a new file sets the
server's engine and devices."""

import copy
import json

import pytest

from lib import httpc
from lib.server_child import ServerChild

CELLS = [("gram64.mixed_95_5", "stale_read")]


def shape_ok(line, bench_json, workload, trace):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in bench_json[kind] if workload in m.get("workloads", [workload])}
    assert set(line["metrics"]) <= want
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"   # a rehearsal says what it ran on
    return want


@pytest.mark.parametrize("workload,control", CELLS)
def test_reference_agrees_with_a_served_run(rehearse, bench_json, workload, control):
    rc, line = rehearse(workload, seed=2**31 + 17)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert shape_ok(line, bench_json, workload, 0) == set(line["metrics"])
    assert line["checks"]["answers_checked"]["value"] > 100
    assert all(v["value"] > 0 for v in line["metrics"].values())


@pytest.mark.parametrize("workload,control", CELLS)
def test_control_is_not_correct(rehearse, workload, control):
    rc, line = rehearse(workload, seed=5, control=control)
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0


def test_traced_run_reports_per_layer_metrics(rehearse, bench_json):
    rc, line = rehearse("gram64.mixed_95_5", seed=6, seconds=5.0, trace=1)
    assert rc == 0 and line["correct"] is True
    want = shape_ok(line, bench_json, "gram64.mixed_95_5", 1)
    assert set(line["metrics"]) == want == {"device_idle_share", "exec_host_ms", "readback_ms", "write_ack_ms"}
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert len(line["breakdown"]["device_ops"]) <= 10 and len(line["breakdown"]["idle_gaps"]) <= 10


def test_an_altered_answer_is_not_correct(rehearse, monkeypatch):
    real = httpc.Conn.query
    n = [0]

    def altered(self, index, pql, trace=False):
        results, spans = real(self, index, pql, trace)
        n[0] += 1
        if n[0] % 50 == 0 and results and isinstance(results[0], int):
            results[0] += 1
        return results, spans

    monkeypatch.setattr(httpc.Conn, "query", altered)
    rc, line = rehearse("gram64.mixed_95_5", seed=8)
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] >= 1


def test_a_write_acknowledged_and_not_applied_is_not_correct(rehearse, monkeypatch):
    real = httpc.Conn.query

    def dropped(self, index, pql, trace=False):
        if pql.startswith("SetBit("):
            return [True], None
        return real(self, index, pql, trace)

    monkeypatch.setattr(httpc.Conn, "query", dropped)
    rc, line = rehearse("gram64.mixed_95_5", seed=9, seconds=4.0)
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["stale_readbacks"]["value"] >= 1


def test_a_window_that_compiled_is_not_correct(rehearse, monkeypatch):
    real = ServerChild.compile_log
    set_up = [False]

    def window_compiled(self, start):
        log = real(self, start)
        if start == 0:           # the set-up's summary: the next reading is the window's
            set_up[0] = True
            return log
        return dict(log, compilations=log["compilations"] + 1) if set_up[0] else log

    monkeypatch.setattr(ServerChild, "compile_log", window_compiled)
    rc, line = rehearse("gram64.mixed_95_5", seed=10)
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["compilations_in_window"] == {"value": 1, "limit": 0}
    assert line["checks"]["wrong_answers"]["value"] == 0


def test_a_configuration_is_added_as_files_alone(rehearse, bench_json, tmp_path):
    """``tests/data/mesh_stub.json`` names another engine and four devices;
    an entry in a BENCHMARK.json that points at it is all it takes."""
    b = copy.deepcopy(bench_json)
    cell = "mesh_stub.mixed_95_5"
    b["configs"] = [{"name": "mesh_stub", "source": "none", "reduced": [], "why": "test data",
                     "file": "benchmark/tests/data/mesh_stub.json"}]
    b["workloads"] = [{"name": cell, "config": "mesh_stub", "traffic": "mixed_95_5", "chips": 4,
                       "why": "test data"}]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [cell]
    path = tmp_path / "bench.json"
    path.write_text(json.dumps(b))
    rc, line = rehearse(cell, seed=12, bench_json=str(path))
    assert rc == 0 and line["correct"] is True
    assert line["device"]["count"] == 4      # and run.py refused unless /status said engine mesh


def test_no_result_without_the_program(run_mod, tmp_path, monkeypatch, capsys):
    """In a directory that holds only BENCHMARK.json and benchmark/ the
    server child cannot start: a non-zero exit and no result line."""
    import shutil
    import subprocess
    import sys

    from conftest import BENCH, ROOT
    shutil.copy(f"{ROOT}/BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "gram64.mixed_95_5",
                        "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
                       cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not any(ln.startswith('{"correct"') for ln in p.stdout.splitlines())
