"""BENCHMARK.json against the contract's limits on names, units and files,
and against the harness: every piece it names is a file under paths."""

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_keys_names_and_units(bench_json):
    b = bench_json
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in b["configs"]] + [w["name"] for w in b["workloads"]]
             + [w["traffic"] for w in b["workloads"]]
             + [m["name"] for m in b["end_to_end"] + b["per_layer"]])
    assert all(NAME.match(n) for n in names), names
    for group in (b["configs"], b["workloads"], b["end_to_end"] + b["per_layer"]):
        ns = [x["name"] for x in group]
        assert len(ns) == len(set(ns))
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
    for x in b["configs"] + b["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"] and "\t" not in x["why"]
    for c in b["configs"]:
        assert 1 <= len(c["source"]) <= 200 and len(c["reduced"]) <= 16
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(b["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_named_piece_is_a_file_under_paths(bench_json):
    b = bench_json
    under = tuple(p.rstrip("/") + "/" for p in b["paths"])
    for c in b["configs"]:
        assert c["file"].startswith(under) and os.path.isfile(os.path.join(ROOT, c["file"]))
    assert {w["config"] for w in b["workloads"]} == {c["name"] for c in b["configs"]}
    files = {c["name"]: c["file"] for c in b["configs"]}
    for w in b["workloads"]:
        path = os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json")
        assert os.path.isfile(path)
        with open(path) as f:
            mix = json.load(f)
        with open(os.path.join(ROOT, files[w["config"]])) as f:
            cfg = json.load(f)
        for kind, name in (("generators", mix["generator"]), ("loops", mix["loop"]),
                           ("datagens", cfg["frame"]["datagen"])):
            assert os.path.isfile(os.path.join(ROOT, "benchmark", kind, name + ".py"))
        assert cfg["chips"] == w["chips"] and set(cfg["server"]) >= {"args", "env"}
        assert set(cfg["reduced"]) == set(next(c for c in b["configs"] if c["name"] == w["config"])["reduced"])
        assert all(k in cfg and k in cfg["reduced_why"] for k in cfg["reduced"])
    for m in b["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics", m["name"] + ".py"))
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in b["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(d, f), ROOT)), f
    for word in b["command"]:
        assert not word.startswith("/") and ".." not in word
