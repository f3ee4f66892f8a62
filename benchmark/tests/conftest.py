import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


@pytest.fixture(scope="session")
def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def run_mod():
    spec = importlib.util.spec_from_file_location("bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def rehearse(run_mod, capsys):
    """Drive one whole run at rehearsal size on the CPU (the harness's look
    for a chip skipped); returns (exit code, the result line)."""
    def go(workload, seed=7, seconds=2.0, trace=0, control="", bench_json=""):
        argv = ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                "--trace", str(trace), "--rehearse"] + (["--control", control] if control else [])
        argv += ["--benchmark-json", bench_json] if bench_json else []
        rc = run_mod.main(argv)
        lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
        return rc, (json.loads(lines[-1]) if rc == 0 else None)
    return go
