#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: from ``BENCHMARK.json`` the
configuration's file and the traffic mix's (``benchmark/traffic/<traffic>.json``),
from those the data generator (``datagens/``), the request generator
(``generators/``) and the loop (``loops/``), and, with ``--trace 1``, one
reader per per-layer metric (``metrics/<metric>.py``).  The run starts
``python -m pilosa_tpu.cli server`` with the configuration's arguments and
environment as its one child before anything here touches jax (a chip
belongs to one process), loads the configuration's data made from
``--seed``, warms up by driving the cell's own traffic until nothing
compiles any more, drives it for ``--seconds``, stops the child, compares
every answer with the plain reference, and prints the result as the last
line of standard output.

No chip, or a server that does not report ``platform: tpu`` with the
cell's number of chips: the run fails with no result.  ``--rehearse`` is
the benchmark's own flag for the CPU sandbox (tiny sizes, platform
``cpu``); ``--control <name>`` puts a reference with one guarantee broken
in the program's place, to show that the comparison calls it wrong.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import statistics
import sys
import threading
import time

T_START = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)  # the program's client, for set-up only

from lib import byname, compare, httpc, spans, trace_reduce  # noqa: E402
from lib.oracle import GramOracle  # noqa: E402
from lib.records import SLICE_WIDTH, Record  # noqa: E402
from lib.server_child import ServerChild, ServerFailure  # noqa: E402

TRACE_AFTER_S = 1.0    # the device trace starts this far into the window ...
TRACE_SPAN_S = 3.0     # ... and spans this long
WARM_PHASE_S = 1.5     # one warm-up phase of the cell's traffic
WARM_PHASES_MAX = 12   # a warm-up that is not quiet by then fails the run


class BenchFailure(Exception):
    """The run cannot give a result."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def plan(workload: str, rehearse: bool, bench_json: str = "") -> dict:
    """The cell's pieces, by name, from BENCHMARK.json and the files it names."""
    bench = load_json(bench_json or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(os.path.join(ROOT, cfg_entry["file"]))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    if rehearse:
        cfg, mix = merged(cfg, cfg.get("rehearse", {})), merged(mix, mix.get("rehearse", {}))

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {"cell": cell, "config": cfg, "mix": mix,
            "gen": byname.load("generators", mix["generator"]),
            "loop": byname.load("loops", mix["loop"]),
            "datagen": byname.load("datagens", cfg["frame"]["datagen"]),
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def child_env(cfg: dict, rehearse: bool) -> dict:
    """The server's environment: the caller's without any ``PILOSA_*``
    variable, then the configuration's own (``server.env``: the engine and
    whatever else the deployment sets), on the chip as in rehearsal."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("PILOSA_")}
    env["JAX_LOG_COMPILES"] = "1"
    # Inside the checkout, at a fixed path (the path is part of every key).
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={cfg['chips']}"
    env.update({k: str(v) for k, v in cfg["server"].get("env", {}).items()})
    return env


def device_of(status: dict, cfg: dict, rehearse: bool) -> dict:
    dev = status["status"]["device"]
    want = "cpu" if rehearse else "tpu"
    if dev.get("platform") != want:
        raise BenchFailure(f"server reports platform {dev.get('platform')!r}, need {want!r}")
    if dev.get("count") != cfg["chips"]:
        raise BenchFailure(f"server reports {dev.get('count')} devices, the cell needs {cfg['chips']}")
    if dev.get("engine") != cfg["engine"] or dev.get("native") is not True:
        raise BenchFailure(f"server reports engine {dev.get('engine')!r}, native {dev.get('native')!r}")
    return dev


def load_data(host: str, cfg: dict, rows, cols) -> None:
    """Set-up only: the program's own client, through a door users load through."""
    from pilosa_tpu.server.client import Client  # imports jax, initializes no backend

    cl = Client(host, timeout=900)
    cl.create_index(cfg["index"])
    cl.create_frame(cfg["index"], cfg["frame"]["name"])
    if cfg["load_door"] not in ("ingest", "bulk"):   # the two streaming doors, one wire
        raise BenchFailure(f"unknown load door {cfg['load_door']!r}")
    cl.ingest_stream(cfg["index"], cfg["frame"]["name"], rows, cols, door=cfg["load_door"])


def streams_for(p: dict, seed: int, phase: int, mix: dict | None = None) -> list:
    cfg, mix = p["config"], mix or p["mix"]
    return [p["gen"].Stream(mix, cfg["frame"]["name"], cfg["frame"]["rows"],
                            cfg["slices"] * SLICE_WIDTH, seed, c, phase)
            for c in range(mix["clients"])]


def warm_up(child, host: str, p: dict, seed: int) -> tuple:
    """The generator's fill (the requests that bring the server to the
    state the window finds, sent once), then short phases of the loop over
    other streams of the same seed - first the generator's stress variants
    of the mix (its rarest requests at the highest concurrency the loop
    reaches), then the mix as the window sends it, each until two phases
    in a row compile nothing.  Returns what was sent too: the warm-up's
    writes are part of the state the window reads, so the reference
    replays them first."""
    cfg, mix = p["config"], p["mix"]
    t = time.perf_counter()
    first = []
    conn = httpc.Conn(host, timeout=900)
    try:
        for req in p["gen"].fill_requests(mix, cfg["frame"]["name"], cfg["frame"]["rows"],
                                          cfg["slices"] * SLICE_WIDTH, seed):
            t_s = time.perf_counter()
            results, _spans = conn.query(cfg["index"], req.body)
            first.append(Record(0, req, t_s, time.perf_counter(), results))
    finally:
        conn.close()
    fill_s = time.perf_counter() - t
    history, phases = [[first]], []

    def phase(kind: str, variant: dict) -> int:
        mark, t = child.err_size(), time.perf_counter()
        history.append(p["loop"].run_clients(
            host, cfg["index"], streams_for(p, seed, 1 + len(phases), variant), WARM_PHASE_S)[1])
        compiled = child.compile_log(mark)["compilations"]
        slow = max((r for recs in history[-1] for r in recs), key=lambda r: r.t_recv - r.t_send)
        phases.append({"mix": kind, "s": round(time.perf_counter() - t, 2), "compilations": compiled,
                       "requests": sum(len(recs) for recs in history[-1]),
                       "slowest": [slow.req.kind, round((slow.t_recv - slow.t_send) * 1e3, 1)]})
        return compiled

    quiet = 2
    for kind, variant in [("stress", v) for v in p["gen"].stress_mixes(mix)] + [("own", mix)]:
        quiet = 0
        while len(phases) < WARM_PHASES_MAX and quiet < 2:
            quiet = quiet + 1 if phase(kind, variant) == 0 else 0
    if quiet < 2:
        raise BenchFailure(f"the warm-up still compiled after {len(phases)} phases: {phases[-3:]}")
    return {"fill_s": fill_s, "phases": phases}, history


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile of all values."""
    v = sorted(values)
    return v[min(len(v) - 1, max(0, math.ceil(q * len(v)) - 1))]


def end_to_end(gen, flat: list, t0: float, seconds: float, setup_s: float) -> dict:
    close = t0 + seconds
    reads = [r for r in flat if r.req.kind != "write" and r.results is not None]
    calls = sum(gen.n_calls(r.req)
                for r in flat if r.ok and r.results is not None and r.t_recv <= close)
    lat = [(r.t_recv - r.t_send) * 1e3 for r in reads]
    out = {"setup_s": setup_s, "calls_per_s": calls / seconds}
    if lat:
        out["read_p50_ms"] = statistics.median(lat)
        out["read_p95_ms"] = percentile(lat, 0.95)
    pairs = write_pairs(flat)
    if pairs:
        out["write_to_read_p95_ms"] = percentile(
            [(rb.t_recv - w.t_send) * 1e3 for w, rb in pairs], 0.95)
    return out


def write_pairs(flat: list) -> list:
    """(SetBit record, its read-back record) for every pair both of whose
    answers came; a pair whose read-back was wrong still counts its time
    (``correct`` says that it was wrong)."""
    pairs, last = [], {}
    for r in flat:
        if r.req.kind == "write":
            last[r.client] = r
        elif r.req.kind == "readback" and r.client in last:
            w = last.pop(r.client)
            if w.results is not None and r.results is not None:
                pairs.append((w, r))
    return pairs


def read_metric(name: str, ctx: dict):
    return byname.load("metrics", name).read(ctx)


def profile_span(host: str, trace_dir: str, t0: float, box: dict) -> None:
    """Trace the process that holds the chip through the server's own
    door, over a few seconds of the steady window."""
    conn = httpc.Conn(host, timeout=300)
    try:
        time.sleep(max(0.0, t0 + TRACE_AFTER_S - time.perf_counter()))
        conn.request("POST", f"/debug/profile/start?dir={trace_dir}")
        box["start"] = time.perf_counter()
        time.sleep(TRACE_SPAN_S)
        box["stop"] = time.perf_counter()
        conn.request("POST", "/debug/profile/stop")
        box["written"] = time.perf_counter()
    except (httpc.HTTPFailure, OSError) as e:
        box["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()


def run(args) -> dict:
    p = plan(args.workload, args.rehearse, args.benchmark_json)
    cfg, mix = p["config"], p["mix"]
    if not args.rehearse:
        held = os.environ.get("JAX_PLATFORMS", "")
        if held and "tpu" not in held.split(","):
            raise BenchFailure(f"JAX_PLATFORMS={held} holds jax off the TPU; this run needs the chip")
    state = os.path.join(ROOT, ".bench", args.workload)
    data_dir, trace_dir = os.path.join(state, "data"), os.path.join(state, "trace")
    shutil.rmtree(state, ignore_errors=True)
    os.makedirs(data_dir)
    if "jax" in sys.modules:  # importing jax is harmless; a backend takes the chip
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise BenchFailure("this process initialized a jax backend before the server child started")
    emit({"phase": "plan", "workload": args.workload, "seed": args.seed, "seconds": args.seconds,
          "trace": args.trace, "rehearse": args.rehearse, "control": args.control,
          "slices": cfg["slices"], "rows": cfg["frame"]["rows"], "clients": mix["clients"]})

    with ServerChild(ROOT, state, data_dir, child_env(cfg, args.rehearse),
                     cfg["server"].get("args", [])) as child:
        t = time.perf_counter()
        rows, cols = p["datagen"].make_frame(args.seed, cfg["slices"], cfg["frame"])  # while jax starts
        t_data = time.perf_counter() - t
        host = child.wait_ready(timeout=600)
        admin = httpc.Conn(host, timeout=300)
        dev = device_of(admin.request("GET", "/status")[0], cfg, args.rehearse)
        t_ready = time.perf_counter()
        load_data(host, cfg, rows, cols)
        t_loaded = time.perf_counter()
        warm, history = warm_up(child, host, p, args.seed)
        setup_s = time.perf_counter() - T_START
        emit({"phase": "setup", "setup_s": setup_s, "datagen_s": t_data,
              "ready_s": t_ready - T_START, "load_s": t_loaded - t_ready,
              "load_door": cfg["load_door"], "bits": int(len(rows)),
              "warm_s": time.perf_counter() - t_loaded, "warm": warm,
              "compiles": {k: v for k, v in child.compile_log(0).items() if k != "shapes"}})

        # The window.
        mark = child.err_size()
        prof: dict = {}
        tracer = None
        streams = streams_for(p, args.seed, phase=0)
        if args.trace:
            tracer = threading.Thread(
                target=profile_span, args=(host, trace_dir, time.perf_counter(), prof))
            tracer.start()
        t0, records = p["loop"].run_clients(host, cfg["index"], streams, args.seconds,
                                            trace=bool(args.trace))
        if tracer is not None:
            tracer.join()
        in_window = child.compile_log(mark)
        dev_after = admin.request("GET", "/status")[0]["status"]["device"]
        admin.close()
        rc = child.stop()
    if rc != 0:
        raise BenchFailure(f"server exited with code {rc}")
    peaks = [d.get("peak_bytes_in_use") or 0 for d in dev_after["devices"]]
    device = {"platform": dev_after["platform"], "kind": dev_after["device_kind"],
              "count": dev_after["count"], "memory_peak_bytes": max(peaks)}

    # The comparison, with the program stopped and its state freed.
    t = time.perf_counter()
    served = [[r.results for r in recs] for recs in records]
    want = compare.expected_answers(
        p["gen"], GramOracle(rows, cols, cfg["frame"]["rows"]), history, records)
    verdict = compare.judge(records, served, want)
    if args.control:   # the program's own verdict goes on an earlier line; the control's decides
        emit({"phase": "served", **verdict})
        verdict = compare.judge(records, compare.control_answers(
            args.control, p["gen"], rows, cols, cfg["frame"]["rows"], SLICE_WIDTH,
            history, records), want)
    flat = sorted((r for recs in records for r in recs), key=lambda r: r.t_send)
    checks = {k: {"value": verdict[k], "limit": 0} for k in compare.CHECKS}
    # Nothing compiles inside the measured window: a window that did is no measurement.
    checks["compilations_in_window"] = {"value": in_window["compilations"], "limit": 0}
    checks["answers_checked"] = {"value": verdict["answers_checked"], "limit": ">=1"}
    correct = (all(c["value"] == 0 for k, c in checks.items() if k != "answers_checked")
               and verdict["answers_checked"] >= 1)
    kinds = {k: sum(r.req.kind == k for r in flat) for k in ("read", "write", "readback")}
    emit({"phase": "window", "requests": kinds, "answers_checked": verdict["answers_checked"],
          "compilations_in_window": in_window["compilations"],
          "cache_hits_in_window": in_window["cache_hits"],
          "programs_compiled_in_window": in_window["shapes"][:16],
          "compare_s": time.perf_counter() - t, "first_wrong": verdict["first_wrong"],
          "errors": [r.error for r in flat if r.error][:3]})

    timed = values = end_to_end(p["gen"], flat, t0, args.seconds, setup_s)
    half = args.seconds / 2   # each half read as a window of its own: drift inside a run, and what half the length reads
    emit({"phase": "halves", "first": end_to_end(
              p["gen"], [r for r in flat if r.t_send < t0 + half], t0, half, setup_s),
          "second": end_to_end(
              p["gen"], [r for r in flat if r.t_send >= t0 + half], t0 + half, half, setup_s)})
    wanted = p["end_to_end"]
    result = {"correct": correct, "attempted": len(flat),
              "failed": sum(r.results is None for r in flat), "metrics": {}, "device": device}
    if args.trace:
        wanted = p["per_layer"]
        if "error" in prof:
            raise BenchFailure(f"device trace failed: {prof['error']}")
        t = time.perf_counter()
        reduced = trace_reduce.reduce_trace(trace_reduce.find_xplane(trace_dir))
        if reduced["busy_s"] <= 0:
            raise BenchFailure("the traced span shows no operation on the device")
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        ctx = {"records": flat, "trace": reduced, "traced": (prof["start"], prof["stop"]),
               "config": cfg, "device": device, "write_pairs": write_pairs(flat)}
        values = {m["name"]: read_metric(m["name"], ctx) for m in wanted}
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        seen: set = set()
        for r in flat:
            spans.lanes(r.spans, seen)
        emit({"phase": "trace", "lanes": sorted(seen), "device_programs": reduced["device_programs"],
              "devices": reduced["devices"], "reduce_s": time.perf_counter() - t,
              "profile_stop_s": prof["written"] - prof["stop"],
              "calls_per_s_traced": timed["calls_per_s"]})
    for m in wanted:
        if values.get(m["name"]) is not None:
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result["checks"] = checks
    shutil.rmtree(data_dir, ignore_errors=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, reported as platform cpu (the sandbox only)")
    ap.add_argument("--control", default="",
                    help="compare a guarantee-breaking reference instead of the served answers")
    ap.add_argument("--benchmark-json", default="",
                    help="another file than BENCHMARK.json at the root (the harness's own tests)")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except (BenchFailure, ServerFailure, httpc.HTTPFailure, OSError, KeyError) as e:
        print(f"benchmark failed: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']} limit={c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
