"""Smoke tests for the benchmark harness and examples.

The driver runs ``python bench.py`` at round end — a broken bench records
nothing, so every config must at least produce its JSON line on tiny
shapes (CPU backend).  Same for the getting-started example.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(env_extra, script="bench.py", timeout=240):
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    # Skip the gcc-compiled reference-loop measurement (several seconds
    # of DRAM streaming per bench process); smoke shapes only check the
    # JSON contract, not the denominator's accuracy.
    env.setdefault("BENCH_REF_BYTES_PER_S", "2.38e10")
    env.update(env_extra)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, script)],
        capture_output=True,
        text=True,
        cwd=REPO,
        env=env,
        timeout=timeout,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-2000:]}"
    return out.stdout


@pytest.mark.parametrize(
    "cfg,extra",
    [
        ("intersect_count", {"BENCH_ITERS": "2", "BENCH_SLICES": "2", "BENCH_ROWS": "4", "BENCH_BATCH": "4"}),
        # Tier scoreboard forced on (shape env normally disables it so
        # big-shape runs can't leak into the 4k-row tier shapes).
        ("intersect_count", {"BENCH_ITERS": "2", "BENCH_SLICES": "2", "BENCH_ROWS": "4",
                             "BENCH_BATCH": "4", "BENCH_TIERS": "1", "BENCH_INTERPRET": "1"}),
        ("setbit", {"BENCH_OPS": "300"}),
        ("topn", {"BENCH_ITERS": "2", "BENCH_TOPN_ROWS": "8"}),
        ("union64", {"BENCH_ITERS": "3", "BENCH_SLICES": "2"}),
        ("timerange", {"BENCH_ITERS": "4", "BENCH_BATCH": "2"}),
        ("executor", {"BENCH_ITERS": "3", "BENCH_SLICES": "2", "BENCH_ROWS": "4",
                      "BENCH_BATCH": "4", "BENCH_BITS_PER_ROW": "50", "BENCH_THREADS": "2"}),
        ("range_executor", {"BENCH_ITERS": "3", "BENCH_SLICES": "2",
                            "BENCH_BATCH": "4", "BENCH_BITS": "200"}),
        # Mixed read/write tier: BENCH_SMOKE exercises the warm-state
        # REPAIR lane end-to-end (patch + rebuild A/B) on CPU.
        ("mixed", {"BENCH_SMOKE": "1"}),
        # The Pallas-kernel configs run in interpret mode here only because
        # the test says so: bench.py never infers it from the backend.
        ("intersect_count_stream", {"BENCH_ITERS": "2", "BENCH_SLICES": "4",
                                    "BENCH_ROWS": "4", "BENCH_BATCH": "4",
                                    "BENCH_CHUNK_SLICES": "2", "BENCH_INTERPRET": "1"}),
        ("intersect_count_4krows", {"BENCH_ITERS": "2", "BENCH_SLICES": "2",
                                    "BENCH_ROWS": "64", "BENCH_BATCH": "4",
                                    "BENCH_INTERPRET": "1"}),
        ("topn_p50", {"BENCH_ITERS": "4", "BENCH_SLICES": "2", "BENCH_ROWS": "4",
                      "BENCH_INTERPRET": "1"}),
    ],
)
def test_bench_config_emits_json(cfg, extra):
    stdout = _run({"BENCH_CONFIG": cfg, **extra})
    line = stdout.strip().splitlines()[-1]
    result = json.loads(line)
    assert {"metric", "value", "unit", "vs_baseline"} <= set(result)
    assert result["value"] > 0
    if extra.get("BENCH_TIERS") == "1":
        names = [t["tier"] for t in result["tiers"]]
        assert len(names) >= 4 and len(set(names)) == len(names)
        assert all("qps" in t and "bandwidth_util" in t for t in result["tiers"])
    if cfg == "mixed":
        names = [t["tier"] for t in result["tiers"]]
        assert names == [
            "mixed_95_5", "mixed_50_50", "mixed_50_50_b8", "mixed_50_50_b64"
        ]
        assert all(
            t["qps"] > 0 and t["rebuild_qps"] > 0 and "speedup" in t
            for t in result["tiers"]
        )
        # The smoke path must actually exercise the patch lane, and the
        # burst tiers must COALESCE: one deferred repair per write burst,
        # so repairs never grow with burst size.
        by = {t["tier"]: t for t in result["tiers"]}
        assert by["mixed_50_50"]["repairs"] > 0
        assert 0 < by["mixed_50_50_b8"]["repairs"] <= by["mixed_50_50"]["repairs"]
        assert 0 < by["mixed_50_50_b64"]["repairs"] <= by["mixed_50_50_b8"]["repairs"]
        # Per-(row, slice) granularity is live: the patch lane fetched
        # planes, bounded by rows x slices per repair.
        assert by["mixed_50_50"]["patch_planes"] > 0


def test_bench_kernel_config_does_not_time_the_interpreter():
    """A Pallas-kernel config that finds no chip fails (in the kernel's
    lowering); it never falls back to interpret mode by itself and prints
    no rate."""
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.pop("BENCH_INTERPRET", None)
    env.update({"JAX_PLATFORMS": "cpu", "BENCH_REF_BYTES_PER_S": "2.38e10",
                "BENCH_CONFIG": "topn_p50", "BENCH_ITERS": "4", "BENCH_SLICES": "2",
                "BENCH_ROWS": "4"})
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=240,
    )
    assert out.returncode != 0
    assert "interpret mode" in out.stderr
    assert '"metric"' not in out.stdout


def test_bench_writelane_emits_json():
    """The native write lane + streaming ingest bench: the in-run A/B
    contract (native beats the Python general lane on singletons, the
    parse+vectorized path on batches; the streaming tier sustains
    ingest with zero read-class sheds) is asserted INSIDE the bench —
    a nonzero exit would fail _run — so this smoke checks the JSON
    shape and re-states the headline invariants."""
    stdout = _run({"BENCH_CONFIG": "writelane", "BENCH_SMOKE": "1"}, timeout=300)
    result = json.loads(stdout.strip().splitlines()[-1])
    assert result["metric"] == "writelane_batched_native_vs_python"
    assert result["value"] > 1.0
    t = result["tiers"]
    assert t["singleton_native_vs_general"] > 1.0
    assert t["batched_native_vs_python"] > 1.0
    assert t["differential_ok"] is True
    assert t["stream_read_sheds"] == 0 and t["stream_reads_served"] > 0
    assert t["stream_pairs_per_s"] > 0


def test_bench_qcache_emits_json():
    """The query-result-cache bench must keep working: a Zipf-skewed
    repeated read mix with interleaved writes, cache on vs off on the
    same schedule.  The Zipf tier must actually HIT (skewed repeats are
    the whole point) and read-your-writes must hold in both tiers (a
    write to a touched fragment forces a miss; the next answer reflects
    it)."""
    stdout = _run({"BENCH_CONFIG": "qcache", "BENCH_SMOKE": "1"}, timeout=300)
    result = json.loads(stdout.strip().splitlines()[-1])
    assert result["metric"] == "qcache_read_qps" and result["value"] > 0
    names = [t["tier"] for t in result["tiers"]]
    assert names == ["qcache_on", "qcache_off"]
    by = {t["tier"]: t for t in result["tiers"]}
    assert by["qcache_on"]["hit_rate"] > 0.5
    assert by["qcache_on"]["hits"] > 0 and by["qcache_on"]["misses"] > 0
    # Cache off = no cache at all: nothing can hit.
    assert by["qcache_off"]["hit_rate"] == 0 and by["qcache_off"]["hits"] == 0
    # Read-your-writes + the numpy ground-truth gate held in BOTH tiers
    # (the bench itself asserts them; the fields record it).
    assert all(t["rw_ok"] and t["gate_ok"] for t in result["tiers"])
    assert all(t["ms_per_request"] > 0 for t in result["tiers"])
    # Tracing overhead guard ran in-run: head sampling at 0.01 must
    # cost <= 5% vs tracing disabled (the bench asserts; the fields
    # record the measured ratio).
    assert by["qcache_on"]["trace_ok"] is True
    assert "trace_overhead" in by["qcache_on"]


def test_bench_overload_emits_json():
    """The request-lifecycle QoS bench must keep working: a real HTTP
    server past saturation, QoS on (bounded admission + deadlines —
    shed rate > 0, goodput holds) vs off (unbounded, p99 degrades)."""
    stdout = _run({"BENCH_CONFIG": "overload", "BENCH_SMOKE": "1"}, timeout=300)
    result = json.loads(stdout.strip().splitlines()[-1])
    assert result["metric"] == "overload_goodput_qps" and result["value"] > 0
    names = [t["tier"] for t in result["tiers"]]
    assert names == ["presat", "overload_qos_on", "overload_qos_off"]
    by = {t["tier"]: t for t in result["tiers"]}
    # Overload really overloads AND the door really sheds.
    assert by["overload_qos_on"]["shed_rate"] > 0
    assert by["overload_qos_on"]["served"] > 0
    # QoS off admits everything: nothing is shed, everything is served.
    assert by["overload_qos_off"]["shed_rate"] == 0
    assert all(t["goodput_qps"] > 0 for t in result["tiers"])


def test_bench_tenancy_emits_json():
    """The multi-tenant hostile-neighbor bench must keep working: a
    polite tenant's isolated p99 baseline, then a hostile flood at 2x
    the door's depth with fair-share isolation ON (polite p99 within
    1.5x baseline, zero polite sheds, hostile really sheds — all
    asserted in-run) and OFF (the A/B degradation is recorded)."""
    stdout = _run({"BENCH_CONFIG": "tenancy", "BENCH_SMOKE": "1"}, timeout=300)
    result = json.loads(stdout.strip().splitlines()[-1])
    assert result["metric"] == "tenancy_polite_p99_ms" and result["value"] > 0
    names = [t["tier"] for t in result["tiers"]]
    assert names == ["polite_baseline", "hostile_flood_on", "hostile_flood_off"]
    by = {t["tier"]: t for t in result["tiers"]}
    assert by["polite_baseline"]["served"] > 0
    # The bench asserted these in-run; the fields record it.
    on = by["hostile_flood_on"]
    assert on["polite"]["shed"] == 0 and on["polite"]["served"] > 0
    assert on["hostile"]["shed"] > 0
    # The /debug/tenants scrape rode along: the door saw both tenants.
    assert on["door"]["polite"]["admitted"] > 0
    assert on["door"]["hostile"]["shed"] > 0
    assert result["vs_baseline"] <= 1.5


def test_bench_replica_emits_json():
    """The replicated-serving-groups bench must keep working: group
    subprocesses behind out-of-process routers, read QPS at 1 vs N
    groups + a router-off direct baseline, with cross-group
    read-your-writes and failover (reads survive a killed group, writes
    503 until quorate) asserted in-run.  The scaling RATIO is recorded,
    not asserted — it needs physical cores the CI box may not have
    (the ``cpus`` field disambiguates)."""
    stdout = _run({"BENCH_CONFIG": "replica", "BENCH_SMOKE": "1"}, timeout=300)
    result = json.loads(stdout.strip().splitlines()[-1])
    assert result["metric"] == "replica_read_qps" and result["value"] > 0
    names = [t["tier"] for t in result["tiers"]]
    assert names == ["direct_1g", "router_1g", "router_2g"]
    by = {t["tier"]: t for t in result["tiers"]}
    assert all(t["read_qps"] > 0 and t["served"] > 0 for t in result["tiers"])
    # The bench asserted these in-run; the fields record it.
    assert by["router_2g"]["rw_ok"] is True
    assert by["router_2g"]["failover_ok"] is True
    assert by["router_2g"]["failovers"] >= 1
    assert by["router_2g"]["write_fanout"] >= 1  # schema + import + probe write
    assert result["scaling_1_to_2"] > 0 and result["cpus"] >= 1


def test_bench_multicore_emits_json():
    """The multi-core host-serving bench must keep working: a real CLI
    server at 1 vs 2 workers (in-process pool threads on free-threaded
    builds, SO_REUSEPORT processes on GIL builds) driven from 1/2/4
    client threads, plus the serve-lane-breadth A/B (native multi-frame
    / tree / Range one-crossing lanes vs the Python general lane,
    byte-parity asserted in-run).  Tier-1 compares results, not
    timings: under BENCH_SMOKE the ratios are recorded, with the reason
    the run did not assert them, and this test holds none of them."""
    stdout = _run({"BENCH_CONFIG": "multicore", "BENCH_SMOKE": "1"}, timeout=600)
    result = json.loads(stdout.strip().splitlines()[-1])
    assert result["metric"] == "multicore_read_qps" and result["value"] > 0
    names = [t["tier"] for t in result["tiers"]]
    assert names == ["serve_1w", "clients_1", "clients_2", "clients_4",
                     "breadth_multiframe", "breadth_tree", "breadth_range"]
    by = {t["tier"]: t for t in result["tiers"]}
    for t in ("serve_1w", "clients_1", "clients_2", "clients_4"):
        assert by[t]["read_qps"] > 0 and by[t]["served"] > 0
    # The breadth A/B asserted byte parity in-run; the fields record
    # positive times on both lanes.
    for t in ("breadth_multiframe", "breadth_tree", "breadth_range"):
        assert by[t]["native_ms"] > 0 and by[t]["python_ms"] > 0
        assert by[t]["speedup"] > 0
    assert result["scaling_1_to_2"] > 0 and result["cpus"] >= 1
    assert result["worker_mode"] in ("threads", "processes")
    assert result["scaling_skip"]  # ratio assert skipped WITH a reason


def test_bench_recovery_emits_json():
    """The durable-write-log recovery bench must keep working: 3 group
    subprocesses behind a durable-WAL CLI router, a group SIGKILLed
    mid-stream with writes still committing on the degraded quorum
    (zero failed writes asserted in-run), then a restart whose WAL
    suffix replay converges and rejoins reads."""
    stdout = _run({"BENCH_CONFIG": "recovery", "BENCH_SMOKE": "1"}, timeout=300)
    result = json.loads(stdout.strip().splitlines()[-1])
    assert result["metric"] == "recovery_write_qps" and result["value"] > 0
    names = [t["tier"] for t in result["tiers"]]
    assert names == ["writes_3g", "writes_2g", "catchup"]
    by = {t["tier"]: t for t in result["tiers"]}
    # The headline: NO failed writes with a group down (the old
    # full-set quorum rule 503'd every one of these).
    assert by["writes_2g"]["failed_batches"] == 0
    assert by["writes_2g"]["write_qps"] > 0
    assert by["writes_3g"]["failed_batches"] == 0
    # Catch-up really replayed the missed suffix and converged.
    assert by["catchup"]["converged"] is True
    assert by["catchup"]["rejoined_reads"] is True
    assert by["catchup"]["replayed"] >= by["catchup"]["lag_at_restart"]
    assert by["catchup"]["catchup_s"] > 0
    assert by["catchup"]["wal"]["durable"] is True
    assert result["catchup_s"] > 0 and result["cpus"] >= 1


def test_bench_resync_emits_json():
    """The automated-resync bench: a BLANK group joins a loaded
    2-group cluster behind a durable-WAL CLI router, self-heals via
    the digest-diff fragment stream, and rejoins with zero failed
    writes during the resync and digest convergence asserted in-run."""
    stdout = _run({"BENCH_CONFIG": "resync", "BENCH_SMOKE": "1"}, timeout=300)
    result = json.loads(stdout.strip().splitlines()[-1])
    assert result["metric"] == "resync_rejoin_s" and result["value"] > 0
    names = [t["tier"] for t in result["tiers"]]
    assert names == ["load", "rejoin"]
    by = {t["tier"]: t for t in result["tiers"]}
    assert by["rejoin"]["failed_writes_during_resync"] == 0
    assert by["rejoin"]["writes_during_resync"] > 0
    assert by["rejoin"]["converged"] is True
    assert by["rejoin"]["bytes_streamed"] > 0
    assert by["rejoin"]["resync_fragments"] >= 1
    assert result["cpus"] >= 1


def test_bench_shard_emits_json():
    """The partitioned-replica-groups bench: write throughput through
    one shard vs two (separate subprocess groups, separate sequencer
    spaces), then a LIVE RESHARD splitting the slice space under
    concurrent write load — zero failed writes and digest convergence
    (moved range only on the new group) asserted in-run.  The write
    scaling RATIO is recorded under BENCH_SMOKE, asserted only on a
    real multi-core run (``scaling_asserted``/``skip_reason`` say
    which)."""
    stdout = _run({"BENCH_CONFIG": "shard", "BENCH_SMOKE": "1"}, timeout=300)
    result = json.loads(stdout.strip().splitlines()[-1])
    assert result["metric"] == "shard_write_qps" and result["value"] > 0
    names = [t["tier"] for t in result["tiers"]]
    assert names == ["router_1s", "router_2s", "reshard"]
    by = {t["tier"]: t for t in result["tiers"]}
    assert by["router_1s"]["write_qps"] > 0 and by["router_1s"]["served"] > 0
    assert by["router_2s"]["write_qps"] > 0 and by["router_2s"]["served"] > 0
    # The bench asserted these in-run; the fields record it.
    assert by["reshard"]["failed_writes"] == 0
    assert by["reshard"]["writes_during_reshard"] > 0
    assert by["reshard"]["moved_fragments"] >= 1
    assert by["reshard"]["map_epoch"] == 1
    assert by["reshard"]["fence_ms"] >= 0
    assert result["scaling_1s_to_2s"] > 0 and result["cpus"] >= 1
    if not result["scaling_asserted"]:
        assert result["skip_reason"]  # skipped WITH a reason, never silently


def test_star_trace_example_runs():
    stdout = _run({}, script=os.path.join("examples", "star_trace.py"))
    assert "top stargazers:" in stdout and "user 1 attrs:" in stdout


def test_graft_entry_dryrun_smoke():
    """The driver's multichip dryrun must keep working (4 virtual devices
    keeps it quick; the driver runs 8)."""
    import subprocess

    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.pop("JAX_PLATFORMS", None)  # the script pins its own CPU mesh
    # The suite's conftest exports XLA_FLAGS for the in-process tests; if
    # it leaks into the subprocess the script skips its own CPU pin
    # (device count pre-set) and the run goes looking for an accelerator.
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "__graft_entry__.py"), "4"],
        capture_output=True,
        text=True,
        cwd=REPO,
        env=env,
        timeout=280,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "dryrun_multichip OK" in out.stdout


def test_graft_entry_compiles_single_chip():
    """entry() must stay jittable (driver compile-check analog)."""
    import subprocess

    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    code = (
        "import __graft_entry__ as g, jax;"
        "fn, args = g.entry();"
        "out = jax.jit(fn)(*args);"
        "print('entry OK', [getattr(o, 'shape', None) for o in out])"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, cwd=REPO, env=env,
        timeout=240,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "entry OK" in out.stdout


def test_bench_lockstep_emits_json():
    stdout = _run(
        {"BENCH_CONFIG": "lockstep", "BENCH_ITERS": "6", "BENCH_BATCH": "4",
         "BENCH_THREADS": "2"},
        timeout=300,
    )
    result = json.loads(stdout.strip().splitlines()[-1])
    assert result["metric"] == "lockstep_service_qps" and result["value"] > 0


def test_bench_lockstep_coalesce_emits_json():
    """The request-coalescing bench path must keep working: both tiers
    (coalesced batch replay vs one entry per request) run a real 2-rank
    job and emit per-request overhead."""
    stdout = _run({"BENCH_CONFIG": "lockstep_coalesce", "BENCH_SMOKE": "1",
                   "BENCH_ITERS": "8", "BENCH_THREADS": "2"},
                  timeout=360)
    result = json.loads(stdout.strip().splitlines()[-1])
    assert result["metric"] == "lockstep_coalesce_rps" and result["value"] > 0
    names = [t["tier"] for t in result["tiers"]]
    assert names == ["coalesce_on", "coalesce_off"]
    assert all(t["rps"] > 0 and t["per_request_ms"] > 0 for t in result["tiers"])


def test_bench_bulk_smoke():
    """The device-build bulk door vs streamed ingest A/B: the digest
    parity and Arrow round-trip contracts are asserted INSIDE the bench
    (a nonzero exit fails _run); BENCH_SMOKE relaxes only the 5x
    throughput gate, which tiny shapes can't meaningfully hold."""
    stdout = _run({"BENCH_CONFIG": "bulk", "BENCH_SMOKE": "1"}, timeout=300)
    result = json.loads(stdout.strip().splitlines()[-1])
    assert result["metric"] == "bulk_build_vs_streamed_ingest"
    t = result["tiers"]
    assert t["bulk_pairs_per_s"] > 0 and t["stream_pairs_per_s"] > 0
    assert t["digest_equal"] is True
    assert t["arrow_roundtrip_bytes"] > 0


def test_bench_executor_gather_smoke():
    stdout = _run({
        "BENCH_CONFIG": "executor_gather", "BENCH_ROWS": "32",
        "BENCH_SLICES": "2", "BENCH_BATCH": "8", "BENCH_ITERS": "2",
        "BENCH_BITS_PER_ROW": "5",
    })
    result = json.loads(stdout.strip().splitlines()[-1])
    assert result["value"] > 0


def test_refloop_bench_compiles_and_runs(tmp_path):
    """The measured CPU stand-in for the reference's hot loop
    (native/refloop_bench.c = popcntAndSliceAsm semantics) must build
    with the baked toolchain and emit its JSON line."""
    import shutil
    import subprocess as sp

    cc = shutil.which("gcc") or shutil.which("cc")
    if cc is None:
        pytest.skip("no C compiler")
    exe = tmp_path / "refloop"
    built = sp.run(
        [cc, "-O2", "-mpopcnt", "-o", str(exe),
         os.path.join(REPO, "native", "refloop_bench.c")],
        capture_output=True, text=True,
    )
    if built.returncode != 0:
        if "mpopcnt" in built.stderr:  # non-x86 host: capability gap
            pytest.skip("-mpopcnt unsupported on this arch")
        raise AssertionError(built.stderr[-1000:])
    out = sp.run([str(exe)], capture_output=True, text=True, timeout=120, check=True)
    d = json.loads(out.stdout.strip())
    assert d["bytes_per_s"] > 1e8 and d["pair_qps_1slice"] > 0
