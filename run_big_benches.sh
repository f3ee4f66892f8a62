#!/bin/bash
# One-off big-shape bench runs.  Results append to big_bench_results.jsonl.
# A chip belongs to one process: run this alone on its machine, one bench
# process at a time (which is what the run() loop below does).
set -u
cd "$(dirname "$0")"
OUT=big_bench_results.jsonl
# PREFLIGHT: the invariant linter must be clean before burning bench
# hours — a stale counters registry or a new untagged finding means the
# tree is mid-change and the run's telemetry names may not match
# COUNTERS.md.  Covers all generation-2 rules too (guarded-fields,
# native-abi, stale-suppression).  Fails fast with the linter's report.
if ! python -m pilosa_tpu.analysis; then
  echo "pilosa_tpu.analysis preflight failed; fix/tag findings first" >&2
  exit 1
fi
# PREFLIGHT 2: the native boundary must be sanitizer-clean before the
# writelane/ingest configs hammer it for an hour — build the ASAN+UBSAN
# flavor and re-run the differential suites against it (the same leg
# tier-1 runs; skips itself with a logged reason when no toolchain).
if ! python -m pytest tests/test_native_sanitized.py -q -p no:cacheprovider; then
  echo "sanitized native leg failed; fix the sanitizer findings first" >&2
  exit 1
fi
# PREFLIGHT 3: the interleaving-explorer scenario suite + the replica
# write-protocol model check must pass before any bench run — the
# recovery/resync/writelane configs hammer exactly the sequencer/WAL/
# catch-up orderings the explorer covers, and a schedule-dependent bug
# should fail HERE with a replayable schedule string, not corrupt an
# hour of bench telemetry.  (Same lane as tier-1's test_sched gate.)
if ! python -m pilosa_tpu.analysis --explore all; then
  echo "interleaving explorer / protocol model preflight failed;" >&2
  echo "replay the printed schedule: python -m pilosa_tpu.analysis --explore <scenario> --schedule <string>" >&2
  exit 1
fi
# PREFLIGHT 4: the observability plane must scrape clean before an hour
# of telemetry rides it — stand up a 3-group bench-shaped cluster with
# one group DOWN, strict-parse every /metrics exposition (group + router)
# and require /debug/fleet to serve a PARTIAL aggregate with the dead
# group stamped stale.  Unparseable exposition or a fleet view that
# drops the dead group fails here, not in the dashboard at hour two.
if ! python - <<'PYEOF'
import json, sys, tempfile, urllib.request

from pilosa_tpu import metrics
from pilosa_tpu.config import Config
from pilosa_tpu.replica import ReplicaRouter
from pilosa_tpu.server.server import Server
from pilosa_tpu.stats import ExpvarStatsClient

with tempfile.TemporaryDirectory() as tmp:
    servers = []
    for i in range(3):
        cfg = Config(data_dir=f"{tmp}/g{i}", host="127.0.0.1:0",
                     engine="numpy", stats="expvar", qcache_enabled=False,
                     replica_group=f"g{i}")
        srv = Server(cfg)
        srv.open()
        servers.append(srv)
    router = ReplicaRouter(
        [f"g{i}={s.host}" for i, s in enumerate(servers)],
        probe_interval_s=0.1, stats=ExpvarStatsClient(),
    ).serve()
    base = f"http://127.0.0.1:{router.port}"
    try:
        def req(method, path, body=None):
            rq = urllib.request.Request(base + path, data=body, method=method)
            with urllib.request.urlopen(rq, timeout=30) as resp:
                return resp.read()

        req("POST", "/index/i", b"{}")
        req("POST", "/index/i/frame/f", b"{}")
        req("POST", "/index/i/query", b'SetBit(rowID=1, frame="f", columnID=1)')
        req("POST", "/index/i/query", b'Count(Bitmap(rowID=1, frame="f"))')
        # Strict-parse every exposition in the fleet: each group's and
        # the router's own.
        for s in servers:
            fams = metrics.parse_exposition(
                urllib.request.urlopen(
                    f"http://{s.host}/metrics", timeout=30).read().decode())
            assert fams, f"empty exposition from group {s.host}"
        metrics.parse_exposition(req("GET", "/metrics").decode())
        # Kill one group; the fleet view must degrade to PARTIAL with
        # the dead group still present, stamped with its error.
        servers[2].close()
        fleet = json.loads(req("GET", "/debug/fleet?timeout-ms=300"))
        assert len(fleet["groups"]) == 3, fleet
        assert fleet["partial"] is True, "fleet view not marked partial"
        dead = [g for g in fleet["groups"] if g["name"] == "g2"][0]
        assert dead.get("error") and dead["staleScrape"], dead
        live = [g for g in fleet["groups"] if g["name"] != "g2"]
        assert all(g["scrape"] is not None for g in live), "live scrape missing"
        print("observability preflight OK:",
              sum(1 for g in fleet['groups'] if not g['staleScrape']),
              "of 3 groups scraped live")
    finally:
        router.close()
        for s in servers[:2]:
            s.close()
PYEOF
then
  echo "observability preflight failed: /metrics unparseable or /debug/fleet" >&2
  echo "did not degrade to a partial aggregate; fix before burning bench hours" >&2
  exit 1
fi
# PREFLIGHT 5: the native kernels must be THREAD-sanitizer clean before
# the concurrent configs drive them from real overlapping threads for an
# hour — build the TSAN flavor and run the true-concurrency harness
# (clean per-fragment leg + the seeded shared-table race fixture that
# proves the leg can see a race at all).  Skips itself with a logged
# reason when the toolchain or the TSAN runtime is missing, same
# contract as the ASAN leg; a real data race fails HERE with the TSAN
# report, not as silent corruption in hour two.
if ! python -m pytest tests/test_native_threaded.py -q -p no:cacheprovider; then
  echo "TSAN native leg failed: a data race (or a blind TSAN fixture) in the" >&2
  echo "concurrent kernel paths; fix the race before burning bench hours" >&2
  exit 1
fi
run() {
  echo "=== $* $(date +%H:%M:%S)" >> $OUT
  timeout 3600 env "$@" python bench.py >> $OUT 2>>big_bench_errors.log
  echo "--- exit=$? $(date +%H:%M:%S)" >> $OUT
}
# 1) >=1B columns resident on one chip (device-generated; relayout copy
#    gone since round 3, so 1024 slices x 64 rows = 8 GB fits).  Long
#    stream for the Gram lane's sustained rate; the NO_GRAM line records
#    the direct resident kernel's bandwidth on the same shape.
run BENCH_CONFIG=intersect_count BENCH_SLICES=1024 BENCH_ITERS=65536 BENCH_TIMED_RUNS=3
run BENCH_CONFIG=intersect_count BENCH_SLICES=1024 PILOSA_TPU_NO_GRAM=1 BENCH_ITERS=128 BENCH_TIMED_RUNS=2
# 2) TopN p50 @ 1.01B columns (BASELINE.json metric).
run BENCH_CONFIG=topn_p50 BENCH_ITERS=64
# 3) Gram-ineligible 4k-row gather headline with bandwidth_util, at the
#    512 KB-row and 2 MB-row DMA shapes.
run BENCH_CONFIG=intersect_count_4krows BENCH_TIMED_RUNS=3
run BENCH_CONFIG=intersect_count_4krows BENCH_SLICES=16 BENCH_TIMED_RUNS=3
# 4) Resident-kernel bandwidth_util at the classic 16-slice shape.
run BENCH_CONFIG=intersect_count PILOSA_TPU_NO_GRAM=1 BENCH_ITERS=512 BENCH_TIMED_RUNS=3
# 5) Bigger-than-HBM stream (device-staged chunks: the device half of
#    the streaming regime, host refill excluded) — at 2.15B and the
#    10B-column north-star scale.
run BENCH_CONFIG=intersect_count_stream BENCH_TIMED_RUNS=2
run BENCH_CONFIG=intersect_count_stream BENCH_SLICES=10240 BENCH_TIMED_RUNS=2
# 6) Product-path gather-regime shapes (chunked-Gram product lane, with
#    forced-NO_GRAM row-major/slice-major tiers recorded in the unit).
run BENCH_CONFIG=executor_gather BENCH_ROWS=1024
run BENCH_CONFIG=executor_gather
# 7) Mixed read/write serving: warm-state repair lane vs forced
#    invalidate-and-rebuild, at 95/5, 50/50, and write-burst coalescing
#    tiers (b8/b64 — one deferred repair per burst; tiers in the JSON);
#    the second line stresses a wider Gram (more rows) per repair and a
#    wider slice span (where per-(row, slice) patch granularity pays).
run BENCH_CONFIG=mixed
run BENCH_CONFIG=mixed BENCH_ROWS=256 BENCH_SLICES=8
# 8) Lockstep request coalescing: single-call requests, coalesced batch
#    replay vs one control-plane entry per request.
run BENCH_CONFIG=lockstep_coalesce
run BENCH_CONFIG=lockstep_coalesce BENCH_THREADS=32
# 8b) Native write request lane + streaming columnar ingest: singleton
#    native-vs-general and batched native-vs-python A/B (both asserted
#    in-run), plus the /ingest streaming tier sustaining a column
#    stream against concurrent QoS-doored reads (zero read sheds
#    asserted).  The second line sizes bigger batches; the third a
#    bigger stream with more readers.
run BENCH_CONFIG=writelane
run BENCH_CONFIG=writelane BENCH_BATCH=256
run BENCH_CONFIG=writelane BENCH_STREAM_PAIRS=2000000 BENCH_THREADS=8
# 9) Generation-keyed query result cache: Zipf-skewed repeated read mix
#    with interleaved writes, cache-on vs cache-off tiers in the JSON
#    (hit rate + ms/request; read-your-writes asserted in-run); the
#    second line pushes a wider pool at heavier skew (dashboard-fleet
#    shape), the third an unskewed mix (worst case for the cache).
run BENCH_CONFIG=qcache
run BENCH_CONFIG=qcache BENCH_QUERY_POOL=512 BENCH_ZIPF_S=1.3
run BENCH_CONFIG=qcache BENCH_ZIPF_S=0.0
#    Tracing on/off A/B rides the qcache config (trace_overhead /
#    trace_ok in the qcache_on tier): head sampling at 0.01 must stay
#    within 5% of tracing disabled — bigger loop for a tighter bound.
#    The observability-plane A/B rides the same config (costs_overhead /
#    costs_ok): dispatch meter + cost ledger + a scrape every n/4
#    requests must also stay within 5% of fully disabled.
run BENCH_CONFIG=qcache BENCH_TRACE_ITERS=40000 BENCH_COSTS_ITERS=40000
# 10) Request-lifecycle QoS under overload: a real HTTP server at 2x door
#    capacity, QoS on (bounded admission + deadlines; shed 429s, p99 near
#    presat) vs off (unbounded; p99 degrades with the queue).  The second
#    line pushes deeper overload on a wider door.
run BENCH_CONFIG=overload
run BENCH_CONFIG=overload BENCH_QOS_DEPTH=8 BENCH_THREADS=64
# 10b) Multi-tenant hostile neighbor: a polite tenant at its weighted
#    fair share of the read door vs a hostile tenant flooding at 2x the
#    door's depth.  The hostile-flood leg asserts IN-RUN that isolation
#    holds: polite p99 within 1.5x its isolated baseline, ZERO polite
#    sheds, and real hostile sheds — then repeats with tenancy off for
#    the A/B.  The second line widens the door and doubles the flood.
run BENCH_CONFIG=tenancy
run BENCH_CONFIG=tenancy BENCH_QOS_DEPTH=16 BENCH_THREADS=32
# 11) Replicated serving groups: read QPS through the replica router at
#    1 vs 2 groups (scaling_1_to_2 is the headline; needs >= 3 cores) +
#    router on/off overhead, with cross-group read-your-writes and
#    failover (reads survive a killed group, writes 503 until quorate)
#    asserted in-run.  The second line scales the group fleet.
run BENCH_CONFIG=replica
run BENCH_CONFIG=replica BENCH_GROUPS=4 BENCH_THREADS=32
# 11b) Multi-core host serving: one host's front door at 1 vs 2 workers
#    (free-threaded pool threads, or SO_REUSEPORT processes on GIL
#    builds) from 1/2/4 client threads — scaling_1_to_2 asserted >= 1.6
#    in-run on a multi-core host — plus the serve-lane-breadth A/B
#    (native multi-frame / tree / Range one-crossing lanes vs the
#    Python general lane, parity + win asserted in-run).  The second
#    line sizes bigger batches over more rows (dashboard shape).
run BENCH_CONFIG=multicore
run BENCH_CONFIG=multicore BENCH_ROWS=64 BENCH_BATCH=128 BENCH_BITS_PER_ROW=50000
# 12) Durable write log + recovery: write throughput with 3 groups vs a
#    SIGKILLed group on the degraded quorum (zero failed writes asserted
#    in-run — the WAL's availability headline) and catch-up time for the
#    restarted group's WAL-suffix replay; the second line sizes a deeper
#    backlog so the replay phase dominates.
run BENCH_CONFIG=recovery
run BENCH_CONFIG=recovery BENCH_RECOVERY_WRITES=4000 BENCH_BATCH=16
# 13) Automated resync: a BLANK group joins a loaded 2-group cluster
#    and self-heals (digest diff -> roaring fragment stream -> seed ->
#    catch-up) — time-to-rejoin, bytes streamed vs WAL-replay traffic,
#    zero failed writes during the resync and digest convergence both
#    asserted in-run; the second line loads enough fragment bulk that
#    the stream phase dominates.
run BENCH_CONFIG=resync
run BENCH_CONFIG=resync BENCH_RESYNC_WRITES=8000 BENCH_BATCH=16
# 14) Partitioned replica groups: write QPS through one shard vs two
#    (the slice space split across groups, each with its own sequencer
#    space; scaling_1s_to_2s asserted >= 1.5 in-run, needs >= 3 cores)
#    plus a LIVE RESHARD splitting the hot range under concurrent write
#    load — zero failed writes and digest convergence (moved slices only
#    on the new group) asserted in-run.  The second line runs longer
#    phases with more clients for a stabler ratio.
run BENCH_CONFIG=shard
run BENCH_CONFIG=shard BENCH_THREADS=24 BENCH_SHARD_SECS=10
# 15) Device-first bulk build vs streamed ingest: the SAME seeded pairs
#    through both doors over HTTP (>= 5x pairs/s, digest-identical
#    fragments, and a byte-identical arrow export -> re-ingest round
#    trip all asserted in-run).  The second line sizes a wider slice
#    span so the per-slice commit and egress paths dominate the sort.
run BENCH_CONFIG=bulk
run BENCH_CONFIG=bulk BENCH_BULK_PAIRS=4000000 BENCH_BULK_SLICES=16 BENCH_BULK_ROWS=256
echo "ALL DONE $(date +%H:%M:%S)" >> $OUT
