"""Benchmark: PQL Intersect+Count throughput (the north-star metric).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Workload (BASELINE.md config 1/4 shape): a Star-Trace style index — a
device-resident row matrix of ``n_slices`` slices × ``n_rows`` rows of
packed SLICE_WIDTH-bit bitmaps — served a stream of
``Count(Intersect(Bitmap(r1), Bitmap(r2)))`` queries.  Queries run in
batches through ONE fused computation per batch via
``dispatch.gather_count`` — the strategy stack the product path uses
(the TPU-native form of the reference's per-slice goroutine fan-out +
SIMD loop, executor.go:1115-1244 + roaring/assembly_amd64.s:60-77):

- row working set tiny → the MXU all-pairs Gram strategy (one int8
  matmul of the unpacked bits computes every pair count; per-query
  answers are lookups, and XLA hoists the matmul out of the stream loop
  since it depends only on the row matrix);
- rows fit VMEM → the resident Pallas kernel (whole row set streamed
  HBM→VMEM once per chunk, queries answered from VMEM);
- otherwise → the scalar-prefetch gather Pallas kernel (two row DMAs
  per (query, slice) grid step, no materialized intermediates).

Timing methodology: all ``iters`` batches are chained inside one jitted
``lax.scan`` and the timer stops only when a digest of every result has
been fetched to host memory, so the run cannot finish early.  The method
was chosen on an earlier rig and has not been measured on this chip; its
replacement (per-request timing on the served path, device time from a
trace) is ROADMAP S1.

vs_baseline (headline): ratio against the MEASURED compiled-loop bound
of the reference's kernel hot loop — native/refloop_bench.c compiles the
exact popcntAndSliceAsm semantics (Σ popcount(a[i] & b[i]),
roaring/assembly_amd64.s:60-77) with -mpopcnt and measures it on this
host, giving a defensible single-core reference-equivalent q/s at the
bench shape.  The single-threaded numpy ratio (the round-1..4
denominator) is kept as the secondary field ``vs_numpy``.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np


def _ref_loop_bytes_per_s() -> float:
    """Measured bytes/s of the reference's AND+POPCNT hot loop on this host.

    Builds and runs ``native/refloop_bench.c`` (the compiled stand-in for
    roaring/assembly_amd64.s:60-77 — the Go toolchain is absent here, see
    BASELINE.md) and returns its DRAM-bound streaming rate.  The result
    is the denominator for the headline ``vs_baseline``: reference
    pair-count q/s at shape (n_slices, 2^20 cols) = rate / (2 * n_slices
    * 128 KiB).  Cached per process; ``BENCH_REF_BYTES_PER_S`` overrides;
    falls back to the value measured on the round-5 build host when the
    C toolchain is unavailable.
    """
    env = os.environ.get("BENCH_REF_BYTES_PER_S")
    if env:
        _ref_loop_bytes_per_s._measured = True  # operator-supplied
        return float(env)
    cached = getattr(_ref_loop_bytes_per_s, "_cache", None)
    if cached is not None:
        return cached
    rate = 2.38e10  # round-5 build-host measurement (fallback)
    measured = False
    try:
        import subprocess
        import tempfile

        src = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "native", "refloop_bench.c")
        with tempfile.TemporaryDirectory() as td:
            exe = os.path.join(td, "refloop_bench")
            subprocess.run(["gcc", "-O2", "-mpopcnt", "-o", exe, src],
                           check=True, capture_output=True, timeout=60)
            out = subprocess.run([exe], check=True, capture_output=True,
                                 timeout=120).stdout
        rate = float(json.loads(out)["bytes_per_s"])
        measured = True
    except Exception:
        import sys

        print("bench: refloop_bench unavailable; vs_baseline uses the "
              "build-host fallback rate (ref_loop_measured=false)",
              file=sys.stderr)
    _ref_loop_bytes_per_s._cache = rate
    _ref_loop_bytes_per_s._measured = measured
    return rate


def _best_of_runs(fn, default_runs=5):
    """Min wall time over N runs (see headline config)."""
    runs = max(1, int(os.environ.get("BENCH_TIMED_RUNS", str(default_runs))))
    dt = float("inf")
    out = None
    for _ in range(runs):
        t0 = time.perf_counter()
        out = fn()
        dt = min(dt, time.perf_counter() - t0)
    return dt, out


def bench_setbit() -> dict:
    """Config 2: SetBit op/sec (the `pilosa bench --operation set-bit`
    analog, ctl/bench.go:71-102).  Reports the CONCURRENT server ingest
    shape as the headline — singleton SetBit requests from BENCH_THREADS
    clients group-committing through the write queue (executor ->
    vectorized fragment batches + one WAL append per commit) — with the
    sequential per-op-durable fragment rate in the unit string for
    apples-to-apples against the reference's single client."""
    n = int(os.environ.get("BENCH_OPS", "20000"))
    n_threads = int(os.environ.get("BENCH_THREADS", "8"))
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.core.fragment import Fragment
    from pilosa_tpu.core.frame import FrameOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor

    rng = np.random.default_rng(7)
    rows = rng.integers(0, 1000, size=n)
    cols = rng.integers(0, 1 << 20, size=n)

    # (a) sequential fragment loop, per-op durability (reference shape).
    with tempfile.TemporaryDirectory() as d:
        f = Fragment(os.path.join(d, "frag"), "i", "f", "standard", 0)
        f.open()
        t0 = time.perf_counter()
        for r, c in zip(rows.tolist(), cols.tolist()):
            f.set_bit(r, c)
        seq_dt = time.perf_counter() - t0
        f.close()

    # (b) concurrent singleton requests through the ingest queue: each
    # client thread issues one PQL SetBit request at a time and waits for
    # its durable ack (exactly the threaded-HTTP-server shape, minus HTTP).
    with tempfile.TemporaryDirectory() as d:
        h = Holder(d)
        h.open()
        h.create_index("b").create_frame("f", FrameOptions())
        ex = Executor(h, engine="numpy", write_queue=True)
        queries = [
            f'SetBit(rowID={r}, frame="f", columnID={c})'
            for r, c in zip(rows.tolist(), cols.tolist())
        ]
        ex.execute("b", queries[0])  # warm (frame/fragment creation)
        t0 = time.perf_counter()
        with ThreadPoolExecutor(n_threads) as pool:
            for _ in pool.map(lambda q: ex.execute("b", q), queries[1:]):
                pass
        q_dt = time.perf_counter() - t0
        wq = ex._write_queue
        mean_batch = wq.stat_items / max(1, wq.stat_batches)
        h.close()
    q_ops = (n - 1) / q_dt
    return {
        "metric": "setbit_ops_per_sec",
        "value": round(q_ops, 1),
        "unit": (
            f"SetBit/sec ({n_threads} concurrent clients, group-commit queue, "
            f"mean batch {mean_batch:.0f}; sequential per-op-durable fragment "
            f"rate {n / seq_dt:,.0f}/s)"
        ),
        "vs_baseline": round(q_ops / (n / seq_dt), 2),
    }


def bench_writelane() -> dict:
    """Config: native write request lane (pn_write_batch) + streaming
    columnar ingest door.

    Tiers (native vs Python A/B asserted in-run):

    - ``singleton``: canonical singleton SetBit requests through the
      NATIVE lane (``PILOSA_TPU_NO_FASTWRITE=1`` so the regex fast
      lane steps aside) vs the Python GENERAL lane (both fast lanes
      off, full parse path) — the native lane must win
      (``singleton_native_vs_general``); the default-config fast-lane
      rate rides along for context (for n=1 the regex + fused
      ``pn_array_add_logged`` crossing is already one native call, so
      the batch lane is not expected to beat it).
    - ``batched``: B-call SetBit bodies, native lane on vs off — one
      fused parse+insert+WAL crossing vs parse + vectorized batch
      (``batched_native_vs_python`` asserted > 1).
    - ``streaming``: a REAL HTTP server ingesting a packed-uint64
      column stream through ``POST .../ingest`` while concurrent read
      clients keep serving under QoS — ZERO read starvation asserted
      (no read-class sheds, every reader progresses) plus the
      sustained ingest pair rate.

    A differential gate runs in-band: the native and general lanes
    applied to the same op stream must leave byte-identical fragments.
    """
    import io
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.core.frame import FrameOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor

    smoke = os.environ.get("BENCH_SMOKE", "").lower() in ("1", "true", "yes")
    n = int(os.environ.get("BENCH_OPS", "4000" if smoke else "20000"))
    batch = int(os.environ.get("BENCH_BATCH", "64"))
    n_rows = int(os.environ.get("BENCH_ROWS", "64"))
    stream_pairs = int(
        os.environ.get("BENCH_STREAM_PAIRS", "40000" if smoke else "400000")
    )
    n_readers = int(os.environ.get("BENCH_THREADS", "2" if smoke else "4"))

    rng = np.random.default_rng(7)
    rows = rng.integers(0, n_rows, size=n)
    cols = rng.integers(0, 1 << 20, size=n)
    rl, cl = rows.tolist(), cols.tolist()

    _ENVS = ("PILOSA_TPU_NO_WRITELANE", "PILOSA_TPU_NO_FASTWRITE")

    def with_env(env: dict):
        for k in _ENVS:
            os.environ.pop(k, None)
        os.environ.update(env)

    def run_ops(env: dict, queries: list, seed_qs: list, ops: int) -> tuple[float, bytes]:
        """Fresh holder + executor under ``env``; a seed pass (same
        containers, sibling bits: c^1) pre-creates the container set so
        the timed pass measures the steady-state lane, not first-touch
        container churn.  Returns (op/s, final fragment bytes)."""
        with_env(env)
        with tempfile.TemporaryDirectory() as d:
            h = Holder(d)
            h.open()
            h.create_index("b").create_frame("f", FrameOptions())
            ex = Executor(h, engine="numpy", qcache=None)
            for q in seed_qs:
                ex.execute("b", q)
            t0 = time.perf_counter()
            for q in queries:
                ex.execute("b", q)
            dt = time.perf_counter() - t0
            frag = h.fragment("b", "f", "standard", 0)
            buf = io.BytesIO()
            frag.write_to(buf)
            h.close()
        for k in _ENVS:
            os.environ.pop(k, None)
        return ops / dt, buf.getvalue()

    def mk_qs(rlist, clist, b):
        if b == 1:
            return [
                f'SetBit(rowID={r}, frame="f", columnID={c})'
                for r, c in zip(rlist, clist)
            ]
        return [
            "".join(
                f'SetBit(rowID={r}, frame="f", columnID={c})'
                for r, c in zip(rlist[i : i + b], clist[i : i + b])
            )
            for i in range(0, len(rlist), b)
        ]

    seed_cols = [c ^ 1 for c in cl]
    singleton_qs = mk_qs(rl, cl, 1)
    singleton_seed = mk_qs(rl, seed_cols, batch)  # fast batched seeding
    batched_qs = mk_qs(rl, cl, batch)
    batched_seed = singleton_seed

    s_native, bytes_native = run_ops(
        {"PILOSA_TPU_NO_FASTWRITE": "1"}, singleton_qs, singleton_seed, n
    )
    s_general, bytes_general = run_ops(
        {"PILOSA_TPU_NO_FASTWRITE": "1", "PILOSA_TPU_NO_WRITELANE": "1"},
        singleton_qs, singleton_seed, n,
    )
    s_fast, bytes_fast = run_ops({}, singleton_qs, singleton_seed, n)
    # Differential gate: identical op stream -> byte-identical storage,
    # whichever lane served it.
    differential_ok = bytes_native == bytes_general == bytes_fast
    assert differential_ok, "write lanes diverged: fragment bytes differ"

    b_native, bb_native = run_ops({}, batched_qs, batched_seed, n)
    b_python, bb_python = run_ops(
        {"PILOSA_TPU_NO_WRITELANE": "1"}, batched_qs, batched_seed, n
    )
    assert bb_native == bb_python, "batched lanes diverged: fragment bytes differ"

    sn_ratio = s_native / s_general
    bt_ratio = b_native / b_python
    # In-run contract: the fused native crossing must beat the Python
    # general lane on singletons and the parse+vectorized path on
    # batches.
    assert sn_ratio > 1.0, (
        f"native singleton lane did not beat the general lane: {sn_ratio:.2f}"
    )
    assert bt_ratio > 1.0, (
        f"native batch lane did not beat the python batch path: {bt_ratio:.2f}"
    )

    # -- streaming tier: ingest vs concurrent reads under QoS ------------
    import json as _json
    import urllib.error
    import urllib.request

    from pilosa_tpu.config import Config
    from pilosa_tpu.server.client import Client
    from pilosa_tpu.server.server import Server

    s_rows = rng.integers(0, n_rows, size=stream_pairs).astype(np.uint64)
    s_cols = rng.integers(0, 1 << 20, size=stream_pairs).astype(np.uint64)
    with tempfile.TemporaryDirectory() as d:
        cfg = Config(
            data_dir=d, host="127.0.0.1:0", engine="numpy", stats="expvar",
            qcache_enabled=False,
        )
        # Small write door: ingest chunks must queue behind it rather
        # than monopolize the server; reads keep their own door.
        cfg.qos_write_depth = 2
        cfg.qos_read_depth = max(4, n_readers * 2)
        srv = Server(cfg)
        srv.open()
        try:
            client = Client(srv.host)
            client.create_index("s")
            client.create_frame("s", "f")
            # Seed a few bits so readers have something to count.
            client.ingest_stream("s", "f", [1, 2, 3], [1, 2, 3])
            stop = [False]

            def reader(i: int) -> dict:
                out = {"served": 0, "shed": 0, "errors": 0}
                k = i
                while not stop[0]:
                    q = f'Count(Bitmap(rowID={k % n_rows}, frame="f"))'
                    k += 1
                    req = urllib.request.Request(
                        f"http://{srv.host}/index/s/query",
                        data=q.encode(), method="POST",
                    )
                    try:
                        with urllib.request.urlopen(req, timeout=30) as resp:
                            resp.read()
                        out["served"] += 1
                    except urllib.error.HTTPError as e:
                        e.read()
                        if e.code in (429, 503):
                            out["shed"] += 1
                        else:
                            out["errors"] += 1
                    except OSError:
                        out["errors"] += 1
                return out

            with ThreadPoolExecutor(n_readers + 1) as pool:
                futs = [pool.submit(reader, i) for i in range(n_readers)]
                t0 = time.perf_counter()
                res = client.ingest_stream(
                    "s", "f", s_rows, s_cols, chunk_pairs=16384
                )
                ingest_dt = time.perf_counter() - t0
                stop[0] = True
                reads = [f.result() for f in futs]
            assert res["done"], "streamed ingest did not complete"
            v = _json.loads(
                urllib.request.urlopen(f"http://{srv.host}/debug/vars").read()
            )
            read_sheds = int(v.get("qos.shed.read", 0))
            # Zero read starvation: ingest backpressure lands on the
            # WRITE door; every reader kept serving and no read shed.
            assert read_sheds == 0, f"reads shed during ingest: {read_sheds}"
            assert all(r["served"] > 0 for r in reads), (
                f"a reader starved during ingest: {reads}"
            )
            stream_rate = stream_pairs / ingest_dt
            reads_served = sum(r["served"] for r in reads)
        finally:
            srv.close()

    return {
        "metric": "writelane_batched_native_vs_python",
        "value": round(bt_ratio, 2),
        "unit": (
            f"x vs python batch path (B={batch}; singleton native "
            f"{s_native:,.0f}/s vs general {s_general:,.0f}/s = "
            f"x{sn_ratio:.2f}, fast lane {s_fast:,.0f}/s; streaming "
            f"{stream_rate:,.0f} pairs/s with {reads_served} concurrent "
            f"reads, 0 read sheds)"
        ),
        "tiers": {
            "singleton_native_ops": round(s_native, 1),
            "singleton_general_ops": round(s_general, 1),
            "singleton_fast_ops": round(s_fast, 1),
            "singleton_native_vs_general": round(sn_ratio, 2),
            "batched_native_ops": round(b_native, 1),
            "batched_python_ops": round(b_python, 1),
            "batched_native_vs_python": round(bt_ratio, 2),
            "stream_pairs_per_s": round(stream_rate, 1),
            "stream_reads_served": reads_served,
            "stream_read_sheds": 0,
            "differential_ok": True,
        },
    }


def bench_topn() -> dict:
    """Config 3: TopN over a ranked frame — candidate scoring via the
    batched intersection-count kernel (fragment.go:493-625 analog)."""
    n_rows = int(os.environ.get("BENCH_TOPN_ROWS", "2048"))
    iters = int(os.environ.get("BENCH_ITERS", "400"))
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pilosa_tpu.ops.bitwise import WORDS_PER_SLICE

    rng = np.random.default_rng(3)
    rows = rng.integers(0, 1 << 32, size=(n_rows, WORDS_PER_SLICE), dtype=np.uint32)
    src = rng.integers(0, 1 << 32, size=(WORDS_PER_SLICE,), dtype=np.uint32)
    masks = rng.integers(0, 1 << 32, size=(iters,), dtype=np.uint32)

    # Scan-chained stream with digest timing (see the headline config):
    # full per-row scores stay materialized in HBM, outside the timed
    # region.
    @jax.jit
    def run_stream(rws, s, ms):
        def step(carry, m):
            inter = jnp.bitwise_and(rws, jnp.bitwise_xor(s, m))
            return carry, jnp.sum(
                lax.population_count(inter).astype(jnp.int32), axis=1
            )

        out = lax.scan(step, 0, ms)[1]
        return out, out.astype(jnp.int64).sum()

    drows, dsrc = jax.device_put(rows), jax.device_put(src)
    dmasks = jax.device_put(masks)
    out_dev, _ = run_stream(drows, dsrc, dmasks)  # warm + compile

    def timed():
        out_d, digest = run_stream(drows, dsrc, dmasks)
        np.asarray(digest)
        return out_d

    dt, out_dev = _best_of_runs(timed)
    out = np.asarray(out_dev)
    dt /= iters
    from pilosa_tpu.roaring import _POPCNT8

    base_iters = max(1, min(2, iters))
    t0 = time.perf_counter()
    for i in range(base_iters):
        base = _POPCNT8[(rows & (src ^ masks[i])).view(np.uint8)].reshape(n_rows, -1).sum(axis=1)
    base_dt = (time.perf_counter() - t0) / base_iters
    assert np.array_equal(out[base_iters - 1], base)
    return {
        "metric": "topn_candidate_scan_rows_per_sec",
        "value": round(n_rows / dt, 1),
        "unit": f"rows/sec scored vs src ({n_rows} rows x 2^20 cols, backend {jax.default_backend()})",
        "vs_baseline": round(base_dt / dt, 2),
    }


def bench_union64() -> dict:
    """Config 4: multi-slice Union+Count mapReduce over 64 slices.

    Same timing methodology as the headline config: all iterations are
    chained inside one jitted ``lax.scan`` and timing stops when the
    results land on the host.  Each scan step
    XORs one operand with a distinct 32-bit mask so every step's union
    is a different computation XLA cannot hoist out of the loop (it
    costs one extra elementwise op in a bandwidth-bound kernel).
    """
    n_slices = int(os.environ.get("BENCH_SLICES", "64"))
    iters = int(os.environ.get("BENCH_ITERS", "16000"))
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pilosa_tpu.ops.bitwise import WORDS_PER_SLICE

    rng = np.random.default_rng(4)
    a = rng.integers(0, 1 << 32, size=(n_slices, WORDS_PER_SLICE), dtype=np.uint32)
    b = rng.integers(0, 1 << 32, size=(n_slices, WORDS_PER_SLICE), dtype=np.uint32)
    masks = rng.integers(0, 1 << 32, size=(iters,), dtype=np.uint32)

    @jax.jit
    def run_stream(x, y, ms):
        def step(carry, m):
            u = jnp.bitwise_or(jnp.bitwise_xor(x, m), y)
            return carry, jnp.sum(lax.population_count(u).astype(jnp.int64))

        out = lax.scan(step, 0, ms)[1]
        return out, out.sum()

    da, db = jax.device_put(a), jax.device_put(b)
    dmasks = jax.device_put(masks)
    got_dev, _ = run_stream(da, db, dmasks)  # warm + compile

    def timed():
        out_d, digest = run_stream(da, db, dmasks)
        np.asarray(digest)
        return out_d

    dt, got_dev = _best_of_runs(timed)
    got = np.asarray(got_dev)
    dt /= iters
    from pilosa_tpu.roaring import _POPCNT8

    base_iters = max(1, min(3, iters))
    t0 = time.perf_counter()
    for i in range(base_iters):
        want = int(_POPCNT8[((a ^ masks[i]) | b).view(np.uint8)].sum())
    base_dt = (time.perf_counter() - t0) / base_iters
    assert got[base_iters - 1] == want
    cols_per_sec = n_slices * (1 << 20) / dt
    return {
        "metric": "union_count_cols_per_sec",
        "value": round(cols_per_sec, 1),
        "unit": f"columns/sec unioned+counted ({n_slices} slices, backend {jax.default_backend()})",
        "vs_baseline": round(base_dt / dt, 2),
    }


def bench_timerange() -> dict:
    """Config 5: time-quantum Range — OR-reduce the YMDH view cover of a
    1-year range (time.go:95-167 analog; ~15 views) then popcount."""
    iters = int(os.environ.get("BENCH_ITERS", "32768"))
    n_views = 15  # typical cover size for a 1-year [start, end) at YMDH
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pilosa_tpu.ops.bitwise import WORDS_PER_SLICE

    rng = np.random.default_rng(5)
    views = rng.integers(0, 1 << 32, size=(n_views, WORDS_PER_SLICE), dtype=np.uint32)
    masks = rng.integers(0, 1 << 32, size=(iters,), dtype=np.uint32)

    # Scan-chained stream (see bench_union64 docstring for why): one
    # dispatch + one host fetch for the whole stream; per-step masks keep
    # every Range a distinct computation.  Each step evaluates a BATCH of
    # range queries (vmapped over masks) — the executor's query-batch
    # fusion shape — so the fixed per-step scan cost amortizes across a
    # view cover that is otherwise only ~2 MB of HBM traffic.
    step_batch = min(int(os.environ.get("BENCH_BATCH", "128")), iters)
    iters -= iters % step_batch
    masks = masks[:iters]

    @jax.jit
    def run_stream(v, ms):
        def one(m):
            acc = lax.reduce(jnp.bitwise_xor(v, m), np.uint32(0), lax.bitwise_or, (0,))
            return jnp.sum(lax.population_count(acc).astype(jnp.int64))

        def step(carry, mrow):
            return carry, jax.vmap(one)(mrow)

        out = lax.scan(step, 0, ms.reshape(-1, step_batch))[1].reshape(-1)
        return out, out.sum()

    dv = jax.device_put(views)
    dmasks = jax.device_put(masks)
    got_dev, _ = run_stream(dv, dmasks)  # warm + compile

    def timed():
        out_d, digest = run_stream(dv, dmasks)
        np.asarray(digest)
        return out_d

    dt, got_dev = _best_of_runs(timed)
    got = np.asarray(got_dev)
    dt /= iters
    from pilosa_tpu.roaring import _POPCNT8

    base_iters = max(1, min(3, iters))
    t0 = time.perf_counter()
    for i in range(base_iters):
        acc = views[0] ^ masks[i]
        for j in range(1, n_views):
            acc |= views[j] ^ masks[i]
        want = int(_POPCNT8[acc.view(np.uint8)].sum())
    base_dt = (time.perf_counter() - t0) / base_iters
    assert got[base_iters - 1] == want
    return {
        "metric": "timerange_union_views_per_sec",
        "value": round(n_views / dt, 1),
        "unit": f"views/sec OR-reduced+counted ({n_views}-view YMDH cover, backend {jax.default_backend()})",
        "vs_baseline": round(base_dt / dt, 2),
    }


def bench_executor() -> dict:
    """End-to-end product path: PQL text -> parser -> Executor ->
    fused device dispatch (_fuse_count_pair_batch) -> results.

    Unlike the headline config (raw kernel throughput), this measures the
    whole single-node product stack the way a client drives it: each
    request is a batch of Count(Intersect(Bitmap, Bitmap)) calls in one
    PQL string, against a Holder-backed frame whose rows live in the
    fragment device cache after warmup.  vs_baseline compares the same
    requests through the numpy engine (the reference-style CPU path).
    """
    n_slices = int(os.environ.get("BENCH_SLICES", "8"))
    n_rows = int(os.environ.get("BENCH_ROWS", "32"))
    batch = int(os.environ.get("BENCH_BATCH", "256"))
    # Enough requests that cold-start (first uncached matrices + the one
    # Gram build) amortizes; steady state is ONE native gram-lane call
    # per request (~0.25ms), so short runs would mostly time the few
    # remaining warm-up stragglers.
    iters = int(os.environ.get("BENCH_ITERS", "240"))
    bits_per_row = int(os.environ.get("BENCH_BITS_PER_ROW", "20000"))
    import tempfile

    from pilosa_tpu.core.frame import FrameOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.pilosa import SLICE_WIDTH

    rng = np.random.default_rng(11)

    def build_query(pairs):
        return " ".join(
            f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
            for a, b in pairs
        )

    all_pairs = rng.integers(0, n_rows, size=(iters, batch, 2))
    queries = [build_query(p.tolist()) for p in all_pairs]

    with tempfile.TemporaryDirectory() as d:
        h = Holder(d)
        h.open()
        idx = h.create_index("bench")
        idx.create_frame("f", FrameOptions())
        fr = idx.frame("f")
        for s in range(n_slices):
            rows = np.repeat(np.arange(n_rows, dtype=np.uint64), bits_per_row)
            cols = rng.integers(0, SLICE_WIDTH, size=len(rows)).astype(
                np.uint64
            ) + np.uint64(s * SLICE_WIDTH)
            fr.import_bits(rows, cols)

        # write_queue=True is the SERVER's executor configuration; it also
        # enables read coalescing, so the threaded variant measures what
        # concurrent clients actually hit.
        ex = Executor(h, write_queue=True)
        backend = ex.engine.name
        # Warm past the strategy ladder: request 1 builds + caches the row
        # matrix, request 2+ upgrade it to the Gram (single-flight build),
        # after which steady state is host-side count lookups.  Timing
        # from a cold cache would mostly measure the one-time matrix
        # upload + Gram matmul, not the serving rate.
        for q in queries[: min(4, len(queries))]:
            ex.execute("bench", q)
        # Drive like a loaded server: concurrent requests overlap parse
        # (CPU) with device dispatch + result fetch, exactly as the
        # threaded HTTP server does.  BENCH_THREADS=1 for pure latency.
        n_threads = int(os.environ.get("BENCH_THREADS", "8"))
        from concurrent.futures import ThreadPoolExecutor

        t0 = time.perf_counter()
        if n_threads > 1:
            with ThreadPoolExecutor(n_threads) as pool:
                for _ in pool.map(lambda q: ex.execute("bench", q), queries):
                    pass
        else:
            for q in queries:
                ex.execute("bench", q)
        dt = time.perf_counter() - t0
        qps = iters * batch / dt

        ex_np = Executor(h, engine="numpy")
        base_iters = max(1, min(3, iters))
        ex_np.execute("bench", queries[0])  # warm: host matrix-cache build
        t0 = time.perf_counter()
        for q in queries[:base_iters]:
            base_out = ex_np.execute("bench", q)
        base_dt = time.perf_counter() - t0
        base_qps = base_iters * batch / base_dt
        # Correctness gate: the fused engine path must agree with the numpy
        # product path on one of the timed queries.
        assert ex.execute("bench", queries[base_iters - 1]) == base_out
        h.close()
    return {
        "metric": "executor_intersect_count_qps",
        "value": round(qps, 1),
        "unit": f"PQL queries/sec end-to-end ({n_slices} slices, batch {batch}, engine {backend})",
        "vs_baseline": round(qps / base_qps, 2),
    }


def bench_executor_gather() -> dict:
    """Product-path GATHER-REGIME shape: steady-state PQL pair-count
    requests over a TALL distinct-row working set (the reference's real
    hot-path shape, executor.go:1115-1244: many distinct rows rather
    than 64 hot ones).

    Since round 4 the executor serves this shape from the chunked
    Gram-at-scale lane (bitwise.pair_gram streams (slice, word-chunk)
    steps, so the Gram has no row ceiling up to PILOSA_TPU_GRAM_ROWS_MAX
    = 4096): after a one-time build, every request is answered by
    host-side native count lookups (pn_gram_counts) with ZERO per-request
    device round trips.

    value       = product-path steady q/s (warm Gram, sequential client).
    vs_baseline = product path vs the NO_GRAM slice-major gather lane
                  (round 3's product path) with a sequential client.
    The unit string records the forced-NO_GRAM lane tiers too: row-major
    and slice-major, sequential AND a 16-thread client (kernel-level lane
    records live in intersect_count_4krows)."""
    n_rows = int(os.environ.get("BENCH_ROWS", "4096"))
    n_slices = int(os.environ.get("BENCH_SLICES", "4"))
    batch = int(os.environ.get("BENCH_BATCH", "512"))
    n_queries = int(os.environ.get("BENCH_ITERS", "8"))
    bits_per_row = int(os.environ.get("BENCH_BITS_PER_ROW", "20"))
    repeats = 3
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    import pilosa_tpu.engine as engine_mod
    from pilosa_tpu.core.frame import FrameOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.pilosa import SLICE_WIDTH

    rng = np.random.default_rng(77)
    with tempfile.TemporaryDirectory() as d:
        h = Holder(d)
        h.open()
        h.create_index("p").create_frame("f", FrameOptions())
        fr = h.index("p").frame("f")
        rows = np.repeat(np.arange(n_rows, dtype=np.uint64), bits_per_row)
        for s in range(n_slices):
            cols = rng.integers(0, SLICE_WIDTH, size=len(rows)).astype(
                np.uint64
            ) + np.uint64(s * SLICE_WIDTH)
            fr.import_bits(rows, cols)

        def build_q(seed):
            # All-distinct operands: want = 2 * pairs, past the resident
            # kernel's predicate.
            perm = np.random.default_rng(seed).permutation(n_rows)
            return " ".join(
                f'Count(Intersect(Bitmap(rowID={int(perm[2 * i])}, frame="f"), '
                f'Bitmap(rowID={int(perm[2 * i + 1])}, frame="f")))'
                for i in range(batch // 2)
            )

        qs = [build_q(i) for i in range(n_queries)]
        total = n_queries * (batch // 2)

        def steady_rates(ex):
            """(sequential q/s, 16-thread q/s) after a full warmup.

            The 16-thread tier is SUSTAINED load — 16 persistent client
            threads each looping the request set — not a pool.map over
            the 8 distinct requests: with the round-5 native serve lane
            a request costs ~100 us, so a fresh-pool 8-item map would
            time thread spawn + handoff, not serving (measured 20x
            under-report on the 1024x4 shape).
            """
            import threading

            for q in qs:  # pass 1: rows page in, kernels compile
                ex.execute("p", q)
            for q in qs:  # pass 2: caches (Gram) build on stable residency
                ex.execute("p", q)
            t0 = time.perf_counter()
            for _ in range(repeats):
                for q in qs:
                    ex.execute("p", q)
            seq = repeats * total / (time.perf_counter() - t0)
            n_threads = 16
            # Size the sustained run from the measured sequential rate:
            # ~3 s of aggregate work regardless of which lane is being
            # measured (the NO_GRAM device tiers are ~1000x slower than
            # the native serve lane; a fixed loop count would run them
            # for minutes).
            loops = max(1, int(seq * 3.0 / (n_threads * total)))

            def client():
                for _ in range(loops):
                    for q in qs:
                        ex.execute("p", q)

            threads = [threading.Thread(target=client) for _ in range(n_threads)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            thr = n_threads * loops * total / (time.perf_counter() - t0)
            return seq, thr

        # write_queue=True is the SERVER's executor configuration; its
        # serve-queue read coalescing merges concurrent flat-lane
        # requests into one vectorized evaluation (16-thread Gram
        # serving measured +76% vs the bare executor).
        ex = Executor(h, write_queue=True)
        backend = ex.engine.name
        qps, qps_thr = steady_rates(ex)
        # Forced-NO_GRAM lane tiers: row-major and slice-major gather —
        # measured WITHOUT the serve queue: coalescing serializes all
        # clients behind one leader's device dispatches, which is right
        # when serving is host-bound (Gram lookups) but destroys the
        # concurrent-dispatch overlap that is the whole point of the
        # 16-thread tier on eager device lanes (taken on an earlier rig;
        # not measured on this chip).
        prior_no_gram = os.environ.get("PILOSA_TPU_NO_GRAM")
        os.environ["PILOSA_TPU_NO_GRAM"] = "1"
        orig = engine_mod.JaxEngine.prefer_rowmajor
        try:
            rm_seq, rm_thr = steady_rates(Executor(h))
            engine_mod.JaxEngine.prefer_rowmajor = lambda self, *a: False
            sm_seq, sm_thr = steady_rates(Executor(h))
        finally:
            engine_mod.JaxEngine.prefer_rowmajor = orig
            if prior_no_gram is None:
                del os.environ["PILOSA_TPU_NO_GRAM"]
            else:
                os.environ["PILOSA_TPU_NO_GRAM"] = prior_no_gram
        # Correctness gate vs numpy on one request.
        assert ex.execute("p", qs[0]) == Executor(h, engine="numpy").execute("p", qs[0])
        h.close()
    return {
        "metric": "executor_gather_qps",
        "value": round(qps, 1),
        "unit": (
            f"PQL queries/sec end-to-end, gather-regime shape ({n_rows} distinct "
            f"rows x {n_slices} slices, batch {batch // 2}, warm chunked-Gram "
            f"product lane, server executor config (single-call native serve "
            f"lane, GIL released), sequential client; {qps_thr:,.0f} q/s "
            f"16-thread sustained; "
            f"NO_GRAM tiers: row-major {rm_seq:,.0f} seq / {rm_thr:,.0f} x16, "
            f"slice-major {sm_seq:,.0f} seq / {sm_thr:,.0f} x16 "
            f"(kernel-level lane record in intersect_count_4krows), "
            f"engine {backend})"
        ),
        "vs_baseline": round(qps / sm_seq, 2),
    }


def bench_range_executor() -> dict:
    """End-to-end fused Range path: batched PQL Count(Range(...)) requests
    through the Executor — parser -> fused multi-view matrix ->
    gather-OR-popcount kernel (the time-quantum dashboard workload;
    time.go:95-167 + executor.go:498-554 analog).  vs_baseline compares
    the same requests through the numpy engine."""
    n_slices = int(os.environ.get("BENCH_SLICES", "4"))
    batch = int(os.environ.get("BENCH_BATCH", "128"))
    iters = int(os.environ.get("BENCH_ITERS", "40"))
    bits = int(os.environ.get("BENCH_BITS", "20000"))
    import tempfile
    from datetime import datetime

    from pilosa_tpu.core.frame import FrameOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.pilosa import SLICE_WIDTH

    rng = np.random.default_rng(13)
    n_rows = 8
    stamps = [
        datetime(2017, m, d, hh)
        for m in range(1, 13) for d in (1, 15) for hh in (0, 12)
    ]
    # Workload: a dashboard-style span pool — 4 fixed "widget" ranges plus
    # 24 randomized day-aligned spans drawn once.  Warmup requests build
    # the multi-view matrix and dispatch the gather-OR kernel per new
    # cover; steady state serves repeats from the host-side cover memo
    # with one device dispatch per request carrying that request's
    # first-seen covers.  The kernel's raw rate has its own config
    # (BENCH_CONFIG=timerange); the executor caps fusion at its matrix
    # row budget, so the pool of distinct ranges is bounded.
    pool = [
        ("2017-01-01T00:00", "2018-01-01T00:00"),
        ("2017-02-01T00:00", "2017-07-15T12:00"),
        ("2017-03-01T00:00", "2017-04-01T00:00"),
        ("2017-06-10T00:00", "2017-06-20T00:00"),
    ]
    # Short day-aligned spans inside Jan-Feb: distinct covers without
    # blowing the fused path's (view, row) combo budget.
    for _ in range(24):
        m1 = int(rng.integers(1, 3))
        d1 = int(rng.integers(1, 28))
        dur = int(rng.integers(1, 22))
        m2, d2 = m1, d1 + dur
        if d2 > 28:
            m2, d2 = m1 + 1, d2 - 28
        pool.append((f"2017-{m1:02d}-{d1:02d}T00:00", f"2017-{m2:02d}-{d2:02d}T00:00"))

    def build_query(rows_, spans_):
        return " ".join(
            f'Count(Range(rowID={r}, frame="t", start="{s}", end="{en}"))'
            for r, (s, en) in zip(rows_, spans_)
        )

    queries = [
        build_query(
            rng.integers(0, n_rows, size=batch).tolist(),
            [pool[int(rng.integers(0, len(pool)))] for _ in range(batch)],
        )
        for _ in range(iters)
    ]

    with tempfile.TemporaryDirectory() as d:
        h = Holder(d)
        h.open()
        idx = h.create_index("bench")
        idx.create_frame("t", FrameOptions(time_quantum="YMD"))
        fr = idx.frame("t")
        rows = rng.integers(0, n_rows, size=bits).astype(np.uint64)
        cols = rng.integers(0, n_slices * SLICE_WIDTH, size=bits).astype(np.uint64)
        ts = [stamps[i] for i in rng.integers(0, len(stamps), size=bits)]
        fr.import_bits(rows, cols, ts)

        ex = Executor(h)
        backend = ex.engine.name
        # Warm over the whole query set: the multi-view matrix reaches its
        # final capacity, kernel shapes compile, and repeated covers land
        # in the memo — the timed loop then measures the dashboard steady
        # state (parse -> fused match -> memo/kernel), which is what a
        # refresh-driven client sees.  Kernel-rate-per-cover has its own
        # config (BENCH_CONFIG=timerange).
        for q in queries:
            ex.execute("bench", q)
        t0 = time.perf_counter()
        for q in queries:
            ex.execute("bench", q)
        dt = time.perf_counter() - t0
        qps = iters * batch / dt

        # Baseline: the same calls executed ONE AT A TIME on the numpy
        # engine — per-call view gathers and OR chains, the reference-style
        # CPU executor shape (fusion and the cover memo only engage on
        # batched requests).
        ex_np = Executor(h, engine="numpy")
        import re

        base_calls = re.findall(r"Count\(Range\([^)]*\)\)", queries[0])
        base_n = min(16, len(base_calls))
        ex_np.execute("bench", base_calls[0])  # warm row caches
        t0 = time.perf_counter()
        base_out = [ex_np.execute("bench", q)[0] for q in base_calls[:base_n]]
        base_dt = time.perf_counter() - t0
        base_qps = base_n / base_dt
        # Correctness gate: fused results must match sequential execution.
        assert ex.execute("bench", queries[0])[:base_n] == base_out
        h.close()
    return {
        "metric": "range_executor_qps",
        "value": round(qps, 1),
        "unit": (
            f"PQL Count(Range) queries/sec, dashboard steady state "
            f"({n_slices} slices, batch {batch}, engine {backend})"
        ),
        "vs_baseline": round(qps / base_qps, 2),
    }


def bench_mixed() -> dict:
    """Mixed read/write serving tier: warm-Gram pair-count batches with
    single-bit SetBit writes interleaved, at 95/5 and 50/50 request
    mixes.  Measures the warm-state REPAIR lane (delta-patched row
    matrices + rank-k Gram updates) against forced
    invalidate-and-rebuild (PILOSA_TPU_REPAIR_ROWS_MAX=0) on the same
    request stream; per-mix steady qps, the latency of the read
    immediately following a write (the repair-vs-rebuild split), and the
    pool repair count land in the ``tiers`` list.  Every write targets a
    column range the import never touches, so each one really mutates
    storage and really invalidates (or patches) the warm state.
    BENCH_SMOKE=1 shrinks every shape to run under CI tier-1 time
    budgets on CPU, exercising the patch lane end to end."""
    smoke = os.environ.get("BENCH_SMOKE", "").lower() in ("1", "true", "yes")
    n_slices = int(os.environ.get("BENCH_SLICES", "2" if smoke else "4"))
    n_rows = int(os.environ.get("BENCH_ROWS", "16" if smoke else "64"))
    batch = int(os.environ.get("BENCH_BATCH", "8" if smoke else "128"))
    n_requests = int(os.environ.get("BENCH_ITERS", "30" if smoke else "400"))
    bits_per_row = int(
        os.environ.get("BENCH_BITS_PER_ROW", "50" if smoke else "20000")
    )
    import tempfile

    from pilosa_tpu.core.frame import FrameOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.pilosa import SLICE_WIDTH

    rng = np.random.default_rng(23)
    reserve = 4096  # import keeps these top columns free for the writes

    def build_read(seed):
        prs = np.random.default_rng(seed).integers(0, n_rows, size=(batch, 2))
        return " ".join(
            f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
            for a, b in prs.tolist()
        )

    read_qs = [build_read(s) for s in range(4)]
    state = {"engine": "?"}

    def run_mix(write_every: int, repair_on: bool, burst: int = 1,
                n_req: int = 0) -> dict:
        """One mixed-traffic run.  ``burst > 1`` switches the 50/50
        schedule from strict alternation to coalescing bursts: ``burst``
        back-to-back writes followed by ``burst`` reads — the whole
        burst's dirty rows accumulate in the ledger/journals and the
        FIRST read dispatches ONE deferred repair for the union (one
        pool rewrite + one rank-k Gram update per burst, not per
        write)."""
        prior = os.environ.get("PILOSA_TPU_REPAIR_ROWS_MAX")
        if not repair_on:
            os.environ["PILOSA_TPU_REPAIR_ROWS_MAX"] = "0"
        n_req = n_req or n_requests
        try:
            with tempfile.TemporaryDirectory() as d:
                h = Holder(d)
                h.open()
                h.create_index("m").create_frame("f", FrameOptions())
                fr = h.index("m").frame("f")
                rows = np.repeat(np.arange(n_rows, dtype=np.uint64), bits_per_row)
                for s in range(n_slices):
                    cols = rng.integers(
                        0, SLICE_WIDTH - reserve, size=len(rows)
                    ).astype(np.uint64) + np.uint64(s * SLICE_WIDTH)
                    fr.import_bits(rows, cols)
                ex = Executor(h)
                state["engine"] = ex.engine.name
                for q in read_qs:  # pass 1: matrices page in, jit compiles
                    ex.execute("m", q)
                for q in read_qs:  # pass 2: the Gram (and serve lane) arm
                    ex.execute("m", q)
                wcount = 0
                calls = 0
                lat_post_write: list = []
                lat_other: list = []
                last_was_write = False
                t0 = time.perf_counter()
                for i in range(n_req):
                    if burst > 1:
                        is_write = i % (2 * burst) < burst  # W^b R^b cycles
                    else:
                        is_write = write_every and i % write_every == write_every - 1
                    if is_write:
                        r = wcount % n_rows
                        c = (SLICE_WIDTH - reserve) + (wcount // n_rows) % reserve
                        ex.execute("m", f'SetBit(rowID={r}, frame="f", columnID={c})')
                        wcount += 1
                        calls += 1
                        last_was_write = True
                    else:
                        t1 = time.perf_counter()
                        ex.execute("m", read_qs[i % len(read_qs)])
                        dt1 = time.perf_counter() - t1
                        (lat_post_write if last_was_write else lat_other).append(dt1)
                        calls += batch
                        last_was_write = False
                dt = time.perf_counter() - t0
                # Correctness gate: warm-lane counts must match the numpy
                # sequential path AFTER the interleaved writes (the
                # read-your-writes contract the repair must not break).
                want = Executor(h, engine="numpy").execute("m", read_qs[0])
                got = ex.execute("m", read_qs[0])
                assert got == want, "mixed-lane counts diverged from numpy"
                repairs = sum(
                    p.stat_repairs for p in ex._matrix_cache.values()
                )
                patch_planes = sum(
                    p.stat_patch_planes for p in ex._matrix_cache.values()
                )
                h.close()
            return {
                "qps": calls / dt,
                "post_write_ms": (
                    1e3 * float(np.mean(lat_post_write)) if lat_post_write else None
                ),
                "steady_ms": 1e3 * float(np.mean(lat_other)) if lat_other else None,
                "repairs": repairs,
                "patch_planes": patch_planes,
            }
        finally:
            if prior is None:
                os.environ.pop("PILOSA_TPU_REPAIR_ROWS_MAX", None)
            else:
                os.environ["PILOSA_TPU_REPAIR_ROWS_MAX"] = prior

    # Coalescing tiers: 50/50 at write-burst sizes 8 and 64 — each
    # burst's writes batch into ONE deferred repair dispatch, so
    # qps/repairs scale with the burst (requests scale so every tier
    # sees several full cycles).
    tiers = []
    plan = [
        ("mixed_95_5", 20, 1, 0),
        ("mixed_50_50", 2, 1, 0),
        ("mixed_50_50_b8", 2, 8, max(n_requests, 8 * 8)),
        ("mixed_50_50_b64", 2, 64, max(n_requests, 8 * 64)),
    ]
    for name, write_every, burst, n_req in plan:
        rep = run_mix(write_every, True, burst=burst, n_req=n_req)
        reb = run_mix(write_every, False, burst=burst, n_req=n_req)
        tiers.append({
            "tier": name,
            "qps": round(rep["qps"], 1),
            "rebuild_qps": round(reb["qps"], 1),
            "speedup": round(rep["qps"] / reb["qps"], 2),
            "repair_post_write_ms": (
                round(rep["post_write_ms"], 3) if rep["post_write_ms"] else None
            ),
            "rebuild_post_write_ms": (
                round(reb["post_write_ms"], 3) if reb["post_write_ms"] else None
            ),
            "steady_ms": round(rep["steady_ms"], 3) if rep["steady_ms"] else None,
            "repairs": rep["repairs"],
            "patch_planes": rep["patch_planes"],
        })
    head = tiers[0]
    return {
        "metric": "mixed_rw_qps",
        "value": head["qps"],
        "unit": (
            f"PQL calls/sec, 95/5 read/write mix ({n_slices} slices x "
            f"{n_rows} rows, batch {batch}, warm-state repair lane vs "
            f"invalidate-and-rebuild x{head['speedup']}; 50/50 mix "
            f"{tiers[1]['qps']:,.0f} calls/s (x{tiers[1]['speedup']} vs "
            f"rebuild), engine {state['engine']})"
        ),
        "vs_baseline": head["speedup"],
        "tiers": tiers,
    }


# Published peak HBM bandwidth of one chip (bytes/sec), keyed by the
# device_kind jax reports.  Source: Google Cloud documentation, "TPU
# v5e" (16 GB of HBM at 819 GB/s per chip).
HBM_PEAK_BYTES_PER_S = {"TPU v5 lite": 819e9}


# Pallas interpret mode, for the CPU smoke tests, which ask for it by name
# (tests/test_bench_smoke.py).  Never inferred from the backend: a bench
# that finds no chip fails in the kernel's lowering, it does not time the
# interpreter.
_INTERPRET = os.environ.get("BENCH_INTERPRET") == "1"


def hbm_roofline() -> float:
    """Peak HBM bytes/sec of the device this process computes on.  A
    device that is not in the table is an error, not a default."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in HBM_PEAK_BYTES_PER_S:
        raise SystemExit(
            f"no published HBM peak for device_kind {kind!r}; add it, with "
            "its source, to HBM_PEAK_BYTES_PER_S"
        )
    return HBM_PEAK_BYTES_PER_S[kind]


def _bandwidth_util(bytes_per_s: float):
    """Share of the chip's HBM roofline, or None off the chip: a CPU run
    has no device bandwidth to report."""
    import jax

    if jax.default_backend() != "tpu":
        return None
    return round(bytes_per_s / hbm_roofline(), 4)


def bench_intersect_stream() -> dict:
    """Headline shape PAST device memory: the slice axis streams through
    HBM in chunks (the executor's slice-streaming regime).  Default 2048
    slices x 64 rows = 16 GiB of packed bitmaps — larger than one v5e
    chip's HBM — with per-query partial counts accumulated across chunk
    steps exactly as the executor's streaming branch does.

    What is measured here is the DEVICE half of that regime: each of the
    n_chunks logical chunks is served by one resident 2 GiB physical
    chunk (the HBM read traffic per pass — the thing the chip actually
    does per chunk — is identical whether the bytes changed since the
    last pass; only the host->device refill differs).  The refill side
    is not part of the timed region: the host upload rate is measured
    separately on a small block and reported in the unit string.
    """
    n_slices = int(os.environ.get("BENCH_SLICES", "2048"))
    n_rows = int(os.environ.get("BENCH_ROWS", "64"))
    batch = int(os.environ.get("BENCH_BATCH", "256"))
    iters = int(os.environ.get("BENCH_ITERS", "8"))
    chunk_slices = int(os.environ.get("BENCH_CHUNK_SLICES", "256"))

    import jax
    import jax.numpy as jnp
    from jax import lax

    from pilosa_tpu.ops import dispatch
    from pilosa_tpu.ops.bitwise import WORDS_PER_SLICE
    from pilosa_tpu.ops.pallas_kernels import fused_resident_count2

    W = WORDS_PER_SLICE
    rng = np.random.default_rng(42)
    n_chunks = (n_slices + chunk_slices - 1) // chunk_slices
    # One pair batch per (outer step, chunk step): in the real streaming
    # regime every chunk serves the SAME batch, but an invariant kernel
    # call inside the chunk scan is loop-hoisted by XLA (first cut of
    # this bench "measured" 981 GB/s — above the roofline — because only
    # one chunk was ever read); distinct pairs per chunk step keep the
    # identical per-chunk HBM traffic while making each step a distinct
    # computation.
    all_pairs = rng.integers(
        0, n_rows, size=(iters, n_chunks, batch, 2), dtype=np.int32
    )

    @jax.jit
    def gen_chunk(key):
        return jax.random.bits(
            key, (chunk_slices, n_rows, W // 128, 128), jnp.uint32
        )

    dchunk = gen_chunk(jax.random.PRNGKey(42))
    dpairs = jax.device_put(all_pairs)


    @jax.jit
    def run_stream(chunk, pairs_stream):
        # Outer scan: one step per query batch; inner scan: one step per
        # logical chunk.  Per-chunk partials come back as scan OUTPUTS
        # and the cross-chunk int64 accumulation happens host-side: on
        # device (no x64) jnp.int64 silently truncates to int32, which
        # overflows past ~16 chunks of full-density counts (the executor's
        # streaming regime accumulates per-chunk engine results host-side
        # the same way).
        def per_batch(carry, prs_chunks):
            def per_chunk(c2, prs):
                return c2, fused_resident_count2(
                    "and", chunk, prs, interpret=_INTERPRET
                )

            return carry, lax.scan(per_chunk, 0, prs_chunks)[1]  # [n_chunks, B]

        out = lax.scan(per_batch, 0, pairs_stream)[1]  # [iters, n_chunks, batch]
        return out, out.sum()  # digest: sync only (int32 wrap is fine)

    out_dev, _ = run_stream(dchunk, dpairs)  # warm + compile

    def timed():
        out_d, digest = run_stream(dchunk, dpairs)
        np.asarray(digest)
        return out_d

    dt, out_dev = _best_of_runs(timed, default_runs=3)
    out = np.asarray(out_dev).astype(np.int64).sum(axis=1)  # [iters, batch]
    qps = iters * batch / dt
    bytes_read = iters * n_chunks * chunk_slices * n_rows * W * 4
    hbm_gbps = bytes_read / dt / 1e9

    # Host->device upload rate on a 64 MiB block (the refill bound).
    blk = np.zeros((64 << 20) // 4, dtype=np.uint32)
    jax.device_put(blk).block_until_ready()
    t0 = time.perf_counter()
    jax.device_put(blk).block_until_ready()
    upload_mbps = 64 / (time.perf_counter() - t0)

    # Ground truth: outer step 0's accumulated counts = sum over chunk
    # steps of that step's per-chunk counts; gate the first chunk batch's
    # slice-0 partial against numpy too.
    from pilosa_tpu.roaring import _POPCNT8

    s0 = np.asarray(dchunk[:1]).reshape(n_rows, W)
    p = all_pairs[0, 0]
    part0 = _POPCNT8[(s0[p[:, 0]] & s0[p[:, 1]]).view(np.uint8)].reshape(
        batch, -1
    ).sum(axis=1, dtype=np.int64)
    rest = np.asarray(
        dispatch.gather_count("and", dchunk[1:], jnp.asarray(p), allow_gram=False)
    ).astype(np.int64)
    want = np.zeros(batch, dtype=np.int64)
    for k in range(n_chunks):
        want += np.asarray(
            dispatch.gather_count(
                "and", dchunk, jnp.asarray(all_pairs[0, k]), allow_gram=False
            )
        ).astype(np.int64)
        if k == 0:
            assert np.array_equal(want - rest, part0), "slice-0 partial mismatch"
    assert np.array_equal(out[0], want), "stream accumulation mismatch"

    cols = n_slices * (1 << 20)
    return {
        "metric": "intersect_count_stream_qps",
        "value": round(qps, 1),
        "unit": (
            f"queries/sec over {cols/1e9:.2f}B columns ({n_slices} slices, "
            f"{n_rows} rows, {n_chunks}x{chunk_slices}-slice chunks, "
            f"{n_chunks * chunk_slices * n_rows * W * 4 / 2**30:.0f} GiB/pass read at "
            f"{hbm_gbps:.0f} GB/s HBM; device half of the streaming regime — "
            f"host refill excluded, host upload measures {upload_mbps:.1f} MiB/s, "
            f"backend {jax.default_backend()})"
        ),
        "vs_baseline": _bandwidth_util(hbm_gbps * 1e9),
        "bandwidth_util": _bandwidth_util(hbm_gbps * 1e9),
    }


def bench_intersect_4krows() -> dict:
    """Gram-INELIGIBLE headline: 4096 distinct rows (>> 16x batch, so the
    all-pairs MXU shortcut can't precompute the answers) forces the
    gather path — the shape a real workload with thousands of distinct
    rows hits.  Uses the row-major pipelined kernel (one contiguous DMA
    descriptor per operand covering every slice): on v5e the DMA engine
    processes descriptors serially at ~1 us each, so achievable bandwidth
    is descriptor-size-bound.  Round-5 ceiling measurement at 4 slices:
    2 descriptors/query (the gather minimum — operand rows are random,
    so no descriptor can carry more than one row) x the measured
    ~1.3 us issue rate = 2.6 us/query = util ~0.49-0.51, which this
    kernel hits exactly; deeper pipelines (depth 4/8) and multi-query
    grid steps both measured SLOWER (VMEM pressure; issue stays serial).
    Past this rung the lane needs bigger rows, not more overlap: 16
    slices (2 MB descriptors) measures 0.64-0.76.  Reports HBM bandwidth
    utilization vs the v5e roofline (true traffic: two operand rows per
    query)."""
    n_slices = int(os.environ.get("BENCH_SLICES", "4"))
    n_rows = int(os.environ.get("BENCH_ROWS", "4096"))
    batch = int(os.environ.get("BENCH_BATCH", "256"))
    iters = int(os.environ.get("BENCH_ITERS", "256"))

    import jax
    import jax.numpy as jnp
    from jax import lax

    from pilosa_tpu.ops.pallas_kernels import fused_gather_count2_rowmajor
    from pilosa_tpu.ops.bitwise import WORDS_PER_SLICE

    W = WORDS_PER_SLICE
    rng = np.random.default_rng(42)
    all_pairs = rng.integers(0, n_rows, size=(iters, batch, 2), dtype=np.int32)

    # Device-generated (see the headline config) in row-major tiled form.
    @jax.jit
    def gen_matrix(key):
        return jax.random.bits(key, (n_rows, n_slices, W // 128, 128), jnp.uint32)

    drm = gen_matrix(jax.random.PRNGKey(42))
    dpairs = jax.device_put(all_pairs)


    @jax.jit
    def run_stream(rm, pairs_stream):
        def step(carry, prs):
            return carry, fused_gather_count2_rowmajor("and", rm, prs, interpret=_INTERPRET)

        out = lax.scan(step, 0, pairs_stream)[1]
        return out, out.astype(jnp.int64).sum()

    out_dev, _ = run_stream(drm, dpairs)  # warm + compile

    def timed():
        out_d, digest = run_stream(drm, dpairs)
        np.asarray(digest)
        return out_d

    dt, out_dev = _best_of_runs(timed)
    out = np.asarray(out_dev)
    qps = iters * batch / dt
    # Gather traffic: 2 rows x n_slices per query, W*4 bytes each.
    bytes_moved = iters * batch * 2 * n_slices * W * 4
    bw_util = _bandwidth_util(bytes_moved / dt)

    # Correctness gate: numpy ground truth for the first few queries from
    # a fetched row subset.
    from pilosa_tpu.roaring import _POPCNT8

    n_gate = min(8, batch)
    gate_rows = sorted({int(r) for r in all_pairs[0, :n_gate].ravel()})
    pos = {r: i for i, r in enumerate(gate_rows)}
    host_rows = np.asarray(drm[np.array(gate_rows)]).reshape(len(gate_rows), n_slices, W)
    for k in range(n_gate):
        a = host_rows[pos[int(all_pairs[0, k, 0])]]
        b = host_rows[pos[int(all_pairs[0, k, 1])]]
        want = int(_POPCNT8[(a & b).view(np.uint8)].sum())
        assert out[0, k] == want, f"gate query {k}: {out[0, k]} != {want}"
    return {
        "metric": "intersect_count_4krows_qps",
        "value": round(qps, 1),
        "unit": (
            f"queries/sec, Gram-ineligible ({n_rows} rows x {n_slices} slices, "
            f"batch {batch}, row-major pipelined gather kernel, "
            f"backend {jax.default_backend()})"
        ),
        "vs_baseline": bw_util,
        "bandwidth_util": bw_util,
    }


def bench_topn_p50() -> dict:
    """TopN latency at a billion columns (BASELINE.json's 'TopN p50 @ 1B
    cols' metric): score EVERY row against a src bitmap over all slices
    (the candidate phase's device work, fragment.go:493-625 analog).
    Default 960 slices x 64 rows = ~1.01B columns, ~7.9 GiB resident on
    one chip, streamed per query through the fused Pallas scorer
    (fused_topn_counts: ~2 MB auto-pipelined blocks, per-row accumulator
    resident in VMEM).

    Queries are chained in one jitted scan and the reported latency is
    scan_time / n_q — a mean, not a percentile (a method taken on an
    earlier rig; per-query timing on this chip is ROADMAP S1/S6).  Each
    step XORs src with a distinct mask so no two queries are the same
    computation."""
    n_slices = int(os.environ.get("BENCH_SLICES", "960"))
    n_rows = int(os.environ.get("BENCH_ROWS", "64"))
    n_q = int(os.environ.get("BENCH_ITERS", "64"))

    import jax
    import jax.numpy as jnp
    from jax import lax

    from pilosa_tpu.ops.bitwise import WORDS_PER_SLICE
    from pilosa_tpu.ops.pallas_kernels import fused_topn_counts

    W = WORDS_PER_SLICE
    rng = np.random.default_rng(42)
    masks = rng.integers(0, 1 << 32, size=(n_q,), dtype=np.uint32)

    # Device-generated: data is set-up, not part of what is measured.
    @jax.jit
    def gen(key):
        rows = jax.random.bits(
            key, (n_slices, n_rows, W // 128, 128), jnp.uint32
        )
        src = jax.random.bits(
            jax.random.fold_in(key, 1), (n_slices, W // 128, 128), jnp.uint32
        )
        return rows, src

    drows, dsrc = gen(jax.random.PRNGKey(42))


    @jax.jit
    def run_stream(rws, s, ms):
        def step(carry, m):
            return carry, fused_topn_counts(rws, s ^ m, interpret=_INTERPRET)

        out = lax.scan(step, 0, ms)[1]  # [n_q, n_rows]
        return out, out.astype(jnp.int64).sum()

    dmasks = jax.device_put(masks)
    out_dev, _ = run_stream(drows, dsrc, dmasks)  # warm + compile
    def timed():
        out_d, digest = run_stream(drows, dsrc, dmasks)
        np.asarray(digest)
        return out_d

    dt, out_dev = _best_of_runs(timed, default_runs=3)
    per_q = dt / n_q
    counts = np.asarray(out_dev)  # [n_q, n_rows] — small fetch

    # Host-side heap merge (the non-device half of TopN) — measured but
    # tiny next to the scan.
    t0 = time.perf_counter()
    top = sorted(zip(counts[0].tolist(), range(n_rows)), reverse=True)[:10]
    heap_dt = time.perf_counter() - t0
    assert top[0][0] > 0

    # Correctness gate: slice 0's counts for query 0 vs numpy.
    from pilosa_tpu.roaring import _POPCNT8

    r0 = np.asarray(drows[:1]).reshape(n_rows, W)
    s0 = np.asarray(dsrc[:1]).reshape(W) ^ masks[0]
    want = _POPCNT8[(r0 & s0).view(np.uint8)].reshape(n_rows, -1).sum(axis=1)
    got = np.asarray(
        fused_topn_counts(drows[:1], (dsrc[:1] ^ masks[0]), interpret=_INTERPRET)
    )
    assert np.array_equal(got, want), "topn counts mismatch (slice 0)"

    bw_util = _bandwidth_util((n_slices * n_rows * W * 4 + n_slices * W * 4) / per_q)
    return {
        "metric": "topn_p50_ms",
        "value": round((per_q + heap_dt) * 1e3, 2),
        "unit": (
            f"ms per TopN over {n_slices * (1 << 20) / 1e6:.0f}M columns "
            f"({n_rows} rows resident, scan-chained mean over {n_q} queries, "
            f"Pallas scorer, backend {jax.default_backend()})"
        ),
        "vs_baseline": bw_util,
        "bandwidth_util": bw_util,
    }


def _run_lockstep_job(queries, n_clients: int, n_ranks: int, env_extra=None,
                      warm: int = 6):
    """Spawn an n-rank lockstep job (tests/lockstep_worker.py), POST
    ``queries`` from ``n_clients`` concurrent clients, tear the job
    down, and return (wall_seconds, responses).  Shared by the lockstep
    throughput bench and the request-coalescing bench (which runs the
    SAME job twice with different coalescing env)."""
    import subprocess
    import sys
    import tempfile
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    repo = os.path.dirname(os.path.abspath(__file__))

    def free_port():
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    coord, control, http = free_port(), free_port(), free_port()
    env = dict(os.environ)
    # CPU subprocess rig by construction (virtual devices + gloo): the
    # ranks must never reach for a chip this process may hold.
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo
    env["XLA_FLAGS"] = ""
    env.update(env_extra or {})
    worker = os.path.join(repo, "tests", "lockstep_worker.py")
    errs = [tempfile.NamedTemporaryFile("w+", delete=False) for _ in range(n_ranks)]
    procs = [
        subprocess.Popen(
            [sys.executable, worker, f"127.0.0.1:{coord}", str(n_ranks), str(pid),
             str(control), str(http)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=errs[pid],
            cwd=repo, env=env, text=True)
        for pid in range(n_ranks)
    ]
    try:
        line = procs[0].stdout.readline()
        assert json.loads(line).get("ready"), line

        def post(q):
            req = urllib.request.Request(
                f"http://127.0.0.1:{http}/index/g/query", data=q.encode(), method="POST")
            return json.loads(urllib.request.urlopen(req, timeout=120).read())

        for q in queries[:warm]:
            post(q)  # warm: matrices, jit, memo
        t0 = time.perf_counter()
        with ThreadPoolExecutor(n_clients) as pool:
            outs = list(pool.map(post, queries))
        dt = time.perf_counter() - t0
    finally:
        try:
            procs[0].stdin.write("\n")
            procs[0].stdin.flush()
        except Exception:
            pass
        stats = {}
        try:  # rank 0's final JSON line carries coalescing telemetry
            for line in procs[0].stdout:
                line = line.strip()
                if line:
                    stats = json.loads(line)
        except Exception:
            pass
        for p in procs:
            try:
                p.wait(timeout=60)
            except Exception:
                p.kill()
        for f in errs:
            f.close()
            os.unlink(f.name)
    return dt, outs, stats


def bench_lockstep() -> dict:
    """Lockstep-service throughput: a 2-rank SPMD job (CPU gloo mesh —
    the shape this box can spawn; on a pod the same path rides ICI)
    serving batched PQL over HTTP with concurrent clients, vs the SAME
    requests through a single in-process executor.  Exercises the
    pipelined total order: N requests in flight on the control plane,
    execution in sequence order on both ranks."""

    batch = int(os.environ.get("BENCH_BATCH", "64"))
    iters = int(os.environ.get("BENCH_ITERS", "60"))
    n_clients = int(os.environ.get("BENCH_THREADS", "6"))
    n_ranks = int(os.environ.get("BENCH_RANKS", "2"))

    rng = np.random.default_rng(17)

    def mk_query():
        pairs = rng.integers(0, 4, size=(batch, 2))
        return " ".join(
            f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
            for a, b in pairs
        )

    queries = [mk_query() for _ in range(iters)]
    dt, outs, _stats = _run_lockstep_job(queries, n_clients, n_ranks)
    qps = iters * batch / dt
    assert all("results" in o and len(o["results"]) == batch for o in outs)

    # Single-rank baseline: same queries through one in-process executor.
    import tempfile as _tf

    from pilosa_tpu.core.frame import FrameOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.pilosa import SLICE_WIDTH

    with _tf.TemporaryDirectory() as d:
        h = Holder(d)
        h.open()
        idx = h.create_index("g")
        idx.create_frame("f", FrameOptions(time_quantum="YM"))
        fr = idx.frame("f")
        for r in range(4):
            for s in range(max(4, 2 * n_ranks)):  # mirror the workers' seed
                fr.set_bit("standard", r, s * SLICE_WIDTH + 10 + r)
                fr.set_bit("standard", r, s * SLICE_WIDTH + 500)
        ex = Executor(h)
        for q in queries[:6]:
            ex.execute("g", q)
        t0 = time.perf_counter()
        for q in queries:
            ex.execute("g", q)
        base_dt = time.perf_counter() - t0
        h.close()
    base_qps = iters * batch / base_dt
    return {
        "metric": "lockstep_service_qps",
        "value": round(qps, 1),
        "unit": (
            f"PQL queries/sec via {n_ranks}-rank lockstep HTTP ({n_clients} clients, "
            f"batch {batch}, pipelined; single-rank in-process executor "
            f"{base_qps:,.0f} q/s on this host)"
        ),
        "vs_baseline": round(qps / base_qps, 3),
    }


def bench_lockstep_coalesce() -> dict:
    """Lockstep request-coalescing tier: SMALL single-call requests from
    many concurrent clients — the shape where the per-request fixed cost
    (HTTP + one control-plane entry + one ack round per request,
    BACKLOG's ~1.9 ms/request) dominates — with coalescing ON (rank 0
    drains its queue into one batch replay entry; the default) vs
    forced OFF (``PILOSA_TPU_LOCKSTEP_COALESCE=1``: one entry per
    request, the PR-1 behavior).  Per-request overhead must DROP with
    batch size; both phases run the same request stream on a fresh
    2-rank job.  BENCH_SMOKE=1 shrinks the stream for CI."""
    smoke = os.environ.get("BENCH_SMOKE", "").lower() in ("1", "true", "yes")
    iters = int(os.environ.get("BENCH_ITERS", "24" if smoke else "400"))
    n_clients = int(os.environ.get("BENCH_THREADS", "4" if smoke else "16"))
    n_ranks = int(os.environ.get("BENCH_RANKS", "2"))

    rng = np.random.default_rng(29)
    queries = [
        f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
        for a, b in rng.integers(0, 4, size=(iters, 2)).tolist()
    ]
    tiers = []
    for name, env_extra in (
        ("coalesce_on", {}),
        ("coalesce_off", {"PILOSA_TPU_LOCKSTEP_COALESCE": "1"}),
    ):
        dt, outs, stats = _run_lockstep_job(queries, n_clients, n_ranks, env_extra)
        assert all("results" in o and len(o["results"]) == 1 for o in outs)
        n_b = stats.get("batches") or 0
        tiers.append({
            "tier": name,
            "rps": round(iters / dt, 1),
            "per_request_ms": round(1e3 * dt / iters, 3),
            "batches": n_b,
            "mean_batch": (
                round(stats.get("requests", 0) / n_b, 2) if n_b else None
            ),
        })
    on, off = tiers[0], tiers[1]
    return {
        "metric": "lockstep_coalesce_rps",
        "value": on["rps"],
        "unit": (
            f"single-call PQL requests/sec via {n_ranks}-rank lockstep HTTP "
            f"({n_clients} clients; coalesced {on['per_request_ms']} ms/req vs "
            f"uncoalesced {off['per_request_ms']} ms/req)"
        ),
        "vs_baseline": round(on["rps"] / off["rps"], 3),
        "tiers": tiers,
    }


def bench_overload() -> dict:
    """Request-lifecycle QoS tier: a REAL HTTP server (numpy engine)
    driven past saturation by closed-loop clients, with the QoS door ON
    (bounded per-class admission + per-request deadlines; overflow
    sheds 429 + Retry-After at the door) vs OFF (unbounded admission,
    no deadline — the pre-QoS behavior).

    Three phases: ``presat`` measures the pre-saturation peak (clients
    == read depth), then the overload phases run 2x the door capacity
    (depth admitted + depth waiting).  Non-collapse contract: with QoS
    on the shed rate is > 0, the SERVED requests' p99 stays near the
    pre-saturation p99, and goodput stays within ~20% of peak; with QoS
    off every request is admitted and the served p99 degrades with the
    queue depth instead.  BENCH_SMOKE=1 shrinks the shapes for CI."""
    import tempfile
    import urllib.error
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.config import Config
    from pilosa_tpu.server.server import Server

    smoke = os.environ.get("BENCH_SMOKE", "").lower() in ("1", "true", "yes")
    depth = int(os.environ.get("BENCH_QOS_DEPTH", "2" if smoke else "4"))
    # 2x the DOOR capacity (depth active + depth waiting) = 4x depth.
    overload_clients = int(os.environ.get("BENCH_THREADS", str(4 * depth)))
    phase_s = float(os.environ.get("BENCH_OVERLOAD_SECS", "1.5" if smoke else "8"))
    deadline_ms = float(os.environ.get("BENCH_DEADLINE_MS", "500" if smoke else "2000"))
    n_slices = int(os.environ.get("BENCH_SLICES", "2" if smoke else "4"))
    n_rows = int(os.environ.get("BENCH_ROWS", "8" if smoke else "16"))
    batch = int(os.environ.get("BENCH_BATCH", "8" if smoke else "32"))

    from pilosa_tpu.pilosa import SLICE_WIDTH

    rng = np.random.default_rng(31)
    queries = []
    for seed in range(8):
        prs = np.random.default_rng(seed).integers(0, n_rows, size=(batch, 2))
        queries.append(" ".join(
            f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
            for a, b in prs.tolist()
        ))

    def mk_server(d, qos_on: bool) -> Server:
        # qcache OFF on both sides: this tier measures the ADMISSION
        # door under real execution load — with the query result cache
        # on, the repeated-query mix is served from memory and the door
        # never saturates (that regime is BENCH_CONFIG=qcache's job).
        cfg = Config(data_dir=d, host="127.0.0.1:0", engine="numpy", stats="expvar",
                     qcache_enabled=False)
        if qos_on:
            cfg.qos_read_depth = depth
            cfg.qos_write_depth = depth
            cfg.qos_queue_wait_ms = 25.0
            cfg.qos_retry_after_ms = 50.0
            cfg.default_deadline_ms = deadline_ms
        else:
            cfg.qos_read_depth = cfg.qos_write_depth = cfg.qos_admin_depth = 0
            cfg.default_deadline_ms = 0.0
        srv = Server(cfg)
        srv.open()
        idx = srv.holder.create_index("o")
        from pilosa_tpu.core.frame import FrameOptions

        idx.create_frame("f", FrameOptions())
        fr = idx.frame("f")
        rows = np.repeat(np.arange(n_rows, dtype=np.uint64), 2000)
        for s in range(n_slices):
            cols = rng.integers(0, SLICE_WIDTH, size=len(rows)).astype(
                np.uint64
            ) + np.uint64(s * SLICE_WIDTH)
            fr.import_bits(rows, cols)
        return srv

    def run_phase(host: str, n_clients: int, dur_s: float) -> dict:
        """Closed-loop load: each client posts back-to-back until the
        phase ends; sheds honor the server's Retry-After."""
        t_end = time.perf_counter() + dur_s

        def client(i: int) -> dict:
            lat: list = []
            out = {"served": 0, "shed": 0, "expired": 0, "timeouts": 0, "errors": 0}
            k = i
            while time.perf_counter() < t_end:
                q = queries[k % len(queries)]
                k += 1
                req = urllib.request.Request(
                    f"http://{host}/index/o/query", data=q.encode(), method="POST")
                t1 = time.perf_counter()
                try:
                    with urllib.request.urlopen(req, timeout=30) as resp:
                        resp.read()
                    lat.append(time.perf_counter() - t1)
                    out["served"] += 1
                except urllib.error.HTTPError as e:
                    e.read()
                    if e.code == 429 or e.code == 503:
                        out["shed"] += 1
                        try:
                            wait = float(e.headers.get("Retry-After", "0.05"))
                        except (TypeError, ValueError):
                            wait = 0.05
                        time.sleep(min(wait, 0.25))
                    elif e.code == 504:
                        out["expired"] += 1
                    else:
                        out["errors"] += 1
                except OSError:
                    out["timeouts"] += 1
            out["lat"] = lat
            return out

        t0 = time.perf_counter()
        with ThreadPoolExecutor(n_clients) as pool:
            outs = list(pool.map(client, range(n_clients)))
        dt = time.perf_counter() - t0
        lat = sorted(x for o in outs for x in o["lat"])
        total = {k: sum(o[k] for o in outs)
                 for k in ("served", "shed", "expired", "timeouts", "errors")}
        offered = sum(total.values())
        return {
            "goodput_qps": round(total["served"] / dt, 1),
            "p50_ms": round(1e3 * lat[len(lat) // 2], 2) if lat else None,
            "p99_ms": (
                round(1e3 * lat[min(len(lat) - 1, int(len(lat) * 0.99))], 2)
                if lat else None
            ),
            "shed_rate": round(total["shed"] / offered, 3) if offered else 0.0,
            **total,
        }

    tiers = []
    with tempfile.TemporaryDirectory() as d:
        srv = mk_server(d, qos_on=True)
        try:
            for q in queries:  # warm: matrices + serve lane
                run = urllib.request.Request(
                    f"http://{srv.host}/index/o/query", data=q.encode(), method="POST")
                urllib.request.urlopen(run, timeout=60).read()
            presat = run_phase(srv.host, depth, phase_s)
            tiers.append({"tier": "presat", "clients": depth, **presat})
            on = run_phase(srv.host, overload_clients, phase_s)
            tiers.append({"tier": "overload_qos_on", "clients": overload_clients, **on})
        finally:
            srv.close()
    with tempfile.TemporaryDirectory() as d:
        srv = mk_server(d, qos_on=False)
        try:
            for q in queries:
                run = urllib.request.Request(
                    f"http://{srv.host}/index/o/query", data=q.encode(), method="POST")
                urllib.request.urlopen(run, timeout=60).read()
            off = run_phase(srv.host, overload_clients, phase_s)
            tiers.append({"tier": "overload_qos_off", "clients": overload_clients, **off})
        finally:
            srv.close()

    on["goodput_vs_peak"] = round(
        on["goodput_qps"] / presat["goodput_qps"], 3
    ) if presat["goodput_qps"] else None
    tiers[1]["goodput_vs_peak"] = on["goodput_vs_peak"]
    p99_ratio = (
        round(off["p99_ms"] / on["p99_ms"], 2)
        if on.get("p99_ms") and off.get("p99_ms") else None
    )
    return {
        "metric": "overload_goodput_qps",
        "value": on["goodput_qps"],
        "unit": (
            f"served requests/sec at 2x door capacity ({overload_clients} clients, "
            f"read depth {depth}; shed rate {on['shed_rate']}, served p99 "
            f"{on['p99_ms']} ms vs presat {presat['p99_ms']} ms; QoS-off p99 "
            f"{off['p99_ms']} ms = {p99_ratio}x worse)"
        ),
        "vs_baseline": p99_ratio,
        "tiers": tiers,
    }


def bench_tenancy() -> dict:
    """Multi-tenant hostile-neighbor tier: a REAL HTTP server with the
    [tenancy] fair-share door ON, a weighted POLITE tenant (the paying
    interactive workload, weight 3) sharing the read door with a
    HOSTILE tenant flooding at >= 2x the door's capacity (2x depth
    closed-loop clients).  Tenants are named by X-Pilosa-Tenant
    headers — the same resolution seam the handler, lockstep front end,
    and replica router share.

    Three phases: ``polite_baseline`` measures the polite tenant's
    ISOLATED p99 (same client count, empty door); ``hostile_flood_on``
    adds the flood with isolation ON and asserts IN-RUN that (a) the
    polite tenant's p99 stays within 1.5x its isolated baseline, (b)
    the polite tenant sheds NOTHING (its share of the wait lane is
    reserved — the flooder can never fill the door against it), and
    (c) the hostile tenant really sheds (the flood was real);
    ``hostile_flood_off`` repeats the flood with tenancy disabled and
    records the polite tenant's degraded p99/sheds for the A/B.
    BENCH_SMOKE=1 shrinks the shapes for CI."""
    import tempfile
    import urllib.error
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.config import Config
    from pilosa_tpu.server.server import Server

    smoke = os.environ.get("BENCH_SMOKE", "").lower() in ("1", "true", "yes")
    # Depth stays 8 even under BENCH_SMOKE: the weighted share split
    # needs a door deep enough that the hostile tenant's GUARANTEED
    # floor (cap never rounds below 1 — presence always buys progress)
    # is a small fraction of the polite tenant's share.  The requests
    # are execution-bound, so even perfect door isolation concedes the
    # floor's slot of CPU to the flooder: with polite at 7/8 of the
    # door the concession is ~1/7th, well inside the 1.5x gate; at
    # depth 2 both tenants round to cap 1 and the gate measures a
    # 50/50 CPU split, not isolation.
    depth = int(os.environ.get("BENCH_QOS_DEPTH", "8"))
    # The polite tenant runs at its fair share of the door (weight 7 of
    # 8 total); the hostile flood offers >= 2x the DOOR capacity (2x
    # depth of closed-loop clients hammering a depth-deep door).
    polite_clients = max(1, (7 * depth) // 8)
    hostile_clients = int(os.environ.get("BENCH_THREADS", str(2 * depth)))
    phase_s = float(os.environ.get("BENCH_TENANCY_SECS", "2.5" if smoke else "8"))
    n_slices = int(os.environ.get("BENCH_SLICES", "2" if smoke else "4"))
    n_rows = int(os.environ.get("BENCH_ROWS", "8" if smoke else "16"))
    batch = int(os.environ.get("BENCH_BATCH", "8" if smoke else "32"))

    from pilosa_tpu.pilosa import SLICE_WIDTH

    rng = np.random.default_rng(47)
    queries = []
    for seed in range(8):
        prs = np.random.default_rng(seed).integers(0, n_rows, size=(batch, 2))
        queries.append(" ".join(
            f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
            for a, b in prs.tolist()
        ))

    def mk_server(d, tenancy_on: bool) -> Server:
        # qcache OFF (the door must saturate on real execution, same as
        # the overload tier); QoS door ON in BOTH legs — the A/B
        # isolates what fair-share adds over plain bounded admission.
        cfg = Config(data_dir=d, host="127.0.0.1:0", engine="numpy",
                     stats="expvar", qcache_enabled=False)
        cfg.qos_read_depth = depth
        cfg.qos_write_depth = depth
        # Generous wait lane: the polite tenant's isolation shows up as
        # BOUNDED waiting, never as sheds — its reserved share of the
        # lane admits within a service time.
        cfg.qos_queue_wait_ms = 2000.0
        # Standard Retry-After: shed hostile clients genuinely back off.
        # A tiny hint here would turn the flood into a doorknock storm
        # whose admission-path CPU (connect/parse/classify/shed) is
        # itself the interference — the door can only isolate work it
        # gets to arbitrate.
        cfg.qos_retry_after_ms = 250.0
        if tenancy_on:
            cfg.tenancy_enabled = True
            cfg.tenancy_weights = "polite=7,hostile=1"
        srv = Server(cfg)
        srv.open()
        idx = srv.holder.create_index("t")
        from pilosa_tpu.core.frame import FrameOptions

        idx.create_frame("f", FrameOptions())
        fr = idx.frame("f")
        rows = np.repeat(np.arange(n_rows, dtype=np.uint64), 2000)
        for s in range(n_slices):
            cols = rng.integers(0, SLICE_WIDTH, size=len(rows)).astype(
                np.uint64
            ) + np.uint64(s * SLICE_WIDTH)
            fr.import_bits(rows, cols)
        return srv

    def run_phase(host: str, groups: dict, dur_s: float) -> dict:
        """Closed-loop per-tenant load: ``groups`` maps tenant name ->
        client count; every client stamps its tenant's header and
        honors Retry-After on sheds.  Returns per-tenant summaries."""
        t_end = time.perf_counter() + dur_s
        plan = [t for t, n in groups.items() for _ in range(n)]

        def client(i: int) -> dict:
            tenant = plan[i]
            lat: list = []
            out = {"tenant": tenant, "served": 0, "shed": 0, "errors": 0}
            k = i
            while time.perf_counter() < t_end:
                q = queries[k % len(queries)]
                k += 1
                req = urllib.request.Request(
                    f"http://{host}/index/t/query", data=q.encode(),
                    method="POST", headers={"X-Pilosa-Tenant": tenant})
                t1 = time.perf_counter()
                try:
                    with urllib.request.urlopen(req, timeout=30) as resp:
                        resp.read()
                    lat.append(time.perf_counter() - t1)
                    out["served"] += 1
                except urllib.error.HTTPError as e:
                    e.read()
                    if e.code in (429, 503):
                        out["shed"] += 1
                        try:
                            wait = float(e.headers.get("Retry-After", "0.05"))
                        except (TypeError, ValueError):
                            wait = 0.05
                        time.sleep(min(wait, 0.5))
                    else:
                        out["errors"] += 1
                except OSError:
                    out["errors"] += 1
            out["lat"] = lat
            return out

        t0 = time.perf_counter()
        with ThreadPoolExecutor(len(plan)) as pool:
            outs = list(pool.map(client, range(len(plan))))
        dt = time.perf_counter() - t0
        per: dict = {}
        for tenant in groups:
            mine = [o for o in outs if o["tenant"] == tenant]
            lat = sorted(x for o in mine for x in o["lat"])
            per[tenant] = {
                "clients": groups[tenant],
                "served": sum(o["served"] for o in mine),
                "shed": sum(o["shed"] for o in mine),
                "errors": sum(o["errors"] for o in mine),
                "goodput_qps": round(sum(o["served"] for o in mine) / dt, 1),
                "p50_ms": round(1e3 * lat[len(lat) // 2], 2) if lat else None,
                "p99_ms": (
                    round(1e3 * lat[min(len(lat) - 1, int(len(lat) * 0.99))], 2)
                    if lat else None
                ),
            }
        return per

    flood = {"polite": polite_clients, "hostile": hostile_clients}
    tiers = []
    with tempfile.TemporaryDirectory() as d:
        srv = mk_server(d, tenancy_on=True)
        try:
            for q in queries:  # warm: matrices + serve lane
                run = urllib.request.Request(
                    f"http://{srv.host}/index/t/query", data=q.encode(), method="POST")
                urllib.request.urlopen(run, timeout=60).read()
            base = run_phase(srv.host, {"polite": polite_clients}, phase_s)
            tiers.append({"tier": "polite_baseline", **base["polite"]})
            on = run_phase(srv.host, flood, phase_s)
            # Server-side per-tenant view under the flood (the
            # /debug/tenants satellite, scraped while the ledger is hot).
            dbg = json.loads(urllib.request.urlopen(
                f"http://{srv.host}/debug/tenants", timeout=30).read())
            tiers.append({"tier": "hostile_flood_on",
                          "polite": on["polite"], "hostile": on["hostile"],
                          "door": {
                              t: {k: row[k] for k in ("weight", "debt",
                                                      "admitted", "shed")}
                              for t, row in dbg.get("tenants", {}).items()
                          }})
        finally:
            srv.close()
    with tempfile.TemporaryDirectory() as d:
        srv = mk_server(d, tenancy_on=False)
        try:
            for q in queries:
                run = urllib.request.Request(
                    f"http://{srv.host}/index/t/query", data=q.encode(), method="POST")
                urllib.request.urlopen(run, timeout=60).read()
            off = run_phase(srv.host, flood, phase_s)
            tiers.append({"tier": "hostile_flood_off",
                          "polite": off["polite"], "hostile": off["hostile"]})
        finally:
            srv.close()

    # -- the hostile-neighbor gate (asserted IN-RUN: a violated
    # isolation contract exits nonzero, it doesn't just record) --------
    base_p99 = base["polite"]["p99_ms"]
    on_p99 = on["polite"]["p99_ms"]
    assert base_p99 and on_p99, (base, on)
    p99_vs_base = round(on_p99 / base_p99, 2)
    assert on_p99 <= 1.5 * base_p99, (
        f"isolation failed: polite p99 {on_p99} ms > 1.5x isolated "
        f"baseline {base_p99} ms under hostile flood"
    )
    assert on["polite"]["shed"] == 0, (
        f"isolation failed: polite tenant shed {on['polite']['shed']} "
        f"requests (its wait-lane share is reserved)"
    )
    assert on["hostile"]["shed"] > 0, (
        "flood never saturated the door: hostile tenant shed nothing "
        f"({hostile_clients} clients, depth {depth})"
    )
    off_p99 = off["polite"]["p99_ms"]
    off_ratio = (
        round(off_p99 / base_p99, 2) if off_p99 and base_p99 else None
    )
    return {
        "metric": "tenancy_polite_p99_ms",
        "value": on_p99,
        "unit": (
            f"polite tenant p99 under a {hostile_clients}-client hostile "
            f"flood (read depth {depth}, weights polite=7 hostile=1; "
            f"{p99_vs_base}x its isolated baseline {base_p99} ms, "
            f"0 polite sheds, {on['hostile']['shed']} hostile sheds; "
            f"tenancy OFF the same flood pushes polite p99 to "
            f"{off_p99} ms = {off_ratio}x baseline)"
        ),
        "vs_baseline": p99_vs_base,
        "tiers": tiers,
    }


def bench_replica() -> dict:
    """Replicated serving groups tier: N group SUBPROCESSES (each a full
    Server with its own holder and GIL — the dev-rig analog of one
    lockstep job per group) behind the ReplicaRouter, read QPS measured
    at 1 vs 2+ groups plus a router-off direct baseline:

    - ``direct_1g``: clients hit group 0's front door directly (no
      router) — the per-group ceiling and the router-overhead baseline;
    - ``router_1g``: the router over ONE group — isolates router cost;
    - ``router_Ng``: the router over all N groups — read throughput
      must SCALE with group count (``scaling_1_to_2`` is the headline
      ratio; acceptance >= 1.6x on the bench host).

    In-run invariants (fields in the router_Ng tier, asserted here):
    cross-group read-your-writes (a write acked by the router is
    visible on a direct read of EVERY group, and immediate router reads
    agree whichever group serves) and failover (killing one group's
    process leaves reads serving from the survivors while writes refuse
    503 until the set is quorate).  Groups are separate PROCESSES, so
    the scaling headline needs physical cores (>= n_groups + 1); a
    1-cpu box records ~1.0 by construction (the ``cpus`` field says
    which regime a line measured).  BENCH_SMOKE=1 shrinks the shapes
    for CI."""
    import subprocess
    import sys
    import tempfile
    import urllib.error
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.server.client import Client

    smoke = os.environ.get("BENCH_SMOKE", "").lower() in ("1", "true", "yes")
    n_groups = int(os.environ.get("BENCH_GROUPS", "2"))
    n_clients = int(os.environ.get("BENCH_THREADS", "4" if smoke else "16"))
    phase_s = float(os.environ.get("BENCH_REPLICA_SECS", "1.2" if smoke else "8"))
    n_slices = int(os.environ.get("BENCH_SLICES", "2" if smoke else "4"))
    n_rows = int(os.environ.get("BENCH_ROWS", "8" if smoke else "16"))
    batch = int(os.environ.get("BENCH_BATCH", "8" if smoke else "32"))
    bits_per_row = int(os.environ.get("BENCH_BITS_PER_ROW", "500" if smoke else "20000"))

    from pilosa_tpu.pilosa import SLICE_WIDTH

    repo = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(repo, "tests", "replica_group_worker.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # numpy engine; jax must not probe TPUs
    env["PYTHONPATH"] = repo
    env.pop("PILOSA_TPU_QCACHE", None)  # measure execution, not cache hits

    queries = []
    for seed in range(8):
        prs = np.random.default_rng(seed).integers(0, n_rows, size=(batch, 2))
        queries.append(" ".join(
            f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
            for a, b in prs.tolist()
        ))

    def read_phase(host: str, dur_s: float) -> dict:
        """Closed-loop read load: each client posts back-to-back."""
        t_end = time.perf_counter() + dur_s

        def client(i: int) -> tuple[int, int]:
            served = errors = 0
            k = i
            while time.perf_counter() < t_end:
                q = queries[k % len(queries)]
                k += 1
                req = urllib.request.Request(
                    f"http://{host}/index/r/query", data=q.encode(), method="POST")
                try:
                    with urllib.request.urlopen(req, timeout=60) as resp:
                        resp.read()
                    served += 1
                except (urllib.error.URLError, OSError):
                    errors += 1
            return served, errors

        t0 = time.perf_counter()
        with ThreadPoolExecutor(n_clients) as pool:
            outs = list(pool.map(client, range(n_clients)))
        dt = time.perf_counter() - t0
        served = sum(s for s, _ in outs)
        errors = sum(e for _, e in outs)
        assert errors == 0, f"read phase saw {errors} transport errors"
        return {"read_qps": round(served / dt, 1), "served": served,
                "clients": n_clients}

    def free_port():
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    errs = [
        tempfile.NamedTemporaryFile("w+", delete=False) for _ in range(n_groups + 2)
    ]
    procs = [
        subprocess.Popen(
            [sys.executable, worker, f"g{i}"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=errs[i],
            cwd=repo, env=env, text=True)
        for i in range(n_groups)
    ]
    tiers = []
    try:
        hosts = []
        for p in procs[:n_groups]:
            line = json.loads(p.stdout.readline())
            assert line.get("ready"), line
            hosts.append(line["host"])

        # ROUTERS run as their own processes (the production shape —
        # `pilosa-tpu replica-router`): the bench process only runs the
        # closed-loop clients, so the measured scaling is group-side,
        # not the bench's own GIL.
        def spawn_router(group_hosts, errfile):
            port = free_port()
            p = subprocess.Popen(
                [sys.executable, "-m", "pilosa_tpu", "replica-router",
                 "--groups", ",".join(
                     f"g{i}={h}" for i, h in enumerate(group_hosts)),
                 "--port", str(port)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=errfile,
                cwd=repo, env=env, text=True)
            line = p.stdout.readline()
            assert "replica-router" in line, line
            return p, port

        router_all, all_port = spawn_router(hosts, errs[n_groups])
        procs.append(router_all)
        router_one, one_port = spawn_router(hosts[:1], errs[n_groups + 1])
        procs.append(router_one)

        # Seed THROUGH the router: schema + import fan to every group
        # (the write path under test is also the loader).
        rc = Client(f"127.0.0.1:{all_port}")
        rc.create_index("r")
        rc.create_frame("r", "f")
        rng = np.random.default_rng(41)
        bits = []
        for r in range(n_rows):
            for s in range(n_slices):
                cols = rng.integers(0, SLICE_WIDTH - 4096, size=bits_per_row)
                bits.extend((r, int(c) + s * SLICE_WIDTH) for c in cols)
        rc.import_bits("r", "f", bits)

        def direct(host, q):
            req = urllib.request.Request(
                f"http://{host}/index/r/query", data=q.encode(), method="POST")
            with urllib.request.urlopen(req, timeout=60) as resp:
                return json.loads(resp.read())["results"]

        for h in hosts:  # warm every group's serve lane
            for q in queries:
                direct(h, q)

        tiers.append({"tier": "direct_1g", "groups": 1,
                      **read_phase(hosts[0], phase_s)})
        tiers.append({"tier": "router_1g", "groups": 1,
                      **read_phase(f"127.0.0.1:{one_port}", phase_s)})

        # Cross-group read-your-writes, proven through the full-set
        # router BEFORE its throughput phase: the acked write is on
        # every group, and immediate router reads agree.
        probe_q = 'Count(Bitmap(rowID=0, frame="f"))'
        base = direct(hosts[0], probe_q)[0]
        rc.execute_query("r", f'SetBit(rowID=0, frame="f", columnID={SLICE_WIDTH - 1})')
        rw_ok = all(direct(h, probe_q) == [base + 1] for h in hosts)
        for _ in range(2 * n_groups):  # router reads spread over groups
            rw_ok = rw_ok and (
                direct(f"127.0.0.1:{all_port}", probe_q) == [base + 1]
            )
        assert rw_ok, "cross-group read-your-writes violated"

        tiers.append({"tier": f"router_{n_groups}g", "groups": n_groups,
                      **read_phase(f"127.0.0.1:{all_port}", phase_s)})

        # Failover: kill the LAST group's process; reads keep serving
        # from the survivors, writes refuse 503 until quorate.
        procs[n_groups - 1].kill()
        ok_reads = 0
        for _ in range(10):
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{all_port}/index/r/query",
                    data=probe_q.encode(), method="POST")
                with urllib.request.urlopen(req, timeout=30) as resp:
                    resp.read()
                ok_reads += 1
            except (urllib.error.URLError, OSError):
                pass  # at most the probe that trips the health mark
        write_503 = False
        try:
            rc.execute_query("r", 'SetBit(rowID=0, frame="f", columnID=7)')
        except Exception as e:  # noqa: BLE001 — ClientError carries .status
            write_503 = getattr(e, "status", None) == 503
        failover_ok = ok_reads >= 8 and write_503
        assert failover_ok, (ok_reads, write_503)
        # Router observability over HTTP (the router runs out-of-process).
        with urllib.request.urlopen(
            f"http://127.0.0.1:{all_port}/debug/vars", timeout=10
        ) as resp:
            snap = json.loads(resp.read())
        tiers[-1]["rw_ok"] = rw_ok
        tiers[-1]["failover_ok"] = failover_ok
        tiers[-1]["failovers"] = snap.get("replica.failover", 0)
        tiers[-1]["write_fanout"] = snap.get("replica.write_fanout", 0)
    finally:
        for p in procs[n_groups:]:  # router processes: no stdin protocol
            try:
                p.terminate()
            except Exception:  # noqa: BLE001
                pass
        for p in procs[:n_groups]:
            try:
                p.stdin.write("\n")
                p.stdin.flush()
            except Exception:  # noqa: BLE001 — already dead
                pass
        for p in procs:
            try:
                p.wait(timeout=30)
            except Exception:  # noqa: BLE001
                p.kill()
        for f in errs:
            f.close()
            os.unlink(f.name)

    by = {t["tier"]: t for t in tiers}
    qps_1 = by["router_1g"]["read_qps"]
    qps_n = by[f"router_{n_groups}g"]["read_qps"]
    scaling = round(qps_n / qps_1, 3) if qps_1 else None
    router_overhead = (
        round(by["direct_1g"]["read_qps"] / qps_1, 3) if qps_1 else None
    )
    return {
        "metric": "replica_read_qps",
        "value": qps_n,
        "unit": (
            f"read requests/sec via the replica router over {n_groups} groups "
            f"({n_clients} clients, batch {batch}; 1-group router {qps_1} q/s "
            f"= x{scaling} scaling on {os.cpu_count()} cpus, direct/router "
            f"overhead x{router_overhead}; rw + failover asserted in-run)"
        ),
        "vs_baseline": scaling,
        "scaling_1_to_2": scaling,
        "router_overhead": router_overhead,
        # Group processes scale with PHYSICAL cores: scaling toward
        # n_groups needs cpus >= n_groups + 1 (router + clients ride the
        # remainder); a 1-cpu CI box records ~1.0 by construction.
        "cpus": os.cpu_count(),
        "tiers": tiers,
    }


def bench_recovery() -> dict:
    """Durable-write-log recovery tier: write availability through the
    replica router when a group dies, and convergence time when it
    comes back.  3 group SUBPROCESSES (pinned data dirs, so a restart
    resumes from disk) behind an out-of-process CLI router running a
    DURABLE WAL:

    - ``writes_3g``: sequential write throughput with the full group
      set (the fixed-cost baseline: WAL append + 3-way fan-out);
    - ``writes_2g``: the LAST group is SIGKILLed mid-stream and the
      writes keep flowing on the degraded quorum — the tier asserts
      ZERO failed writes in this phase (the old full-set rule 503'd
      every one of them);
    - ``catchup``: the killed group restarts (same data dir, bumped
      epoch), the router replays the missed WAL suffix, and the tier
      measures time-to-rejoin plus asserts CONVERGENCE (identical
      query results on every group) and that reads route to the
      rejoined group again.

    ``BENCH_RECOVERY_WRITES`` sizes each write phase; ``BENCH_SMOKE=1``
    shrinks for CI."""
    import shutil
    import subprocess
    import sys
    import tempfile
    import urllib.error
    import urllib.request

    from pilosa_tpu.server.client import Client

    smoke = os.environ.get("BENCH_SMOKE", "").lower() in ("1", "true", "yes")
    n_writes = int(os.environ.get("BENCH_RECOVERY_WRITES", "60" if smoke else "600"))
    batch = int(os.environ.get("BENCH_BATCH", "8"))

    repo = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(repo, "tests", "replica_group_worker.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo
    env.pop("PILOSA_TPU_QCACHE", None)

    def free_port():
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    root = tempfile.mkdtemp(prefix="pilosa_recovery_")
    errs = [open(os.path.join(root, f"err{i}.log"), "w+") for i in range(4)]
    # FIXED front-door ports: a restarted group must come back at the
    # same address the router holds.
    group_ports = [free_port() for _ in range(3)]

    def spawn_group(i: int, epoch: int):
        genv = dict(env)
        genv["PILOSA_WORKER_DATA_DIR"] = os.path.join(root, f"g{i}")
        genv["PILOSA_WORKER_HOST"] = f"127.0.0.1:{group_ports[i]}"
        p = subprocess.Popen(
            [sys.executable, worker, f"g{i}@{epoch}"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=errs[i],
            cwd=repo, env=genv, text=True)
        line = json.loads(p.stdout.readline())
        assert line.get("ready"), line
        return p, line["host"]

    procs = []
    tiers = []
    try:
        groups = [spawn_group(i, 1) for i in range(3)]
        procs = [p for p, _ in groups]
        hosts = [h for _, h in groups]

        router_port = free_port()
        router = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu", "replica-router",
             "--groups", ",".join(f"g{i}={h}" for i, h in enumerate(hosts)),
             "--port", str(router_port),
             "--wal-dir", os.path.join(root, "wal"),
             "--probe-interval", "0.1"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=errs[3], cwd=repo, env=env, text=True)
        procs.append(router)
        line = router.stdout.readline()
        assert "replica-router" in line, line

        rc = Client(f"127.0.0.1:{router_port}", timeout=60)
        rc.create_index("r")
        rc.create_frame("r", "f")

        def write_phase(start: int, n: int) -> dict:
            """Sequential batched writes; every one must COMMIT."""
            failed = 0
            t0 = time.perf_counter()
            for k in range(start, start + n, batch):
                q = " ".join(
                    f'SetBit(rowID=1, frame="f", columnID={c})'
                    for c in range(k, min(k + batch, start + n))
                )
                try:
                    rc.execute_query("r", q)
                except Exception:  # noqa: BLE001 — ClientError carries status
                    failed += 1
            dt = time.perf_counter() - t0
            return {
                "write_qps": round(n / dt, 1),
                "writes": n,
                "failed_batches": failed,
                "batch": batch,
            }

        def rstatus() -> dict:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{router_port}/replica/status", timeout=10
            ) as resp:
                return json.loads(resp.read())

        def direct_count(host: str) -> int:
            req = urllib.request.Request(
                f"http://{host}/index/r/query",
                data=b'Count(Bitmap(rowID=1, frame="f"))', method="POST")
            with urllib.request.urlopen(req, timeout=30) as resp:
                return json.loads(resp.read())["results"][0]

        tiers.append({"tier": "writes_3g", "groups": 3, **write_phase(0, n_writes)})
        assert tiers[-1]["failed_batches"] == 0, tiers[-1]

        # Kill the LAST group hard, mid-stream: writes must KEEP
        # COMMITTING on the degraded quorum — the headline behavior the
        # WAL buys (the old full-set rule turned this into a 503 storm).
        procs[2].kill()
        tiers.append({
            "tier": "writes_2g", "groups": 2,
            **write_phase(n_writes, n_writes),
        })
        no_storm = tiers[-1]["failed_batches"] == 0
        assert no_storm, tiers[-1]
        assert direct_count(hosts[0]) == direct_count(hosts[1]) == 2 * n_writes

        # Restart the dead group (same data dir, bumped epoch) and time
        # catch-up: restart -> probe -> WAL suffix replay -> rejoin.
        t_restart = time.perf_counter()
        p2, h2 = spawn_group(2, 2)
        procs[2] = p2
        hosts[2] = h2
        catchup_s = None
        deadline = time.monotonic() + (60 if smoke else 300)
        while time.monotonic() < deadline:
            g2 = next(g for g in rstatus()["groups"] if g["name"] == "g2")
            if g2["healthy"] and g2["caughtUp"]:
                catchup_s = round(time.perf_counter() - t_restart, 3)
                break
            time.sleep(0.05)
        assert catchup_s is not None, "g2 never rejoined"
        converged = (
            direct_count(hosts[2]) == direct_count(hosts[0]) == 2 * n_writes
        )
        assert converged
        # Reads route to the rejoined group again.
        served = set()
        for _ in range(12):
            req = urllib.request.Request(
                f"http://127.0.0.1:{router_port}/index/r/query",
                data=b'Count(Bitmap(rowID=1, frame="f"))', method="POST")
            with urllib.request.urlopen(req, timeout=30) as resp:
                resp.read()
                served.add((resp.headers.get("X-Pilosa-Group") or "").split("@")[0])
        rejoined_reads = "g2" in served
        with urllib.request.urlopen(
            f"http://127.0.0.1:{router_port}/debug/vars", timeout=10
        ) as resp:
            snap = json.loads(resp.read())
        tiers.append({
            "tier": "catchup",
            "catchup_s": catchup_s,
            "replayed": snap.get("replica.replayed", 0),
            "lag_at_restart": n_writes // batch + (1 if n_writes % batch else 0),
            "converged": converged,
            "rejoined_reads": rejoined_reads,
            "wal": rstatus()["wal"],
        })
    finally:
        for p in procs:
            try:
                p.kill()
            except Exception:  # noqa: BLE001
                pass
        for p in procs:
            try:
                p.wait(timeout=30)
            except Exception:  # noqa: BLE001
                pass
        for f in errs:
            f.close()
        shutil.rmtree(root, ignore_errors=True)

    by = {t["tier"]: t for t in tiers}
    qps3, qps2 = by["writes_3g"]["write_qps"], by["writes_2g"]["write_qps"]
    return {
        "metric": "recovery_write_qps",
        "value": qps2,
        "unit": (
            f"committed writes/sec on the DEGRADED quorum (2/3 groups, batch "
            f"{batch}; full set {qps3} w/s; zero failed writes with a group "
            f"down; catch-up replayed the {by['catchup']['replayed']}-record "
            f"WAL suffix in {by['catchup']['catchup_s']} s and the group "
            f"rejoined reads converged)"
        ),
        "vs_baseline": round(qps2 / qps3, 3) if qps3 else None,
        "catchup_s": by["catchup"]["catchup_s"],
        "cpus": os.cpu_count(),
        "tiers": tiers,
    }


def bench_resync() -> dict:
    """Automated-resync tier: a BLANK group joins a loaded 2-group
    cluster and self-heals with zero operator action.  3 group
    subprocesses behind an out-of-process CLI router (durable WAL);
    g2 is configured at the router but never started during the load:

    - ``load``: writes build real fragment bulk on g0/g1 while g2's
      backlog accumulates in the WAL;
    - ``rejoin``: g2 starts on a BLANK data dir; the probe finds
      applied_seq=0 over a non-empty sequence space and drives the
      resync (digest diff -> fragment stream -> seed -> catch-up).
      The tier measures TIME-TO-REJOIN, BYTES STREAMED vs the donor's
      full fragment copy and vs the WAL's replay-it-all alternative,
      asserts ZERO FAILED WRITES during the resync (a writer hammers
      the router the whole time), and asserts digest-level
      convergence in-run.

    ``BENCH_RESYNC_WRITES`` sizes the load; ``BENCH_SMOKE=1`` shrinks
    for CI."""
    import shutil
    import subprocess
    import sys
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from pilosa_tpu.server.client import Client

    smoke = os.environ.get("BENCH_SMOKE", "").lower() in ("1", "true", "yes")
    n_writes = int(os.environ.get("BENCH_RESYNC_WRITES", "80" if smoke else "800"))
    batch = int(os.environ.get("BENCH_BATCH", "8"))

    repo = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(repo, "tests", "replica_group_worker.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo
    env.pop("PILOSA_TPU_QCACHE", None)

    def free_port():
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    root = tempfile.mkdtemp(prefix="pilosa_resync_")
    errs = [open(os.path.join(root, f"err{i}.log"), "w+") for i in range(4)]
    group_ports = [free_port() for _ in range(3)]

    def spawn_group(i: int, epoch: int):
        genv = dict(env)
        genv["PILOSA_WORKER_DATA_DIR"] = os.path.join(root, f"g{i}")
        genv["PILOSA_WORKER_HOST"] = f"127.0.0.1:{group_ports[i]}"
        p = subprocess.Popen(
            [sys.executable, worker, f"g{i}@{epoch}"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=errs[i],
            cwd=repo, env=genv, text=True)
        line = json.loads(p.stdout.readline())
        assert line.get("ready"), line
        return p, line["host"]

    procs = []
    tiers = []
    try:
        groups = [spawn_group(i, 1) for i in range(2)]  # g2 stays down
        procs = [p for p, _ in groups]
        hosts = [h for _, h in groups] + [f"127.0.0.1:{group_ports[2]}"]

        router_port = free_port()
        router = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu", "replica-router",
             "--groups", ",".join(f"g{i}={h}" for i, h in enumerate(hosts)),
             "--port", str(router_port),
             "--wal-dir", os.path.join(root, "wal"),
             "--probe-interval", "0.1"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=errs[3], cwd=repo, env=env, text=True)
        procs.append(router)
        line = router.stdout.readline()
        assert "replica-router" in line, line

        rc = Client(f"127.0.0.1:{router_port}", timeout=60)
        rc.create_index("r")
        rc.create_frame("r", "f")

        def rget(path: str) -> dict:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{router_port}{path}", timeout=10
            ) as resp:
                return json.loads(resp.read())

        def gget(host: str, path: str) -> bytes:
            with urllib.request.urlopen(f"http://{host}{path}", timeout=30) as r:
                return r.read()

        # LOAD: real fragment bulk across several rows/frames while
        # g2's backlog grows in the WAL.
        t0 = time.perf_counter()
        for k in range(0, n_writes, batch):
            q = " ".join(
                f'SetBit(rowID={1 + (c % 5)}, frame="f", columnID={c})'
                for c in range(k, min(k + batch, n_writes))
            )
            rc.execute_query("r", q)
        load_s = time.perf_counter() - t0
        wal_bytes = rget("/replica/status")["wal"]["bytes"]
        donor_digest = json.loads(gget(hosts[0], "/replica/digest"))
        full_copy_bytes = 0
        for path in donor_digest["fragments"]:
            idx, frame, view, slice_i = path.split("/")
            full_copy_bytes += len(gget(
                hosts[0],
                f"/fragment/data?index={idx}&frame={frame}&view={view}&slice={slice_i}",
            ))
        tiers.append({
            "tier": "load", "writes": n_writes, "batch": batch,
            "load_s": round(load_s, 3), "wal_bytes": wal_bytes,
            "full_copy_bytes": full_copy_bytes,
        })

        # REJOIN: start g2 blank; hammer writes the whole time (the
        # tier's zero-failed-writes assertion) until it is back.
        failed = [0]
        extra = [0]
        stop = threading.Event()

        def writer():
            k = n_writes
            while not stop.is_set():
                try:
                    rc.execute_query(
                        "r", f'SetBit(rowID=9, frame="f", columnID={k})'
                    )
                    extra[0] += 1
                except Exception:  # noqa: BLE001 — counted, asserted zero
                    failed[0] += 1
                k += 1

        wt = threading.Thread(target=writer)
        wt.start()
        t_join = time.perf_counter()
        p2, h2 = spawn_group(2, 1)
        procs.append(p2)
        rejoin_s = None
        deadline = time.monotonic() + (120 if smoke else 600)
        while time.monotonic() < deadline:
            g2 = next(g for g in rget("/replica/status")["groups"]
                      if g["name"] == "g2")
            if g2["healthy"] and g2["caughtUp"] and not g2["stale"]:
                rejoin_s = round(time.perf_counter() - t_join, 3)
                break
            time.sleep(0.05)
        stop.set()
        wt.join()
        assert rejoin_s is not None, "g2 never rejoined"
        assert failed[0] == 0, f"{failed[0]} writes failed during resync"
        snap = rget("/debug/vars")
        streamed = snap.get("replica.resync_bytes", 0)
        # CONVERGENCE, digest-level: byte-identical content everywhere.
        digs = {h: json.loads(gget(h, "/replica/digest"))["digest"] for h in hosts}
        assert len(set(digs.values())) == 1, digs
        tiers.append({
            "tier": "rejoin",
            "rejoin_s": rejoin_s,
            "bytes_streamed": streamed,
            "full_copy_bytes": full_copy_bytes,
            "wal_bytes": wal_bytes,
            "resync_fragments": snap.get("replica.resync_fragments", 0),
            "replayed": snap.get("replica.replayed", 0),
            "writes_during_resync": extra[0],
            "failed_writes_during_resync": failed[0],
            "converged": True,
        })
    finally:
        for p in procs:
            try:
                p.kill()
            except Exception:  # noqa: BLE001
                pass
        for p in procs:
            try:
                p.wait(timeout=30)
            except Exception:  # noqa: BLE001
                pass
        for f in errs:
            f.close()
        shutil.rmtree(root, ignore_errors=True)

    by = {t["tier"]: t for t in tiers}
    rj = by["rejoin"]
    return {
        "metric": "resync_rejoin_s",
        "value": rj["rejoin_s"],
        "unit": (
            f"seconds for a BLANK group to rejoin a loaded 2-group cluster "
            f"(streamed {rj['bytes_streamed']} B of roaring fragments vs "
            f"{rj['wal_bytes']} B of WAL replay traffic; "
            f"{rj['writes_during_resync']} writes committed during the "
            f"resync with zero failures; digest convergence asserted in-run)"
        ),
        "bytes_streamed": rj["bytes_streamed"],
        "full_copy_bytes": rj["full_copy_bytes"],
        "wal_bytes": rj["wal_bytes"],
        "cpus": os.cpu_count(),
        "tiers": tiers,
    }


def bench_shard() -> dict:
    """Partitioned replica groups tier: WRITE throughput at 1 shard vs
    2 shards, plus a LIVE RESHARD leg.  Each shard is its own replica
    set with its own sequencer lock and WAL sequence space, so adding a
    shard multiplies write capacity — two shards sequence concurrently
    where one shard serializes everything through a single lock AND a
    single group process:

    - ``router_1s``: one shard, one subprocess group — every write
      through one sequencer (the PR 6-16 write ceiling);
    - ``router_2s``: two shards (slice ranges [0,4) / [4,inf)), one
      subprocess group each — clients split across the ranges, each
      request body stays within one range so it routes whole to its
      owner; acceptance ``scaling_1s_to_2s >= BENCH_SHARD_MIN_SCALING``
      (default 1.5) is ASSERTED in-run on a multi-core host (shards are
      separate processes: a 1-cpu box records the ratio with
      ``skip_reason`` instead — scaling needs cores);
    - ``reshard``: a single open-ended shard splits at slice 4 onto a
      standby group WHILE writer threads hammer the router — zero
      failed writes asserted in-run (fence-held writes just block
      briefly), then digest convergence: the old group's /replica/digest
      holds no moved-range fragment, the new group's holds them all,
      and the router-merged count equals exactly the acked writes.

    BENCH_SMOKE=1 shrinks phases for CI."""
    import subprocess
    import sys
    import tempfile
    import urllib.error
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.pilosa import SLICE_WIDTH
    from pilosa_tpu.replica.digest import parse_fragment_path

    smoke = os.environ.get("BENCH_SMOKE", "").lower() in ("1", "true", "yes")
    n_clients = int(os.environ.get("BENCH_THREADS", "4" if smoke else "12"))
    phase_s = float(os.environ.get("BENCH_SHARD_SECS", "1.0" if smoke else "6"))
    batch = int(os.environ.get("BENCH_BATCH", "8" if smoke else "32"))
    n_rows = int(os.environ.get("BENCH_ROWS", "8" if smoke else "16"))
    min_scaling = float(os.environ.get("BENCH_SHARD_MIN_SCALING", "1.5"))
    split_at = 4  # slices [0, 4) stay, [4, inf) move / shard away

    repo = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(repo, "tests", "replica_group_worker.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo
    env.pop("PILOSA_TPU_QCACHE", None)

    def free_port():
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    def spawn_group(name, errfile):
        p = subprocess.Popen(
            [sys.executable, worker, name],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=errfile,
            cwd=repo, env=env, text=True)
        line = json.loads(p.stdout.readline())
        assert line.get("ready"), line
        return p, line["host"]

    def spawn_router(args, errfile):
        port = free_port()
        p = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu", "replica-router",
             "--port", str(port), *args],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=errfile,
            cwd=repo, env=env, text=True)
        line = p.stdout.readline()
        assert "replica-router" in line, line
        return p, port

    def stop_group(p):
        try:
            p.stdin.write("\n")
            p.stdin.flush()
        except Exception:  # noqa: BLE001 — already dead
            pass
        try:
            p.wait(timeout=30)
        except Exception:  # noqa: BLE001
            p.kill()

    def stop_router(p):
        try:
            p.terminate()
        except Exception:  # noqa: BLE001
            pass
        try:
            p.wait(timeout=30)
        except Exception:  # noqa: BLE001
            p.kill()

    def post(host, path, body, timeout=60):
        req = urllib.request.Request(
            f"http://{host}{path}", data=body, method="POST")
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, resp.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def seed_schema(host):
        assert post(host, "/index/w", b"{}")[0] == 200
        assert post(host, "/index/w/frame/f", b"{}")[0] == 200

    def query(host, q, qs=""):
        st, body = post(host, f"/index/w/query{qs}", q.encode())
        assert st == 200, body
        return json.loads(body)["results"]

    # Closed-loop write load: client i owns slice (i % len(ranges)) of
    # its range set, every request body stays inside ONE slice range so
    # a 2-shard map routes it whole (the fast path, no splitting), and
    # every columnID is unique per client so acked bits == set bits.
    def write_phase(host, dur_s, row=1):
        t_end = time.perf_counter() + dur_s

        def client(i):
            served = errors = 0
            sl = split_at + (i % split_at) if i % 2 else i % split_at
            k = 0
            while time.perf_counter() < t_end:
                base = sl * SLICE_WIDTH + (i * 1_000_000 + k * batch) % (SLICE_WIDTH - batch)
                body = " ".join(
                    f'SetBit(rowID={(k + j) % n_rows}, frame="f", '
                    f'columnID={base + j})'
                    for j in range(batch)
                ).encode()
                k += 1
                st, _ = post(host, "/index/w/query", body)
                if st == 200:
                    served += 1
                else:
                    errors += 1
            return served, errors

        t0 = time.perf_counter()
        with ThreadPoolExecutor(n_clients) as pool:
            outs = list(pool.map(client, range(n_clients)))
        dt = time.perf_counter() - t0
        served = sum(s for s, _ in outs)
        errors = sum(e for _, e in outs)
        assert errors == 0, f"write phase saw {errors} failed writes"
        return {"write_qps": round(served / dt, 1), "served": served,
                "clients": n_clients, "batch": batch}

    errs = [tempfile.NamedTemporaryFile("w+", delete=False) for _ in range(8)]
    tiers = []
    try:
        # -- tier 1: one shard, one group ---------------------------------
        g0, h0 = spawn_group("gA", errs[0])
        r1, p1 = spawn_router(["--groups", f"gA={h0}"], errs[1])
        host1 = f"127.0.0.1:{p1}"
        seed_schema(host1)
        write_phase(host1, 0.2)  # warm the lane
        tiers.append({"tier": "router_1s", "shards": 1, **write_phase(host1, phase_s)})
        stop_router(r1)
        stop_group(g0)

        # -- tier 2: two shards, one group each ---------------------------
        g1, h1 = spawn_group("gA", errs[2])
        g2, h2 = spawn_group("gB", errs[3])
        r2, p2 = spawn_router(
            ["--shard-map", f"s0=0-{split_at}:gA={h1};s1={split_at}-:gB={h2}"],
            errs[4])
        host2 = f"127.0.0.1:{p2}"
        seed_schema(host2)
        write_phase(host2, 0.2)
        tiers.append({"tier": "router_2s", "shards": 2, **write_phase(host2, phase_s)})
        stop_router(r2)
        stop_group(g1)
        stop_group(g2)

        # -- tier 3: live reshard under write load ------------------------
        g3, h3 = spawn_group("gA", errs[5])
        g4, h4 = spawn_group("gB", errs[6])  # standby split target
        r3, p3 = spawn_router(["--groups", f"gA={h3}"], errs[7])
        host3 = f"127.0.0.1:{p3}"
        seed_schema(host3)
        # Pre-load both halves of the future split so fragments move.
        for sl in range(2 * split_at):
            assert post(
                host3, "/index/w/query",
                f'SetBit(rowID=0, frame="f", columnID={sl * SLICE_WIDTH})'.encode(),
            )[0] == 200

        import threading

        failures, acks = [], [0]
        stop_flag = threading.Event()

        def writer(i):
            k = 0
            while not stop_flag.is_set():
                sl = k % (2 * split_at)  # keep the moved range hot
                col = sl * SLICE_WIDTH + 8 + (i * 500_000 + k) % 400_000
                st, body = post(
                    host3, "/index/w/query",
                    f'SetBit(rowID=2, frame="f", columnID={col})'.encode(),
                )
                if st != 200:
                    failures.append((st, body[:200]))
                elif json.loads(body)["results"] == [True]:
                    acks[0] += 1  # count NEW bits only (dups ack False)
                k += 1

        writers = [threading.Thread(target=writer, args=(i,), daemon=True)
                   for i in range(max(2, n_clients // 4))]
        for t in writers:
            t.start()
        time.sleep(0.3)  # writers in flight before the fence
        t0 = time.perf_counter()
        st, body = post(
            host3, "/replica/reshard",
            json.dumps({
                "shard": "s0", "at": split_at, "name": "s1",
                "groups": [f"gB={h4}"],
            }).encode(),
            timeout=120,
        )
        reshard_ms = round((time.perf_counter() - t0) * 1e3, 1)
        assert st == 200, body
        flip = json.loads(body)
        time.sleep(0.3)  # post-flip writes land through the new map
        stop_flag.set()
        for t in writers:
            t.join(timeout=30)
        assert not failures, (
            f"{len(failures)} writes failed during the live reshard: "
            f"{failures[:3]}"
        )
        # Zero lost writes: router-merged count == acked new bits.
        assert query(host3, 'Count(Bitmap(rowID=2, frame="f"))') == [acks[0]]
        # Digest convergence: the moved range lives ONLY on the new
        # group now — old digest has no moved-range fragment, new
        # digest holds nothing else.
        with urllib.request.urlopen(f"http://{h3}/replica/digest", timeout=30) as resp:
            old_frags = json.loads(resp.read()).get("fragments") or {}
        with urllib.request.urlopen(f"http://{h4}/replica/digest", timeout=30) as resp:
            new_frags = json.loads(resp.read()).get("fragments") or {}
        old_slices = {parse_fragment_path(p)[3] for p in old_frags}
        new_slices = {parse_fragment_path(p)[3] for p in new_frags}
        assert all(s < split_at for s in old_slices), sorted(old_slices)
        assert new_slices and all(s >= split_at for s in new_slices), (
            sorted(new_slices))
        tiers.append({
            "tier": "reshard", "shards": 2,
            "reshard_ms": reshard_ms,
            "fence_ms": flip["fenceMs"],
            "moved_fragments": flip["moved"]["fragments"],
            "moved_bytes": flip["moved"]["bytes"],
            "writes_during_reshard": acks[0],
            "failed_writes": len(failures),
            "map_epoch": flip["mapEpoch"],
        })
        stop_router(r3)
        stop_group(g3)
        stop_group(g4)
    finally:
        for f in errs:
            f.close()
            os.unlink(f.name)

    by = {t["tier"]: t for t in tiers}
    qps_1 = by["router_1s"]["write_qps"]
    qps_2 = by["router_2s"]["write_qps"]
    scaling = round(qps_2 / qps_1, 3) if qps_1 else None
    # Shards are separate PROCESSES: the scaling acceptance needs
    # physical cores (2 groups + router + clients).  A starved box
    # records the ratio and the reason instead of a meaningless assert.
    cpus = os.cpu_count() or 1
    skip_reason = None
    if cpus < 3:
        skip_reason = f"only {cpus} cpu(s): shard scaling needs >= 3 cores"
    elif smoke:
        skip_reason = "BENCH_SMOKE: phases too short for a stable ratio"
    if skip_reason is None:
        assert scaling is not None and scaling >= min_scaling, (
            f"2-shard write scaling x{scaling} < x{min_scaling} "
            f"(router_1s {qps_1} q/s, router_2s {qps_2} q/s on {cpus} cpus)"
        )
    return {
        "metric": "shard_write_qps",
        "value": qps_2,
        "unit": (
            f"write requests/sec via the replica router over 2 slice-shards "
            f"({n_clients} clients, batch {batch}; 1-shard router {qps_1} q/s "
            f"= x{scaling} scaling on {cpus} cpus; live reshard moved "
            f"{by['reshard']['moved_fragments']} fragments with "
            f"{by['reshard']['failed_writes']} failed writes, fence "
            f"{by['reshard']['fence_ms']} ms; zero-loss + digest "
            f"convergence asserted in-run)"
        ),
        "vs_baseline": scaling,
        "scaling_1s_to_2s": scaling,
        "scaling_asserted": skip_reason is None,
        "skip_reason": skip_reason,
        "min_scaling": min_scaling,
        "cpus": cpus,
        "tiers": tiers,
    }


def bench_qcache() -> dict:
    """Query-result-cache tier: a Zipf-skewed repeated read mix (the
    dashboard steady state — the same few queries hit over and over)
    with occasional writes, cache ON (generation-keyed qcache, admission
    floor 0 so CPU-smoke shapes admit) vs OFF on the same request
    schedule.  Reports per-tier hit rate and ms/request; read-your-writes
    is proven in-run (a SetBit touching a cached query's rows forces a
    miss and the next answer reflects the write), and a final numpy
    correctness gate re-checks every pool query.  BENCH_SMOKE=1 shrinks
    the shapes for CI."""
    smoke = os.environ.get("BENCH_SMOKE", "").lower() in ("1", "true", "yes")
    n_slices = int(os.environ.get("BENCH_SLICES", "2" if smoke else "4"))
    n_rows = int(os.environ.get("BENCH_ROWS", "32" if smoke else "64"))
    batch = int(os.environ.get("BENCH_BATCH", "8" if smoke else "32"))
    n_requests = int(os.environ.get("BENCH_ITERS", "400" if smoke else "4000"))
    pool_n = int(os.environ.get("BENCH_QUERY_POOL", "32" if smoke else "128"))
    zipf_s = float(os.environ.get("BENCH_ZIPF_S", "1.1"))
    write_every = int(os.environ.get("BENCH_WRITE_EVERY", "100"))
    bits_per_row = int(
        os.environ.get("BENCH_BITS_PER_ROW", "50" if smoke else "20000")
    )
    import tempfile

    from pilosa_tpu.core.frame import FrameOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.pilosa import SLICE_WIDTH
    from pilosa_tpu.qcache import QueryCache

    rng = np.random.default_rng(37)
    reserve = 4096  # import keeps these top columns free for the writes

    # The query pool: pool_n distinct dashboard batches over one frame.
    pool = []
    for seed in range(pool_n):
        prs = np.random.default_rng(1000 + seed).integers(0, n_rows, size=(batch, 2))
        pool.append(" ".join(
            f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
            for a, b in prs.tolist()
        ))
    # Zipf-skewed schedule over the pool (rank k drawn with p ~ 1/k^s),
    # shared by both tiers so on/off see the same byte-identical stream.
    p = 1.0 / np.arange(1, pool_n + 1) ** zipf_s
    p /= p.sum()
    order = np.random.default_rng(7).choice(pool_n, size=n_requests, p=p)
    state = {"engine": "?"}

    def run(cache_on: bool) -> dict:
        with tempfile.TemporaryDirectory() as d:
            h = Holder(d)
            h.open()
            h.create_index("q").create_frame("f", FrameOptions())
            fr = h.index("q").frame("f")
            rows = np.repeat(np.arange(n_rows, dtype=np.uint64), bits_per_row)
            for s in range(n_slices):
                cols = rng.integers(
                    0, SLICE_WIDTH - reserve, size=len(rows)
                ).astype(np.uint64) + np.uint64(s * SLICE_WIDTH)
                fr.import_bits(rows, cols)
            qc = QueryCache(min_cost_ms=0.0) if cache_on else None
            ex = Executor(h, qcache=qc)
            state["engine"] = ex.engine.name
            # Warm-up: two full pool passes page every row into the
            # device pool, build the Gram, arm the serve lane, trigger
            # every jit shape, and (cache-on) prime the fingerprint memo
            # — then the cache CONTENTS and counters reset, so the timed
            # phase measures steady-state serving (first occurrence of a
            # query is still a real miss, repeats are real hits) instead
            # of the one-time parse/compile cascade.
            for _ in range(2):
                for q in pool:
                    ex.execute("q", q)
            # ... and the write -> repair lane (one warm-up write + a
            # read that repairs the serve state), so the per-tier run
            # doesn't depend on which tier ran first in this process
            # (jit caches are process-wide).
            ex.execute("q", f'SetBit(rowID=0, frame="f", columnID={SLICE_WIDTH - 2})')
            for q in pool[:4]:
                ex.execute("q", q)
            if qc is not None:
                qc.clear()
                qc.hits = qc.misses = qc.bypasses = qc.ineligible = 0
                qc.evictions = qc.stores = 0
            wcount = 0
            lat: list = []
            t0 = time.perf_counter()
            for i, k in enumerate(order.tolist()):
                if write_every and i % write_every == write_every - 1:
                    r = wcount % n_rows
                    c = (SLICE_WIDTH - reserve) + wcount % reserve
                    ex.execute("q", f'SetBit(rowID={r}, frame="f", columnID={c})')
                    wcount += 1
                    continue
                t1 = time.perf_counter()
                ex.execute("q", pool[k])
                lat.append(time.perf_counter() - t1)
            dt = time.perf_counter() - t0
            # Counter snapshot BEFORE the proof/gate queries below add
            # their own hits/misses.
            hits = qc.hits if qc is not None else 0
            misses = qc.misses if qc is not None else 0
            # Read-your-writes proof: cache the hottest query, write a
            # fresh column into BOTH rows of its first pair (the
            # intersection grows by exactly one), and the next answer
            # must reflect it — the write's generation bump forced the
            # miss.
            q0 = pool[int(order[0])]
            c0 = ex.execute("q", q0)
            prs0 = np.random.default_rng(1000 + int(order[0])).integers(
                0, n_rows, size=(batch, 2)
            )
            a, b = int(prs0[0, 0]), int(prs0[0, 1])
            wc = SLICE_WIDTH - 1  # reserved tail: never touched by the import
            ex.execute("q", f'SetBit(rowID={a}, frame="f", columnID={wc})')
            if b != a:
                ex.execute("q", f'SetBit(rowID={b}, frame="f", columnID={wc})')
            c1 = ex.execute("q", q0)
            rw_ok = c1[0] == c0[0] + 1
            # Correctness gate: every pool query (cached or not) matches
            # the numpy sequential path after all the interleaved writes.
            npx = Executor(h, engine="numpy", qcache=None)
            gate_ok = all(
                ex.execute("q", q) == npx.execute("q", q) for q in pool[:8]
            )
            out = {
                "qps": len(lat) / dt,
                "ms_per_request": 1e3 * float(np.mean(lat)),
                "p99_ms": 1e3 * float(np.quantile(lat, 0.99)),
                "hit_rate": hits / (hits + misses) if hits + misses else 0.0,
                "hits": hits,
                "misses": misses,
                "evictions": qc.evictions if qc is not None else 0,
                "cache_bytes": qc.bytes if qc is not None else 0,
                "rw_ok": bool(rw_ok),
                "gate_ok": bool(gate_ok),
            }
            h.close()
        assert out["gate_ok"], "qcache tier diverged from numpy ground truth"
        assert out["rw_ok"], "read-your-writes violated: a write did not force a miss"
        return out

    def trace_overhead_check() -> dict:
        """In-run guard for the request tracer's OFF path: serving with
        a head-sampling tracer at sample-rate 0.01 must cost <= 5% vs
        tracing fully disabled — the unsampled path is a single branch
        per instrumentation site, and this keeps it that way.  Best-of-N
        tight loops over a warm cached query on both sides (min is
        robust to scheduler noise); an absolute per-request escape
        hatch (< 20 us) keeps CI boxes with coarse timers honest."""
        import tempfile

        from pilosa_tpu.trace import Tracer

        n = int(os.environ.get("BENCH_TRACE_ITERS", "1500" if smoke else "6000"))
        with tempfile.TemporaryDirectory() as d:
            h = Holder(d)
            h.open()
            h.create_index("q").create_frame("f", FrameOptions())
            fr = h.index("q").frame("f")
            rows = np.repeat(np.arange(8, dtype=np.uint64), 50)
            fr.import_bits(rows, rng.integers(0, SLICE_WIDTH, size=len(rows)).astype(np.uint64))
            ex = Executor(h, qcache=QueryCache(min_cost_ms=0.0))
            q = pool[0]
            for _ in range(3):
                ex.execute("q", q)  # warm: jit, serve lane, cache entry
            tracer = Tracer(sample_rate=0.01)
            from pilosa_tpu.executor import ExecOptions

            def loop(traced: bool) -> float:
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    if traced:
                        for _i in range(n):
                            tr = tracer.begin(None)  # ~1% sampled
                            if tr is None:
                                ex.execute("q", q)
                            else:
                                ex.execute("q", q, opt=ExecOptions(span=tr.root))
                                tracer.finish_request(
                                    tr, name="bench", dt_ms=tr.root.finish().ms
                                )
                    else:
                        for _i in range(n):
                            ex.execute("q", q)
                    best = min(best, time.perf_counter() - t0)
                return best

            t_off = loop(False)
            t_on = loop(True)
            h.close()
        overhead = t_on / t_off - 1.0
        ok = overhead <= 0.05 or (t_on - t_off) / n <= 20e-6
        assert ok, (
            f"tracing at sample-rate=0.01 cost {overhead * 100:.1f}% vs disabled "
            f"(off {t_off / n * 1e6:.1f} us/req, on {t_on / n * 1e6:.1f} us/req) — "
            "the unsampled path must stay a single branch per site"
        )
        return {"trace_overhead": round(overhead, 4), "trace_ok": ok,
                "trace_sampled": tracer.stat_sampled}

    def costs_overhead_check() -> dict:
        """In-run guard for the observability plane (PR 14): serving
        with the dispatch meter + cost ledger armed AND a Prometheus
        scrape every n/4 requests (a far harsher cadence than a real
        15 s scrape interval) must cost <= 5% vs all of it disabled.
        Same best-of-N / absolute-escape-hatch shape as the trace
        check above."""
        import tempfile

        from pilosa_tpu import metrics as metrics_mod
        from pilosa_tpu.costs import CostLedger
        from pilosa_tpu.executor import ExecOptions
        from pilosa_tpu.stats import ExpvarStatsClient
        from pilosa_tpu.trace import Tracer

        n = int(os.environ.get("BENCH_COSTS_ITERS", "1500" if smoke else "6000"))
        scrape_every = max(1, n // 4)
        with tempfile.TemporaryDirectory() as d:
            h = Holder(d)
            h.open()
            h.create_index("q").create_frame("f", FrameOptions())
            fr = h.index("q").frame("f")
            rows = np.repeat(np.arange(8, dtype=np.uint64), 50)
            fr.import_bits(rows, rng.integers(0, SLICE_WIDTH, size=len(rows)).astype(np.uint64))
            q = pool[0]

            ex_off = Executor(h, qcache=QueryCache(min_cost_ms=0.0))
            stats = ExpvarStatsClient()
            ledger = CostLedger(stats=stats)
            tracer = Tracer(sample_rate=0.01, stats=stats, costs=ledger)
            ex_on = Executor(h, qcache=QueryCache(min_cost_ms=0.0), stats=stats)
            for _ in range(3):
                ex_off.execute("q", q)
                ex_on.execute("q", q)

            def loop(metered: bool) -> float:
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    if metered:
                        for _i in range(n):
                            tr = tracer.begin(None)
                            if tr is None:
                                ex_on.execute("q", q)
                            else:
                                ex_on.execute(
                                    "q", q, opt=ExecOptions(span=tr.root)
                                )
                                tracer.finish_request(
                                    tr, name="bench", dt_ms=tr.root.finish().ms,
                                    body=q.encode(),
                                )
                            if _i % scrape_every == scrape_every - 1:
                                metrics_mod.parse_exposition(
                                    metrics_mod.render(stats)
                                )
                    else:
                        for _i in range(n):
                            ex_off.execute("q", q)
                    best = min(best, time.perf_counter() - t0)
                return best

            t_off = loop(False)
            t_on = loop(True)
            entries = len(ledger)
            h.close()
        overhead = t_on / t_off - 1.0
        ok = overhead <= 0.05 or (t_on - t_off) / n <= 20e-6
        assert ok, (
            f"cost ledger + exposition cost {overhead * 100:.1f}% vs disabled "
            f"(off {t_off / n * 1e6:.1f} us/req, on {t_on / n * 1e6:.1f} us/req) — "
            "metering must stay a branch + a couple of dict ops per dispatch"
        )
        assert entries > 0, "cost ledger folded no traced requests"
        return {"costs_overhead": round(overhead, 4), "costs_ok": ok,
                "costs_entries": entries}

    # Two alternating passes per tier, best-of by ms/request: jit and
    # allocator caches are process-wide, so whichever tier runs first
    # pays residual one-time costs — best-of-two with alternation keeps
    # the A/B honest in one process (same reason _best_of_runs exists).
    offs = [run(False)]
    ons = [run(True)]
    offs.append(run(False))
    ons.append(run(True))
    on = min(ons, key=lambda r: r["ms_per_request"])
    off = min(offs, key=lambda r: r["ms_per_request"])
    trace_ab = trace_overhead_check()
    costs_ab = costs_overhead_check()
    tiers = [
        {"tier": "qcache_on", **{k: (round(v, 3) if isinstance(v, float) else v) for k, v in on.items()}, **trace_ab, **costs_ab},
        {"tier": "qcache_off", **{k: (round(v, 3) if isinstance(v, float) else v) for k, v in off.items()}},
    ]
    speedup = off["ms_per_request"] / on["ms_per_request"]
    return {
        "metric": "qcache_read_qps",
        "value": round(on["qps"], 1),
        "unit": (
            f"requests/sec, Zipf(s={zipf_s}) read mix over {pool_n} distinct "
            f"batch-{batch} queries ({n_slices} slices x {n_rows} rows, one "
            f"write per {write_every} requests; hit_rate {on['hit_rate']:.2f}, "
            f"{on['ms_per_request']:.3f} ms/request vs cache-off "
            f"{off['ms_per_request']:.3f} = x{speedup:.2f}, engine "
            f"{state['engine']})"
        ),
        "vs_baseline": round(speedup, 2),
        "tiers": tiers,
    }


def bench_multicore() -> dict:
    """Multi-core host serving tier: ONE host's serving stack on 1 vs 2
    workers, plus the serve-lane-breadth A/B.

    Part A drives a REAL server (the ``pilosa-tpu server`` CLI — pool,
    QoS door, native serve lane, the whole front door) from T∈{1,2,4}
    closed-loop client threads.  "Worker" means whatever the build can
    actually parallelize: the in-process thread pool on a free-threaded
    CPython, the `[server] workers` SO_REUSEPORT process fallback on a
    GIL build (DEVELOPMENT.md "Multi-core serving" decision table) — the
    same env knobs either way, so the tier measures the deployed shape.
    The headline ``scaling_1_to_2`` (2-worker read QPS / 1-worker, both
    at 4 clients) is asserted >= 1.6 in-run on a multi-core host; a
    1-cpu box records the ratio and the skip reason instead (``cpus``
    says which regime a line measured, like BENCH_CONFIG=replica).

    Part B is the serve-lane-breadth A/B, in-process for determinism:
    each new native one-crossing shape — multi-frame pair batches,
    Range covers, nested tree batches — timed against the Python
    general lane (PILOSA_TPU_NO_FASTLANE=1: full Python parse +
    per-call eval) on the same executor and data.  Native must BEAT the
    Python lane on every shape (asserted in-run); these wins are
    per-core and multiply with part A's worker count."""
    import shutil
    import subprocess
    import sys
    import tempfile
    import urllib.error
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    smoke = os.environ.get("BENCH_SMOKE", "").lower() in ("1", "true", "yes")
    phase_s = float(os.environ.get("BENCH_MULTICORE_SECS", "1.0" if smoke else "6"))
    n_rows = int(os.environ.get("BENCH_ROWS", "8" if smoke else "16"))
    n_slices = int(os.environ.get("BENCH_SLICES", "1" if smoke else "2"))
    batch = int(os.environ.get("BENCH_BATCH", "8" if smoke else "32"))
    bits_per_row = int(os.environ.get("BENCH_BITS_PER_ROW", "500" if smoke else "20000"))
    ab_iters = int(os.environ.get("BENCH_ITERS", "5" if smoke else "20"))
    min_scaling = float(os.environ.get("BENCH_MULTICORE_MIN_SCALING", "1.6"))

    from pilosa_tpu.pilosa import SLICE_WIDTH

    repo = os.path.dirname(os.path.abspath(__file__))
    gil_enabled = getattr(sys, "_is_gil_enabled", lambda: True)()
    free_threaded = not gil_enabled
    worker_mode = "threads" if free_threaded else "processes"

    queries = []
    for seed in range(8):
        prs = np.random.default_rng(seed).integers(0, n_rows, size=(batch, 2))
        queries.append(" ".join(
            f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))'
            for a, b in prs.tolist()
        ))

    def read_phase(host: str, n_clients: int, dur_s: float) -> dict:
        """Closed-loop read load.  A 503 from the pool door counts as a
        shed (the 1-worker tier's bounded queue can legitimately shed
        under 4 closed-loop clients); transport errors stay fatal."""
        t_end = time.perf_counter() + dur_s

        def client(i: int) -> tuple[int, int]:
            served = sheds = 0
            k = i
            while time.perf_counter() < t_end:
                q = queries[k % len(queries)]
                k += 1
                req = urllib.request.Request(
                    f"http://{host}/index/m/query", data=q.encode(), method="POST")
                try:
                    with urllib.request.urlopen(req, timeout=60) as resp:
                        resp.read()
                    served += 1
                except urllib.error.HTTPError as e:
                    assert e.code in (429, 503), f"unexpected status {e.code}"
                    sheds += 1
                except (urllib.error.URLError, OSError) as e:
                    raise AssertionError(f"transport error under load: {e}")
            return served, sheds

        t0 = time.perf_counter()
        with ThreadPoolExecutor(n_clients) as pool:
            outs = list(pool.map(client, range(n_clients)))
        dt = time.perf_counter() - t0
        served = sum(s for s, _ in outs)
        sheds = sum(sh for _, sh in outs)
        assert served > 0, "no reads served"
        return {"read_qps": round(served / dt, 1), "served": served,
                "sheds": sheds, "clients": n_clients}

    data_dir = tempfile.mkdtemp(prefix="bench_multicore_")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo
    env["PILOSA_DATA_DIR"] = data_dir
    env["PILOSA_HOST"] = "127.0.0.1:0"
    env["PILOSA_ENGINE"] = "numpy"
    env["PILOSA_STATS"] = "expvar"
    env["PILOSA_TPU_QCACHE"] = "0"  # measure execution, not cache hits

    def start_server(workers: int):
        """One serving 'width-w' incarnation of the CLI server."""
        env_s = dict(env)
        # Free-threaded: width = pool threads.  GIL build: width =
        # SO_REUSEPORT processes, one serving thread each, so the 1w
        # baseline and the 2w tier differ ONLY in worker count.
        env_s["PILOSA_TPU_SERVER_MAX_THREADS"] = str(workers if free_threaded else 1)
        env_s["PILOSA_TPU_SERVER_WORKERS"] = str(workers if workers > 1 else 0)
        errf = tempfile.NamedTemporaryFile("w+", delete=False)
        p = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu", "server"],
            stdout=subprocess.PIPE, stderr=errf, cwd=repo, env=env_s, text=True)
        host = None
        for _ in range(64):
            line = p.stdout.readline()
            if not line:
                break
            if "serving on http://" in line:
                host = line.split("http://", 1)[1].split()[0]
                break
        assert host, f"server (workers={workers}) never reported ready"
        return p, host, errf

    def stop_server(p, errf):
        p.terminate()
        try:
            p.wait(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
        errf.close()
        os.unlink(errf.name)

    def warm(host: str, rounds: int = 4):
        """Warm EVERY worker's serve lane (SO_REUSEPORT spreads
        connections, so one pass per worker is not guaranteed — a few
        rounds of the full query set gets all of them hot and the Gram
        serve state armed)."""
        for _ in range(rounds):
            for q in queries:
                req = urllib.request.Request(
                    f"http://{host}/index/m/query", data=q.encode(), method="POST")
                with urllib.request.urlopen(req, timeout=60) as resp:
                    resp.read()

    # Seed ONCE before any server opens: the SO_REUSEPORT siblings each
    # open the same data-dir read-only-by-convention (writes route
    # through the replica router when multi-process consistency matters
    # — DEVELOPMENT.md), so the bench is a pure read workload.
    from pilosa_tpu.core.frame import FrameOptions
    from pilosa_tpu.core.holder import Holder

    tiers = []
    try:
        h = Holder(data_dir)
        h.open()
        h.create_index("m").create_frame("f", FrameOptions())
        rng = np.random.default_rng(41)
        rows_l, cols_l = [], []
        for r in range(n_rows):
            for s in range(n_slices):
                cols = rng.integers(0, SLICE_WIDTH - 4096, size=bits_per_row)
                rows_l.extend([r] * bits_per_row)
                cols_l.extend((int(c) + s * SLICE_WIDTH) for c in cols)
        h.index("m").frame("f").import_bits(np.array(rows_l), np.array(cols_l))
        h.close()

        p1, host1, err1 = start_server(1)
        try:
            warm(host1)
            tiers.append({"tier": "serve_1w", "workers": 1,
                          **read_phase(host1, 4, phase_s)})
        finally:
            stop_server(p1, err1)

        p2, host2, err2 = start_server(2)
        try:
            warm(host2)
            for t in (1, 2, 4):
                tiers.append({"tier": f"clients_{t}", "workers": 2,
                              **read_phase(host2, t, phase_s)})
        finally:
            stop_server(p2, err2)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)

    by = {t["tier"]: t for t in tiers}
    qps_1w = by["serve_1w"]["read_qps"]
    qps_2w = by["clients_4"]["read_qps"]  # same client load as serve_1w
    scaling = round(qps_2w / qps_1w, 3) if qps_1w else None
    cpus = os.cpu_count() or 1
    scaling_skip = None
    if smoke:
        # A smoke run shares its host with the rest of the suite: it
        # checks results, and records its timing ratios unasserted.
        scaling_skip = f"BENCH_SMOKE: ratio x{scaling} recorded, assert skipped"
    elif cpus >= 2:
        assert scaling >= min_scaling, (
            f"2-worker reads only x{scaling} of 1-worker on a {cpus}-cpu "
            f"host (need >= {min_scaling})")
    else:
        scaling_skip = (
            f"1-cpu host: {worker_mode} cannot scale by construction; "
            f"ratio x{scaling} recorded, assert skipped")

    # ---- part B: serve-lane breadth vs the Python general lane ----------
    from pilosa_tpu.executor import Executor

    def time_best(fn) -> float:
        best = float("inf")
        for _ in range(ab_iters):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    def lane_ab(ex_native, ex_py, index: str, body: str, tier: str) -> dict:
        """Best-of wall time: native lane vs PILOSA_TPU_NO_FASTLANE=1
        (full Python parse + per-call eval) on the same data.  The B
        side runs on the NUMPY engine — the cheapest Python-lane
        implementation, so the measured win is conservative."""
        got = ex_native.execute(index, body)  # warm: arms serve state / Gram
        got = ex_native.execute(index, body)
        got = ex_native.execute(index, body)
        native_s = time_best(lambda: ex_native.execute(index, body))
        os.environ["PILOSA_TPU_NO_FASTLANE"] = "1"
        try:
            want = ex_py.execute(index, body)  # warm the Python lane too
            py_s = time_best(lambda: ex_py.execute(index, body))
        finally:
            del os.environ["PILOSA_TPU_NO_FASTLANE"]
        assert got == want, f"{tier}: native disagrees with Python lane"
        speedup = py_s / native_s if native_s else float("inf")
        assert smoke or speedup > 1.0, (
            f"{tier}: native x{speedup:.2f} does not beat the Python lane "
            f"({native_s * 1e3:.3f} vs {py_s * 1e3:.3f} ms)")
        return {"tier": tier, "native_ms": round(native_s * 1e3, 3),
                "python_ms": round(py_s * 1e3, 3),
                "speedup": round(speedup, 2), "calls": body.count("Count(")}

    bdir = tempfile.mkdtemp(prefix="bench_breadth_")
    try:
        hb = Holder(bdir)
        hb.open()
        rng = np.random.default_rng(7)

        # multi-frame pair batches (pn_serve_multi): one crossing serves
        # a batch that interleaves two frames' armed Gram states.
        ib = hb.create_index("b")
        ib.create_frame("f", FrameOptions())
        ib.create_frame("g", FrameOptions())
        for fn_ in ("f", "g"):
            hb.index("b").frame(fn_).import_bits(
                rng.integers(0, n_rows, 4 * bits_per_row),
                rng.integers(0, n_slices * SLICE_WIDTH, 4 * bits_per_row))
        parts = []
        for a, b in rng.integers(0, n_rows, size=(batch, 2)).tolist():
            parts.append(f'Count(Intersect(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")))')
            parts.append(f'Count(Union(Bitmap(rowID={a}, frame="g"), Bitmap(rowID={b}, frame="g")))')
        # The Gram serve states behind pn_serve_pairs/pn_serve_multi need
        # an engine whose pair_gram works (the numpy engine declines it),
        # so the native side runs the jax executor; the native lane
        # itself is pure C either way.
        exj = Executor(hb, engine="jax")
        exnp = Executor(hb, engine="numpy")
        ab = [lane_ab(exj, exnp, "b", " ".join(parts), "breadth_multiframe")]

        # nested tree batches (pn_serve_tree): fused parse+eval over the
        # armed container table, single-slice index.
        it = hb.create_index("t")
        it.create_frame("f", FrameOptions())
        hb.index("t").frame("f").import_bits(
            rng.integers(0, n_rows, 4 * bits_per_row),
            rng.integers(0, SLICE_WIDTH, 4 * bits_per_row))
        tparts = []
        for a, b, c, d in rng.integers(0, n_rows, size=(batch, 4)).tolist():
            tparts.append(
                f'Count(Intersect(Union(Bitmap(rowID={a}, frame="f"), Bitmap(rowID={b}, frame="f")), '
                f'Difference(Bitmap(rowID={c}, frame="f"), Bitmap(rowID={d}, frame="f"))))')
        ab.append(lane_ab(exnp, exnp, "t", " ".join(tparts), "breadth_tree"))

        # Range covers (pn_pql_match_range): the all-Count(Range) matcher
        # + fused per-view evaluation.
        ir = hb.create_index("r")
        ir.create_frame("tf", FrameOptions(time_quantum="YMD"))
        exr = Executor(hb, engine="numpy")
        stamps = ["2017-01-05T10:00", "2017-02-14T00:00", "2017-03-02T15:00",
                  "2017-06-30T23:00"]
        for r in range(min(n_rows, 4)):
            for ts in stamps:
                for c in rng.integers(0, SLICE_WIDTH, 24).tolist():
                    exr.execute("r", f'SetBit(rowID={r}, frame="tf", columnID={c}, timestamp="{ts}")')
        # Body sized with ``batch`` like the other tiers: the range
        # lane's win is the fused batch parse + view enumeration, a
        # per-call constant, so a handful of calls sits inside timing
        # noise while 4x batch makes the margin decisive.
        rwindows = [("2017-01-01T00:00", "2017-07-01T00:00"),
                    ("2017-02-01T00:00", "2017-03-01T00:00"),
                    ("2017-01-01T00:00", "2017-04-01T00:00"),
                    ("2017-03-01T00:00", "2017-07-01T00:00")]
        rparts = []
        for i in range(4 * batch):
            s_, e_ = rwindows[i % len(rwindows)]
            rparts.append(
                f'Count(Range(rowID={i % min(n_rows, 4)}, frame="tf", '
                f'start="{s_}", end="{e_}"))')
        ab.append(lane_ab(exr, exr, "r", " ".join(rparts), "breadth_range"))
        hb.close()
    finally:
        shutil.rmtree(bdir, ignore_errors=True)

    tiers.extend(ab)
    breadth_min = min(t["speedup"] for t in ab)
    return {
        "metric": "multicore_read_qps",
        "value": qps_2w,
        "unit": (
            f"read requests/sec from one host at 2 {worker_mode[:-2]}s "
            f"(4 clients, batch {batch}; 1-worker {qps_1w} q/s = "
            f"x{scaling} scaling on {cpus} cpus; serve-lane breadth "
            f"native-vs-python x{breadth_min}+ on multiframe/tree/range)"
        ),
        "vs_baseline": scaling,
        "scaling_1_to_2": scaling,
        "scaling_skip": scaling_skip,
        "free_threaded": free_threaded,
        "worker_mode": worker_mode,
        # Worker scaling needs PHYSICAL cores (clients ride the same
        # box); a 1-cpu CI box records ~1.0 by construction and skips
        # the ratio assert with the reason above.
        "cpus": cpus,
        "tiers": tiers,
    }


def bench_bulk() -> dict:
    """BENCH_CONFIG=bulk: the device-build bulk door vs the PR-10
    streamed ingest door on the SAME seeded data, over HTTP against a
    numpy-engine server.

    Three in-run contracts (assertions, not just numbers):
    - THROUGHPUT: the bulk build commits >= BENCH_BULK_MIN_X (default
      5) times the pairs/s of the streamed set_bits door — the whole
      point of packing planes with the sort/segment/scatter kernel and
      deferring roaring materialization.
    - DIFFERENTIAL: the bulk-built frame is digest-identical to the
      streamed frame, slice by slice (materialization happens under the
      checksum touch — the lazy ledger is part of what's being proven).
    - ROUND TRIP: Arrow egress of the bulk frame re-ingested through
      the bulk door re-exports byte-identical per slice.
    """
    import tempfile
    import zlib as _zlib

    from pilosa_tpu.config import Config
    from pilosa_tpu.server.client import Client
    from pilosa_tpu.server.server import Server

    # BENCH_SMOKE=1: tiny shape, throughput gate off — smoke proves the
    # chunk wire + digest parity + arrow round trip, not perf (fixed
    # per-request overheads swamp a 100k-pair run).
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    n_pairs = int(
        os.environ.get("BENCH_BULK_PAIRS", "100000" if smoke else "1000000")
    )
    n_rows = int(os.environ.get("BENCH_BULK_ROWS", "64"))
    n_slices = int(os.environ.get("BENCH_BULK_SLICES", "4"))
    min_x = float(
        os.environ.get("BENCH_BULK_MIN_X", "0" if smoke else "5")
    )
    rng = np.random.default_rng(18)
    rows = rng.integers(0, n_rows, size=n_pairs).astype(np.uint64)
    cols = rng.integers(0, n_slices << 20, size=n_pairs).astype(np.uint64)

    with tempfile.TemporaryDirectory() as d:
        cfg = Config(
            data_dir=d, host="127.0.0.1:0", engine="numpy", stats="expvar",
            qcache_enabled=False,
        )
        srv = Server(cfg)
        srv.open()
        try:
            client = Client(srv.host)
            client.create_index("x")
            for fr in ("s", "b", "r"):
                client.create_frame("x", fr)

            t0 = time.perf_counter()
            res = client.ingest_stream("x", "s", rows, cols, chunk_pairs=65536)
            stream_dt = time.perf_counter() - t0
            assert res["done"], "streamed ingest did not complete"

            t0 = time.perf_counter()
            res = client.bulk_stream("x", "b", rows, cols, chunk_pairs=65536)
            bulk_dt = time.perf_counter() - t0
            assert res["done"], "bulk build did not complete"

            stream_rate = n_pairs / stream_dt
            bulk_rate = n_pairs / bulk_dt
            ratio = bulk_rate / stream_rate
            assert ratio >= min_x, (
                f"bulk build only {ratio:.2f}x the streamed door "
                f"({bulk_rate:,.0f} vs {stream_rate:,.0f} pairs/s); "
                f"need >= {min_x}x"
            )

            # Differential: digest-identical frames, slice by slice.
            # The checksum touch materializes the bulk frame's overlay
            # through the lazy ledger — the contract under test.
            idx = srv.holder.index("x")
            for s in range(n_slices):
                fs = idx.frame("s").view("standard").fragment(s)
                fb = idx.frame("b").view("standard").fragment(s)
                assert fs is not None and fb is not None, f"slice {s} missing"
                assert fs.checksum() == fb.checksum(), (
                    f"bulk-built slice {s} diverged from streamed"
                )

            # Round trip: Arrow egress -> bulk re-ingest -> re-export,
            # byte-identical per slice (deterministic batch framing).
            rt_bytes = 0
            for s in range(n_slices):
                a = client.export_arrow("x", "b", "standard", s)
                crc = _zlib.crc32(a)
                status, out = client.ingest_chunk(
                    "x", "r", 0, len(a), crc, a, ccrc=crc,
                    door="bulk", arrow=True,
                )
                assert status == 200 and out.get("done"), (
                    f"arrow re-ingest of slice {s} failed: {status} {out}"
                )
                rt_bytes += len(a)
            for s in range(n_slices):
                a = client.export_arrow("x", "b", "standard", s)
                b = client.export_arrow("x", "r", "standard", s)
                assert a == b, f"arrow round trip of slice {s} not byte-identical"
        finally:
            srv.close()

    return {
        "metric": "bulk_build_vs_streamed_ingest",
        "value": round(ratio, 2),
        "unit": (
            f"x pairs/s vs /ingest ({bulk_rate:,.0f} vs "
            f"{stream_rate:,.0f} pairs/s over {n_pairs:,} pairs x "
            f"{n_rows} rows x {n_slices} slices; digest-equal; arrow "
            f"round trip {rt_bytes:,} bytes byte-identical)"
        ),
        "tiers": {
            "bulk_pairs_per_s": round(bulk_rate, 1),
            "stream_pairs_per_s": round(stream_rate, 1),
            "bulk_vs_stream": round(ratio, 2),
            "digest_equal": True,
            "arrow_roundtrip_bytes": rt_bytes,
        },
    }


def main() -> None:
    from pilosa_tpu.engine import configure_compile_cache

    configure_compile_cache()  # before the first jit
    cfg = os.environ.get("BENCH_CONFIG", "intersect_count")
    if cfg != "intersect_count":
        result = {
            "setbit": bench_setbit,
            "lockstep": bench_lockstep,
            "lockstep_coalesce": bench_lockstep_coalesce,
            "topn": bench_topn,
            "union64": bench_union64,
            "timerange": bench_timerange,
            "executor": bench_executor,
            "executor_gather": bench_executor_gather,
            "range_executor": bench_range_executor,
            "mixed": bench_mixed,
            "writelane": bench_writelane,
            "overload": bench_overload,
            "tenancy": bench_tenancy,
            "qcache": bench_qcache,
            "replica": bench_replica,
            "multicore": bench_multicore,
            "recovery": bench_recovery,
            "resync": bench_resync,
            "bulk": bench_bulk,
            "shard": bench_shard,
            "intersect_count_stream": bench_intersect_stream,
            "intersect_count_4krows": bench_intersect_4krows,
            "topn_p50": bench_topn_p50,
        }[cfg]()
        print(json.dumps(result))
        return
    n_slices = int(os.environ.get("BENCH_SLICES", "16"))
    n_rows = int(os.environ.get("BENCH_ROWS", "64"))
    batch = int(os.environ.get("BENCH_BATCH", "256"))
    # Shapes past device memory switch to the slice-streaming executor
    # regime — the same decision the product mapReduce makes.  The
    # resident ceiling is the matrix itself (~14 GB usable of 15.75 GB
    # HBM): since round 3 the kernels take the matrix in its born-tiled
    # 4D form, so XLA no longer materializes a relayout copy that used to
    # double the footprint (the round-2 1024-slice OOM).
    from pilosa_tpu.ops.bitwise import WORDS_PER_SLICE as _W

    resident_max = int(os.environ.get("BENCH_RESIDENT_MAX", str(14 << 30)))
    if n_slices * n_rows * _W * 4 > resident_max:
        print(json.dumps(bench_intersect_stream()))
        return
    # Long enough that the one-dispatch stream's fixed costs (dispatch,
    # digest fetch, the hoisted Gram build) amortize: with the Gram
    # strategy a batch step is a few table lookups, so a sustained-rate
    # measurement needs a LONG stream.  The length was chosen on an
    # earlier rig; not measured on this chip.
    iters = int(os.environ.get("BENCH_ITERS", "262144"))
    # Bit density ~2^-k via AND of k random words (throughput over packed
    # words is density-independent; this just keeps counts realistic).
    density_k = int(os.environ.get("BENCH_DENSITY_K", "4"))

    from pilosa_tpu.ops.bitwise import WORDS_PER_SLICE

    W = WORDS_PER_SLICE  # 32768 words = 2^20 bits per slice-row
    rng = np.random.default_rng(42)

    # ---- TPU path -------------------------------------------------------
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pilosa_tpu.ops import dispatch

    # Billion-column matrices are generated ON DEVICE (data is set-up,
    # not what is measured).  Small shapes keep the host path so the full
    # numpy baseline and whole-stream correctness gate apply.
    hostgen_max = int(os.environ.get("BENCH_HOSTGEN_MAX", str(1 << 30)))
    device_gen = n_slices * n_rows * W * 4 > hostgen_max

    from pilosa_tpu.ops import bitwise as _bw
    from pilosa_tpu.ops.dispatch import _use_gram

    gram_mode = _use_gram(n_slices, n_rows, W, batch)

    @jax.jit
    def run_stream_gram(g, pairs_stream):
        # Gram strategy with the build hoisted EXPLICITLY: at big slice
        # counts the chunked Gram build is itself a while loop, which XLA
        # does not hoist out of the query scan (it would rebuild the Gram
        # every step) — so the bench mirrors the product executor: build
        # once (run_gram_build below), stream lookups against it.
        def step(carry, prs):
            return carry, _bw.gram_pair_counts("and", g, prs)

        out = lax.scan(step, 0, pairs_stream)[1]
        return out, out.astype(jnp.int64).sum()

    @jax.jit
    def run_stream(rm, pairs_stream):
        def step(carry, prs):
            return carry, dispatch.gather_count("and", rm, prs, allow_gram=False)

        out = lax.scan(step, 0, pairs_stream)[1]
        # Digest depends on EVERY step: fetching it synchronizes on the
        # whole stream while the full per-query results stay materialized
        # in HBM (a returned output — XLA cannot elide it).
        return out, out.astype(jnp.int64).sum()


    if device_gen:
        @jax.jit
        def gen_matrix(key):
            rm = jax.random.bits(key, (n_slices, n_rows, W // 128, 128), jnp.uint32)
            for i in range(density_k - 1):
                rm &= jax.random.bits(
                    jax.random.fold_in(key, i + 1),
                    (n_slices, n_rows, W // 128, 128),
                    jnp.uint32,
                )
            return rm

        drm = gen_matrix(jax.random.PRNGKey(42))
        # Host mirror of the FIRST slice only (for the correctness gate).
        row_matrix = np.asarray(drm[:1]).reshape(1, n_rows, W)
    else:
        row_matrix = rng.integers(0, 1 << 32, size=(n_slices, n_rows, W), dtype=np.uint32)
        for _ in range(density_k - 1):
            row_matrix &= rng.integers(
                0, 1 << 32, size=(n_slices, n_rows, W), dtype=np.uint32
            )
        # Born-tiled 4D device form: no relayout copy inside jit.
        drm = jax.device_put(row_matrix.reshape(n_slices, n_rows, W // 128, 128))
    # Pair stream generated on device (the host array would be
    # iters*batch*8 bytes — half a GB at the default length); the
    # correctness gate fetches only the rows it needs.
    @jax.jit
    def gen_pairs(key):
        return jax.random.randint(key, (iters, batch, 2), 0, n_rows, jnp.int32)

    dpairs = gen_pairs(jax.random.PRNGKey(7))
    all_pairs = np.asarray(dpairs[: max(1, min(3, iters))])  # gate mirror
    if gram_mode:
        # Build once, like the product executor's cached Gram; steady
        # state streams lookups against the device-resident [R, R].
        dgram = jax.jit(_bw.pair_gram)(drm)
        t0 = time.perf_counter()
        np.asarray(jax.jit(_bw.pair_gram)(drm).sum())  # timed rebuild
        gram_build_s = time.perf_counter() - t0
        launch = lambda: run_stream_gram(dgram, dpairs)
    else:
        gram_build_s = 0.0
        launch = lambda: run_stream(drm, dpairs)
    # Warmup compiles and runs the full stream once.
    out_dev, _ = launch()
    out = np.asarray(out_dev[: len(all_pairs)])

    # Timed region: dispatch the stream and fetch the 8-byte digest.  The
    # digest is data-dependent on all iters*batch per-query results, so
    # timing stops only when the device has computed and materialized
    # every result in HBM.  The full result tensor is deliberately NOT
    # fetched inside the timer; it stays on the device, where a server
    # would stream it to clients from.
    #
    # Best of N timed runs (min wall time) — a choice made on an earlier
    # rig; medians with their spread are ROADMAP S1.
    def timed():
        out_d, digest = launch()
        np.asarray(digest)
        return out_d

    dt, out_dev = _best_of_runs(timed)
    qps = iters * batch / dt
    # Post-timing fetch for the correctness gate: only the gated prefix
    # (the full tensor is ~270 MB at the default stream length — bytes
    # the gate never looks at).
    out = np.asarray(out_dev[: max(1, min(3, iters))])

    # ---- CPU numpy baseline (single-threaded popcount loop) -------------
    from pilosa_tpu.roaring import _POPCNT8

    base_slices = row_matrix.shape[0]  # all slices, or 1 when device_gen

    def numpy_batch(i):
        p = all_pairs[i]
        a = row_matrix[:, p[:, 0], :]
        b = row_matrix[:, p[:, 1], :]
        inter = a & b
        return _POPCNT8[inter.view(np.uint8)].reshape(base_slices, batch, -1).sum(axis=(0, 2))

    base_iters = max(1, min(3, iters))
    numpy_batch(0)  # warm: first-touch page faults + LUT cache
    t0 = time.perf_counter()
    base_out = None
    for i in range(base_iters):
        base_out = numpy_batch(i)
    base_dt = time.perf_counter() - t0
    # Extrapolate the single-slice host mirror to the full slice count
    # (the numpy loop is linear in slices; device_gen shapes would need
    # hours of LUT work for an exact all-slice baseline).
    base_qps = base_iters * batch / (base_dt * n_slices / base_slices)
    if device_gen:
        # Gate against the slice-0 mirror: same pairs, device counts
        # restricted to slice 0 must equal the numpy counts.
        gate = np.asarray(
            dispatch.gather_count("and", drm[:1], jnp.asarray(all_pairs[base_iters - 1]),
                                  allow_gram=False)
        )
        assert np.array_equal(gate, base_out), "TPU/CPU result mismatch (slice 0)"
        if gram_mode:
            # And the Gram lookups must equal the direct kernel over the
            # FULL matrix (the all-slice ground truth numpy can't afford).
            kq = np.asarray(
                dispatch.gather_count(
                    "and", drm, jnp.asarray(all_pairs[0]), allow_gram=False
                )
            )
            assert np.array_equal(out[0], kq), "gram/kernel mismatch"
    else:
        assert np.array_equal(out[base_iters - 1], base_out), "TPU/CPU result mismatch"

    unit = f"queries/sec ({n_slices} slices x 2^20 cols, batch {batch}"
    if gram_mode and gram_build_s > 0.01:
        unit += f", one-time chunked Gram build {gram_build_s:.2f}s"
    unit += f", backend {jax.default_backend()})"
    # Headline denominator: the measured compiled reference loop (one
    # core), not the numpy stand-in — see module docstring.  A reference
    # pair count at this shape streams both operands once:
    # 2 * n_slices * 128 KiB per query through the AND+POPCNT loop.
    ref_bps = _ref_loop_bytes_per_s()
    ref_qps = ref_bps / (2.0 * n_slices * W * 4)
    result = {
        "metric": "intersect_count_qps",
        "value": round(qps, 1),
        "unit": unit,
        "vs_baseline": round(qps / ref_qps, 2),
        "vs_numpy": round(qps / base_qps, 2),
        "ref_loop_qps_1core": round(ref_qps, 1),
        "ref_loop_measured": getattr(_ref_loop_bytes_per_s, "_measured", False),
    }
    # HBM-bandwidth accounting is only meaningful when the strategy
    # actually MOVES the bitmaps per batch: with the Gram shortcut active
    # each query is a table lookup, so bandwidth_util is reported null
    # (the honest answer — see BASELINE.md's strategy ablation).
    # The resident-vs-gather split mirrors dispatch's ACTUAL strategy
    # predicate (resident_strategy includes the VMEM-fit clause, not just
    # the row/batch ratio) so the traffic formula matches the kernel that
    # ran.
    from pilosa_tpu.ops.pallas_kernels import resident_strategy as _resident

    if not gram_mode:
        if _resident(n_rows, W, batch):  # resident: whole row set per batch
            bytes_moved = iters * n_slices * n_rows * W * 4
        else:  # gather kernel: two operand rows per (query, slice)
            bytes_moved = iters * batch * 2 * n_slices * W * 4
        result["bandwidth_util"] = _bandwidth_util(bytes_moved / dt)
    else:
        result["bandwidth_util"] = None

    # ---- tier scoreboard ------------------------------------------------
    # One flattering scalar is not a scoreboard (VERDICT r3 item 5): the
    # driver artifact carries every serving tier with its own util so
    # round-over-round numbers stay comparable regardless of which lane
    # is fastest that day.  Tiers run on the DRIVER's default invocation
    # (no shape env overrides) — big-shape runs via run_big_benches.sh
    # must not leak their BENCH_SLICES/ROWS/ITERS into the 4k-row tier
    # shapes (a 1024-slice x 4096-row tier matrix would be ~0.5 TB).
    # BENCH_TIERS=1/0 forces either way.
    tiers_on = os.environ.get(
        "BENCH_TIERS",
        "0" if any(
            os.environ.get(k) for k in ("BENCH_SLICES", "BENCH_ROWS", "BENCH_ITERS")
        ) else "1",
    ) not in ("0", "false", "no")
    if tiers_on:
        # Label by what actually served the headline: the dispatch
        # strategy predicate mirrors the bandwidth accounting above
        # (NO_GRAM tall-row shapes run the gather kernel, not resident).
        if gram_mode:
            head_tier = "gram"
            head_note = "all-pairs MXU Gram, host/table lookup serving (no per-query bitmap traffic)"
        elif _resident(n_rows, W, batch):
            head_tier = "resident_nogram"
            head_note = "direct resident kernel headline (PILOSA_TPU_NO_GRAM)"
        else:
            head_tier = "gather_nogram"
            head_note = "direct gather kernel headline (PILOSA_TPU_NO_GRAM)"
        tiers = [{
            "tier": head_tier,
            "qps": result["value"],
            "bandwidth_util": result["bandwidth_util"],
            "note": head_note,
        }]
        iters_t = max(1, min(iters, int(os.environ.get("BENCH_TIER_ITERS", "2048"))))
        if gram_mode:
            # Resident/no-Gram tier: the direct kernel on the SAME shape.
            dp_t = dpairs[:iters_t]
            out_t, _ = run_stream(drm, dp_t)  # compile + warm
            def timed_t():
                out_d, digest = run_stream(drm, dp_t)
                np.asarray(digest)
                return out_d
            dt_t, out_t = _best_of_runs(timed_t)
            if _resident(n_rows, W, batch):
                moved = iters_t * n_slices * n_rows * W * 4
            else:
                moved = iters_t * batch * 2 * n_slices * W * 4
            tiers.append({
                "tier": "resident_nogram",
                "qps": round(iters_t * batch / dt_t, 1),
                "bandwidth_util": _bandwidth_util(moved / dt_t),
            })
        # 4k-row gather tiers: the Gram-ineligible tall-row-set shape, in
        # both kernel layouts (row-major = the descriptor-rate record).
        t4 = bench_intersect_4krows()
        tiers.append({
            "tier": "gather_4krows_rowmajor",
            "qps": t4["value"],
            "bandwidth_util": t4.get("bandwidth_util"),
        })
        s4 = int(os.environ.get("BENCH_SLICES", "4"))
        r4 = int(os.environ.get("BENCH_ROWS", "4096"))
        b4 = batch
        it4 = max(1, min(iters_t, int(os.environ.get("BENCH_ITERS", "256"))))
        @jax.jit
        def gen_sm(key):
            return jax.random.bits(key, (s4, r4, W // 128, 128), jnp.uint32)
        dsm = gen_sm(jax.random.PRNGKey(43))
        p4 = jax.device_put(
            np.random.default_rng(9).integers(0, r4, size=(it4, b4, 2), dtype=np.int32)
        )
        @jax.jit
        def run_sm(rm, ps):
            def step(carry, prs):
                return carry, dispatch.gather_count("and", rm, prs, allow_gram=False)
            out2 = lax.scan(step, 0, ps)[1]
            return out2, out2.astype(jnp.int64).sum()
        run_sm(dsm, p4)  # compile + warm
        def timed_sm():
            out_d, digest = run_sm(dsm, p4)
            np.asarray(digest)
            return out_d
        dt_sm, _ = _best_of_runs(timed_sm)
        moved_sm = it4 * b4 * 2 * s4 * W * 4
        tiers.append({
            "tier": "gather_4krows_slicemajor",
            "qps": round(it4 * b4 / dt_sm, 1),
            "bandwidth_util": _bandwidth_util(moved_sm / dt_sm),
        })
        result["tiers"] = tiers
    print(json.dumps(result))


if __name__ == "__main__":
    main()
