#!/usr/bin/env python3
"""The one on-chip entry point: serve a real index from the attached TPU
and check every answer, then run every kernel against numpy.

    python chip_smoke.py                 # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4       # only the four-chip mesh deployment
    python chip_smoke.py --rehearse      # tiny sizes, CPU allowed (a rehearsal,
                                         # reported as platform "cpu")

*Served phase.*  Without importing jax, the script rebuilds the native
library from source, starts ``python -m pilosa_tpu.cli server`` as its one
child, loads a 64-slice index (67,108,864 columns) through the doors users
load through (``POST .../bulk`` and ``POST /import``), sends each query
shape over HTTP, and compares every answer with a numpy oracle computed
here from the same seeded (row, column) lists, independent of
``pilosa_tpu``.  The device named in the last line is what the *server*
reported in ``GET /status``.  A second start of the server on the same data
answers the first query again, to show what the compile cache saves.

*Kernels phase.*  After the child has exited and released the chip, every
Pallas kernel and strategy tier runs in this process against numpy at
W = 32768, followed by the generated differential cases of
``pilosa_tpu.ops.diffcheck``.

Every line of standard output is one JSON object.  The last one is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
and nothing more; anything that fails ends the run with ``{"ok": false,
...}`` and a non-zero exit code.  A chip belongs to one process: the
child is started before this process imports jax, and this process touches
jax only after the child has exited.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import time
from datetime import datetime, timedelta, timezone

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SLICE_WIDTH = 1 << 20  # the row width never changes: W = 32768 uint32 words
INDEX = "smoke"
GIB = 1 << 30

# The deployment (ISSUE 21, Tentpole 1) and its rehearsal-sized twin.
FULL = dict(
    slices=64,
    a_rows=256, a_pool=4096, a_bits=96,     # Gram-eligible: one default row pool (2 GiB)
    b_rows=8192, b_pool=64, b_bits=3,       # tall: above gram_rows_max, pages the pool
    b_batch_rows=320,                       # > 256-slot pool => two parts, evictions
    c_rows=16, c_pool=1024, c_bits=16,      # YMDH time-quantum frame
    c_days=3, c_hours=(3, 9, 15, 21),
    min_peak_bytes=2 * GIB,
    kernels=dict(S=4, R=96, W=32768, B=64, K=4, fuzz_cases=8),
)
REHEARSE = dict(
    slices=2,
    a_rows=16, a_pool=512, a_bits=40,
    b_rows=64, b_pool=32, b_bits=3,
    b_batch_rows=24,
    c_rows=4, c_pool=128, c_bits=8,
    c_days=2, c_hours=(3, 15),
    min_peak_bytes=0,
    kernels=dict(S=2, R=8, W=2048, B=8, K=4, fuzz_cases=1),
)
T0 = datetime(2017, 3, 1, tzinfo=timezone.utc)


class SmokeFailure(Exception):
    """A phase failed; the run ends ``ok: false``."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- seeded data and the numpy oracle ---------------------------------------


def _windows(rng, n_slices, n_rows, pool, bits_of_row, offsets=None):
    """Sparse rows that still intersect: per slice, a shuffled pool of
    ``pool`` local columns; row r takes a window of ``bits_of_row[r]``
    consecutive pool entries from a random offset, so two rows share bits
    where their windows overlap.  Returns (rows, cols), sorted by (slice,
    row, col) as ``pilosa-tpu sort`` would leave an import file."""
    bits_of_row = np.asarray(bits_of_row, dtype=np.int64)
    kmax = int(bits_of_row.max())
    rows_out, cols_out = [], []
    for s in range(n_slices):
        local = rng.choice(SLICE_WIDTH, size=pool, replace=False)
        off = rng.integers(0, pool, size=n_rows) if offsets is None else offsets(rng, s)
        j = np.arange(kmax)[None, :]
        take = j < bits_of_row[:, None]
        picked = local[(off[:, None] + j) % pool]
        r = np.broadcast_to(np.arange(n_rows)[:, None], picked.shape)[take]
        c = picked[take] + s * SLICE_WIDTH
        order = np.lexsort((c, r))
        rows_out.append(r[order])
        cols_out.append(c[order])
    return (np.concatenate(rows_out).astype(np.uint64),
            np.concatenate(cols_out).astype(np.uint64))


def make_data(seed: int, z: dict) -> dict:
    rng = np.random.default_rng(seed)
    s = z["slices"]
    # (a) rows 1..7 are prefixes of row 0's window, so TopN(Bitmap(0)) has
    # one right answer in every slice; the other rows fall where they fall.
    a_bits = z["a_bits"] + (np.arange(z["a_rows"]) * 7) % 32
    a_bits[:8] = z["a_bits"] + 96 - 8 * np.arange(8)

    def a_offsets(rng, _s):
        off = rng.integers(0, z["a_pool"], size=z["a_rows"])
        off[:8] = off[0]
        return off

    a = _windows(rng, s, z["a_rows"], z["a_pool"], a_bits, a_offsets)
    b = _windows(rng, s, z["b_rows"], z["b_pool"], np.full(z["b_rows"], z["b_bits"]))
    c_rows, c_cols = _windows(
        rng, s, z["c_rows"], z["c_pool"], np.full(z["c_rows"], z["c_bits"]))
    hours = np.array([d * 24 + h for d in range(z["c_days"]) for h in z["c_hours"]])
    c_ts = int(T0.timestamp()) + 3600 * rng.choice(hours, size=len(c_rows)) + 60
    return {"a": a, "b": b, "c": (c_rows, c_cols), "c_ts": c_ts}


class Oracle:
    """Plain numpy sets of columns per (frame, row): the reference every
    served answer is compared with."""

    def __init__(self, data: dict):
        self.rows: dict = {}
        for frame in ("a", "b", "c"):
            rows, cols = data[frame]
            order = np.argsort(rows, kind="stable")
            self.rows[frame] = (rows[order], cols[order])
        self.c_ts = data["c_ts"][np.argsort(data["c"][0], kind="stable")]
        self.edits: dict = {}

    def cols(self, frame: str, row: int) -> np.ndarray:
        if (frame, row) in self.edits:
            return self.edits[(frame, row)]
        rows, cols = self.rows[frame]
        lo, hi = np.searchsorted(rows, [row, row + 1])
        return np.unique(cols[lo:hi])

    def set_bit(self, frame: str, row: int, col: int, on: bool) -> None:
        cur = self.cols(frame, row)
        fn = np.union1d if on else np.setdiff1d
        self.edits[(frame, row)] = fn(cur, np.array([col], dtype=cur.dtype))

    def eval(self, frame: str, tree) -> np.ndarray:
        """tree: a row id, or (op, left, right) with op one of
        Intersect/Union/Difference/Xor."""
        if not isinstance(tree, tuple):
            return self.cols(frame, tree)
        fn = {"Intersect": np.intersect1d, "Union": np.union1d,
              "Difference": np.setdiff1d, "Xor": np.setxor1d}[tree[0]]
        return fn(self.eval(frame, tree[1]), self.eval(frame, tree[2]))

    def range_count(self, row: int, start: datetime, end: datetime) -> int:
        rows, cols = self.rows["c"]
        lo, hi = np.searchsorted(rows, [row, row + 1])
        ts = self.c_ts[lo:hi]
        keep = (ts >= start.timestamp()) & (ts < end.timestamp())
        return int(len(np.unique(cols[lo:hi][keep])))

    def row_counts(self, frame: str) -> np.ndarray:
        return np.bincount(self.rows[frame][0].astype(np.int64))


def pql(frame: str, tree) -> str:
    if not isinstance(tree, tuple):
        return f'Bitmap(rowID={tree}, frame="{frame}")'
    return f"{tree[0]}({pql(frame, tree[1])}, {pql(frame, tree[2])})"


# -- the server child -------------------------------------------------------


class ServerChild:
    """``python -m pilosa_tpu.cli server`` on an ephemeral port; its output
    goes to files under the output directory.  A context manager: however
    the block ends, the child is not left running."""

    def __init__(self, tag: str, out_dir: str, data_dir: str, env: dict):
        self.out_path = os.path.join(out_dir, f"server_{tag}.out")
        self.err_path = os.path.join(out_dir, f"server_{tag}.err")
        self._out = open(self.out_path, "wb")
        self._err = open(self.err_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.cli", "server",
             "--data-dir", data_dir, "--host", "127.0.0.1:0"],
            stdout=self._out, stderr=self._err, cwd=ROOT, env=env,
        )

    def __enter__(self) -> "ServerChild":
        return self

    def __exit__(self, *exc) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._out.close()
        self._err.close()

    def wait_ready(self, timeout: float) -> str:
        """Block until the startup line; returns host:port."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with open(self.out_path, "rb") as f:
                m = re.search(rb"serving on http://(\S+)", f.read())
            if m:
                return m.group(1).decode()
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited with code {self.proc.returncode} before "
                    f"serving: {self.err_tail()}")
            time.sleep(0.2)
        raise SmokeFailure(f"server not ready after {timeout:.0f} s: {self.err_tail()}")

    def err_tail(self, n: int = 1500) -> str:
        with open(self.err_path, "rb") as f:
            return f.read()[-n:].decode(errors="replace")

    def err_size(self) -> int:
        return os.path.getsize(self.err_path)

    def compile_log(self, start: int) -> dict:
        """What jax logged (JAX_LOG_COMPILES) since byte ``start`` of the
        child's stderr: compilations, their seconds, persistent-cache hits."""
        with open(self.err_path, "rb") as f:
            f.seek(start)
            text = f.read().decode(errors="replace")
        secs = [float(x) for x in re.findall(
            r"Finished XLA compilation of .* in ([0-9.]+) sec", text)]
        return {"compilations": len(secs), "compile_seconds": round(sum(secs), 3),
                "cache_hits": len(re.findall(r"Persistent compilation cache hit", text))}

    def kernels_compiled(self) -> list:
        """The named device programs the child compiled (Pallas kernels,
        the Gram, the bulk pack kernel, the mesh tier's shard_map bodies):
        which device lanes really answered."""
        with open(self.err_path, "rb") as f:
            names = set(re.findall(rb"Compiling jit\((\w+)\)", f.read()))
        return sorted(n.decode() for n in names
                      if n.startswith((b"fused_", b"pair_gram", b"pack", b"kernel",
                                       b"gather_count")))

    def stop(self, timeout: float = 300.0) -> int:
        """SIGTERM, wait; the exit code (a child that will not stop is
        killed and reported)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                raise SmokeFailure(f"server ignored SIGTERM for {timeout:.0f} s")
        return self.proc.returncode


def build_native(clean: bool) -> float:
    """Build native/libpilosa_native.so from the committed source.  A
    library that travelled here from another machine (``-march=native``)
    is cleared first; the rehearsal, on the machine that built it, only
    brings it up to date."""
    t0 = time.monotonic()
    steps = ([["make", "-C", "native", "clean"]] if clean else []) + [["make", "-C", "native"]]
    for cmd in steps:
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        check(p.returncode == 0, f"{' '.join(cmd)} failed: {p.stderr[-800:]}")
    return round(time.monotonic() - t0, 1)


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# -- the served phase -------------------------------------------------------


class Served:
    """One run of the served phase against one engine ("jax" on one chip,
    "mesh" on four)."""

    def __init__(self, args, z: dict, out_dir: str, data_dir: str):
        self.args, self.z = args, z
        self.out_dir, self.data_dir = out_dir, data_dir
        self.engine = "mesh" if args.chips == 4 else "jax"
        self.lanes: dict = {}
        self.n_checked = 0
        self.client = None
        self.oracle = None

    def child_env(self) -> dict:
        env = dict(os.environ)
        env["JAX_LOG_COMPILES"] = "1"  # compile seconds, read from its stderr
        if self.engine == "mesh":
            env["PILOSA_ENGINE"] = "mesh"
        if self.args.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={self.args.chips}"
            # A row pool that frame (a) fills exactly, as 2 GiB does at 64
            # slices, so that the rehearsal pages (b)'s batch too.
            env["PILOSA_TPU_POOL_BYTES"] = str(
                self.z["slices"] * self.z["a_rows"] * (SLICE_WIDTH // 8))
            if self.engine == "mesh":
                # The mesh tier's kernels in interpret mode, as
                # tests/test_parallel.py runs them on virtual devices.
                env["PILOSA_TPU_PALLAS_INTERPRET"] = "1"
        return env

    # .. requests ..

    def query(self, body: str, tag: str) -> list:
        """One HTTP query request (protobuf, as the Python client sends
        it), traced so the answering lane is known."""
        from pilosa_tpu.trace import Span

        span = Span("chip_smoke")
        resp = self.client.execute_query(INDEX, body, trace_span=span, timeout=900)
        seen = self.lanes.setdefault(tag, set())

        def walk(node):
            tags = node.get("tags") or {}
            name = str(node.get("name", ""))
            if name == "device":
                seen.add(f"device={tags.get('lane')}")
            elif tags.get("lane"):
                seen.add(f"lane={tags['lane']}")
            if name.startswith("call."):
                seen.add(name)
            if tags.get("qcache") == "hit":
                seen.add("qcache=hit")
            for ch in node.get("children", []):
                if isinstance(ch, dict):
                    walk(ch)

        for ch in span.children:
            if isinstance(ch, dict):
                walk(ch)
        return resp["results"]

    def counts(self, frame: str, trees: list, tag: str) -> None:
        """Count(tree) for each tree in ONE request body; compare all."""
        body = " ".join(f"Count({pql(frame, t)})" for t in trees)
        got = [int(r.get("n", 0)) for r in self.query(body, tag)]
        want = [int(len(self.oracle.eval(frame, t))) for t in trees]
        check(got == want, f"{tag}: served {got[:8]}... != oracle {want[:8]}... "
              f"({sum(g != w for g, w in zip(got, want))} of {len(want)} differ)")
        self.n_checked += len(trees)

    def topn(self, body: str, want_counts: dict, n: int, tag: str) -> None:
        """TopN: the served pairs must carry the oracle's count for their
        id, in the oracle's descending order of counts (ids may swap
        inside a tie)."""
        pairs = self.query(body, tag)[0].get("pairs", [])
        got = [(int(p["id"]), int(p.get("count", 0))) for p in pairs]
        best = sorted(want_counts.values(), reverse=True)[:n]
        check([c for _, c in got] == best,
              f"{tag}: served counts {[c for _, c in got]} != oracle {best}")
        check(all(want_counts.get(i) == c for i, c in got),
              f"{tag}: a served (id, count) pair disagrees with the oracle: {got}")
        self.n_checked += 1

    # .. phases ..

    def start(self, tag: str) -> ServerChild:
        # One process on the chip at a time: the first child starts before
        # this process has imported jax at all; by the second, the client
        # module has imported it, and what must still hold is that no
        # backend was ever initialized here (that is what takes the chip).
        if "jax" in sys.modules:
            from jax._src import xla_bridge

            check(not xla_bridge.backends_are_initialized(),
                  "this process initialized a jax backend before starting the server child")
        return ServerChild(tag, self.out_dir, self.data_dir, self.child_env())

    def device_checks(self, dev: dict) -> None:
        want_platform = "cpu" if self.args.rehearse else "tpu"
        check(dev.get("platform") == want_platform,
              f"server reports platform {dev.get('platform')!r}, need {want_platform!r}")
        check(dev.get("engine") == self.engine,
              f"server reports engine {dev.get('engine')!r}, need {self.engine!r}")
        check(dev.get("native") is True, "server reports native: false")
        check(dev.get("count") == self.args.chips,
              f"server reports {dev.get('count')} devices, need {self.args.chips}")

    def load(self, data: dict) -> None:
        cl = self.client
        cl.create_index(INDEX)
        cl.create_frame(INDEX, "a")
        cl.create_frame(INDEX, "b")
        cl.create_frame(INDEX, "c", {"timeQuantum": "YMDH"})
        t0 = time.monotonic()
        rows, cols = data["a"]
        cl.bulk_stream(INDEX, "a", rows, cols)  # POST .../bulk: the device build kernel
        t1 = time.monotonic()
        rows, cols = data["b"]
        cl.import_bits(INDEX, "b", list(zip(rows.tolist(), cols.tolist())))  # POST /import
        t2 = time.monotonic()
        rows, cols = data["c"]
        cl.import_bits(INDEX, "c", list(zip(rows.tolist(), cols.tolist(),
                                            data["c_ts"].tolist())))
        t3 = time.monotonic()
        emit({"phase": "load", "ok": True, "slices": self.z["slices"],
              "columns": self.z["slices"] * SLICE_WIDTH,
              "frames": {
                  "a": {"door": "bulk", "rows": self.z["a_rows"],
                        "bits": int(len(data["a"][0])), "seconds": round(t1 - t0, 1)},
                  "b": {"door": "import", "rows": self.z["b_rows"],
                        "bits": int(len(data["b"][0])), "seconds": round(t2 - t1, 1)},
                  "c": {"door": "import", "rows": self.z["c_rows"], "timeQuantum": "YMDH",
                        "bits": int(len(data["c"][0])), "seconds": round(t3 - t2, 1)}}})

    def queries(self) -> None:
        z, rng = self.z, np.random.default_rng(self.args.seed + 1)
        ops = ("Intersect", "Union", "Difference", "Xor")

        # (a) singly, then as one fused body that touches every row (the
        # pool fills to its full 2 GiB), then twice more: the second hit
        # builds the Gram, the third is served from it.
        for op in ops[1:]:
            self.counts("a", [(op, 1, 2)], f"a.single.{op}")
        perm = rng.permutation(z["a_rows"])
        half = z["a_rows"] // 2
        for k, rep in enumerate(("cold", "gram_build", "gram_warm")):
            # The same rows paired anew each time: an identical body would
            # be answered by the query cache and never reach the device.
            self.counts("a", [(ops[i % 4], int(perm[i]), int(perm[half + (i + k) % half]))
                              for i in range(half)], f"a.batch.{rep}")
        # Depth-3 nested trees.
        self.counts("a", [
            ("Intersect", ("Union", 1, 2), ("Difference", 3, ("Xor", 4, 5))),
            ("Union", ("Intersect", 0, 9), ("Xor", ("Difference", 10, 11), 12)),
        ], "a.tree3")
        # TopN, plain (rank cache) and filtered by a source row (scorers).
        counts = {r: int(c) for r, c in enumerate(self.oracle.row_counts("a")) if c}
        self.topn('TopN(frame="a", n=5)', counts, 5, "a.topn")
        src = self.oracle.cols("a", 0)
        inter = {r: int(len(np.intersect1d(self.oracle.cols("a", r), src)))
                 for r in range(z["a_rows"])}
        self.topn('TopN(Bitmap(rowID=0, frame="a"), frame="a", n=5)',
                  {r: c for r, c in inter.items() if c}, 5, "a.topn_src")
        # A Bitmap whose bits are compared exactly.
        bits = self.query(pql("a", 5), "a.bitmap")[0].get("bitmap", {}).get("bits", [])
        check(np.array_equal(np.asarray(bits, dtype=np.uint64), self.oracle.cols("a", 5)),
              "a.bitmap: served bits differ from the oracle's")
        self.n_checked += 1

        # (b) singly, then one body over more distinct rows than the pool
        # has slots: it is answered in parts and rows page in and out.
        for op in ops:
            self.counts("b", [(op, 7, 8)], f"b.single.{op}")
        perm = rng.permutation(z["b_rows"])[: z["b_batch_rows"]]
        batch_b = [(ops[i % 4], int(perm[2 * i]), int(perm[2 * i + 1]))
                   for i in range(len(perm) // 2)]
        self.counts("b", batch_b, "b.batch.paged")
        self.counts("b", [("Intersect", ("Union", 7, 8), ("Difference", 9, ("Xor", 10, 11)))],
                    "b.tree3")

        # (c) Count(Range) over hour, day and mixed covers.
        day = timedelta(days=1)
        spans = [(T0, T0 + z["c_days"] * day),
                 (T0 + timedelta(hours=6), T0 + day + timedelta(hours=12)),
                 (T0 + day, T0 + 2 * day)]
        fmt = "%Y-%m-%dT%H:%M"
        body, want = [], []
        for row in range(min(3, z["c_rows"])):
            for lo, hi in spans:
                body.append(f'Count(Range(rowID={row}, frame="c", '
                            f'start="{lo.strftime(fmt)}", end="{hi.strftime(fmt)}"))')
                want.append(self.oracle.range_count(row, lo, hi))
        got = [int(r.get("n", 0)) for r in self.query(body[0], "c.range.single")]
        check(got == want[:1], f"c.range.single: served {got} != oracle {want[:1]}")
        got = [int(r.get("n", 0)) for r in self.query(" ".join(body), "c.range.batch")]
        check(got == want, f"c.range.batch: served {got} != oracle {want}")
        check(sum(want) > 0, "c.range: the oracle's counts are all zero (bad data)")
        self.n_checked += 1 + len(want)

    def read_your_write(self) -> None:
        """SetBit / ClearBit on a row just read, then the same reads
        again: the warm state is repaired, not served stale."""
        for frame, (r1, r2) in (("a", (2, 1)), ("b", (7, 8))):
            only2 = np.setdiff1d(self.oracle.cols(frame, r2), self.oracle.cols(frame, r1))
            both = np.intersect1d(self.oracle.cols(frame, r1), self.oracle.cols(frame, r2))
            check(len(only2) > 0 and len(self.oracle.cols(frame, r1)) > 0,
                  f"{frame}: rows {r1},{r2} leave nothing to write (bad data)")
            add = int(only2[0])
            drop = int(both[0]) if len(both) else int(self.oracle.cols(frame, r1)[0])
            res = self.query(f'SetBit(rowID={r1}, frame="{frame}", columnID={add})',
                             f"{frame}.setbit")
            check(res[0].get("changed") is True, f"{frame}.setbit: changed != true")
            self.oracle.set_bit(frame, r1, add, True)
            trees = [(op, r1, r2) for op in ("Intersect", "Union", "Difference", "Xor")]
            self.counts(frame, trees, f"{frame}.after_setbit")
            res = self.query(f'ClearBit(rowID={r1}, frame="{frame}", columnID={drop})',
                             f"{frame}.clearbit")
            check(res[0].get("changed") is True, f"{frame}.clearbit: changed != true")
            self.oracle.set_bit(frame, r1, drop, False)
            self.counts(frame, trees, f"{frame}.after_clearbit")

    def run(self, data: dict) -> dict:
        from pilosa_tpu.engine import compile_cache_dir  # imports no jax

        cache_dir = compile_cache_dir()
        entries_before = cache_entries(cache_dir)
        first = [("Intersect", 1, 2)]
        self.oracle = Oracle(data)

        with self.start("first") as child:
            host = child.wait_ready(timeout=600)
            # The client module imports jax (never a backend: see start()).
            from pilosa_tpu.server.client import Client

            self.client = Client(host, timeout=900)
            dev = self.client.status()["device"]
            emit({"phase": "server", "ok": True, "device": dev})
            self.device_checks(dev)
            self.load(data)
            # The first query of a start, timed and with its compilations:
            # the same shape is asked again after the restart below.
            mark, t0 = child.err_size(), time.monotonic()
            self.counts("a", first, "a.single.Intersect")
            cold = {**child.compile_log(mark), "seconds": round(time.monotonic() - t0, 2),
                    "cache_entries_before": entries_before}
            self.queries()
            self.read_your_write()
            dev = self.client.status()["device"]
            whole = child.compile_log(0)
            kernels = child.kernels_compiled()
            rc = child.stop()
        check(rc == 0, f"server exited with code {rc}")
        peaks = [d.get("peak_bytes_in_use") for d in dev["devices"]]
        in_use = [d.get("bytes_in_use") for d in dev["devices"]]
        emit({"phase": "queries", "ok": True, "answers_checked": self.n_checked,
              "lanes": {k: sorted(v) for k, v in sorted(self.lanes.items())},
              "device_programs": kernels, "first_start_compiles": whole})
        problems = []
        if not self.args.rehearse:  # the CPU backend reports no device memory
            if not all(isinstance(p, int) for p in peaks + in_use):
                problems.append("the server reports no device memory counters")
            elif sum(peaks) < self.z["min_peak_bytes"]:
                problems.append(f"peak device bytes {sum(peaks)} < {self.z['min_peak_bytes']}: "
                                "the device does not hold the state")
            elif min(in_use) == 0 or max(in_use) > 2 * min(in_use):
                problems.append(f"state is not spread over the devices: bytes_in_use {in_use}")
        emit({"phase": "device_memory", "ok": not problems, "peak_bytes_in_use": peaks,
              "bytes_in_use": in_use, "required_peak": self.z["min_peak_bytes"]})
        check(not problems, "; ".join(problems))

        # Second start, same data: the first query again.  Everything it
        # compiles must come from the cache the first start filled.
        entries_mid = cache_entries(cache_dir)
        with self.start("second") as child:
            self.client = Client(child.wait_ready(timeout=600), timeout=900)
            mark, t0 = child.err_size(), time.monotonic()
            self.counts("a", first, "a.single.Intersect")
            warm = {**child.compile_log(mark), "seconds": round(time.monotonic() - t0, 2),
                    "cache_entries_before": entries_mid}
            rc = child.stop()
        check(rc == 0, f"restarted server exited with code {rc}")
        new_entries = cache_entries(cache_dir) - entries_mid
        all_cached = new_entries == 0 and warm["cache_hits"] == warm["compilations"]
        emit({"phase": "compile_cache", "ok": all_cached, "dir": cache_dir,
              "query": f"Count({pql('a', first[0])})",
              "cold_start": cold, "warm_start": warm,
              "new_entries_on_warm_start": new_entries})
        check(all_cached, "the second start compiled something the first had not cached")
        return dev


# -- the kernels phase ------------------------------------------------------


def kernels_phase(k: dict, interpret: bool, seed: int) -> dict:
    """Every Pallas kernel and strategy tier against numpy, in this
    process (the chip is free: the served phase's child has exited)."""
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.engine import configure_compile_cache

    configure_compile_cache()
    from pilosa_tpu.ops import bitwise as bw
    from pilosa_tpu.ops import diffcheck, dispatch
    from pilosa_tpu.ops import pallas_kernels as pk

    kw = {"interpret": True} if interpret else {}
    rng = np.random.default_rng(seed + 2026)
    S, R, W, B, K = k["S"], k["R"], k["W"], k["B"], k["K"]
    rm = rng.integers(0, 1 << 32, size=(S, R, W), dtype=np.uint32)
    rm4 = jax.device_put(rm.reshape(S, R, W // 128, 128))
    rm_t4 = jax.device_put(
        np.ascontiguousarray(rm.transpose(1, 0, 2)).reshape(R, S, W // 128, 128))
    pairs = rng.integers(0, R, size=(B, 2), dtype=np.int32)
    idx = rng.integers(0, R, size=(B, K), dtype=np.int32)
    src = rng.integers(0, 1 << 32, size=(S, W), dtype=np.uint32)
    src4 = jnp.asarray(src.reshape(S, W // 128, 128))
    failed: list = []
    n = 0

    def chk(name, got, want):
        nonlocal n
        n += 1
        if not np.array_equal(np.asarray(got), want):
            failed.append(name)

    np_ops = {"and": lambda a, b: a & b, "or": lambda a, b: a | b,
              "xor": lambda a, b: a ^ b, "andnot": lambda a, b: a & ~b}
    a2, b2 = rm[0], rm[1]
    chk("fused_count1", pk.fused_count1(jnp.asarray(a2), **kw), bw.np_popcount(a2).sum(axis=1))
    for op, fn in np_ops.items():
        chk(f"fused_count2 {op}", pk.fused_count2(op, jnp.asarray(a2), jnp.asarray(b2), **kw),
            bw.np_popcount(fn(a2, b2)).sum(axis=1))
    chk("fused_count2 shared-b", pk.fused_count2("and", jnp.asarray(a2), jnp.asarray(b2[0]), **kw),
        bw.np_popcount(a2 & b2[0]).sum(axis=1))
    chk("fused_count2 tiled", pk.fused_count2("and", rm4[0], src4[0], tiled=True, **kw),
        bw.np_popcount(rm[0] & src[0]).sum(axis=1))

    dp, di = jnp.asarray(pairs), jnp.asarray(idx)
    for op, fn in np_ops.items():
        want = bw.np_popcount(fn(rm[:, pairs[:, 0], :], rm[:, pairs[:, 1], :])
                              ).reshape(S, B, -1).sum(axis=(0, 2))
        chk(f"resident {op}", pk.fused_resident_count2(op, rm4, dp, **kw), want)
        chk(f"gather {op}", pk.fused_gather_count2(op, rm4, dp, **kw), want)
        chk(f"rowmajor {op}", pk.fused_gather_count2_rowmajor(op, rm_t4, dp, **kw), want)
    for op in ("and", "or", "andnot"):
        want = bw.np_gather_count_multi(op, rm, idx)
        chk(f"multi {op}", pk.fused_gather_count_multi(op, rm4, di, **kw), want)
        chk(f"multi rowmajor {op}", pk.fused_gather_count_multi_rowmajor(op, rm_t4, di, **kw), want)
    chk("topn_counts", pk.fused_topn_counts(rm4, src4, **kw),
        bw.np_popcount(rm & src[:, None, :]).reshape(S, R, -1).sum(axis=(0, 2)))
    for depth in (2, 3, 4):
        kt = 1 << depth
        leaves = rng.integers(0, R, size=(B, kt), dtype=np.int32)
        opc = rng.integers(0, 5, size=(B, kt - 1), dtype=np.int32)
        # Chunked reference: a one-shot numpy gather at depth 4 is ~2 GB.
        want = np.concatenate([bw.np_gather_count_tree(rm, leaves[i:i + 8], opc[i:i + 8])
                               for i in range(0, B, 8)])
        chk(f"tree depth {depth}",
            pk.fused_gather_count_tree(rm4, jnp.asarray(leaves), jnp.asarray(opc), **kw), want)
    cand = rng.integers(0, R, size=(min(17, R),), dtype=np.int32)
    chk("gather_src_counts", pk.fused_gather_src_counts(rm4, jnp.asarray(cand), src4, **kw),
        np.stack([[int(bw.np_popcount(rm[s, p] & src[s]).sum()) for p in cand]
                  for s in range(S)]))

    # The Gram: against numpy on the sampled pairs, then the chunked scan
    # path against the one-shot.
    g1 = np.asarray(bw.pair_gram(jnp.asarray(rm)))
    chk("gram == numpy", g1[pairs[:, 0], pairs[:, 1]],
        bw.np_popcount(rm[:, pairs[:, 0], :] & rm[:, pairs[:, 1], :]
                       ).reshape(S, B, -1).sum(axis=(0, 2)))
    orig = bw.GRAM_ONESHOT_BYTES
    bw.GRAM_ONESHOT_BYTES = 1
    try:
        chk("chunked gram == one-shot", bw.pair_gram(rm4), g1)
    finally:
        bw.GRAM_ONESHOT_BYTES = orig
    if not interpret:
        # Dispatch-level strategy tiers (they choose Pallas by backend, so
        # they mean something only on the chip): 3D and 4D forms agree.
        for op in ("and", "or"):
            chk(f"dispatch 3D/4D parity {op}",
                dispatch.gather_count(op, rm4, dp, allow_gram=False),
                np.asarray(dispatch.gather_count(op, jnp.asarray(rm), dp, allow_gram=False)))

    # Generated differential cases: the same lane-by-lane cases the CPU
    # suite runs in interpret mode (tests/test_differential_kernels.py).
    fuzz = diffcheck.run_lanes(seed=seed + 2026, cases_per_lane=k["fuzz_cases"],
                               interpret=interpret)
    failed.extend(f"fuzz {f}" for f in fuzz)
    d = jax.devices()[0]
    ms = d.memory_stats() or {}
    out = {"phase": "kernels", "ok": not failed, "checks": n, "failed": failed,
           "W": W, "fuzz_cases_per_lane": k["fuzz_cases"], "interpret": interpret,
           "platform": d.platform, "kind": d.device_kind, "count": len(jax.devices()),
           "peak_bytes_in_use": ms.get("peak_bytes_in_use")}
    emit(out)
    check(not failed, f"kernels phase: {len(failed)} mismatches: {failed[:6]}")
    return out


# -- main ---------------------------------------------------------------------


def run(args) -> dict:
    z = REHEARSE if args.rehearse else FULL
    if args.rehearse:
        # The rehearsal is a CPU run, here as in the child, and says so.
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        if args.chips == 4:
            z = dict(z, slices=4)  # a slice axis the four virtual devices can share
    else:
        held = os.environ.get("JAX_PLATFORMS", "")
        check(not held or "tpu" in held.split(","),
              f"JAX_PLATFORMS={held} holds jax off the TPU; this run needs the chip "
              "(--rehearse is the CPU rehearsal)")
    emit({"phase": "plan", "ok": True, "rehearse": args.rehearse, "chips": args.chips,
          "seed": args.seed, "slices": z["slices"], "columns": z["slices"] * SLICE_WIDTH,
          "reduced": []})
    emit({"phase": "native_build", "ok": True, "clean": not args.rehearse,
          "seconds": build_native(clean=not args.rehearse)})

    out_dir = os.path.join(ROOT, "chiprun_out", "chip_smoke")
    data_dir = os.path.join(ROOT, ".chip_smoke", "data")
    shutil.rmtree(data_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    os.makedirs(data_dir)
    check("jax" not in sys.modules, "jax was imported before the server child started")
    try:
        dev = Served(args, z, out_dir, data_dir).run(make_data(args.seed, z))
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    device = {"platform": dev["platform"], "kind": dev["device_kind"], "count": dev["count"]}
    if args.chips == 1:
        # The served phase's child is gone; this process may now take the chip.
        k = kernels_phase(z["kernels"], interpret=args.rehearse, seed=args.seed)
        check((k["platform"], k["kind"], k["count"]) ==
              (device["platform"], device["kind"], device["count"]),
              f"kernels phase ran on {k['platform']}/{k['kind']} x{k['count']}, "
              f"the server on {device}")
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="data seed (default 0)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-chip mesh deployment (engine \"mesh\")")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend there is (CPU allowed); "
                         "reports the platform it really ran on")
    args = ap.parse_args(argv)
    try:
        device = run(args)
    except Exception as e:  # every failure ends the run non-zero, ok: false
        emit({"ok": False, "error": f"{type(e).__name__}: {e}"})
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
