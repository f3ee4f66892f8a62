"""Request-scoped distributed tracing: spans, sampling, slow-query log.

No reference analog — the reference's observability stops at aggregate
expvar counters.  The stack already has counters/histograms (stats.py),
profiles (pprof.py), QoS shed/latency metrics, and qcache hit/miss
telemetry, but none of them can answer "where did THIS request's 72 ms
go?" across parse -> admission -> cache -> slice fan-out -> remote hop
-> device dispatch.  Per-op cost varies wildly with container density
and strategy lane (the same PQL shape can hit the Gram lane, the fused
gather kernels, or the Python general lane), so aggregate histograms
cannot localize a regression; this subsystem attributes time to stages
per request.

Design:

- **Span** — one timed stage: name, start offset, duration, a small tag
  dict (strategy lane, slice counts, cache outcome), children.  Spans
  form a tree rooted at the serving door (HTTP handler or the lockstep
  front end).  Child creation is append-only and thread-safe under the
  GIL, so fan-out worker threads attach their spans concurrently.
- **Head sampling** — the sample decision is made ONCE at the door
  (``Tracer.begin``): an inbound ``X-Pilosa-Trace`` header forces the
  trace (the client override and the cross-node hop), otherwise a coin
  flip against ``[trace] sample-rate`` decides.  An unsampled request
  builds NO span objects — every instrumentation site downstream guards
  on ``span is None``, so the off path is a single branch per site
  (the qcache bench asserts sample-rate 0.01 costs <= 5% vs disabled).
- **Slow-query bypass** — requests whose total duration exceeds
  ``[trace] slow-ms`` are recorded in the ring even when the sampler
  said no (a synthesized root-only trace carries the total + the
  request fingerprint), and ADDITIONALLY emit one structured log line
  on the ``pilosa_tpu.slowquery`` logger: query fingerprint, per-stage
  ms breakdown (when the trace was sampled — head sampling cannot
  retroactively reconstruct stages for unsampled requests), and the
  cache/QoS disposition tags.  Force-sample a repro
  (``X-Pilosa-Trace: 1``) to get the full breakdown for a known-slow
  query.
- **Cross-node propagation** — a coordinator's remote hop sends its
  trace id in ``X-Pilosa-Trace``; the peer (forced by the header)
  traces its own execution and returns the serialized span tree in the
  ``X-Pilosa-Trace-Spans`` response header, which the client grafts
  under the coordinator's ``remote`` span — one trace shows both sides
  of the hop.  All offsets are relative to each span's own start, so
  no clock sync is assumed (the same rule as QoS deadline hops).
- **Lockstep determinism** — in the lockstep service the sampling
  decision is made once on rank 0 at ship time and rides the batch
  wire entry as a per-request ``trace`` flag; every rank reads the
  same flag (never its own RNG), so the decision is identical
  everywhere — the same determinism rule as expired-request drops and
  error isolation.  Only rank 0 records spans (ship/execute phases);
  tracing never changes execution, so workers only count the flags.

- **One clock with the device trace** — while a sampled span is open it
  is also a ``jax.profiler.TraceAnnotation`` of the same name (entered
  in ``Span.__init__``, left in ``finish``), so a device profile taken
  through ``/debug/profile/start|stop`` holds the program's spans in
  its host plane, on its clock, and an idle gap of the device is named
  by the layer that held the host (``pool.repair``, ``encode``), not by
  a Python frame.  Outside a profile an annotation costs one flag check;
  an unsampled request builds neither span nor annotation.  jax is
  imported at the first sampled span, so this module imports without it.
- **Span names of the served path** — root (``POST /index/<i>/query``,
  from the request line's arrival; tag ``cpu_ms`` = the serving thread's
  CPU time over the same interval, tag ``t0_s`` = that arrival on
  ``time.perf_counter()``, the machine's monotonic clock, so a client on
  the same machine can place the root between its own send and receive
  stamps), ``door.read``, ``qos.admit``,
  ``qcache.lookup`` / ``qcache.commit``, ``serve.validate``, ``serve.repair`` >
  ``pool.lock_wait``, ``pool.repair`` > ``pool.fetch`` / ``pool.scatter``
  / ``pool.gram`` (> ``mesh.fetch``: the mesh engine's wait for a reduced
  result), ``serve.queue`` / ``serve.pass`` (the read coalescer: a
  request's wait for the batch that took it, and that batch from start to
  done, tags ``leader`` and ``batch``; recorded after the fact, the work
  inside has its own names on the leader's thread), ``pool.refresh``,
  ``pool.miss`` > ``pool.miss.fetch`` / ``pool.miss.scatter``, ``device``
  (tag ``lane``; a gather dispatch says what it gathered), ``device.fetch``
  (one chip: the host's wait for a dispatch's counts; ``mesh.fetch`` on a
  mesh), ``write.apply``, ``parse``, ``fused``, ``call.<Name>``,
  ``slices`` / ``slice_chunk``, ``remote``, ``encode``, ``interp.gc`` (a
  full collection of the interpreter that overlapped the request, on
  whichever thread it ran: it stops them all; tags ``collected``,
  ``t0_s``).  Outside the tree, as annotations only: ``door.reply`` (the
  status line, headers and payload of a traced response going out).

Finished traces land in a bounded in-memory ring served at
``/debug/traces`` (JSON, newest-first, ``?min-ms=`` filter).  Config:
``[trace] sample-rate / slow-ms / ring`` TOML, ``PILOSA_TPU_TRACE_*``
env, wired through Config into the server, lockstep CLI, and handler.
"""

from __future__ import annotations

import gc
import itertools
import json
import logging
import os
import random
import threading

from pilosa_tpu.analysis import lockcheck
import time
from collections import deque
from typing import Any, Optional

# Request header: "1"/"true" = client force-sample override; any other
# value is a propagated trace id from an upstream hop (which also
# forces sampling, so the coordinator's trace always gets its sub-spans).
TRACE_HEADER = "X-Pilosa-Trace"
# Response header: the serialized span tree of a force-traced request,
# grafted by the caller under its remote-hop span.
TRACE_SPANS_HEADER = "X-Pilosa-Trace-Spans"

# Serialized span payloads ride an HTTP header (stdlib servers cap a
# header line at 64 KiB); past this the wire form degrades to the root
# span only rather than breaking the response.
_SPANS_HEADER_MAX = 30000

DEFAULT_RING = 256

_slow_logger = logging.getLogger("pilosa_tpu.slowquery")

# Trace ids: a per-process prefix and a process-wide counter (no uuid4
# per sampled request).  A forked worker draws a prefix of its own.
_id_prefix = os.urandom(4).hex()
_id_seq = itertools.count(1)


def _new_id_prefix() -> None:
    global _id_prefix
    _id_prefix = os.urandom(4).hex()


os.register_at_fork(after_in_child=_new_id_prefix)


def new_trace_id() -> str:
    return f"{_id_prefix}{next(_id_seq) & 0xFFFFFFFF:08x}"


# jax.profiler.TraceAnnotation, resolved at the first sampled span (False
# where jax is not importable).
_annotation_cls: Any = None


def _open_annotation(name: str):
    """An entered ``TraceAnnotation`` of ``name`` (leave it with
    ``__exit__(None, None, None)``), or None where jax is not there."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        try:
            from jax.profiler import TraceAnnotation as cls
        except ImportError:  # no jax: spans alone
            cls = False
        _annotation_cls = cls
    if cls is False:
        return None
    ann = cls(name)
    ann.__enter__()
    return ann


def open_annotation(name: str):
    """For a site outside any span (the door's reply, after the root has
    ended): an entered annotation to leave with ``__exit__``, or None."""
    return _open_annotation(name)


class GcWatch:
    """The interpreter's full collections, as the one ``gc.callbacks``
    entry of the process.  A collection of generation 2 stops every thread
    for as long as it walks the heap: it is stamped, covered by an
    ``interp.gc`` annotation, counted (``collections``, ``pause_ms``: the
    gauges ``gc.full_collections`` and ``gc.full_pause_ms`` at scrape
    time) and kept among the last ``KEPT`` pauses ``(t0, t1, collected)``,
    from which a sampled request takes those that overlapped it.  Younger
    generations return at the first comparison.

    A collection starts wherever a thread allocates, under any lock, so
    the callback takes none and imports nothing: plain attributes, and an
    annotation only once a sampled span has resolved the class."""

    KEPT = 64

    def __init__(self):
        self.collections = 0
        self.pause_ms = 0.0
        self.pauses: "deque[tuple]" = deque(maxlen=self.KEPT)
        self._t0 = 0.0
        self._ann = None

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
            if _annotation_cls:
                self._ann = _open_annotation("interp.gc")
            return
        t1 = time.perf_counter()
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        self.pauses.append((self._t0, t1, info["collected"]))
        self.collections += 1
        self.pause_ms += (t1 - self._t0) * 1e3

    def overlapping(self, t0: float, t1: float) -> list:
        """The kept pauses that overlap ``[t0, t1]``, oldest first."""
        out = []
        for p in reversed(list(self.pauses)):   # newest first, to the first that ended before t0
            if p[1] <= t0:
                break
            if p[0] < t1:
                out.append(p)
        return out[::-1]


_gc_watch: Optional[GcWatch] = None


def gc_watch() -> GcWatch:
    """The process's one ``GcWatch``, installed at the first call; a later
    call adds no entry."""
    global _gc_watch
    if _gc_watch is None:
        _gc_watch = GcWatch()
        gc.callbacks.append(_gc_watch)
    return _gc_watch


class Span:
    """One timed stage of a request.  Finish is idempotent; an
    unfinished span serializes with its duration measured at
    serialization time (a crash/timeout mid-stage still shows where
    the time went).  ``t0`` backdates the start (the root starts when
    the request line arrived); ``annotate=False`` is for a span made
    after the fact, which has nothing left to cover in a profile."""

    __slots__ = ("name", "trace_id", "t0", "ms", "tags", "children", "_ann")

    def __init__(self, name: str, trace_id: str = "",
                 t0: Optional[float] = None, annotate: bool = True):
        self.name = name
        self.trace_id = trace_id
        self.t0 = time.perf_counter() if t0 is None else t0
        self.ms: Optional[float] = None
        self.tags: dict = {}
        self.children: list = []
        self._ann = _open_annotation(name) if annotate else None

    def child(self, name: str) -> "Span":
        sp = Span(name, self.trace_id)
        self.children.append(sp)  # list.append: atomic under the GIL
        return sp

    def record(self, name: str, t0: float, t1: float) -> "Span":
        """A finished child over a stretch that was timed before the
        span tree existed (perf_counter seconds)."""
        sp = Span(name, self.trace_id, t0=t0, annotate=False)
        sp.ms = (t1 - t0) * 1e3
        self.children.append(sp)
        return sp

    def finish(self) -> "Span":
        if self.ms is None:
            self.ms = (time.perf_counter() - self.t0) * 1e3
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(None, None, None)
        return self

    def annotate(self, **tags) -> "Span":
        self.tags.update(tags)
        return self

    def graft(self, payload) -> None:
        """Attach a peer's already-serialized span tree (the decoded
        X-Pilosa-Trace-Spans JSON) under this span.  Stored verbatim —
        remote offsets are relative to the REMOTE request's start, so
        no clock translation is needed or attempted."""
        if isinstance(payload, list):
            self.children.extend(p for p in payload if isinstance(p, dict))
        elif isinstance(payload, dict):
            self.children.append(payload)

    def to_json(self, base_t0: Optional[float] = None) -> dict:
        base = self.t0 if base_t0 is None else base_t0
        ms = self.ms
        if ms is None:  # still running at serialization time
            ms = (time.perf_counter() - self.t0) * 1e3
        out = {
            "name": self.name,
            "start_ms": round((self.t0 - base) * 1e3, 3),
            "ms": round(ms, 3),
        }
        if self.tags:
            out["tags"] = dict(self.tags)
        if self.children:
            out["children"] = [
                c if isinstance(c, dict) else c.to_json(base)
                for c in list(self.children)
            ]
        return out

    def stage_breakdown(self) -> dict:
        """{child name: total ms} over direct children (duplicate names
        sum) — the slow-query log's per-stage view."""
        out: dict = {}
        for c in list(self.children):
            if isinstance(c, dict):
                name, ms = c.get("name", "?"), float(c.get("ms", 0.0))
            else:
                name = c.name
                ms = c.ms if c.ms is not None else 0.0
            out[name] = round(out.get(name, 0.0) + ms, 3)
        return out


class Trace:
    """One sampled request: the root span plus door metadata."""

    __slots__ = ("id", "root", "forced", "propagate", "wall_ts")

    def __init__(self, name: str, trace_id: str = "", forced: bool = False,
                 propagate: bool = False, t0: Optional[float] = None,
                 annotate: bool = True):
        self.id = trace_id or new_trace_id()
        self.root = Span(name, self.id, t0=t0, annotate=annotate)
        self.forced = forced
        # An inbound X-Pilosa-Trace header means the caller wants the
        # span tree back in the response header (a hop, or a client
        # that will read /debug/traces anyway — the extra header is
        # harmless there).
        self.propagate = propagate
        self.wall_ts = time.time()

    def to_json(self, slow_ms: float = 0.0) -> dict:
        root = self.root.to_json()
        return {
            "id": self.id,
            "name": self.root.name,
            "ts": round(self.wall_ts, 3),
            "ms": root["ms"],
            "forced": self.forced,
            "slow": bool(slow_ms > 0 and root["ms"] >= slow_ms),
            "spans": root,
        }


def fingerprint(body: bytes, max_snippet: int = 120) -> dict:
    """Stable identity for a (possibly huge) query body: short hash +
    readable snippet.  Used by the slow-query log so dashboards can
    group recurring slow shapes without storing whole requests."""
    import hashlib

    if not body:
        return {"fp": "", "snippet": ""}
    snippet = body[:max_snippet].decode("utf-8", errors="replace")
    return {
        "fp": hashlib.blake2b(body, digest_size=6).hexdigest(),
        "snippet": snippet,
    }


@lockcheck.guarded_class
class Tracer:
    """Sampling gate + bounded trace ring + slow-query log.

    Thread-safe.  Always constructible: with ``sample_rate=0`` and
    ``slow_ms=0`` only force-header requests trace (the production
    default — an operator can still ``X-Pilosa-Trace: 1`` a repro
    without a restart)."""

    _guarded_by_ = {
        "stat_sampled": "trace._mu",
        "stat_slow": "trace._mu",
        "_ring": "trace._mu",
    }

    def __init__(
        self,
        sample_rate: float = 0.0,
        slow_ms: float = 0.0,
        ring: int = DEFAULT_RING,
        stats=None,
        rng: Optional[random.Random] = None,
        costs=None,
        gc_watch: Optional[GcWatch] = None,
    ):
        from pilosa_tpu.stats import NOP_STATS

        self.sample_rate = max(0.0, min(1.0, float(sample_rate)))
        self.slow_ms = max(0.0, float(slow_ms))
        self.stats = stats if stats is not None else NOP_STATS
        # Per-fingerprint cost ledger (costs.CostLedger): every recorded
        # trace folds into EWMA cost/bandwidth estimates keyed by
        # (index, frame, fingerprint, lane).  None = ledger disabled.
        self.costs = costs
        # The process's GcWatch (the server's tracer has it): a sampled
        # request shows the full collections that overlapped it.
        self.gc_watch = gc_watch
        self._rng = rng if rng is not None else random.Random()
        self._mu = lockcheck.named_lock("trace._mu")
        self._ring: "deque[dict]" = deque(maxlen=max(1, int(ring)))
        self.stat_sampled = 0
        self.stat_slow = 0

    # -- the door ---------------------------------------------------------

    def decide(self, force: bool = False) -> bool:
        """The head-sampling coin flip (exposed separately for the
        lockstep service, which decides once on rank 0 at ship time)."""
        if force:
            return True
        return self.sample_rate > 0.0 and self._rng.random() < self.sample_rate

    def begin(self, headers=None, name: str = "request",
              t0: Optional[float] = None) -> Optional[Trace]:
        """The per-request entry: an inbound ``X-Pilosa-Trace`` header
        forces the trace (and carries the upstream trace id unless it is
        a bare "1"-style override); otherwise the sampler decides.
        Returns None for the (common) unsampled request — callers pass
        ``trace.root`` downstream only when a trace exists, so every
        downstream site stays a single ``span is None`` branch.  ``t0``
        is the door's take-up stamp (perf_counter): the root starts
        there, not at the sampling decision, which needs the headers."""
        raw = (headers or {}).get(_TRACE_HEADER_L)
        if raw is None:
            if not (self.sample_rate > 0.0 and self._rng.random() < self.sample_rate):
                return None
            trace = Trace(name, t0=t0)
        else:
            tid = "" if raw.strip().lower() in ("1", "true", "yes") else raw.strip()
            trace = Trace(name, trace_id=tid, forced=True, propagate=True, t0=t0)
        with self._mu:
            self.stat_sampled += 1
        self.stats.count("trace.sampled")
        return trace

    # -- completion -------------------------------------------------------

    def finish_request(
        self,
        trace: Optional[Trace],
        *,
        name: str,
        dt_ms: float,
        body: bytes = b"",
        status: int = 0,
        tags: Optional[dict] = None,
    ) -> Optional[dict]:
        """Close out one request: record a sampled trace in the ring;
        detect slowness for EVERY request (sampled or not — the slow
        path bypasses sampling) and emit the slow-query log line; return
        extra response headers (the serialized span tree) when the
        caller asked for propagation.  The unsampled fast path is one
        comparison."""
        slow = self.slow_ms > 0.0 and dt_ms >= self.slow_ms
        sampled = trace is not None
        if not sampled and not slow:
            return None
        if not sampled:
            # Unsampled but slow: synthesize a root-only trace so the
            # ring and the log still carry the event (head sampling
            # cannot reconstruct stages after the fact).
            trace = Trace(name, annotate=False)
            trace.root.ms = dt_ms
            trace.root.tags["unsampled"] = True
        root = trace.root
        root.finish()
        if sampled and self.gc_watch is not None:
            for t0, t1, collected in self.gc_watch.overlapping(root.t0, root.t0 + root.ms / 1e3):
                root.record("interp.gc", t0, t1).annotate(collected=collected, t0_s=round(t0, 6))
        if status:
            root.tags["status"] = status
        if tags:
            root.tags.update(tags)
        self.record(trace)
        if self.costs is not None:
            self.costs.fold(trace, dt_ms, body)
        if slow:
            self._log_slow(trace, dt_ms, body)
        if trace.propagate:
            payload = json.dumps([root.to_json()], separators=(",", ":"))
            if len(payload) > _SPANS_HEADER_MAX:
                # Header-size degradation: keep the root timing, drop
                # the tree rather than breaking the HTTP response.
                slim = root.to_json()
                slim.pop("children", None)
                slim["truncated"] = True
                payload = json.dumps([slim], separators=(",", ":"))
                self.stats.count("trace.spans_truncated")
            return {TRACE_SPANS_HEADER: payload}
        return None

    def publish_gc(self) -> None:
        """Pull-model gauges, at scrape time: the process's full
        collections so far and the milliseconds they stopped it for."""
        if self.gc_watch is not None:
            self.stats.gauge("gc.full_collections", float(self.gc_watch.collections))
            self.stats.gauge("gc.full_pause_ms", self.gc_watch.pause_ms)

    def record(self, trace: Trace) -> None:
        with self._mu:
            self._ring.appendleft(trace.to_json(self.slow_ms))

    def _log_slow(self, trace: Trace, dt_ms: float, body: bytes) -> None:
        with self._mu:
            self.stat_slow += 1
        self.stats.count("trace.slow")
        rec = {
            "trace_id": trace.id,
            "name": trace.root.name,
            "ms": round(dt_ms, 3),
            **fingerprint(body),
            "stages": trace.root.stage_breakdown(),
            # Cache/QoS disposition tags land on the root span
            # (qcache=hit/miss/deferred/bypass/ineligible, qos=shed/expired,
            # lane=...) — surfaced flat so the log line is greppable.
            "tags": {k: v for k, v in trace.root.tags.items()},
        }
        _slow_logger.warning("slow-query %s", json.dumps(rec, separators=(",", ":")))

    # -- /debug/traces ----------------------------------------------------

    def traces_json(self, min_ms: float = 0.0, limit: int = 64) -> list[dict]:
        """Newest-first finished traces, optionally filtered by total
        duration (the /debug/traces payload)."""
        with self._mu:
            snap = list(self._ring)
        if min_ms > 0:
            snap = [t for t in snap if t["ms"] >= min_ms]
        return snap[: max(0, int(limit))]

    def __len__(self) -> int:
        return len(self._ring)


_TRACE_HEADER_L = TRACE_HEADER.lower()


def from_config(cfg, stats=None, costs=None) -> Tracer:
    """Build the server's tracer from Config ([trace] TOML +
    PILOSA_TPU_TRACE_* env, resolved by Config itself).  Always returns
    a Tracer: with the all-zero defaults only force-header requests
    trace, which costs one header lookup per request."""
    return Tracer(
        sample_rate=getattr(cfg, "trace_sample_rate", 0.0),
        slow_ms=getattr(cfg, "trace_slow_ms", 0.0),
        ring=getattr(cfg, "trace_ring", DEFAULT_RING),
        stats=stats,
        costs=costs,
        gc_watch=gc_watch(),
    )


def from_env(stats=None, costs=None) -> Optional[Tracer]:
    """Env-only construction for direct embedders (the lockstep service
    when no ctor args are given); None when tracing is fully off so the
    service skips even the per-request header lookup."""
    import os

    rate = float(os.environ.get("PILOSA_TPU_TRACE_SAMPLE_RATE", "0") or 0)  # analysis-ok: env-knob-outside-config: from_env is the documented opt-in for direct embedders; the server wires [trace] config
    slow = float(os.environ.get("PILOSA_TPU_TRACE_SLOW_MS", "0") or 0)  # analysis-ok: env-knob-outside-config: from_env is the documented opt-in for direct embedders; the server wires [trace] config
    ring = int(os.environ.get("PILOSA_TPU_TRACE_RING", str(DEFAULT_RING)))  # analysis-ok: env-knob-outside-config: from_env is the documented opt-in for direct embedders; the server wires [trace] config
    if rate <= 0 and slow <= 0:
        return None
    return Tracer(sample_rate=rate, slow_ms=slow, ring=ring, stats=stats,
                  costs=costs)
