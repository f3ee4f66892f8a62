"""Ingest front doors: group-commit write queue + columnar streaming.

Two ingest mechanisms live here:

1. :class:`WriteQueue` — the group-commit micro-batching queue for
   concurrent singleton SetBit requests (below).

2. :class:`StreamIngestor` — the columnar streaming bulk-ingest door
   (``POST /index/<i>/frame/<f>/ingest``): zero-tuple (row, col)
   column chunks — Arrow IPC record batches when ``pyarrow`` is
   importable, the length-prefixed packed-uint64 framing otherwise —
   decoded straight into numpy arrays and applied through the batched
   ``Frame.set_bits`` path.  Per-chunk CRC, resumable offsets
   (mirroring the import-roaring staging), deadline checks between
   chunks, and an import-parity rank-cache recalculation at transfer
   completion.  Transport-agnostic: the HTTP handler and the lockstep
   front end both drive it; the replica router classifies the route as
   a write, so chunks are sequenced, WAL-logged, and replayed like any
   other write (re-applying a chunk is idempotent — SetBit converges).

Group-commit ingest queue: concurrent singleton writes -> one batch.

The reference ingests singleton SetBits at a few hundred ns each because
its whole write path is compiled Go (fragment.go:371-459).  Here the
per-op interpreter cost is the bottleneck, so the server routes singleton
SetBit requests through a micro-batching queue: whoever finds the queue
leaderless commits ONE drained batch (a vectorized fragment pass + one
WAL append per touched view/slice), then hands leadership off — under
sustained load leadership rotates FIFO through the waiting threads, so no
request is starved behind other clients' batches.  An idle queue adds no
artificial latency (the first writer leads immediately; no timer).

Read-your-writes: a client's next request can only be sent after its ack,
and the ack happens after the batch (including its op) committed, so its
subsequent reads observe the write.  Per-item errors: apply_batch may
return an exception INSTANCE as an item's result — it is raised on that
submitter only; an exception RAISED by apply_batch poisons the whole
batch (transport-level failures; SetBit is idempotent, retries converge).
"""

from __future__ import annotations

import struct
import threading
import time
import zlib

from pilosa_tpu.analysis import lockcheck
from typing import Callable, Optional, Sequence

# -- columnar chunk wire formats --------------------------------------------

# Packed-uint64 framing: [b"PI64"][u32 n LE][rows u64*n LE][cols u64*n LE].
PACKED_MAGIC = b"PI64"

# Arrow IPC stream content type (record batches with uint64 columns
# "row" and "col"); served only when pyarrow is importable.
ARROW_CONTENT_TYPE = "application/vnd.apache.arrow.stream"


def arrow_available() -> bool:
    try:
        import pyarrow  # noqa: F401

        return True
    except ImportError:
        return False


class IngestError(Exception):
    """Chunk rejected; ``status`` maps to the HTTP answer and
    ``staged`` tells a resuming sender where the transfer stands."""

    def __init__(self, status: int, message: str, staged: int = 0):
        super().__init__(message)
        self.status = status
        self.staged = staged


def encode_packed(rows, cols) -> bytes:
    """Encode one packed-uint64 chunk (client/bench/test helper)."""
    import numpy as np

    rows = np.ascontiguousarray(rows, dtype="<u8")
    cols = np.ascontiguousarray(cols, dtype="<u8")
    if len(rows) != len(cols):
        raise ValueError("row/col length mismatch")
    return (
        PACKED_MAGIC + struct.pack("<I", len(rows))
        + rows.tobytes() + cols.tobytes()
    )


def decode_packed(body: bytes):
    """Decode a packed-uint64 chunk -> (rows u64[n], cols u64[n]);
    zero-copy views over the request body."""
    import numpy as np

    if len(body) < 8 or body[:4] != PACKED_MAGIC:
        raise IngestError(400, "bad chunk: missing PI64 header")
    (n,) = struct.unpack_from("<I", body, 4)
    if len(body) != 8 + 16 * n:
        raise IngestError(
            400, f"bad chunk: declared {n} pairs, got {len(body) - 8} payload bytes"
        )
    rows = np.frombuffer(body, dtype="<u8", count=n, offset=8)
    cols = np.frombuffer(body, dtype="<u8", count=n, offset=8 + 8 * n)
    return rows, cols


def _arrow_u64_column(pa, table, name):
    """One named column of an Arrow table as a uint64 numpy array.

    Tolerant of real producer variety: chunked columns concatenate,
    dictionary-encoded columns decode to their value type, and any
    integer type casts (safely) to uint64.  A missing column or a
    non-integer type raises a POINTED 400 naming the problem — schema
    mistakes at 100M rows must not read as 'bad arrow chunk: KeyError'.
    """
    import numpy as np

    if name not in table.column_names:
        raise IngestError(
            400,
            f"bad arrow chunk: missing required column {name!r} "
            f"(present: {table.column_names})",
        )
    col = table.column(name)
    if isinstance(col, pa.ChunkedArray):
        col = col.combine_chunks()
    if pa.types.is_dictionary(col.type):
        col = col.dictionary_decode()
    if not pa.types.is_integer(col.type):
        raise IngestError(
            400,
            f"bad arrow chunk: column {name!r} has type {col.type}, "
            "expected an integer type castable to uint64",
        )
    try:
        col = col.cast(pa.uint64())
    except pa.ArrowInvalid as e:
        raise IngestError(
            400, f"bad arrow chunk: column {name!r} not castable to uint64: {e}"
        )
    return np.ascontiguousarray(
        col.to_numpy(zero_copy_only=False), dtype=np.uint64
    )


def decode_arrow(body: bytes):
    """Decode an Arrow IPC stream chunk -> (rows, cols) uint64 arrays.

    Requires uint64-castable ``row`` and ``col`` columns; extra columns
    are ignored (producers often ship their full table), dictionary
    encoding and multi-chunk columns are accepted.  415 without pyarrow,
    pointed 400s for schema mistakes."""
    try:
        import pyarrow as pa
    except ImportError:
        raise IngestError(
            415, "arrow ingest unavailable: pyarrow not importable on this server"
        )
    try:
        table = pa.ipc.open_stream(body).read_all()
    except (pa.ArrowInvalid, ValueError) as e:
        raise IngestError(400, f"bad arrow chunk: {e}")
    return (
        _arrow_u64_column(pa, table, "row"),
        _arrow_u64_column(pa, table, "col"),
    )


def apply_columnar(frame, rows, cols, executor=None, index: str = "",
                   deadline=None):
    """Apply one decoded columnar chunk through the batched write path:
    one vectorized ``set_bits`` pass per touched (view, slice) — no
    Python tuples, no per-op parse.  Mirrors the import path's view
    fan-out (standard + inverse when enabled; the wire carries no
    timestamps, so no time views).  Returns the changed count."""
    import numpy as np

    from pilosa_tpu.core.view import VIEW_INVERSE, VIEW_STANDARD

    rows = np.asarray(rows, dtype=np.uint64)
    cols = np.asarray(cols, dtype=np.uint64)
    ch = frame.set_bits(VIEW_STANDARD, rows, cols)
    if deadline is not None:
        deadline.check("ingest apply")
    if frame.inverse_enabled:
        frame.set_bits(VIEW_INVERSE, cols, rows)
    if executor is not None and ch.any():
        executor.note_external_write(
            index, frame.name, np.unique(rows[ch]).tolist()
        )
    return int(ch.sum())


def recalc_frame_caches(frame) -> None:
    """Import-parity rule: bulk ingest recalculates rank-cache rankings
    IMMEDIATELY at transfer completion (a TopN right after a streamed
    ingest must be fresh, not ranking-debounce stale).  Iteration is
    sorted — this runs on every lockstep rank."""
    for vname in sorted(frame.views):
        view = frame.views[vname]
        for s in sorted(view.fragments):
            view.fragments[s].recalculate_cache()


@lockcheck.guarded_class
class StreamIngestor:
    """Staged, resumable columnar streaming ingest (transport-agnostic).

    One in-progress transfer per (index, frame) key, identified by the
    whole payload's ``(total, crc)`` — a different pair restarts the
    transfer.  Chunks must arrive at the staged offset; an idempotent
    re-send of an already-applied chunk acks with the staged offset
    (SetBit converges), a gap answers 409 + ``staged`` so the sender
    resumes.  Unlike the import-roaring stager, chunks are APPLIED as
    they arrive (constant memory — the transfer state is offsets and a
    running CRC, never the payload), so "resume" means re-telling the
    sender where the applied frontier is.  At completion the running
    CRC is checked against the declared one and the ``complete`` hook
    runs (rank-cache recalculation).
    """

    # Lockset race detector declaration: the transfer table (offsets,
    # running CRCs, busy flags) is written by concurrent chunk uploads;
    # the in-place dict mutations are covered by the static
    # guarded-fields rule, a rebind by the runtime lockset check.
    _guarded_by_ = {"_transfers": "ingest.stream._mu"}

    def __init__(self, apply: Callable, complete: Optional[Callable] = None,
                 stats=None, max_transfers: int = 256,
                 max_chunk_bytes: int = 4 << 20):
        from pilosa_tpu.stats import NOP_STATS

        self._apply = apply  # (key, rows, cols, deadline) -> changed count
        self._complete = complete  # (key) -> None
        self.stats = stats if stats is not None else NOP_STATS
        self.max_transfers = max_transfers
        self.max_chunk_bytes = max_chunk_bytes
        self._mu = lockcheck.named_lock("ingest.stream._mu")
        self._transfers: dict = {}  # key -> state dict

    def probe(self, key, total: int, crc: int) -> dict:
        """Where does (key, total, crc)'s transfer stand?  (The resume
        question a restarted sender asks before streaming.)"""
        with self._mu:
            st = self._transfers.get(key)
            if st is None or st["total"] != total or st["crc"] != crc:
                return {"staged": 0, "done": False}
            return {"staged": st["off"], "done": False}

    def chunk(self, key, off: int, total: int, crc: int, body: bytes,
              chunk_crc: Optional[int] = None, arrow: bool = False,
              deadline=None) -> dict:
        """Stage-and-apply one chunk; returns ``{"staged", "done",
        "ops"}`` or raises :class:`IngestError` (offset gap, CRC
        mismatch, malformed chunk, oversized chunk)."""
        if total < 0 or off < 0:
            raise IngestError(400, "bad off/total")
        if len(body) > self.max_chunk_bytes:
            raise IngestError(
                413,
                f"chunk of {len(body)} bytes exceeds the "
                f"{self.max_chunk_bytes}-byte door; split the stream",
            )
        if total == 0:
            return {"staged": 0, "done": True, "ops": 0}
        with self._mu:
            st = self._transfers.get(key)
            if st is not None and (st["total"] != total or st["crc"] != crc):
                # A different payload for this frame: the previous
                # transfer is dead — restart cleanly.
                self._transfers.pop(key, None)
                st = None
            if st is None:
                if off != 0:
                    raise IngestError(
                        409, "unknown transfer; resume from 0", staged=0
                    )
                if len(self._transfers) >= self.max_transfers:
                    self._transfers.pop(next(iter(self._transfers)))
                    self.stats.count("ingest.evicted")
                st = {"total": total, "crc": crc, "off": 0, "rcrc": 0,
                      "ops": 0, "busy": False}
                self._transfers[key] = st
                self.stats.count("ingest.transfers")
            if off + len(body) <= st["off"]:
                # Idempotent re-send of an applied chunk (router WAL
                # replay, client retry): ack the frontier, touch nothing.
                self.stats.count("ingest.resumed")
                return {"staged": st["off"], "done": False, "ops": st["ops"]}
            if off != st["off"]:
                self.stats.count("ingest.gap")
                raise IngestError(
                    409, f"offset gap at {off}; staged={st['off']}",
                    staged=st["off"],
                )
            if st["busy"]:
                raise IngestError(
                    409, "chunk already in flight for this transfer",
                    staged=st["off"],
                )
            st["busy"] = True
        done = False
        ok = False
        try:
            if chunk_crc is not None and zlib.crc32(body) != chunk_crc:
                self.stats.count("ingest.crc_errors")
                raise IngestError(400, "chunk crc mismatch", staged=st["off"])
            if deadline is not None:
                deadline.check("ingest chunk")
            rows, cols = decode_arrow(body) if arrow else decode_packed(body)
            self._apply(key, rows, cols, deadline)
            ok = True
        finally:
            with self._mu:
                st["busy"] = False
                if ok:
                    st["off"] += len(body)
                    st["rcrc"] = zlib.crc32(body, st["rcrc"])
                    st["ops"] += len(rows)
                    self.stats.count("ingest.chunks")
                    self.stats.count("ingest.bytes", len(body))
                    self.stats.count("ingest.ops", len(rows))
                    if st["off"] > total:
                        self._transfers.pop(key, None)
                        raise IngestError(
                            409, "chunk overruns declared total", staged=0
                        )
                    if st["off"] == total:
                        done = True
                        self._transfers.pop(key, None)
                        if st["rcrc"] != crc:
                            # The bits ARE applied (we stream, not
                            # stage); a whole-payload mismatch with
                            # every chunk CRC-clean means the SENDER's
                            # declared CRC is wrong — surface loudly,
                            # the idempotent re-stream converges.
                            self.stats.count("ingest.crc_errors")
                            raise IngestError(
                                409,
                                "payload crc mismatch at completion; "
                                "re-stream to converge",
                                staged=0,
                            )
        if done:
            self.stats.count("ingest.completed")
            if self._complete is not None:
                self._complete(key)
        return {"staged": st["off"], "done": done, "ops": st["ops"]}


def _stamp(stamps: list, i: int) -> None:
    """Now, in field ``i`` of the stamps of every item that a sampled
    request brought (none: no clock is read)."""
    if stamps:
        t = time.perf_counter()
        for st in stamps:
            st[i] = t


@lockcheck.guarded_class
class WriteQueue:
    """Rotating-leader group commit (no dedicated thread, no idle timer)."""

    # Lockset race detector declarations: leadership rotation state and
    # the batch telemetry move under the queue lock (the `_cv` wraps
    # the same ``ingest._mu`` lock object).
    _guarded_by_ = {
        "_committing": "ingest._mu",
        "stat_batches": "ingest._mu",
        "stat_items": "ingest._mu",
    }

    def __init__(self, apply_batch: Callable[[Sequence], list], max_batch: int = 4096):
        self._apply = apply_batch
        self.max_batch = max_batch
        self._mu = lockcheck.named_lock("ingest._mu")
        self._cv = lockcheck.named_condition("ingest._mu", self._mu)
        self._items: list = []  # [(item, slot)]
        self._committing = False
        # Telemetry: batches committed / items seen (bench + tests).
        self.stat_batches = 0
        self.stat_items = 0

    def submit(self, item, span=None):
        """Enqueue one item; blocks until its batch commits.  Returns the
        per-item result from apply_batch (raising it if it is an
        exception), or raises the whole batch's error.

        Under a sampled request's ``span`` the item's slot also carries
        three stamps - its append, the start of the batch that took it
        and that batch's completion - and the request gets two finished
        children from them: ``serve.queue`` (append to batch start: the
        wait for the batches ahead) and ``serve.pass`` (batch start to
        done; tags ``leader``: this thread ran it, ``batch``: items in
        it).  An item without a span takes no stamp."""
        slot = [False, None, None, None]  # done, result, exception, a sampled request's stamps
        if span is not None:
            # appended, batch start, batch done, leader's thread, batch size
            slot[3] = [time.perf_counter(), 0.0, 0.0, 0, 0]
        with self._cv:
            self._items.append((item, slot))
            while not slot[0]:
                if not self._committing and self._items:
                    # Leaderless with work pending: this thread commits
                    # exactly ONE batch, then re-checks its own slot —
                    # leadership rotates instead of camping on one thread.
                    self._committing = True
                    batch = self._items[: self.max_batch]
                    del self._items[: len(batch)]
                    self.stat_batches += 1
                    self.stat_items += len(batch)
                    self._mu.release()
                    stamped = [s[3] for _, s in batch if s[3] is not None]
                    for st in stamped:
                        st[3], st[4] = threading.get_ident(), len(batch)
                    _stamp(stamped, 1)
                    try:
                        results = self._apply([it for it, _ in batch])
                        _stamp(stamped, 2)
                        for (_, s), r in zip(batch, results):
                            s[1] = r
                            s[0] = True
                    except BaseException as e:  # noqa: BLE001 — poison batch
                        _stamp(stamped, 2)
                        for _, s in batch:
                            s[2] = e
                            s[0] = True
                    finally:
                        self._mu.acquire()
                        self._committing = False
                        self._cv.notify_all()
                    continue
                self._cv.wait()
        if span is not None:
            appended, began, done, leader, n = slot[3]
            span.record("serve.queue", appended, began)
            span.record("serve.pass", began, done).annotate(
                leader=leader == threading.get_ident(), batch=n)
        if slot[2] is not None:
            raise slot[2]
        if isinstance(slot[1], BaseException):
            raise slot[1]
        return slot[1]
