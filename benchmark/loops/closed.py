"""Loop ``closed``: a fixed number of clients, each a thread with one
keep-alive connection, each sending its next request when the last is
answered.  Records everything; judges nothing.  A loop file gives the
harness ``run_clients(host, index, streams, seconds, trace)`` ->
(t0, records by client)."""

from __future__ import annotations

import threading
import time

from lib import httpc
from lib.records import Record


def run_clients(host: str, index: str, streams: list, seconds: float,
                trace: bool = False) -> tuple:
    """Drive every stream for ``seconds``; returns (t0, records by client).
    A client sends nothing new after the close, except the read-back that
    completes a write pair it began inside the window."""
    records = [[] for _ in streams]
    start = threading.Barrier(len(streams) + 1)
    t0_box = [0.0]

    def client(i: int, stream) -> None:
        conn = httpc.Conn(host)
        out = records[i]
        try:
            start.wait()
            end = t0_box[0] + seconds
            owed = False
            while owed or time.perf_counter() < end:
                req = stream.next()
                t_s = time.perf_counter()
                try:
                    results, spans = conn.query(index, req.body, trace=trace)
                    out.append(Record(i, req, t_s, time.perf_counter(), results, spans=spans))
                except (httpc.HTTPFailure, OSError, ValueError) as e:
                    out.append(Record(i, req, t_s, time.perf_counter(), None,
                                      error=f"{type(e).__name__}: {e}"))
                owed = req.kind == "write"
        finally:
            conn.close()

    threads = [threading.Thread(target=client, args=(i, s), daemon=True)
               for i, s in enumerate(streams)]
    for t in threads:
        t.start()
    t0_box[0] = time.perf_counter()
    start.wait()
    for t in threads:
        t.join()
    return t0_box[0], records
