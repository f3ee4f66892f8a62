"""The timed window's own HTTP client: PQL text in, JSON out, one
keep-alive connection per client thread, nothing of the program and no
jax.  (Named ``httpc`` so that it can never shadow the standard ``http``.)
"""

from __future__ import annotations

import http.client
import json

TRACE_HEADER = "X-Pilosa-Trace"
SPANS_HEADER = "X-Pilosa-Trace-Spans"


class HTTPFailure(Exception):
    def __init__(self, status: int, text: str):
        super().__init__(f"HTTP {status}: {text[:300]}")
        self.status = status


class Conn:
    def __init__(self, host: str, timeout: float = 120.0):
        self.host, self.timeout = host, timeout
        self._c = http.client.HTTPConnection(host, timeout=timeout)

    def close(self) -> None:
        self._c.close()

    def request(self, method: str, path: str, body: bytes = b"", trace: bool = False):
        """(parsed JSON body, span tree or None).  One reconnect if the
        kept-alive socket had been closed under us before any byte went."""
        headers = {"Content-Type": "text/plain", "Accept": "application/json"}
        if trace:
            headers[TRACE_HEADER] = "1"
        try:
            self._c.request(method, path, body=body, headers=headers)
            resp = self._c.getresponse()
        except (http.client.RemoteDisconnected, BrokenPipeError, ConnectionResetError):
            self._c.close()
            self._c = http.client.HTTPConnection(self.host, timeout=self.timeout)
            self._c.request(method, path, body=body, headers=headers)
            resp = self._c.getresponse()
        payload = resp.read()
        if resp.status >= 400:
            raise HTTPFailure(resp.status, payload.decode(errors="replace"))
        spans = resp.getheader(SPANS_HEADER) if trace else None
        return (json.loads(payload) if payload else {},
                json.loads(spans) if spans else None)

    def query(self, index: str, pql: str, trace: bool = False):
        """``POST /index/<index>/query``: the entry the window drives."""
        out, spans = self.request("POST", f"/index/{index}/query", pql.encode(), trace)
        return out.get("results", []), spans
