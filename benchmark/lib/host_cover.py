"""The device's idle time by what the host was doing, from the run's
``.xplane.pb``: the first device plane's idle intervals between the door's
markers, cut at the edges of the program's annotations in the host plane
(every sampled span is a ``jax.profiler.TraceAnnotation`` of its name while
open, on any thread).  Each piece goes to the shortest annotation open over
it that is no root span (``POST /index/...``: a root covers its whole request
and names no layer); a piece that only roots cover goes to ``(root only)``,
one that nothing covers to ``(no span)``.  ``trace_reduce``'s ``idle_gaps``
names the ten longest gaps by one covering event each; this accounts for all
of the idle time.

The host plane also holds the runtime's own events (``PjitFunction(f)``,
``tpu::System::Execute=>Done``, ``Release semaphore``, ``shard_args``); the
program's span names are lower-case dotted words (``pool.miss.fetch``,
``interp.gc``, ``call.Count``) and a few single words (``device``,
``encode``, ...), and that is how they are told apart.  A trace without a
device plane (a CPU rehearsal): nothing to read, None."""

from __future__ import annotations

import re

import numpy as np

from lib import trace_reduce

ROOT = re.compile(r"^(GET|POST|PUT|PATCH|DELETE) /")
SPAN = re.compile(r"^([a-z][a-z0-9_]*(\.[A-Za-z0-9_<>]+)+|device|encode|parse|fused|forward|remote|slices|slice_chunk)$")
ROOT_ONLY, NO_SPAN = "(root only)", "(no span)"


def attribute(idle: list, spans: list) -> dict:
    """{name: time} of the ``idle`` intervals ``[(start, end)]`` (disjoint)
    under ``spans`` ``[(start, end, name)]``, in the intervals' own unit: one
    sweep over all edges; between two edges the open set does not change."""
    events = []   # (time, what, index): 0 a span closes, 1 one opens, 2 idle ends, 3 idle begins
    for i, (s, e, _name) in enumerate(spans):
        if e > s:
            events.append((s, 1, i))
            events.append((e, 0, i))
    for s, e in idle:
        if e > s:
            events.append((s, 3, -1))
            events.append((e, 2, -1))
    events.sort()
    layer = [not ROOT.match(name) for _s, _e, name in spans]
    out: dict = {}
    open_spans: set = set()
    owner, idle_now, last = NO_SPAN, False, 0.0
    for t, what, i in events:
        if idle_now and t > last:
            out[owner] = out.get(owner, 0.0) + (t - last)
        last = t
        if what >= 2:
            idle_now = what == 3
            continue
        if what == 1:
            open_spans.add(i)
        else:
            open_spans.discard(i)
        layers = [j for j in open_spans if layer[j]]
        if layers:
            owner = spans[min(layers, key=lambda j: spans[j][1] - spans[j][0])][2]
        else:
            owner = ROOT_ONLY if open_spans else NO_SPAN
    return out


def idle_by_span(path: str):
    """{"idle_s", "named_s", "by_span": [[name, seconds], ...] largest first}
    for the first device plane of the ``.xplane.pb`` at ``path``, or None."""
    from jax.profiler import ProfileData

    ops, spans = None, []
    lo, hi = -np.inf, np.inf
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:") and ops is None:
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ops = trace_reduce._events(line)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.endswith(" start_trace"):
                        lo = max(lo, ev.start_ns + ev.duration_ns)
                    elif ev.name.endswith(" stop_trace"):
                        hi = min(hi, ev.start_ns)
                    elif ROOT.match(ev.name) or SPAN.match(ev.name):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    if ops is None or len(ops[1]) == 0:
        return None
    _names, starts, durs = ops
    lo = lo if np.isfinite(lo) else float(starts.min())
    hi = hi if np.isfinite(hi) else float((starts + durs).max())
    s, e = trace_reduce.union_ns(np.clip(starts, lo, hi), np.clip(starts + durs, lo, hi))
    idle = list(zip(np.concatenate(([lo], e)).tolist(), np.concatenate((s, [hi])).tolist()))
    by = attribute(idle, spans)
    ranked = sorted(([k, v / 1e9] for k, v in by.items()), key=lambda kv: -kv[1])
    return {"idle_s": sum(by.values()) / 1e9,
            "named_s": sum(v for k, v in by.items() if k not in (ROOT_ONLY, NO_SPAN)) / 1e9,
            "by_span": ranked}
