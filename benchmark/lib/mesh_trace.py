"""What a cell on several chips is for, from the run's ``.xplane.pb``:
each device's busy time between the door's markers, the time in
collectives on the busiest device, and device time by the program's own
trace names (``jax.named_scope``).  ``trace_reduce.py`` averages over the
device planes and knows an op by its HLO text alone; this file opens the
same trace again and keeps the devices apart.

Events are read with ``jax.profiler.ProfileData`` (as ``trace_reduce``
reads them).  What an op *is* - its ``hlo_category`` (``all-reduce``,
``loop fusion``, ...) and its ``tf_op`` (``jit(f)/<named scopes>/<primitive>``)
- is a stat of the event's metadata, which ``ProfileData`` does not hand
on; the few fields needed are taken from the file's protobuf wire format
directly (``XSpace.planes[].event_metadata``), with nothing but Python.
Where the metadata cannot be read, an op's category is the opcode in its
HLO text and its scope is unknown.

One device plane or none (one chip; a CPU rehearsal): nothing to read,
``reduce_mesh`` returns None and every reader built on it returns None.
"""

from __future__ import annotations

import glob
import json
import os
import re

import numpy as np

from lib import byname, trace_reduce

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all")
_OPCODE = re.compile(r"\s(" + "|".join(COLLECTIVES) + r")(?:-start|-done)?\(")
_CALL = re.compile(r"^[\w.<>-]+\(.*\)$")
# jax's own structural names in an op's path: no name the program gave.
_STRUCTURAL = re.compile(r"^(shard_map|while|body|cond|scan|checkpoint|pjit|closed_call|branch_\d+_fun)$")
_reduced: dict = {}


# -- the protobuf wire format, as far as the metadata needs it ---------------

def _varint(buf: memoryview, at: int):
    value, shift = 0, 0
    while True:
        b = buf[at]
        at += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, at


def _fields(buf: memoryview):
    """(field number, wire type, value) of one message: varints as ints,
    length-delimited fields as memoryviews, fixed fields as raw bytes."""
    at, n = 0, len(buf)
    while at < n:
        key, at = _varint(buf, at)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value = buf[at:at + size]
            at += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = bytes(buf[at:at + size])
            at += size
        else:
            raise ValueError(f"wire type {wire} at byte {at}")
        yield field, wire, value


def _map_entry(buf: memoryview):
    key, value = 0, None
    for f, _w, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_metadata(path: str) -> dict:
    """{device plane name: {event name: {"hlo_category": ..., "tf_op": ...}}}
    from the file's ``XPlane.event_metadata`` (string stats, direct or by
    reference into ``stat_metadata``).  Empty where the file is not such a
    protobuf."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: dict = {}
    try:
        for f_no, _w, plane in _fields(data):
            if f_no != 1:
                continue
            name, events, stat_names = "", [], {}
            for f2, _w2, v in _fields(plane):
                if f2 == 2:
                    name = bytes(v).decode(errors="replace")
                elif f2 == 4:
                    events.append(_map_entry(v)[1])
                elif f2 == 5:
                    key, meta = _map_entry(v)
                    for f3, _w3, v3 in _fields(meta):
                        if f3 == 2:
                            stat_names[key] = bytes(v3).decode(errors="replace")
            if not name.startswith("/device:TPU:"):
                continue
            wanted = {k for k, n in stat_names.items() if n in ("hlo_category", "tf_op")}
            ops = out.setdefault(name, {})
            for meta in events:
                ev_name, stats = "", {}
                for f3, _w3, v3 in _fields(meta):
                    if f3 == 2:
                        ev_name = bytes(v3).decode(errors="replace")
                    elif f3 == 5:
                        stat = dict((f4, v4) for f4, _w4, v4 in _fields(v3))
                        if stat.get(1) in wanted:
                            text = (bytes(stat[5]).decode(errors="replace") if 5 in stat
                                    else stat_names.get(stat.get(7), ""))
                            stats[stat_names[stat[1]]] = text
                if ev_name and stats:
                    ops[ev_name] = stats
    except (ValueError, IndexError, TypeError):   # not this protobuf
        return {}
    return out


# -- the reduction -------------------------------------------------------------

def category(name: str, meta: dict) -> str:
    """An op's category: the metadata's ``hlo_category``, else the
    collective opcode in its HLO text, else ''."""
    cat = meta.get("hlo_category", "")
    if not cat:
        m = _OPCODE.search(name)
        cat = m.group(1) if m else ""
    return cat


def scope(meta: dict) -> str:
    """The program's name for an op, from ``tf_op``
    (``jit(f)/<scope>/.../<primitive>``): the jitted program and the
    first component below it that is neither a call wrapper (``jit(g)``)
    nor one of jax's structural names (``shard_map``, ``while``, ...) -
    ``jit(f)/<scope>`` - or the program alone.  '' where the op has no
    ``tf_op``."""
    parts = [p for p in meta.get("tf_op", "").rstrip(":").split("/") if p]
    if not parts:
        return ""
    inner = [p for p in parts[1:-1] if not _CALL.match(p) and not _STRUCTURAL.match(p)]
    return f"{parts[0]}/{inner[0]}" if inner else parts[0]


def _largest(seconds: dict, top: int = 12) -> list:
    return [[k, v] for k, v in sorted(seconds.items(), key=lambda kv: -kv[1])[:top]]


def reduce_mesh(path: str):
    """``path``: an ``.xplane.pb`` with two or more ``/device:TPU:<n>``
    planes, else None.  Seconds throughout; the window is
    ``trace_reduce``'s (between the door's ``start_trace`` and
    ``stop_trace`` markers).

    ``busy_s``: per device, the union of its "XLA Ops" intervals inside the
    window; ``busiest``: that device's index.  ``collective_s``: on the
    busiest device, the time of ops whose category is a collective, by
    category in ``collectives``.  ``repairs``: ``pool.repair`` spans that
    began inside the window (the program's spans are annotations on the
    host plane).  ``by_scope``: on the busiest device, time by ``scope``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, lo, hi = [], -np.inf, np.inf
    first, last, repairs_at = np.inf, -np.inf, []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        if not is_dev and plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if is_dev and line.name != trace_reduce.OPS_LINE:
                continue
            names, starts, durs = trace_reduce._events(line)
            if len(starts) == 0:
                continue
            first, last = min(first, starts.min()), max(last, (starts + durs).max())
            if is_dev:
                devices.append((plane.name, names, starts, durs))
                continue
            for n, s0, d in zip(names, starts, durs):
                if n.endswith(" start_trace"):
                    lo = max(lo, s0 + d)
                elif n.endswith(" stop_trace"):
                    hi = min(hi, s0)
                elif n == "pool.repair":
                    repairs_at.append(s0)
    if len(devices) < 2:
        return None
    lo = max(first, lo - trace_reduce.CLOCK_SLACK_NS)
    hi = min(last, hi + trace_reduce.CLOCK_SLACK_NS)
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    busy = []
    for _plane, _names, starts, durs in devices:
        s, e = trace_reduce.union_ns(np.clip(starts, lo, hi), np.clip(starts + durs, lo, hi))
        busy.append(float((e - s).sum()) / 1e9)
    top = int(np.argmax(busy))
    plane, names, starts, durs = devices[top]
    inside = np.clip(np.minimum(starts + durs, hi) - np.maximum(starts, lo), 0, None) / 1e9
    metadata = op_metadata(path).get(plane, {})
    coll, scopes = {}, {}
    for n, d in zip(names, inside):
        if d <= 0:
            continue
        meta = metadata.get(n, {})
        cat = category(n, meta)
        if cat in COLLECTIVES:
            coll[cat] = coll.get(cat, 0.0) + d
        key = scope(meta) or "(no tf_op)"
        scopes[key] = scopes.get(key, 0.0) + d
    return {"window_s": (hi - lo) / 1e9, "devices": [d[0] for d in devices], "busy_s": busy,
            "busiest": top, "collective_s": sum(coll.values()), "collectives": _largest(coll),
            "repairs": int(sum(lo <= t <= hi for t in repairs_at)), "by_scope": _largest(scopes),
            "ops_with_metadata": sum(n in metadata for n in set(names)), "ops": len(set(names))}


def of_run(ctx: dict):
    """The reduction of the traced run that ``ctx`` belongs to (its
    ``.xplane.pb`` lies under ``.bench/<workload>/trace``: the newest one
    of the configuration's cells), made once and printed once as an earlier
    line of the run (``{"phase": "mesh_trace", ...}``).  None on one
    device, and where the run left no trace."""
    root = os.path.dirname(byname.BENCH)
    found = []
    for trace_dir in glob.glob(os.path.join(root, ".bench", ctx["config"]["name"] + ".*", "trace")):
        try:
            found.append(trace_reduce.find_xplane(trace_dir))
        except FileNotFoundError:
            pass
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _reduced:
        _reduced.clear()
        _reduced[key] = reduce_mesh(path)
        if _reduced[key] is not None:
            print(json.dumps({"phase": "mesh_trace", **_reduced[key]}), flush=True)
    return _reduced[key]
