"""From the profiler's ``.xplane.pb`` to numbers: the device's busy
union, the traced window, time per device program and per op, and the
longest idle gaps with what the host was doing in them.

Read with ``jax.profiler.ProfileData`` and nothing else (importing jax
initializes no backend, so the server child keeps the chip).  The device
planes are ``/device:TPU:<n>``; a CPU rehearsal has none, and there the
XLA CPU client's worker threads of ``/host:CPU`` stand in (platform
``cpu``: a rehearsal's numbers, never a device's).
"""

from __future__ import annotations

import glob
import os

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SKIP = ("ThreadpoolListener",)
CLOCK_SLACK_NS = 1e6


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union_ns(starts: np.ndarray, ends: np.ndarray):
    """Merged intervals of possibly nested or overlapping ones:
    (merged starts, merged ends)."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.concatenate(([True], s[1:] > reach[:-1]))
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx)


def _events(line):
    names, starts, durs = [], [], []
    for ev in line.events:
        names.append(ev.name)
        starts.append(ev.start_ns)
        durs.append(ev.duration_ns)
    return names, np.asarray(starts, dtype=np.float64), np.asarray(durs, dtype=np.float64)


def _totals(names, durs, top: int = 10) -> list:
    tot: dict = {}
    for n, d in zip(names, durs):
        tot[n] = tot.get(n, 0.0) + d
    return [[n, s / 1e9] for n, s in sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def reduce_trace(path: str) -> dict:
    """``path``: an ``.xplane.pb``.  Seconds throughout.

    ``window_s``: the traced span on the trace's own clock - from the end
    of the profiler's ``start_trace`` call to the start of its
    ``stop_trace`` call where the host plane shows them (stopping takes
    seconds under load, and they are not the steady window), else first
    to last event of the whole trace.  ``busy_s``: union of the intervals
    in which an op ran on the device inside that span, averaged over the
    device planes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev_lines, mod_lines, host = [], [], []   # per device: (names, starts, durs)
    lo, hi = np.inf, -np.inf
    span_lo, span_hi = -np.inf, np.inf     # between the profiler's own start and stop calls
    planes = list(pd.planes)
    has_device = any(p.name.startswith("/device:TPU:") for p in planes)
    for plane in planes:
        is_dev = plane.name.startswith("/device:TPU:")
        ops = mods = None
        for line in plane.lines:
            names, starts, durs = _events(line)
            if len(starts) == 0:
                continue
            lo, hi = min(lo, starts.min()), max(hi, (starts + durs).max())
            if is_dev and line.name == OPS_LINE:
                ops = (names, starts, durs)
            elif is_dev and line.name == MODULES_LINE:
                mods = (names, starts, durs)
            elif plane.name == "/host:CPU":
                if not has_device and line.name.startswith("tf_XLA"):
                    keep = [i for i, n in enumerate(names) if not n.startswith(_SKIP)]
                    dev_lines.append(([names[i] for i in keep], starts[keep], durs[keep]))
                else:
                    host.append((line.name, names, starts, durs))
                    for n, s0, d in zip(names, starts, durs):
                        if n.endswith(" start_trace"):
                            span_lo = max(span_lo, s0 + d)
                        elif n.endswith(" stop_trace"):
                            span_hi = min(span_hi, s0)
        if ops is not None:
            dev_lines.append(ops)
            if mods is not None:
                mod_lines.append(mods)
    if not dev_lines or not np.isfinite(lo):
        return {"window_s": 0.0, "busy_s": 0.0, "devices": 0, "device_ops": [],
                "device_programs": [], "idle_gaps": []}
    # The device's clock runs some tenths of a millisecond apart from the host's.
    lo, hi = max(lo, span_lo - CLOCK_SLACK_NS), min(hi, span_hi + CLOCK_SLACK_NS)
    if not has_device:   # the CPU's worker threads are one "device" together
        dev_lines = [([n for d in dev_lines for n in d[0]],
                      np.concatenate([d[1] for d in dev_lines]),
                      np.concatenate([d[2] for d in dev_lines]))]
    busy, gaps = [], []
    for names, starts, durs in dev_lines:
        s, e = union_ns(np.clip(starts, lo, hi), np.clip(starts + durs, lo, hi))
        busy.append(float((e - s).sum()))
        if not gaps:   # idle gaps of the first device, trace edges included
            gs = np.concatenate(([lo], e))
            ge = np.concatenate((s, [hi]))
            gaps = sorted(zip((ge - gs).tolist(), gs.tolist(), ge.tolist()), reverse=True)[:10]
    all_names = [n for d in dev_lines for n in d[0]]
    all_durs = np.concatenate([d[2] for d in dev_lines]) / len(dev_lines)
    programs = _totals([n for m in mod_lines for n in m[0]],
                       np.concatenate([m[2] for m in mod_lines]) / len(mod_lines)) if mod_lines else []
    return {"window_s": (hi - lo) / 1e9, "busy_s": sum(busy) / len(busy) / 1e9,
            "devices": len(dev_lines), "device_ops": _totals(all_names, all_durs),
            "device_programs": programs,
            "idle_gaps": [[_host_doing(host, gs, ge), d / 1e9] for d, gs, ge in gaps if d > 0]}


def _host_doing(host: list, gs: float, ge: float) -> str:
    """What the host was doing in a gap: the shortest host event that
    covers the whole gap, else the longest one that starts inside it."""
    best, best_d = "", np.inf
    inside, inside_d = "", 0.0
    for line_name, names, starts, durs in host:
        ends = starts + durs
        cover = np.flatnonzero((starts <= gs) & (ends >= ge))
        if len(cover):
            i = cover[np.argmin(durs[cover])]
            if durs[i] < best_d:
                best, best_d = f"{line_name or 'host'}: {names[i]}", durs[i]
        within = np.flatnonzero((starts >= gs) & (starts < ge))
        if len(within):
            i = within[np.argmax(durs[within])]
            if durs[i] > inside_d:
                inside, inside_d = f"{line_name or 'host'}: {names[i]}", durs[i]
    return (best or inside or "no host event")[:120]
