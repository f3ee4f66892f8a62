"""Device time under one of the program's own trace names
(``jax.named_scope``), from the run's ``.xplane.pb``: the ops of every
device plane whose ``tf_op`` path (``jit(f)/<scopes>/<primitive>``) holds
the scope as a component, inside the span between the door's markers.
Events by ``jax.profiler.ProfileData``, an op's ``tf_op`` by
``mesh_trace.op_metadata`` (the metadata ``ProfileData`` does not hand on).
A trace without device planes (a CPU rehearsal), or a program whose ops
do not carry the scope: nothing to read, None."""

from __future__ import annotations

import glob
import os

import numpy as np

from lib import byname, mesh_trace, trace_reduce


def newest_xplane(ctx: dict):
    """The ``.xplane.pb`` of the traced run that ``ctx`` belongs to (under
    ``.bench/<workload>/trace``: the newest of the configuration's cells)."""
    root = os.path.dirname(byname.BENCH)
    found = []
    for trace_dir in glob.glob(os.path.join(root, ".bench", ctx["config"]["name"] + ".*", "trace")):
        try:
            found.append(trace_reduce.find_xplane(trace_dir))
        except FileNotFoundError:
            pass
    return max(found, key=os.path.getmtime) if found else None


def scope_seconds(path: str, scope: str):
    """{"seconds": device time of the scope's ops inside the traced span,
    summed over the device planes, "ops": how many events, "by_op": the
    largest by name} or None."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    lo, hi, lines = -np.inf, np.inf, []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        if not is_dev and plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if is_dev and line.name == trace_reduce.OPS_LINE:
                lines.append((plane.name,) + trace_reduce._events(line))
            elif not is_dev:
                for ev in line.events:
                    if ev.name.endswith(" start_trace"):
                        lo = max(lo, ev.start_ns + ev.duration_ns)
                    elif ev.name.endswith(" stop_trace"):
                        hi = min(hi, ev.start_ns)
    if not lines:
        return None
    lo, hi = lo - trace_reduce.CLOCK_SLACK_NS, hi + trace_reduce.CLOCK_SLACK_NS
    metadata = mesh_trace.op_metadata(path)
    total, n, by_op = 0.0, 0, {}
    for plane, names, starts, durs in lines:
        meta = metadata.get(plane, {})
        inside = np.clip(np.minimum(starts + durs, hi) - np.maximum(starts, lo), 0, None) / 1e9
        for name, d in zip(names, inside):
            if d > 0 and scope in meta.get(name, {}).get("tf_op", "").split("/"):
                total += d
                n += 1
                by_op[name[:80]] = by_op.get(name[:80], 0.0) + d
    if n == 0:
        return None
    return {"seconds": total, "ops": n,
            "by_op": sorted(([k, v] for k, v in by_op.items()), key=lambda kv: -kv[1])[:6]}
