"""Layer: device.  The share, in percent, of the device's idle time in the
traced span that the program's own spans name: ``lib/host_cover.py`` cuts the
first device's idle time between the door's markers at the edges of the host
plane's annotations and gives each piece to the shortest annotation open over
it that is no root span; what only a root covers, and what nothing covers,
has no name.  Prints ``{"phase": "idle_named", "by_span": [[name, seconds],
...]}``: the device's idle time by what the host was doing, the ten largest.
Source: device_trace.  Moves ``calls_per_s``.  Nothing to read from a trace
without a device plane (a CPU rehearsal)."""

import json

from lib import host_cover, scope_trace


def read(ctx):
    path = scope_trace.newest_xplane(ctx)
    cover = host_cover.idle_by_span(path) if path else None
    if not cover or cover["idle_s"] <= 0:
        return None
    share = 100.0 * cover["named_s"] / cover["idle_s"]
    print(json.dumps({"phase": "idle_named", "idle_s": cover["idle_s"], "named_s": cover["named_s"],
                      "by_span": cover["by_span"][:10], "idle_named_share": share}), flush=True)
    return share
