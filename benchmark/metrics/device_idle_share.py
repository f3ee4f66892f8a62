"""Layer: device.  1 - (union of device-op intervals / traced span), from
the ``.xplane.pb`` the server wrote between ``/debug/profile/start`` and
``stop``.  Source: device_trace.  Moves ``calls_per_s``."""


def read(ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
