"""Pieces found by name: ``benchmark/<kind>/<name>.py``, loaded as a
module.  A later PR adds a traffic generator, a loop, a data generator or
a per-layer reader as a new file and names it in a data file; nothing
here has a list to edit."""

from __future__ import annotations

import importlib.util
import os
import re

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KINDS = ("generators", "loops", "datagens", "metrics")
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_cache: dict = {}


def load(kind: str, name: str):
    if kind not in KINDS or not _NAME.match(name):
        raise KeyError(f"no such piece: {kind}/{name}")
    path = os.path.join(BENCH, kind, name + ".py")
    if path not in _cache:
        if not os.path.isfile(path):
            raise KeyError(f"benchmark/{kind}/{name}.py is not there")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _cache[path] = mod
    return _cache[path]
