"""Walking the span trees that ``X-Pilosa-Trace`` returns, for the readers
that take one layer's spans out of them (``lib/spans.py`` reads the roots
only).  A request's tree is a list of root spans, each ``{"name",
"start_ms", "ms", "tags", "children"}``; the program names its spans after
its layers (``door.read``, ``qos.admit``, ``serve.validate``,
``serve.repair``, ``pool.lock_wait``, ``pool.repair``, ``pool.fetch``,
``pool.scatter``, ``pool.gram``, ``device``, ``write.apply``, ``encode``).
A program without those spans gives trees without them, and every reader
then finds nothing and returns None."""

import math

from lib import spans


def named(tree, names):
    """Every span called one of ``names``, each once: a span of that name
    nested inside another of that name is part of the outer one."""
    for node in spans.roots(tree):
        if node.get("name") in names:
            yield node
        else:
            yield from named(node.get("children"), names)


def ms_of(tree, *names):
    """(how many, their milliseconds together) of one request's spans."""
    found = [float(n.get("ms", 0.0)) for n in named(tree, names)]
    return len(found), sum(found)


def root_tag(tree, key):
    """A tag of the request's root span, or None."""
    for node in spans.roots(tree):
        value = (node.get("tags") or {}).get(key)
        if value is not None:
            return value
    return None


def trees(ctx, writes: bool):
    """Span trees of the window's ``SetBit`` requests, or of its reads
    (read-backs are reads)."""
    return [r.spans for r in ctx["records"] if r.spans and (r.req.kind == "write") == writes]


def all_spans_ms(ctx, name):
    """Milliseconds of every span of that name in the window, whoever ran it."""
    return [float(n.get("ms", 0.0)) for r in ctx["records"] if r.spans
            for n in named(r.spans, (name,))]


def mean(values):
    return sum(values) / len(values) if values else None


def percentile(values, q):
    """Nearest rank, as ``run.py`` takes its percentiles."""
    v = sorted(values)
    return v[min(len(v) - 1, max(0, math.ceil(q * len(v)) - 1))] if v else None
