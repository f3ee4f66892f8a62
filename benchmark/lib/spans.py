"""Arithmetic on the span trees that ``X-Pilosa-Trace`` returns: a list
of root spans, each ``{"name", "start_ms", "ms", "tags", "children"}``."""


def roots(tree) -> list:
    if isinstance(tree, dict):
        return [tree]
    return [t for t in (tree or []) if isinstance(t, dict)]


def root_ms(tree) -> float:
    """Milliseconds of one request's root spans."""
    return sum(float(r.get("ms", 0.0)) for r in roots(tree))


def lanes(tree, into: set) -> None:
    """Every lane tag and ``call.*`` span name seen, for the earlier lines."""
    for node in roots(tree):
        tags = node.get("tags") or {}
        if tags.get("lane"):
            into.add(f"{node.get('name', '').split(' ')[0]}:lane={tags['lane']}")
        if str(node.get("name", "")).startswith("call."):
            into.add(node["name"])
        lanes(node.get("children"), into)
