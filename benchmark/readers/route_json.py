"""A per-layer metric from a JSON route of the workers' system server, read
at ``window_start`` and after the drain.

Parameters: ``path`` (the harness fetches every path some metric names),
``pointer`` (list of keys; ``*`` fans out over a list or a dict's values),
``mode`` (``last`` or ``delta``), ``reduce`` over workers and fan-out
(``sum`` or ``max``), ``scale``.
"""


def _walk(node, pointer):
    if not pointer:
        return [node] if isinstance(node, (int, float)) and not isinstance(node, bool) else []
    key, rest = pointer[0], pointer[1:]
    if key == "*":
        items = node.values() if isinstance(node, dict) else node if isinstance(node, list) else []
        return [v for item in items for v in _walk(item, rest)]
    if isinstance(node, dict) and key in node:
        return _walk(node[key], rest)
    return []


def read(p, ctx):
    reduce = max if p.get("reduce", "sum") == "max" else sum
    def at(when):
        vals = [v for target in ctx.targets("workers")
                for v in _walk(ctx.routes.get((when, target, p["path"])), p["pointer"])]
        return reduce(vals) if vals else None
    last = at("drained")
    if last is None:
        return None
    if p.get("mode", "last") == "delta":
        first = at("window_start")
        if first is None:
            return None
        last -= first
    return last * p.get("scale", 1.0)
