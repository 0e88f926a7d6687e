"""Kind ``snapshot``: the change over the window of a counter of the
program's ledgers, by path.

``{"reader": "snapshot", "path": ["executor", "callables", "re:^encoder:",
"dispatches"]}``: a path segment ``re:<pattern>`` sums over the matching
keys."""

from __future__ import annotations

import re


def _walk(node, path: list[str]):
    if not path:
        return float(node) if isinstance(node, (int, float)) else None
    if not isinstance(node, dict):
        return None
    head, rest = path[0], path[1:]
    if head.startswith("re:"):
        found = [
            _walk(v, rest) for k, v in node.items() if re.search(head[3:], str(k))
        ]
        found = [v for v in found if v is not None]
        return sum(found) if found else None
    return _walk(node.get(head), rest) if head in node else None


def read(spec: dict, ctx: dict):
    after = _walk(ctx["after"], spec["path"])
    if after is None:
        return None
    return after - (_walk(ctx["before"], spec["path"]) or 0.0)
