"""Kind ``derived``: arithmetic over other readers' numbers.

``{"reader": "derived", "op": "div", "terms": [spec, spec, ...]}`` folds
the terms from the left with ``add``, ``sub``, ``mul`` or ``div``; any
term with nothing to read, or a division by nought, gives nothing."""

from __future__ import annotations

from chipbench import readers

OPS = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b,
    "div": lambda a, b: a / b if b else None,
}


def read(spec: dict, ctx: dict):
    values = [readers.evaluate(term, ctx) for term in spec["terms"]]
    if any(v is None for v in values):
        return None
    out = values[0]
    for v in values[1:]:
        out = OPS[spec["op"]](out, v)
        if out is None:
            return None
    return out
