"""Kind ``trace_module``: the roofline share, in percent, of an XLA module
found in the traced seconds by a pattern on its name (``jit__decode``):
the least time the chip could take for one run (``cost``, a function of
``chipbench/cost``, and its arguments, each a number or a reader's spec)
over the device time a run took."""

from __future__ import annotations

import re

from chipbench import cost, readers


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace:
        return None
    seconds = runs = 0.0
    for name, m in trace["modules"].items():
        if re.search(spec["module"], name):
            seconds += m["seconds"]
            runs += m["runs"]
    if runs <= 0 or seconds <= 0:
        return None
    per_run = seconds / runs
    args = {k: readers.evaluate(v, ctx) for k, v in spec.get("args", {}).items()}
    if any(v is None for v in args.values()):
        return None
    needed = cost.lookup(spec["cost"])(ctx["sections"][spec["section"]], **args)
    least, _bound = cost.least_seconds(needed, ctx["peak"])
    return 100.0 * least / per_run
