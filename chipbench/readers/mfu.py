"""Kind ``mfu``: the whole step's share of the chip's peak, in percent:
the operations the window's useful work needs (a ``cost`` function and
its arguments) over the window's seconds and the peak FLOP/s."""

from __future__ import annotations

from chipbench import cost, readers


def read(spec: dict, ctx: dict):
    args = {k: readers.evaluate(v, ctx) for k, v in spec.get("args", {}).items()}
    if any(v is None for v in args.values()) or not ctx["span_s"]:
        return None
    needed = cost.lookup(spec["cost"])(ctx["sections"][spec["section"]], **args)
    if needed["flops"] <= 0:
        return None
    return 100.0 * needed["flops"] / ctx["span_s"] / ctx["peak"]["flops_per_s"]
