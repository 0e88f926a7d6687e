"""Kind ``trace_idle``: the share of the traced seconds in which no
operation ran on the device, in percent."""

from __future__ import annotations


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
