"""Kind ``registry``: the mean of one of the metric registry's histograms
over the window, its sum's change over its count's change (exact, unlike
a quantile read from the buckets)."""

from __future__ import annotations


def read(spec: dict, ctx: dict):
    after = ctx["after"]["histograms"].get(spec["histogram"])
    if after is None:
        return None
    before = ctx["before"]["histograms"].get(spec["histogram"], {"sum": 0.0, "count": 0})
    count = after["count"] - before["count"]
    if count <= 0:
        return None
    return (after["sum"] - before["sum"]) / count
