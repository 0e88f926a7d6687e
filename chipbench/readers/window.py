"""Kind ``window``: a number the harness itself took: ``setup_s`` (process
start to the first timed request), ``span_s`` (the window's start to the
last response), a percentile or the mean of the client's latencies
(``{"stat": "latency_ms", "percentile": 50}``, percentile 0 being the
shortest; ``{"stat": "latency_mean_ms"}``) or a count of the deployment's
useful work (``{"stat": "work", "key": "decoder_tokens"}``)."""

from __future__ import annotations

from chipbench import stats


def read(spec: dict, ctx: dict):
    stat = spec["stat"]
    if stat in ("setup_s", "span_s"):
        return ctx[stat]
    if stat == "latency_ms":
        return stats.percentile(ctx["latencies_ms"], spec["percentile"])
    if stat == "latency_mean_ms":
        values = ctx["latencies_ms"]
        return sum(values) / len(values) if values else None
    if stat == "work":
        return ctx["work"].get(spec["key"])
    raise ValueError(f"unknown window stat {stat!r}")
