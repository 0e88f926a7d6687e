"""Kind ``spans``: a percentile or the mean, in milliseconds, of the
duration of one named span of the program's request traces
(``pathway_tpu.engine.tracing.recent_requests``), over the requests that
finished in the window (``readers/timeline.py`` says which seconds).

``{"reader": "spans", "span": "generate.ttft", "stat": "percentile",
"percentile": 50, "from_s": 10.0}``

The program keeps the newest ``PATHWAY_TRACE_BUFFER`` finished requests;
where that ring is full and its oldest request finished inside the window
it may have dropped requests of the window, and there is nothing sound to
read."""

from __future__ import annotations

from chipbench import stats
from chipbench.readers import timeline


def measure(spec: dict, requests: list[dict], since: float, until: float, capacity: int):
    """The stat of ``spec`` over finished ``requests`` (any order); the
    tests hand it traces built by hand."""
    finished = [
        (r["start"] + r["duration_s"], r) for r in requests if r.get("duration_s") is not None
    ]
    if len(finished) >= capacity and min(at for at, _r in finished) >= since:
        return None
    values = [
        1e3 * s["duration_s"]
        for at, r in finished
        if since <= at <= until
        for s in r["spans"]
        if s["name"] == spec["span"]
    ]
    if not values:
        return None
    if spec["stat"] == "percentile":
        return stats.percentile(values, spec["percentile"])
    if spec["stat"] == "mean":
        return sum(values) / len(values)
    raise ValueError(f"unknown spans stat {spec['stat']!r}")


def read(spec: dict, ctx: dict):
    from pathway_tpu.engine import tracing
    from pathway_tpu.internals.config import env_int

    since, until = timeline.window(spec, ctx)
    capacity = max(1, int(env_int("PATHWAY_TRACE_BUFFER")))
    return measure(spec, tracing.recent_requests(capacity), since, until, capacity)
