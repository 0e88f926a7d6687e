"""Kind ``timeline``: a share or a mean taken from the program's host
timeline (``pathway_tpu.engine.tracing.timeline``: closed intervals of the
threads' own work, named and stamped with ``time.time()``), read in the
benchmark's process when the window has closed.

``{"reader": "timeline", "stat": ..., "names": [...], "from_s": 10.0}``

``share_pct``: the union of the intervals named by ``names``, less the
union of those named by ``minus``, cut to the window, over the window.
``uncovered_pct``: 100 less that.  ``per_ms``: the summed durations of the
intervals named by ``names`` that started in the window over the count of
those named by ``per``, in milliseconds.  The window is the traced seconds
where the run has a trace (``from_s`` after the window's start, which has
to be the traffic mix's ``trace.start_s``, for the trace's ``window_s``),
else the whole window.  With no interval of ``names`` in the window, or a
program that keeps no timeline, there is nothing to read."""

from __future__ import annotations

from chipbench.trace_reduce import union_ns as union_length  # of (start, end) pairs, any unit


def window(spec: dict, ctx: dict) -> tuple[float, float]:
    """The wall-clock seconds the metric is read over."""
    start = ctx["start_wall"]
    trace = ctx.get("trace")
    if trace:
        since = start + spec["from_s"]
        return since, since + trace["window_s"]
    return start, start + ctx["span_s"]


def measure(spec: dict, records: list[dict], since: float, until: float):
    """The stat of ``spec`` over ``records`` and the window; the tests
    hand it a timeline built by hand."""
    if until <= since:
        return None

    def cut(names) -> list[tuple[float, float]]:
        return [
            (max(r["start"], since), min(r["end"], until))
            for r in records
            if r["name"] in names and r["end"] > since and r["start"] < until
        ]

    stat = spec["stat"]
    if stat == "per_ms":
        started = [r for r in records if since <= r["start"] < until]
        count = sum(1 for r in started if r["name"] == spec["per"])
        named = [r["end"] - r["start"] for r in started if r["name"] in spec["names"]]
        if not named or not count:
            return None
        return 1e3 * sum(named) / count
    named = cut(spec["names"])
    if not named:
        return None
    holes = cut(spec.get("minus", ()))
    covered = union_length(named + holes)[0] - union_length(holes)[0]  # |A \ B| = |A u B| - |B|
    share = 100.0 * covered / (until - since)
    if stat == "share_pct":
        return share
    if stat == "uncovered_pct":
        return 100.0 - share
    raise ValueError(f"unknown timeline stat {stat!r}")


def read(spec: dict, ctx: dict):
    from pathway_tpu.engine import tracing

    timeline = getattr(tracing, "timeline", None)
    if timeline is None:  # a program from before the timeline
        return None
    since, until = window(spec, ctx)
    return measure(spec, timeline(since, until), since, until)
