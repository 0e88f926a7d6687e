"""The reader kinds ``timeline`` and ``spans``: the program's host timeline
and request traces read as per-layer metrics, on a timeline built by hand
and through the program's own ring; and the metric files that use them."""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from chipbench import manifest, readers  # noqa: E402
from chipbench.readers import spans, timeline  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
T0 = 1_700_000_000.0  # the window's start on the wall clock


def record(name: str, start: float, end: float, track: str = "sched") -> dict:
    return {"track": track, "name": name, "start": T0 + start, "end": T0 + end, "attributes": {}}


def by_hand() -> list[dict]:
    """Ten seconds: no request for the first two and the last one; the
    scheduler idle for the first three (a request retrieves in the third)
    and the last one; four decode ticks."""
    records = [
        record("serve.idle", -5.0, 2.0, "serve"), record("serve.idle", 9.0, 14.0, "serve"),
        record("sched.idle", -5.0, 3.0), record("sched.idle", 9.0, 14.0),
        record("device.call", 2.5, 3.0, "executor"),
        record("device.inflight", 3.0, 5.0), record("device.inflight", 4.5, 8.5),
        record("epoch.async_wait", 2.9, 9.0, "engine"),
    ]
    for i in range(4):
        at = 4.0 + i
        records += [
            record("tick.admit", at, at + 0.001), record("tick.decode.prepare", at + 0.001, at + 0.003),
            record("tick.decode.enqueue", at + 0.003, at + 0.004), record("tick.decode.sync", at + 0.004, at + 0.020),
            record("tick.deliver", at + 0.020, at + 0.021),
        ]
    records.append(record("tick.admit", 3.5, 3.502))  # a prefill-only tick's
    return records


@pytest.mark.parametrize("spec,expected", [
    ({"stat": "share_pct", "names": ["serve.idle"]}, 30.0),
    ({"stat": "share_pct", "names": ["sched.idle"], "minus": ["serve.idle"]}, 10.0),
    ({"stat": "share_pct", "names": ["epoch.async_wait"]}, 61.0),
    ({"stat": "uncovered_pct", "names": ["device.inflight", "device.call"]}, 40.0),
    ({"stat": "per_ms", "names": ["tick.admit", "tick.decode.prepare", "tick.deliver"], "per": "tick.decode.sync"}, (4 * 4.0 + 2.0) / 4),
    ({"stat": "share_pct", "names": ["no.such.interval"]}, None),
    ({"stat": "per_ms", "names": ["tick.admit"], "per": "no.such.tick"}, None),
], ids=lambda v: f"{v['stat']}:{v['names'][0]}" if isinstance(v, dict) else None)
def test_timeline_stats_on_a_timeline_built_by_hand(spec, expected):
    got = timeline.measure(spec, by_hand(), T0, T0 + 10.0)
    # a double holds the wall clock to a fifth of a microsecond
    assert got == (pytest.approx(expected, rel=1e-4) if expected is not None else None)


def test_timeline_window_is_the_traced_seconds_or_the_whole_window():
    ctx = {"start_wall": T0, "span_s": 52.5, "trace": None}
    assert timeline.window({"from_s": 10.0}, ctx) == (T0, T0 + 52.5)
    ctx["trace"] = {"window_s": 29.96}
    assert timeline.window({"from_s": 10.0}, ctx) == (T0 + 10.0, T0 + 39.96)
    # a cut window reads only what lies inside it: the last idle second
    spec = {"stat": "share_pct", "names": ["serve.idle"]}
    assert timeline.measure(spec, by_hand(), T0 + 8.0, T0 + 10.0) == pytest.approx(50.0)
    assert timeline.measure(spec, by_hand(), T0 + 3.0, T0 + 3.0) is None


def request(finished: float, ttft_ms: float) -> dict:
    return {
        "start": T0 + finished - 1.7, "duration_s": 1.7,
        "spans": [
            {"name": "generate.ttft", "start": T0 + finished - 1.6, "duration_s": ttft_ms / 1e3},
            {"name": "generate.decode", "start": T0 + finished - 1.3, "duration_s": 1.3},
        ],
    }


def test_spans_stats_over_the_requests_finished_in_the_window():
    requests = [request(at, ttft) for at, ttft in ((1.0, 900.0), (3.0, 300.0), (5.0, 310.0), (7.0, 296.0), (12.0, 50.0))]
    spec = {"span": "generate.ttft", "stat": "percentile", "percentile": 50}
    assert spans.measure(spec, requests, T0 + 2.0, T0 + 10.0, 256) == pytest.approx(300.0)
    assert spans.measure({**spec, "stat": "mean"}, requests, T0 + 2.0, T0 + 10.0, 256) == pytest.approx(302.0)
    assert spans.measure({**spec, "span": "no.such.span"}, requests, T0 + 2.0, T0 + 10.0, 256) is None
    assert spans.measure(spec, requests, T0 + 20.0, T0 + 30.0, 256) is None
    # a full ring whose oldest request finished inside the window may have
    # dropped requests of the window: nothing sound to read
    assert spans.measure(spec, requests, T0 + 0.5, T0 + 10.0, 5) is None
    assert spans.measure(spec, requests, T0 + 2.0, T0 + 10.0, 5) == pytest.approx(300.0)


def test_both_kinds_read_the_programs_own_rings():
    import time

    from pathway_tpu.engine import tracing

    tracing.reset_for_tests()
    try:
        start = time.time()
        with tracing.interval("engine", "epoch.async_wait", rows=1):
            time.sleep(0.05)
        trace = tracing.RequestTrace("/v2/answer")
        trace.add_span("generate.ttft", time.time(), 0.25, prompt_len=7)
        trace.finish(status=200)
        ctx = {"start_wall": start, "span_s": time.time() - start + 0.05, "trace": None}
        share = readers.evaluate(
            {"reader": "timeline", "stat": "share_pct", "names": ["epoch.async_wait"], "from_s": 10.0}, ctx)
        assert 20.0 < share < 100.0
        assert readers.evaluate(
            {"reader": "spans", "span": "generate.ttft", "stat": "percentile", "percentile": 50, "from_s": 10.0}, ctx
        ) == pytest.approx(250.0)
        # a program from before the timeline: nothing to read, nothing raised
        del_timeline = tracing.timeline
        try:
            del tracing.timeline
            assert readers.evaluate(
                {"reader": "timeline", "stat": "share_pct", "names": ["epoch.async_wait"], "from_s": 10.0}, ctx
            ) is None
        finally:
            tracing.timeline = del_timeline
    finally:
        tracing.reset_for_tests()


PROGRAM_CLOCK = [
    m for m in BENCH["per_layer"]
    if manifest.metric_file("per_layer", m["name"])["reader"] in ("timeline", "spans")
]


@pytest.mark.parametrize("metric", PROGRAM_CLOCK, ids=lambda m: m["name"])
def test_metric_files_read_the_seconds_their_cells_trace(metric):
    body = manifest.metric_file("per_layer", metric["name"])
    assert metric["source"] == "program_span" and metric["workloads"]
    for cell in metric["workloads"]:
        mix = manifest.traffic_mix(manifest.cell_entry(BENCH, cell)["traffic"])
        assert body["from_s"] == mix["trace"]["start_s"]
    if body["reader"] == "timeline":
        assert body["stat"] in ("share_pct", "uncovered_pct", "per_ms") and body["names"]
        assert (body["stat"] == "per_ms") == ("per" in body)
    else:
        assert body["stat"] in ("percentile", "mean") and body["span"]


def test_the_names_the_metric_files_read_are_opened_by_the_program():
    """Every interval, span and histogram a metric file names is written
    somewhere in the program's source, under that name."""
    source = "\n".join(
        (REPO / "pathway_tpu" / path).read_text()
        for path in ("engine/tracing.py", "engine/serving.py", "engine/dataflow.py",
                     "serving/generation.py", "device/executor.py", "internals/runner.py")
    )
    assert len(PROGRAM_CLOCK) >= 6
    for metric in PROGRAM_CLOCK:
        body = manifest.metric_file("per_layer", metric["name"])
        names = body.get("names", []) + body.get("minus", []) + [body.get("per"), body.get("span")]
        for name in filter(None, names):
            assert f'"{name}"' in source, (metric["name"], name)
    stage_wait = manifest.metric_file("per_layer", "epoch.stage_wait_ms.answer")
    assert stage_wait["reader"] == "registry" and f'"{stage_wait["histogram"]}"' in source
