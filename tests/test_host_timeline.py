"""The host timeline of ``engine/tracing.py`` (ISSUE 26): closed intervals
of the threads' own work beside the request traces, on their clock —
the ring and its switch, the scheduler's tick phases and the device's
in-flight intervals, the epoch barrier and what a request waits for its
epoch."""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

import pytest

from pathway_tpu.engine import metrics as em
from pathway_tpu.engine import tracing

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.reset_for_tests()
    yield
    tracing.reset_for_tests()


def _durations(records, name):
    return [r["end"] - r["start"] for r in records if r["name"] == name]


# ---------------------------------------------------------------------------
# The ring, the window, the switch, the collector
# ---------------------------------------------------------------------------


def test_ring_is_bounded_and_drops_oldest(monkeypatch):
    monkeypatch.setattr(tracing, "_timeline", type(tracing._timeline)(maxlen=8))
    for i in range(20):
        tracing.end(tracing.begin("t", "x", i=i))
    records = tracing.timeline()
    assert [r["attributes"]["i"] for r in records] == list(range(12, 20))
    # the totals count what the ring has dropped too
    assert tracing.phase_totals()[("t", "x")][1] == 20


def test_nested_intervals_give_self_time():
    with tracing.interval("engine", "outer"):
        time.sleep(0.02)
        with tracing.interval("engine", "inner", rows=3):
            time.sleep(0.03)
        time.sleep(0.01)
    inner, outer = sorted(tracing.timeline(), key=lambda r: r["end"] - r["start"])
    assert (inner["name"], outer["name"]) == ("inner", "outer")
    assert outer["start"] <= inner["start"] and inner["end"] <= outer["end"]
    assert inner["attributes"] == {"rows": 3}
    self_s = (outer["end"] - outer["start"]) - (inner["end"] - inner["start"])
    assert 0.03 <= self_s < 0.03 + 0.05
    # attributes given at the close land on the record too
    tracing.end(tracing.begin("sched", "late"), programs=2)
    assert tracing.timeline()[-1]["attributes"] == {"programs": 2}


def test_switch_tiles_and_window_cuts():
    first = tracing.begin("sched", "a")
    time.sleep(0.01)
    second = tracing.switch(first, "b", rows=1)
    time.sleep(0.01)
    tracing.end(second)
    a, b = tracing.timeline()
    assert (a["name"], b["name"], b["track"]) == ("a", "b", "sched")
    assert a["end"] == b["start"]  # one clock reading for both
    # a window keeps what overlaps it, oldest first
    assert [r["name"] for r in tracing.timeline(until=a["end"] - 1e-4)] == ["a"]
    assert [r["name"] for r in tracing.timeline(since=b["start"] + 1e-4)] == ["b"]
    middle = (a["start"] + a["end"]) / 2
    assert [r["name"] for r in tracing.timeline(middle, b["end"] + 1)] == ["a", "b"]
    assert tracing.timeline(since=b["end"] + 1.0) == []


def test_switch_off_records_nothing_and_enters_no_annotation(monkeypatch):
    entered = []

    class Annotation:
        def __init__(self, name):
            entered.append(name)

        def __exit__(self, *_exc):
            pass

        listening = True

        @classmethod
        def is_enabled(cls):
            return cls.listening

    monkeypatch.setattr(tracing, "_TraceAnnotation", Annotation)
    with tracing.interval("t", "on"):
        pass
    assert entered == ["on"] and len(tracing.timeline()) == 1
    Annotation.listening = False  # no profiler session: the interval alone
    with tracing.interval("t", "unheard"):
        pass
    assert entered == ["on"] and len(tracing.timeline()) == 2
    Annotation.listening = True
    monkeypatch.setenv("PATHWAY_TRACE_REQUESTS", "0")
    tracing.reset_for_tests()  # the timeline looks at the knob again
    assert tracing.begin("t", "off") is None
    with tracing.interval("t", "off"):
        pass
    tracing.end(None)
    assert tracing.switch(None, "off") is None
    assert entered == ["on"]
    assert len(tracing.timeline()) == 0 and tracing.phase_totals() == {}
    # a request's own look at the knob flips the timeline with it, at once
    monkeypatch.setenv("PATHWAY_TRACE_REQUESTS", "1")
    assert tracing.begin_request("/q") is not None
    assert tracing.begin("t", "on-again") is not None


def test_collector_totals_equal_the_rings_sums():
    for i in range(5):
        with tracing.interval("sched", "tick.admit"):
            time.sleep(0.001 * i)
    tracing.end(tracing.begin("serve", "serve.idle"))
    records = tracing.timeline()
    scalars = em.get_registry().scalar_metrics()
    for track, name in (("sched", "tick.admit"), ("serve", "serve.idle")):
        labels = f"{{track={track},name={name}}}"
        durations = _durations(records, name)
        assert scalars[f"host.phase.count{labels}"] == len(durations)
        assert scalars[f"host.phase.seconds{labels}"] == pytest.approx(sum(durations))
    # flight-recorder dumps carry the totals, never the ring
    assert tracing.snapshot()["timeline"]["sched/tick.admit"]["count"] == 5
    json.dumps(tracing.snapshot())


def test_admission_controller_opens_serve_idle_between_requests():
    from pathway_tpu.engine.serving import AdmissionController

    controller = AdmissionController(
        inflight_limit=4, inflight_bytes=1 << 20, queue_limit=8, target_delay_ms=250.0,
        shed_dwell_s=1.0, recover_s=5.0, drain_s=10.0,
    )
    with controller._lock:
        one = controller._grant_locked("/q", 1, 0.0)
        two = controller._grant_locked("/q", 1, 0.0)
    controller.release(one)
    assert tracing.timeline() == [] and controller._idle is None  # one still in flight
    controller.release(two)
    time.sleep(0.01)
    with controller._lock:
        three = controller._grant_locked("/q", 1, 0.0)
    (idle,) = tracing.timeline()
    assert (idle["track"], idle["name"]) == ("serve", "serve.idle")
    assert idle["end"] - idle["start"] >= 0.01
    controller.release(three)


# ---------------------------------------------------------------------------
# The scheduler: tick phases, device.inflight, generate.prefill
# ---------------------------------------------------------------------------

PHASES = (
    "tick.admit", "tick.prefill.prepare", "tick.prefill.enqueue", "tick.decode.prepare",
    "tick.decode.upload", "tick.decode.enqueue", "tick.decode.sync", "tick.deliver",
)


def test_tick_phases_tile_the_tick_and_inflight_never_overlaps():
    pytest.importorskip("jax")
    from pathway_tpu.models.decoder import shared_decoder
    from pathway_tpu.serving import generation

    lm = shared_decoder("pw-tiny-decoder", max_cache=64)
    sched = generation.GenerationScheduler(
        lm, slots=2, page_size=16, prefill_chunk=4, queue_limit=16
    )
    traces = [tracing.RequestTrace("/v1/generate") for _ in range(3)]
    reqs = [
        generation.GenRequest([3, 5, 7, 11, 13, 17, 19, 23, 29, 31], 6, trace=traces[0]),
        generation.GenRequest([2, 4, 6], 3, trace=traces[1]),
        generation.GenRequest([8, 9, 10, 12, 14], 4, trace=traces[2]),
    ]
    with sched._lock:
        sched._queue.append(reqs[0])
    ticks = []  # (wall start, wall end) of every hand-driven tick
    lone_sent = False
    try:
        for _ in range(100):
            if all(r.future.done() for r in reqs):
                break
            if len(ticks) == 4:  # the short one arrives while the long one decodes
                with sched._lock:
                    sched._queue.append(reqs[1])
            if not lone_sent and reqs[0].future.done() and reqs[1].future.done():
                lone_sent = True  # the third meets a drained device: a lone answer
                with sched._lock:
                    sched._queue.append(reqs[2])
            started = time.time()
            sched._tick()
            ticks.append((started, time.time()))
    finally:
        sched.shutdown()
    assert all(r.future.done() for r in reqs) and len(ticks) >= 12
    assert sched._step is None and sched._inflight is None
    records = [r for r in tracing.timeline() if r["track"] == "sched"]
    phases = [r for r in records if r["name"] in PHASES]
    assert {r["name"] for r in phases} == set(PHASES)
    admits = [r for r in phases if r["name"] == "tick.admit"]
    assert [r["attributes"]["tick"] for r in admits] == list(range(1, len(ticks) + 1))
    shares = []
    for started, ended in ticks:
        mine = [r for r in phases if started <= r["start"] and r["end"] <= ended]
        assert mine[0]["name"] == "tick.admit"
        assert all(a["end"] == b["start"] for a, b in zip(mine, mine[1:]))
        # a decode step is enqueued before the one in flight is read
        names = [r["name"] for r in mine]
        if "tick.decode.enqueue" in names and "tick.decode.sync" in names:
            assert names.index("tick.decode.enqueue") < names.index("tick.decode.sync")
        # the step's arrays are made between its bookkeeping and its call
        if "tick.decode.enqueue" in names:
            at = names.index("tick.decode.upload")
            assert names[at - 1:at + 2] == [
                "tick.decode.prepare", "tick.decode.upload", "tick.decode.enqueue"
            ]
        shares.append(sum(r["end"] - r["start"] for r in mine) / (ended - started))
    # each tick's phases tile its wall time (a tick the machine took the
    # thread away from, between the test's clock and the tick's, is let off)
    assert sorted(shares)[len(shares) // 10] >= 0.95
    inflight = sorted(
        (r for r in records if r["name"] == "device.inflight"), key=lambda r: r["start"]
    )
    assert all(a["end"] <= b["start"] for a, b in zip(inflight, inflight[1:]))
    # the device never drains while a step runs ahead: the long prompt's
    # three chunks, the short one's chunk and the six steps they decode in
    # (the short one's three among them) ride one interval; the lone answer
    # that follows rides another, its two chunks and all four of its steps
    assert [r["attributes"] for r in inflight] == [{"programs": 10}, {"programs": 6}]
    assert inflight[0]["start"] < ticks[0][1] and inflight[0]["end"] > ticks[7][0]
    syncs = [r for r in phases if r["name"] == "tick.decode.sync"]
    enqueues = [r for r in phases if r["name"] == "tick.decode.enqueue"]
    uploads = [r for r in phases if r["name"] == "tick.decode.upload"]
    assert len(syncs) == len(enqueues) == len(uploads) == 10  # every step enqueued is read, once
    assert sum(r["attributes"]["programs"] for r in inflight) == len(syncs) + sum(
        1 for r in phases if r["name"] == "tick.prefill.enqueue"
    )
    for sync in syncs:  # the read of a step lies inside the interval it rode
        assert any(
            interval["start"] <= sync["start"] and sync["end"] <= interval["end"] + 1e-3
            for interval in inflight
        )
    # one generate.prefill span a request, and it ends with the sync that
    # hands out the first token (two clocks read within the same microseconds)
    for trace, chunks in zip(traces, (3, 1, 2)):
        (prefill,) = [s for s in trace.spans if s["name"] == "generate.prefill"]
        (ttft,) = [s for s in trace.spans if s["name"] == "generate.ttft"]
        assert prefill["attributes"]["chunks"] == chunks
        assert prefill["start"] >= ttft["start"]
        prefill_end = prefill["start"] + prefill["duration_s"]
        assert prefill_end >= ttft["start"] + ttft["duration_s"] - 1e-3
        assert len(trace.spans) <= 5  # queue, prefill, ttft, decode


def test_idle_scheduler_records_one_interval_per_wait():
    pytest.importorskip("jax")
    from pathway_tpu.models.decoder import shared_decoder
    from pathway_tpu.serving import generation

    lm = shared_decoder("pw-tiny-decoder", max_cache=64)
    sched = generation.GenerationScheduler(
        lm, slots=2, page_size=16, prefill_chunk=8, queue_limit=16
    )
    try:
        assert len(sched.submit_ids([3, 5, 7], max_new_tokens=2).result(timeout=120)) == 2
        time.sleep(1.2)  # two 0.5 s time-outs of the wait pass
        assert len(sched.submit_ids([3, 5, 7], max_new_tokens=2).result(timeout=120)) == 2
    finally:
        sched.shutdown()
    idles = [r for r in tracing.timeline() if r["name"] == "sched.idle"]
    between = [r for r in idles if r["end"] - r["start"] >= 1.0]
    assert len(between) == 1
    # the panel's gauges come from one collector, evaluated when scraped
    scalars = em.get_registry().scalar_metrics()
    assert scalars["generate.slots.total"] == 2.0 and scalars["generate.slots.active"] == 0.0
    assert scalars["generate.pages.used"] == 0.0 and scalars["generate.kv.bytes.peak"] > 0


# ---------------------------------------------------------------------------
# Serving: what a request waits for its epoch behind another's async UDF
# ---------------------------------------------------------------------------

SERVER_SCRIPT = """
import asyncio, json, os, sys, threading, time, urllib.request
import pathway_tpu as pw
from pathway_tpu.engine import metrics, tracing

port, out = int(sys.argv[1]), sys.argv[2]

class QuerySchema(pw.Schema):
    a: int

@pw.udf
async def slow(a: int) -> int:
    await asyncio.sleep(0.4)
    return a + 1

server = pw.io.http.PathwayWebserver(host="127.0.0.1", port=port)
queries, respond = pw.io.http.rest_connector(
    webserver=server, route="/slow", schema=QuerySchema, delete_completed_queries=True,
)
respond(queries.select(result=slow(pw.this.a)))

def post(a):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/slow", data=json.dumps({"a": a}).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())

def waits():
    points = [p for p in metrics.get_registry().histogram_points() if p["name"] == "serve.epoch.wait.ms"]
    return sum(p["count"] for p in points), [p["labels"] for p in points]

def drive():
    deadline = time.time() + 60
    while True:
        try:
            post(0)
            break
        except OSError:
            if time.time() > deadline:
                os._exit(3)
            time.sleep(0.2)
    time.sleep(0.3)
    before, _ = waits()
    since = time.time()
    answers = []
    second = threading.Thread(target=lambda: (time.sleep(0.05), answers.append(post(20))))
    second.start()
    answers.append(post(10))
    second.join()
    after, labels = waits()
    with open(out, "w") as f:
        json.dump({
            "answers": sorted(answers), "counted": after - before, "labels": labels,
            "requests": [r for r in tracing.recent_requests(10) if r["start"] >= since],
            "timeline": tracing.timeline(since),
        }, f)
    os._exit(0)

threading.Thread(target=drive, daemon=True).start()
pw.run(terminate_on_error=False)
"""


def test_second_request_waits_for_its_epoch_behind_the_firsts_async_udf(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script, out = tmp_path / "serve.py", tmp_path / "out.json"
    script.write_text(SERVER_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, str(script), str(port), str(out)],
        capture_output=True, text=True, env=env, timeout=180,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    seen = json.loads(out.read_text())
    assert seen["answers"] == [11, 21]
    assert seen["counted"] == 2 and seen["labels"] == [{"route": "/slow"}]
    first, second = sorted(seen["requests"], key=lambda r: r["start"])
    waits = []
    for request in (first, second):
        names = [s["name"] for s in request["spans"]]
        assert names.count("serve.epoch.wait") == 1
        (wait,) = [s for s in request["spans"] if s["name"] == "serve.epoch.wait"]
        assert wait["attributes"]["epoch"] % 2 == 0
        waits.append(wait)
    # the first met an idle engine; the second was committed while the
    # first's epoch sat in its barrier, and its epoch started after it
    barriers = [
        r for r in seen["timeline"]
        if r["name"] == "epoch.async_wait" and r["end"] > waits[0]["start"]
    ]
    assert barriers[0]["attributes"] == {"rows": 1} and barriers[0]["track"] == "engine"
    barrier_s = barriers[0]["end"] - barriers[0]["start"]
    assert barrier_s >= 0.4
    gap_s = waits[1]["start"] - waits[0]["start"]
    assert gap_s >= 0.04
    assert waits[0]["duration_s"] < 0.2
    assert waits[1]["duration_s"] >= barrier_s - gap_s - 0.02
    assert waits[1]["start"] + waits[1]["duration_s"] >= barriers[0]["end"]
    # the barrier lies inside its epoch's run
    runs = [r for r in seen["timeline"] if r["name"] == "epoch.run"]
    assert any(
        run["start"] <= barriers[0]["start"] and barriers[0]["end"] <= run["end"]
        and run["attributes"]["epoch"] == waits[0]["attributes"]["epoch"]
        for run in runs
    )
