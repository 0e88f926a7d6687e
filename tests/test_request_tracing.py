"""Request-scoped distributed tracing (engine/tracing.py) — ISSUE 19.

One trace id across the serving path: W3C ``traceparent`` in/out, child
spans with ids minted at creation, ambient + explicit propagation across
the thread hops (batcher coalesce, device dispatch, generation ticks),
histogram exemplars, and the surfacing layer (``/status`` requests
section, waterfall rendering, flight-recorder snapshot).
"""

from __future__ import annotations

import asyncio
import json
import threading
import time

import numpy as np
import pytest

from pathway_tpu.engine import faults
from pathway_tpu.engine import flight_recorder as blackbox
from pathway_tpu.engine import metrics as em
from pathway_tpu.engine import serving
from pathway_tpu.engine import tracing
from pathway_tpu.engine.metrics import MetricsRegistry
from pathway_tpu.engine.serving import AdmissionController, Deadline
from pathway_tpu.utils.batching import AsyncMicroBatcher

W3C_PARENT = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.reset_for_tests()
    faults.clear_plan()
    yield
    tracing.reset_for_tests()
    faults.clear_plan()


def _counter(name: str, **labels) -> float:
    return em.get_registry().counter(name, **labels).value


def _mk_controller(**overrides) -> AdmissionController:
    kwargs = dict(
        inflight_limit=4,
        inflight_bytes=1 << 20,
        queue_limit=8,
        target_delay_ms=250.0,
        shed_dwell_s=1.0,
        recover_s=5.0,
        drain_s=10.0,
    )
    kwargs.update(overrides)
    return AdmissionController(**kwargs)


# ---------------------------------------------------------------------------
# RequestTrace basics: ids, parent links, cap, finish, ring
# ---------------------------------------------------------------------------


def test_minted_ids_and_traceparent_shape():
    t = tracing.RequestTrace("/v1/embed")
    assert len(t.trace_id) == 32 and len(t.root_span_id) == 16
    assert t.parent_span_id == ""  # minted root: no upstream caller
    assert t.traceparent() == f"00-{t.trace_id}-{t.root_span_id}-01"


def test_ingress_traceparent_adopted():
    t = tracing.RequestTrace("/v1/embed", W3C_PARENT)
    assert t.trace_id == "ab" * 16
    # the caller's span id becomes OUR root's parent — the collector
    # stitches our serve.request under the upstream client span
    assert t.parent_span_id == "cd" * 8
    assert t.root_span_id != "cd" * 8


def test_child_spans_parent_to_root_and_chain():
    t = tracing.RequestTrace("/q")
    first = t.add_span("serve.admission", time.time(), 0.001, inflight=1)
    second = t.add_span("serve.batch", time.time(), 0.002, parent_span_id=first)
    t.finish(status=200)
    by_name = {s["name"]: s for s in t.spans}
    assert by_name["serve.admission"]["parent_span_id"] == t.root_span_id
    assert by_name["serve.batch"]["parent_span_id"] == first
    assert second != first
    root = by_name["serve.request"]
    assert root["span_id"] == t.root_span_id
    assert root["attributes"]["status"] == 200
    assert {s["trace_id"] for s in t.spans} == {t.trace_id}


def test_span_cap_drops_newest_and_counts():
    before = _counter("trace.spans.dropped")
    t = tracing.RequestTrace("/q")
    for i in range(tracing.MAX_SPANS_PER_TRACE + 5):
        t.add_span(f"s{i}", time.time(), 0.0)
    assert len(t.spans) == tracing.MAX_SPANS_PER_TRACE
    t.finish(status=200)  # the root close always lands
    assert len(t.spans) == tracing.MAX_SPANS_PER_TRACE + 1
    assert t.summary()["spans_dropped"] == 5
    assert _counter("trace.spans.dropped") - before == 5.0


def test_finish_is_idempotent_and_rings_once():
    t = tracing.RequestTrace("/q")
    t.finish(status=200)
    first_duration = t.duration_s
    t.finish(status=500)  # late second close: the first wins
    assert t.status == 200 and t.duration_s == first_duration
    assert len(tracing.recent_requests()) == 1
    state = tracing.requests_state()
    assert state["trace.requests.buffered"] == 1.0
    assert "trace.requests.slowest.ms" in state


def test_slowest_requests_orders_by_duration():
    for ms, route in ((5, "/fast"), (50, "/slow"), (20, "/mid")):
        t = tracing.RequestTrace(route)
        t.started = time.time() - ms / 1000.0
        t.finish(status=200)
    slowest = tracing.slowest_requests(2)
    assert [t["route"] for t in slowest] == ["/slow", "/mid"]
    recent = tracing.recent_requests(2)
    assert recent[0]["route"] == "/mid"  # newest first


def test_begin_request_off_switch(monkeypatch):
    monkeypatch.setenv("PATHWAY_TRACE_REQUESTS", "0")
    assert not tracing.enabled()
    assert tracing.begin_request("/q") is None
    monkeypatch.setenv("PATHWAY_TRACE_REQUESTS", "1")
    assert tracing.begin_request("/q") is not None


def test_active_trace_and_key_binding():
    t = tracing.begin_request("/q")
    assert tracing.active_trace(t.traceparent()) is t
    assert tracing.active_trace("garbage") is None
    assert tracing.active_trace(None) is None
    tracing.bind_key(7, t)
    assert tracing.trace_for_key(7) is t
    assert tracing.trace_for_key(8) is None
    tracing.unbind_key(7)
    assert tracing.trace_for_key(7) is None
    t.finish(status=200)  # finish unregisters from the active index
    assert tracing.active_trace(t.traceparent()) is None


def test_ambient_scope_and_span_context_manager():
    t = tracing.RequestTrace("/q")
    assert tracing.current_trace() is None
    with tracing.trace_scope(t):
        assert tracing.current_trace() is t
        with t.span("serve.epoch.wait", source="rest"):
            pass
    assert tracing.current_trace() is None
    (span,) = t.spans
    assert span["name"] == "serve.epoch.wait"
    assert span["attributes"]["source"] == "rest"
    # None-scope is a no-op (tracing disabled costs one branch)
    with tracing.trace_scope(None):
        assert tracing.current_trace() is None


# ---------------------------------------------------------------------------
# Histogram exemplars
# ---------------------------------------------------------------------------


def test_histogram_exemplar_rendered_in_openmetrics():
    reg = MetricsRegistry(enabled=True)
    h = reg.histogram(
        "serve.latency.ms", "request latency", buckets=(1, 10, 100)
    )
    h.observe(0.5)  # untraced: no exemplar for this bucket
    h.observe(5.0, trace_id="ab" * 16)
    h.observe(7.0, trace_id="cd" * 16)  # same bucket: last trace wins
    text = reg.render_prometheus()
    assert '# {trace_id="' + "cd" * 16 + '"} 7 ' in text
    assert "ab" * 16 not in text
    points = reg.exemplar_points()
    (exemplar,) = points["serve.latency.ms"]
    assert exemplar["trace_id"] == "cd" * 16
    assert exemplar["value"] == 7.0
    assert exemplar["le"] == "10.0"


def test_untraced_histogram_pays_no_exemplar_state():
    reg = MetricsRegistry(enabled=True)
    h = reg.histogram("epoch.duration.ms", "epochs", buckets=(1, 10))
    h.observe(5.0)
    assert h._exemplars is None  # lazily allocated only when traced
    assert reg.exemplar_points() == {}


# ---------------------------------------------------------------------------
# Admission: the trace's birthplace
# ---------------------------------------------------------------------------


def test_admission_births_trace_with_span():
    serving.reset_for_tests()
    before = _counter("trace.requests")
    c = _mk_controller()
    ticket = asyncio.run(
        c.admit("/v1/q", 10, Deadline.from_ms(30_000), trace_parent=W3C_PARENT)
    )
    assert ticket.trace is not None
    assert ticket.trace.trace_id == "ab" * 16  # ingress header adopted
    (span,) = ticket.trace.spans
    assert span["name"] == "serve.admission"
    assert "inflight" in span["attributes"]
    assert _counter("trace.requests") - before == 1.0
    c.release(ticket)
    serving.reset_for_tests()


def test_admission_rejection_finishes_trace_with_status():
    serving.reset_for_tests()
    c = _mk_controller()
    c.begin_drain()

    async def scenario():
        with pytest.raises(serving.DrainingError):
            await c.admit("/v1/q", 10, Deadline.from_ms(30_000))

    asyncio.run(scenario())
    (summary,) = tracing.recent_requests()
    assert summary["status"] == 503
    assert summary["route"] == "/v1/q"
    serving.reset_for_tests()


# ---------------------------------------------------------------------------
# Cross-event-loop batcher propagation
# ---------------------------------------------------------------------------


def test_batcher_coalesce_spans_across_event_loops():
    """Two serving threads (each its own asyncio loop, its own ambient
    trace) coalesce into ONE batch: each trace gets its OWN serve.batch
    span, and the batch thread sees both traces via _JOB_TRACES."""
    from pathway_tpu.device.executor import _current_traces

    gate = threading.Event()
    seen_in_batch: list[tuple] = []

    class GatedBatcher(AsyncMicroBatcher):
        def flush(self):
            if not gate.is_set():
                return  # hold coalescing open until both loops submitted
            super().flush()

    def process(items):
        seen_in_batch.append(_current_traces())
        return [x * 10 for x in items]

    batcher = GatedBatcher(
        process, max_batch_size=8, flush_delay=0.005, run_in_thread=True
    )
    traces = [tracing.RequestTrace("/a"), tracing.RequestTrace("/b")]
    results: dict[int, int] = {}

    def worker(i: int):
        async def one():
            with tracing.trace_scope(traces[i]):
                return await batcher.submit(i + 1)

        results[i] = asyncio.run(one())

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for th in threads:
        th.start()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        with batcher._lock:
            if len(batcher._pending) == 2:
                break
        time.sleep(0.001)
    gate.set()
    for th in threads:
        th.join(timeout=10)
    assert results == {0: 10, 1: 20}  # each waiter got its own result
    assert len(seen_in_batch) == 1  # ONE coalesced batch served both
    assert set(seen_in_batch[0]) == set(traces)
    for t in traces:
        (span,) = [s for s in t.spans if s["name"] == "serve.batch"]
        assert span["attributes"]["batch_size"] == 2
        assert span["trace_id"] == t.trace_id
        assert span["parent_span_id"] == t.root_span_id


def test_batcher_captures_trace_at_submit_not_dispatch():
    """The ambient trace is read in the WAITER's context; the flush may
    run anywhere (here: a bare thread with no ambient trace)."""
    calls: list[tuple] = []
    batcher = AsyncMicroBatcher(
        lambda items: [calls.append(None) or x for x in items],
        max_batch_size=4,
        flush_delay=0.001,
        run_in_thread=True,
    )
    t = tracing.RequestTrace("/q")

    async def one():
        with tracing.trace_scope(t):
            return await batcher.submit(42)

    assert asyncio.run(one()) == 42
    assert any(s["name"] == "serve.batch" for s in t.spans)


# ---------------------------------------------------------------------------
# Device executor span attributes (retry / fallback / cache)
# ---------------------------------------------------------------------------


def _linear_executor():
    pytest.importorskip("jax")
    from pathway_tpu.device import BucketPolicy, DeviceExecutor

    ex = DeviceExecutor(collector_name=None)
    ex.register(
        "lin",
        lambda x: x * 2.0 + 1.0,
        policy=BucketPolicy(max_bucket=8),
    )
    return ex


def _dispatch_spans(trace):
    return [s for s in trace.spans if s["name"] == "device.dispatch"]


def test_device_dispatch_span_cold_then_warm():
    ex = _linear_executor()
    rows = np.ones((2, 4), np.float32)
    t = tracing.RequestTrace("/q")
    try:
        with tracing.trace_scope(t):
            ex.run_batch("lin", (rows,))
            ex.run_batch("lin", (rows,))
    finally:
        ex.close()
    spans = _dispatch_spans(t)
    assert [s["attributes"]["cache"] for s in spans] == ["cold", "warm"]
    for s in spans:
        assert s["attributes"]["callable"] == "lin"
        assert s["attributes"]["rows"] == 2
        assert "retries" not in s["attributes"]
        assert "fallback" not in s["attributes"]


def test_device_dispatch_span_records_retries(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_RETRY_BACKOFF_MS", "1")
    ex = _linear_executor()
    rows = np.ones((2, 4), np.float32)
    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "device_error", "source": "lin", "nth": 2}], seed=13
        )
    )
    t = tracing.RequestTrace("/q")
    try:
        with tracing.trace_scope(t):
            ex.run_batch("lin", (rows,))  # warms the cache (dispatch #1)
            out = ex.run_batch("lin", (rows,))  # fails once, retried
    finally:
        ex.close()
    np.testing.assert_allclose(np.asarray(out), rows * 2.0 + 1.0)
    retried = [s for s in _dispatch_spans(t) if "retries" in s["attributes"]]
    assert retried and retried[0]["attributes"]["retries"] >= 1


def test_device_dispatch_span_records_fallback(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_RETRY_BACKOFF_MS", "1")
    ex = _linear_executor()
    rows = np.ones((2, 4), np.float32)
    # every device attempt fails: retries exhaust, the host fallback
    # serves the batch — the span must say so
    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "device_error", "source": "lin", "from_nth": 1}],
            seed=13,
        )
    )
    t = tracing.RequestTrace("/q")
    try:
        with tracing.trace_scope(t):
            out = ex.run_batch("lin", (rows,))
    finally:
        ex.close()
    np.testing.assert_allclose(np.asarray(out), rows * 2.0 + 1.0)
    (span,) = _dispatch_spans(t)
    assert span["attributes"]["fallback"] is True
    assert span["attributes"]["retries"] >= 1


def test_device_submit_carries_ambient_trace_across_thread_hop():
    ex = _linear_executor()
    t = tracing.RequestTrace("/q")
    try:
        with tracing.trace_scope(t):
            fut = ex.submit(lambda: 7, name="hostjob")
        assert fut.result(timeout=30) == 7
    finally:
        ex.close()
    (span,) = [s for s in t.spans if s["name"] == "device.job"]
    assert span["attributes"]["job"] == "hostjob"
    assert span["trace_id"] == t.trace_id


# ---------------------------------------------------------------------------
# Generation scheduler spans
# ---------------------------------------------------------------------------


def test_generation_spans_and_ttft_matches_histogram():
    pytest.importorskip("jax")
    from pathway_tpu.models.decoder import shared_decoder
    from pathway_tpu.serving import generation

    lm = shared_decoder("pw-tiny-decoder", max_cache=64)
    sched = generation.GenerationScheduler(
        lm, slots=2, page_size=16, prefill_chunk=4, queue_limit=16
    )
    t = tracing.RequestTrace("/v1/generate")
    try:
        with tracing.trace_scope(t):
            fut = sched.submit_ids([3, 5, 7, 11, 13, 17], max_new_tokens=4)
        out = fut.result(timeout=120)
        assert len(out) == 4
    finally:
        sched.shutdown()
    names = [s["name"] for s in t.spans]
    assert "generate.queue" in names
    assert "generate.ttft" in names
    assert "generate.decode" in names
    # one span for the whole prefill: 6 tokens in chunks of 4, from the
    # first chunk's enqueue to the sync that hands out the first token
    (prefill,) = [s for s in t.spans if s["name"] == "generate.prefill"]
    assert prefill["attributes"]["chunks"] == 2
    assert prefill["attributes"]["prompt_len"] == 6
    assert 0.0 < prefill["attributes"]["enqueue_s"] <= prefill["duration_s"]
    (ttft,) = [s for s in t.spans if s["name"] == "generate.ttft"]
    (decode,) = [s for s in t.spans if s["name"] == "generate.decode"]
    assert ttft["attributes"]["prompt_len"] == 6
    assert decode["attributes"]["tokens"] == 4
    # the TTFT span duration IS the measured first-token latency: the
    # histogram exemplar observed the same value (ms) under our trace id
    fam = em.get_registry().family("generate.ttft.ms")
    assert fam is not None
    exemplars = [
        ex
        for _key, child in fam.items()
        for ex in child.exemplars().values()
        if ex[0] == t.trace_id
    ]
    assert exemplars
    trace_id, value_ms, _ts = exemplars[0]
    assert value_ms == pytest.approx(ttft["duration_s"] * 1e3, rel=1e-6)


def test_generation_untraced_requests_record_nothing():
    pytest.importorskip("jax")
    from pathway_tpu.models.decoder import shared_decoder
    from pathway_tpu.serving import generation

    before = _counter("trace.spans")
    lm = shared_decoder("pw-tiny-decoder", max_cache=64)
    sched = generation.GenerationScheduler(
        lm, slots=2, page_size=16, prefill_chunk=8, queue_limit=16
    )
    try:
        out = sched.submit_ids([3, 5, 7], max_new_tokens=3).result(timeout=120)
        assert len(out) == 3
    finally:
        sched.shutdown()
    assert _counter("trace.spans") == before


# ---------------------------------------------------------------------------
# Epoch-thread hop: async-UDF node re-enters the row's trace scope
# ---------------------------------------------------------------------------


def test_async_udf_runs_under_bound_key_trace():
    from pathway_tpu.engine.dataflow import _run_udf_traced

    t = tracing.RequestTrace("/q")
    tracing.bind_key(7, t)

    async def fn(key, row):
        cur = tracing.current_trace()
        return cur.trace_id if cur is not None else None

    assert asyncio.run(_run_udf_traced(fn, 7, {"x": 1})) == t.trace_id
    # unbound key: no scope, no overhead beyond one dict check
    assert asyncio.run(_run_udf_traced(fn, 8, {"x": 1})) is None


# ---------------------------------------------------------------------------
# Surfacing: /status sections, waterfalls, flight-recorder snapshot
# ---------------------------------------------------------------------------


def _finished_trace(route="/v1/q", ms=25.0) -> tracing.RequestTrace:
    t = tracing.RequestTrace(route)
    t.started = time.time() - ms / 1000.0
    t.add_span("serve.admission", t.started, 0.001, inflight=1)
    t.add_span("serve.batch", t.started + 0.002, 0.004, batch_size=3)
    t.finish(status=200)
    return t


def test_status_carries_requests_and_slo_sections():
    from pathway_tpu.engine import slo
    from pathway_tpu.engine.http_server import render_status
    from pathway_tpu.engine.probes import ProberStats

    t = _finished_trace()
    reg = MetricsRegistry(enabled=True)
    reg.histogram(
        "serve.latency.ms", "latency", buckets=(1, 10, 100)
    ).observe(25.0, trace_id=t.trace_id)
    payload = json.loads(render_status(ProberStats(), "run-1", registry=reg))
    assert payload["requests"]["slowest"][0]["trace_id"] == t.trace_id
    span_names = [
        s["name"] for s in payload["requests"]["slowest"][0]["spans"]
    ]
    assert "serve.request" in span_names
    (exemplar,) = payload["requests"]["exemplars"]["serve.latency.ms"]
    assert exemplar["trace_id"] == t.trace_id
    names = [s["name"] for s in payload["slo"]["slos"]]
    assert "serve-latency" in names and "ttft" in names
    slo.reset_for_tests()


def test_render_waterfall_and_requests():
    from pathway_tpu.internals.top import render_requests, render_waterfall

    t = _finished_trace(route="/v1/embed", ms=30.0)
    text = render_waterfall(t.summary())
    assert t.trace_id in text
    assert "[/v1/embed]" in text
    assert "serve.admission" in text and "serve.batch" in text
    assert "serve.request" in text
    assert "█" in text  # proportional duration bars
    listing = render_requests([t.summary()])
    assert t.trace_id in listing
    assert "empty" not in listing
    assert "PATHWAY_TRACE_REQUESTS" in render_requests([])


def test_flight_recorder_dump_includes_tracing_snapshot(tmp_path):
    from pathway_tpu.engine.flight_recorder import FlightRecorder

    t = _finished_trace()
    rec = FlightRecorder()
    rec.configure(root=str(tmp_path), worker=0, run_id="r")
    rec.set_tracing_supplier(tracing.snapshot)
    rec.record("test.event", detail="x")
    path = rec.dump(reason="test")
    assert path is not None
    payload = json.loads(open(path).read())
    assert payload["requests"]["buffered"] == 1
    assert payload["requests"]["slowest"][0]["trace_id"] == t.trace_id


def test_tracing_snapshot_shape():
    _finished_trace(ms=5.0)
    _finished_trace(ms=40.0)
    snap = tracing.snapshot()
    assert snap["buffered"] == 2
    assert snap["slowest"][0]["duration_s"] > snap["slowest"][1]["duration_s"]
    assert len(snap["recent"]) == 2
