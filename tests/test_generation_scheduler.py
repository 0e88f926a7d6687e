"""Continuous-batching scheduler pins (ISSUE 18 tentpole).

Two kinds of test live here.  White-box tests drive ``_tick()`` by hand
(no worker thread) so admit/evict ordering, deadline shedding, and
chunked-prefill fairness are deterministic — no sleeps, no timing
assumptions.  End-to-end tests go through ``submit_ids`` and the worker
thread and pin the output contract: greedy continuous batching must emit
EXACTLY the argmax chain of the full causal forward, which keeps no cache
(``tests/decoder_oracle.py``), for the same prompts.
"""

from __future__ import annotations

import pytest

jax = pytest.importorskip("jax")

import numpy as np  # noqa: E402

from pathway_tpu.engine import faults  # noqa: E402
from pathway_tpu.engine import metrics as em  # noqa: E402
from pathway_tpu.engine import serving as edge  # noqa: E402
from pathway_tpu.models.decoder import PageExhaustedError, shared_decoder  # noqa: E402
from pathway_tpu.serving import generation  # noqa: E402
from tests.decoder_oracle import generate_ids, reference_greedy  # noqa: E402

MODEL = "pw-tiny-decoder"
MAX_CACHE = 64


@pytest.fixture(autouse=True)
def _clean_plan():
    faults.clear_plan()
    yield
    faults.clear_plan()


def _lm():
    return shared_decoder(MODEL, max_cache=MAX_CACHE)


def _prompt(rng, n):
    return [int(t) for t in rng.integers(1, 500, n)]


def _idle(sched):
    with sched._lock:
        return (
            not sched._queue
            and all(s is None for s in sched._slots)
            and sched._step is None
        )


def _drive(sched, max_ticks=500):
    """Run manual ticks until idle (white-box: the thread never starts)."""
    for _ in range(max_ticks):
        if _idle(sched):
            return
        sched._tick()
    raise AssertionError("scheduler did not drain")


def _enqueue(sched, req):
    with sched._lock:
        sched._queue.append(req)


# ---------------------------------------------------------------------------
# End-to-end: determinism and slot reuse through the worker thread
# ---------------------------------------------------------------------------


def test_greedy_matches_static_batching():
    """THE determinism pin: continuous batching with churn (slots=2,
    5 requests of mixed length forcing queue + slot reuse) emits exactly
    the full forward's greedy tokens for every prompt."""
    lm = _lm()
    rng = np.random.default_rng(7)
    prompts = [_prompt(rng, n) for n in (3, 11, 1, 7, 20)]
    news = [6, 4, 8, 5, 3]
    ref = [
        reference_greedy(lm, p, mn)
        for p, mn in zip(prompts, news)
    ]
    sched = generation.GenerationScheduler(
        lm, slots=2, page_size=16, prefill_chunk=8, queue_limit=16
    )
    try:
        futs = [
            sched.submit_ids(p, max_new_tokens=mn)
            for p, mn in zip(prompts, news)
        ]
        got = [f.result(timeout=120) for f in futs]
        assert got == ref
        snap = sched.snapshot()
        assert snap["active"] == 0 and snap["queued"] == 0
        # every page went back to the pool and every reservation unwound
        assert snap["pages_used"] == 0 and snap["pages_reserved"] == 0
        # the acceptance accounting: peak paged KV stayed below the dense
        # slots x max_cache resident footprint
        assert 0 < snap["kv_bytes_peak"] < snap["kv_bytes_dense"]
    finally:
        sched.shutdown()


def test_pool_exhaustion_queues_instead_of_oom():
    """A pool sized for ~one request at a time: three requests complete
    serially via admission backpressure — PageExhaustedError must never
    surface (reservation makes mid-generation allocation infallible)."""
    lm = _lm()
    rng = np.random.default_rng(8)
    # each request spans 2 pages (prompt 4 + 8 new = 12 tokens, page 8);
    # pool has 3 usable pages, so two such requests can never coexist
    sched = generation.GenerationScheduler(
        lm, slots=2, page_size=8, pages=4, prefill_chunk=8, queue_limit=16
    )
    try:
        prompts = [_prompt(rng, 4) for _ in range(3)]
        futs = [sched.submit_ids(p, max_new_tokens=8) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
        for p, out in zip(prompts, got):
            assert out == reference_greedy(lm, p, 8)
        assert sched.allocator.peak_pages <= 3
    finally:
        sched.shutdown()


def test_queue_overflow_raises_overloaded():
    """Bounded queue, not OOM: with the pool too small to ever admit,
    the queue fills and the edge answers 429 with a retry hint."""
    lm = _lm()
    sched = generation.GenerationScheduler(
        lm, slots=1, page_size=8, pages=2, prefill_chunk=8, queue_limit=2
    )
    sched._running = True  # white-box: keep the worker thread off
    try:
        # needs 2 pages; the pool's single usable page can never satisfy it
        f1 = sched.submit_ids([1, 2, 3], max_new_tokens=10)
        f2 = sched.submit_ids([1, 2, 3], max_new_tokens=10)
        with pytest.raises(edge.OverloadedError) as exc_info:
            sched.submit_ids([1, 2, 3], max_new_tokens=10)
        assert exc_info.value.retry_after_s == 1.0
    finally:
        sched._running = False
        sched.shutdown()
    # shutdown fails the stuck queue entries instead of hanging clients
    assert isinstance(f1.exception(), edge.RequestFailedError)
    assert isinstance(f2.exception(), edge.RequestFailedError)


def test_submit_rejects_unservable_max_new_tokens():
    lm = _lm()
    sched = generation.GenerationScheduler(
        lm, slots=1, page_size=16, prefill_chunk=8, queue_limit=2
    )
    sched._running = True
    try:
        with pytest.raises(ValueError, match="max_new_tokens"):
            sched.submit_ids([1], max_new_tokens=MAX_CACHE)
    finally:
        sched._running = False
        sched.shutdown()


# ---------------------------------------------------------------------------
# White-box ticks: admission ordering, deadlines, fairness, churn
# ---------------------------------------------------------------------------


def test_admit_skips_unreservable_head_of_queue():
    """A huge request that cannot reserve pages yet must not block small
    ones behind it: admission scans the WHOLE queue."""
    lm = _lm()
    sched = generation.GenerationScheduler(
        lm, slots=2, page_size=8, pages=5, prefill_chunk=8, queue_limit=16
    )
    big = generation.GenRequest([1] * 8, 40)  # 48 tokens -> 6 pages: never fits now
    small = generation.GenRequest([1, 2], 4)  # 6 tokens -> 1 page
    _enqueue(sched, big)
    _enqueue(sched, small)
    sched._tick()
    with sched._lock:
        active = [s.req for s in sched._slots if s is not None]
    assert small in active and big not in active
    assert big in sched._queue  # still waiting, not dropped
    for _ in range(200):
        if small.future.done():
            break
        sched._tick()
    assert small.future.result(timeout=5) is not None
    # big needs 6 pages but the pool only has 4 usable: it can never be
    # admitted.  That is queue backpressure, not a crash:
    assert big in sched._queue and not big.future.done()
    sched.shutdown()
    assert isinstance(big.future.exception(), edge.RequestFailedError)


def test_deadline_shed_mid_generation():
    """A row whose deadline lapses mid-generation is evicted at the next
    tick, counted under serve.deadline.exceeded{where=decode}, and its
    future reports how far it got."""
    lm = _lm()
    sched = generation.GenerationScheduler(
        lm, slots=1, page_size=16, prefill_chunk=8, queue_limit=4
    )
    req = generation.GenRequest([5, 6, 7], 40, deadline=edge.Deadline.from_ms(60_000))
    _enqueue(sched, req)
    sched._tick()  # admit + prefill + first decode step enqueued
    sched._tick()  # the second enqueued, the first read
    assert len(req.out) >= 1 and not req.future.done()
    key = "serve.deadline.exceeded{where=decode}"
    before = em.get_registry().scalar_metrics().get(key, 0.0)
    req.deadline = edge.Deadline.from_ms(0)  # lapse it, mid-generation
    sched._tick()
    after = em.get_registry().scalar_metrics().get(key, 0.0)
    assert after - before == 1.0
    with pytest.raises(edge.DeadlineExceededError, match="token"):
        req.future.result(timeout=1)
    with sched._lock:  # the slot was reclaimed and its pages freed
        assert all(s is None for s in sched._slots)
    assert sched.allocator.used_pages == 0 and sched.allocator.reserved == 0
    sched.shutdown()


def test_lapsed_queued_request_is_shed_from_queue():
    lm = _lm()
    sched = generation.GenerationScheduler(
        lm, slots=1, page_size=16, prefill_chunk=8, queue_limit=4
    )
    sched._running = True
    with pytest.raises(edge.DeadlineExceededError):
        sched.submit_ids([1], max_new_tokens=4, deadline=edge.Deadline.from_ms(0))
    # lapse AFTER queueing: shed at the next tick with where=generate-queue
    req = generation.GenRequest([1], 4, deadline=edge.Deadline.from_ms(60_000))
    _enqueue(sched, req)
    req.deadline = edge.Deadline.from_ms(0)
    key = "serve.deadline.exceeded{where=generate-queue}"
    before = em.get_registry().scalar_metrics().get(key, 0.0)
    sched._tick()
    after = em.get_registry().scalar_metrics().get(key, 0.0)
    assert after - before >= 1.0
    with pytest.raises(edge.DeadlineExceededError):
        req.future.result(timeout=1)
    sched._running = False
    sched.shutdown()


def test_chunked_prefill_does_not_stall_short_prompts():
    """Fairness: while a long prompt prefills in chunks (here the one
    rung an explicit ``prefill_chunk=4`` leaves), a short prompt admitted
    alongside it reaches its first token immediately — the long prompt
    cannot monopolize the device between decode ticks."""
    lm = _lm()
    sched = generation.GenerationScheduler(
        lm, slots=2, page_size=16, prefill_chunk=4, queue_limit=8
    )
    rng = np.random.default_rng(9)
    long = generation.GenRequest(_prompt(rng, 20), 4)  # 5 prefill chunks
    short = generation.GenRequest(_prompt(rng, 2), 4)
    _enqueue(sched, long)
    _enqueue(sched, short)
    sched._tick()
    sched._tick()
    # one tick enqueues short's only chunk and its first decode step, the
    # next reads that step: short has its first token; long is still
    # mid-prefill
    assert short.first_token_at is not None
    assert long.first_token_at is None
    _drive(sched)
    assert short.future.result(timeout=5) == reference_greedy(lm, short.prompt_ids, 4)
    assert long.future.result(timeout=5) == reference_greedy(lm, long.prompt_ids, 4)
    sched.shutdown()


def test_request_churn_fault_no_head_of_line_blocking():
    """The request_churn chaos pin: a synthetic burst lands mid-long-
    generation, every burst request reaches its first token while the
    long generation is STILL running, and the long request completes
    untouched.

    The bound the pin stands for (ISSUE 28): a waiting prompt holds the
    others' next decode step up for no longer than ONE program of the
    widest rung (``PATHWAY_GENERATE_PREFILL_CHUNK`` tokens, one row) per
    prompt that waits in that tick; the burst's one-token prompts fit the
    narrowest rung and share ONE ``[slots, narrowest]`` program."""
    lm = _lm()
    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "request_churn", "source": MODEL, "nth": 2, "count": 3}]
        )
    )
    sched = generation.GenerationScheduler(
        lm, slots=2, page_size=16, prefill_chunk=8, queue_limit=16
    )
    churn_key = "generate.churn.synthetic"
    churn_before = em.get_registry().scalar_metrics().get(churn_key, 0.0)
    long = generation.GenRequest([3, 1, 4], 40)
    _enqueue(sched, long)
    burst_served_while_long_ran = False
    for _ in range(500):
        with sched._lock:
            idle = not sched._queue and all(s is None for s in sched._slots)
        if idle:
            break
        sched._tick()
        if len(sched._churn_ttfts) >= 3 and not long.future.done():
            burst_served_while_long_ran = True
    assert long.future.result(timeout=5) == reference_greedy(lm, [3, 1, 4], 40)
    assert burst_served_while_long_ran, (
        "synthetic burst should reach first tokens before the long "
        "generation finishes"
    )
    churn_after = em.get_registry().scalar_metrics().get(churn_key, 0.0)
    assert churn_after - churn_before == 3.0
    sched.shutdown()


def test_tick_failure_fails_requests_not_the_thread():
    """A poisoned tick (simulated device error) must fail the in-flight
    futures with RequestFailedError context rather than hang clients."""
    lm = _lm()
    sched = generation.GenerationScheduler(
        lm, slots=1, page_size=16, prefill_chunk=8, queue_limit=4
    )
    req = generation.GenRequest([1, 2], 4)
    _enqueue(sched, req)
    boom = RuntimeError("device fell over")
    sched._fail_all(boom)
    assert req.future.exception() is boom
    assert sched.allocator.used_pages == 0
    sched.shutdown()


# ---------------------------------------------------------------------------
# Shared-scheduler wiring
# ---------------------------------------------------------------------------


def test_shared_scheduler_is_per_model_singleton():
    try:
        a = generation.shared_scheduler(MODEL, max_cache=MAX_CACHE)
        b = generation.shared_scheduler(MODEL, max_cache=MAX_CACHE)
        assert a is b
        c = generation.shared_scheduler(MODEL, max_cache=32)
        assert c is not a
    finally:
        generation.reset_shared_schedulers()


def _sliding_window_lm():
    import dataclasses

    from pathway_tpu.models import decoder as dec

    lm = dec.DecoderLM(MODEL, max_cache=MAX_CACHE)
    # shorter than the sequences below, longer than a page
    lm.config = dataclasses.replace(lm.config, sliding_window=12)
    return lm


@pytest.mark.parametrize("build", [
    lambda: shared_decoder("pw-tiny-decoder", max_cache=MAX_CACHE),
    lambda: shared_decoder("pw-tiny-moe-decoder", max_cache=MAX_CACHE),
    _sliding_window_lm,
    lambda: shared_decoder("pw-tiny-hybrid-decoder", max_cache=MAX_CACHE),
], ids=["dense", "moe", "sliding_window", "hybrid"])
def test_scheduler_greedy_is_the_full_forwards_argmax(build):
    """Every kind of model the scheduler serves, two ragged rows side by
    side through chunked prefill and paged decode: the answers are the
    cache-free oracle's."""
    lm = build()
    rng = np.random.default_rng(17)
    prompts = [_prompt(rng, 19), _prompt(rng, 5)]
    got = generate_ids(
        lm, prompts, max_new_tokens=14,
        scheduler=dict(slots=2, page_size=8, prefill_chunk=8),
    )
    assert got == [reference_greedy(lm, p, 14) for p in prompts]


def test_generation_snapshot_rides_flight_recorder(tmp_path):
    import json
    import pathlib

    from pathway_tpu.engine.flight_recorder import FlightRecorder

    lm = _lm()
    sched = generation.GenerationScheduler(
        lm, slots=1, page_size=16, prefill_chunk=8, queue_limit=4
    )
    rec = FlightRecorder()
    rec.configure(root=str(tmp_path), worker=0, run_id="r", attempt=0)
    rec.set_generation_supplier(sched.snapshot)
    try:
        path = rec.dump("generation test")
        assert path is not None
        payload = json.loads(pathlib.Path(path).read_text())
        assert payload["generation"]["slots"] == 1
        assert payload["generation"]["pages_used"] == 0
        assert payload["generation"]["kv_bytes_dense"] > 0
    finally:
        sched.shutdown()


def test_allocator_never_surfaces_page_exhausted_under_churn():
    """Property sweep: random scripted churn against a small pool — the
    reservation discipline keeps alloc() infallible for admitted rows."""
    lm = _lm()
    sched = generation.GenerationScheduler(
        lm, slots=3, page_size=8, pages=9, prefill_chunk=8, queue_limit=64
    )
    rng = np.random.default_rng(13)
    reqs = []
    try:
        for t in range(60):
            if t < 30 and rng.random() < 0.5:
                req = generation.GenRequest(
                    _prompt(rng, int(rng.integers(1, 10))),
                    int(rng.integers(2, 12)),
                )
                _enqueue(sched, req)
                reqs.append(req)
            with sched._lock:
                idle = not sched._queue and all(
                    s is None for s in sched._slots
                )
            if idle and t >= 30:
                break
            try:
                sched._tick()
            except PageExhaustedError:  # pragma: no cover - the pin
                pytest.fail("pool OOM despite admission reservation")
        _drive(sched)
        assert all(r.future.done() for r in reqs)
        assert sched.allocator.used_pages == 0
        assert sched.allocator.reserved == 0
    finally:
        sched.shutdown()


# ---------------------------------------------------------------------------
# The decode step that runs ahead of the host's read (ISSUE 30)
# ---------------------------------------------------------------------------


def _scalars():
    return dict(em.get_registry().scalar_metrics())


def _grew(before, name):
    return _scalars().get(name, 0.0) - before.get(name, 0.0)


def _drive_in_the_parents_order(sched, max_ticks=500):
    """The order before run-ahead: every step is read as soon as it is
    enqueued, so a tick never finds one in flight."""
    for _ in range(max_ticks):
        if _idle(sched):
            return
        sched._tick()
        step, sched._step = sched._step, None
        if step is not None:
            sched._deliver(step)
    raise AssertionError("scheduler did not drain")


def _first_repeat_free(tokens, start=1):
    """Index ``j >= start`` of a token that does not occur before it."""
    return next(j for j in range(start, len(tokens)) if tokens[j] not in tokens[:j])


@pytest.mark.parametrize("sampling", [
    {}, {"temperature": 0.9, "top_p": 0.8},
], ids=["greedy", "seeded-sampling"])
@pytest.mark.parametrize("rows", [1, 3], ids=["one-row", "several-rows"])
def test_run_ahead_gives_the_parents_tokens(rows, sampling):
    """Requests that end by count get, token for token, what the order
    before gave them: the same steps hold the same rows and split the
    same keys, only the read of each comes a tick later."""
    lm = _lm()
    rng = np.random.default_rng(30)
    prompts = [_prompt(rng, n) for n in (5, 11, 2)[:rows]]
    news = (9, 4, 6)[:rows]
    got = []
    for drive in (_drive, _drive_in_the_parents_order):
        sched = generation.GenerationScheduler(
            lm, slots=4, page_size=16, prefill_chunk=8, queue_limit=16, seed=5
        )
        reqs = [generation.GenRequest(p, n, **sampling) for p, n in zip(prompts, news)]
        for req in reqs:
            _enqueue(sched, req)
        before = _scalars()
        drive(sched)
        got.append([r.future.result(timeout=5) for r in reqs])
        steps = _grew(before, "generate.decode.steps")
        assert steps == max(news) and _grew(before, "generate.decode.wasted") == 0
        # a model with no routed layer has no step that loops over experts
        assert _scalars()["generate.moe.decode.in_place"] == 0.0
        # every step but the first was enqueued with the one before unread
        overlapped = steps - 1 if drive is _drive else 0
        assert _grew(before, "generate.decode.overlapped") == overlapped
        sched.shutdown()
    assert got[0] == got[1] and [len(out) for out in got[0]] == list(news)
    if not sampling:
        assert got[0] == [
            reference_greedy(lm, p, n) for p, n in zip(prompts, news)
        ]


def test_slot_reuse_under_run_ahead_matches_static_batching():
    """Greedy rows do not see each other: with two slots and five requests
    (a slot is taken again a tick later than before) every answer is the
    full forward's."""
    lm = _lm()
    rng = np.random.default_rng(31)
    prompts = [_prompt(rng, n) for n in (3, 11, 1, 7, 20)]
    news = [6, 4, 8, 5, 3]
    sched = generation.GenerationScheduler(
        lm, slots=2, page_size=16, prefill_chunk=8, queue_limit=16
    )
    reqs = [generation.GenRequest(p, n) for p, n in zip(prompts, news)]
    for req in reqs:
        _enqueue(sched, req)
    _drive(sched)
    assert [r.future.result(timeout=5) for r in reqs] == [
        reference_greedy(lm, p, n) for p, n in zip(prompts, news)
    ]
    assert sched.allocator.used_pages == 0 and sched.allocator.reserved == 0
    sched.shutdown()


def test_eos_read_a_step_late_drops_the_step_too_many():
    """A token that turns out to be EOS is read with the next step already
    enqueued for its row: that step's token is dropped, the answer is what
    the order before gave, and the waste is counted."""
    lm = _lm()
    prompt = _prompt(np.random.default_rng(32), 6)
    free_run = reference_greedy(lm, prompt, 12)
    j = _first_repeat_free(free_run, start=3)
    lm.eos_id = free_run[j]
    outs = []
    for drive in (_drive, _drive_in_the_parents_order):
        sched = generation.GenerationScheduler(
            lm, slots=2, page_size=16, prefill_chunk=8, queue_limit=4
        )
        req = generation.GenRequest(prompt, 12)
        _enqueue(sched, req)
        before = _scalars()
        drive(sched)
        outs.append(req.future.result(timeout=5))
        run_ahead = drive is _drive
        assert _grew(before, "generate.decode.wasted") == (1 if run_ahead else 0)
        assert _grew(before, "generate.decode.steps") == j + 1 + run_ahead
        assert _grew(before, "generate.tokens") == j
        assert sched._step is None and sched._inflight is None
        assert sched.allocator.used_pages == 0 and sched.allocator.reserved == 0
        sched.shutdown()
    assert outs[0] == outs[1] == free_run[:j]


@pytest.mark.parametrize("model,page,first_len", [
    (MODEL, 16, 9),
    # its window layers keep a ring a slot (4 pages of 8): the first
    # answer overruns it, and the stale step writes into it once more
    ("pw-tiny-hybrid-decoder", 8, 40),
], ids=["pw-tiny-decoder", "pw-tiny-hybrid-decoder"])
def test_slot_retaken_under_a_stale_step_answers_as_alone(model, page, first_len):
    """One slot.  The first answer ends by EOS with a step too many in
    flight; the request that waited takes the slot in the next tick, while
    that step is unread.  It never receives the stale token, its prefill
    (later in the device's order) overwrites what the stale step wrote,
    and its answer is the one it gets alone."""
    lm = shared_decoder(model, max_cache=128)
    rng = np.random.default_rng(33)
    first_prompt = [int(t) for t in rng.integers(104, 500, first_len)]
    second_prompt = [int(t) for t in rng.integers(104, 500, 13)]

    def scheduler():
        return generation.GenerationScheduler(
            lm, slots=1, page_size=page, prefill_chunk=64, queue_limit=4
        )

    lm.eos_id = None
    alone = scheduler()
    probe = generation.GenRequest(first_prompt, 10)
    _enqueue(alone, probe)
    _drive(alone)
    free_run = probe.future.result(timeout=5)
    j = _first_repeat_free(free_run, start=2)
    lm.eos_id = free_run[j]
    want = generation.GenRequest(second_prompt, 7)
    _enqueue(alone, want)
    _drive(alone)
    alone.shutdown()
    assert lm.eos_id not in want.future.result(timeout=5)

    sched = scheduler()
    first = generation.GenRequest(first_prompt, 10)
    second = generation.GenRequest(second_prompt, 7)
    _enqueue(sched, first)
    _enqueue(sched, second)
    before = _scalars()
    retaken_under_a_stale_step = False
    for _ in range(100):
        if first.future.done() and second.future.done() and sched._step is None:
            break
        stale = sched._step if first.future.done() else None
        sched._tick()
        if stale is not None and stale.rows[0][1] is first:
            with sched._lock:  # read in the tick that gave the slot away
                retaken_under_a_stale_step = sched._slots[0].req is second
    assert retaken_under_a_stale_step
    assert first.future.result(timeout=5) == free_run[:j]
    assert second.future.result(timeout=5) == want.future.result(timeout=5)
    assert _grew(before, "generate.decode.wasted") == 1
    assert sched.allocator.used_pages == 0 and sched.allocator.reserved == 0
    sched.shutdown()


def _no_interval_left_open(sched):
    from pathway_tpu.engine import tracing

    assert sched._step is None and sched._inflight is None
    inflight = [r for r in tracing.timeline() if r["name"] == "device.inflight"]
    assert inflight  # the timeline holds closed intervals only
    return inflight


def test_deadline_eviction_with_a_step_in_flight_reads_and_drops_it():
    lm = _lm()
    sched = generation.GenerationScheduler(
        lm, slots=1, page_size=16, prefill_chunk=8, queue_limit=4
    )
    req = generation.GenRequest([5, 6, 7], 40, deadline=edge.Deadline.from_ms(60_000))
    _enqueue(sched, req)
    for _ in range(3):
        sched._tick()
    assert sched._step is not None and len(req.out) == 2
    before = _scalars()
    req.deadline = edge.Deadline.from_ms(0)
    sched._tick()  # evicts, enqueues nothing, reads the step in flight at once
    with pytest.raises(edge.DeadlineExceededError, match=r"\(2 token"):
        req.future.result(timeout=1)
    assert _grew(before, "generate.decode.wasted") == 1
    assert _grew(before, "generate.decode.steps") == 0
    assert "failed" not in _no_interval_left_open(sched)[-1]["attributes"]
    assert sched.allocator.used_pages == 0 and sched.allocator.reserved == 0
    sched.shutdown()


def test_shutdown_with_a_step_in_flight_fails_the_future_and_lets_the_step_go():
    lm = _lm()
    sched = generation.GenerationScheduler(
        lm, slots=1, page_size=16, prefill_chunk=8, queue_limit=4
    )
    req = generation.GenRequest([5, 6, 7], 40)
    _enqueue(sched, req)
    for _ in range(3):
        sched._tick()
    assert sched._step is not None and not req.future.done()
    sched.shutdown()
    assert isinstance(req.future.exception(timeout=1), edge.RequestFailedError)
    assert _no_interval_left_open(sched)[-1]["attributes"].get("failed") is True
    assert sched.allocator.used_pages == 0 and sched.allocator.reserved == 0


def test_raising_tick_with_a_step_in_flight_fails_requests_not_the_thread():
    """Through the worker thread: the fourth decode step fails to enqueue
    with the third in flight.  Both requests of the tick fail, nothing is
    left pending or open, and the thread serves the next request."""
    lm = _lm()
    sched = generation.GenerationScheduler(
        lm, slots=2, page_size=16, prefill_chunk=8, queue_limit=4
    )
    step, calls = sched._decode_fn, [0]

    def failing(*args):
        calls[0] += 1
        if calls[0] == 4:
            raise RuntimeError("device fell over")
        return step(*args)

    sched._decode_fn = failing
    try:
        futures = [sched.submit_ids([3, 1, 4], max_new_tokens=30) for _ in range(2)]
        for future in futures:
            with pytest.raises(RuntimeError, match="device fell over"):
                future.result(timeout=120)
        assert _no_interval_left_open(sched)[-1]["attributes"].get("failed") is True
        assert sched.snapshot()["tick_failures"] == 1
        again = sched.submit_ids([3, 1, 4], max_new_tokens=5).result(timeout=120)
        assert again == reference_greedy(lm, [3, 1, 4], 5)
    finally:
        sched.shutdown()
    assert sched._step is None and sched._inflight is None


def _pool_leaves(sched):
    import jax

    return jax.tree_util.tree_leaves((sched._k_pool, sched._v_pool))


@pytest.mark.parametrize("model", [MODEL, "pw-tiny-hybrid-decoder"])
def test_a_program_consumes_the_pools_it_is_given(model):
    """Every program of a tick (the prefill, then the decode step) takes
    the pools it is handed and leaves them deleted; the scheduler holds the
    ones that came back, the only copy there is."""
    lm = shared_decoder(model, max_cache=128)
    sched = generation.GenerationScheduler(
        lm, slots=2, page_size=8, prefill_chunk=16, queue_limit=4
    )
    _enqueue(sched, generation.GenRequest([5, 6, 7], 6))
    for _ in range(3):  # prefill + first step, then a step a tick
        given = _pool_leaves(sched)
        sched._tick()
        assert all(leaf.is_deleted() for leaf in given)
        held = _pool_leaves(sched)
        assert not any(leaf.is_deleted() for leaf in held)
        assert [leaf.shape for leaf in held] == [leaf.shape for leaf in given]
    sched.shutdown()
    assert not any(leaf.is_deleted() for leaf in _pool_leaves(sched))


def test_a_step_that_fails_with_the_pools_taken_leaves_the_scheduler_serving():
    """The fourth decode step runs, consuming the pools it was given, and
    only then fails: both requests fail, the failure is counted once, the
    pools are made anew, and the next request is answered as it would be
    by a scheduler that never failed."""
    lm = _lm()
    sched = generation.GenerationScheduler(
        lm, slots=2, page_size=16, prefill_chunk=8, queue_limit=4
    )
    step, calls, taken = sched._decode_fn, [0], []

    def failing(*args):
        calls[0] += 1
        out = step(*args)
        if calls[0] == 4:
            taken.extend([args[1], args[2]])
            raise RuntimeError("device fell over after the step")
        return out

    sched._decode_fn = failing
    try:
        futures = [sched.submit_ids([3, 1, 4], max_new_tokens=30) for _ in range(2)]
        for future in futures:
            with pytest.raises(RuntimeError, match="fell over after the step"):
                future.result(timeout=120)
        assert taken and all(pool.is_deleted() for pool in taken)
        assert sched.snapshot()["tick_failures"] == 1
        assert not any(leaf.is_deleted() for leaf in _pool_leaves(sched))
        assert _no_interval_left_open(sched)[-1]["attributes"].get("failed") is True
        again = sched.submit_ids([3, 1, 4], max_new_tokens=5).result(timeout=120)
        assert again == reference_greedy(lm, [3, 1, 4], 5)
        assert sched.snapshot()["tick_failures"] == 1
    finally:
        sched.shutdown()
    assert sched.allocator.used_pages == 0 and sched.allocator.reserved == 0


def test_a_second_answer_compiles_nothing_the_first_did_not():
    from pathway_tpu.engine.profiler import install_jax_accounting

    assert install_jax_accounting(force=True)
    lm = _lm()
    sched = generation.GenerationScheduler(
        lm, slots=2, page_size=16, prefill_chunk=8, queue_limit=4
    )
    rng = np.random.default_rng(34)
    _enqueue(sched, generation.GenRequest(_prompt(rng, 7), 9))
    _drive(sched)
    before = _scalars()
    req = generation.GenRequest(_prompt(rng, 6), 9)
    _enqueue(sched, req)
    _drive(sched)
    assert len(req.future.result(timeout=5)) == 9
    assert _grew(before, "jax.compile.count") == 0 and _grew(before, "jax.cache.miss") == 0
    sched.shutdown()


def test_lone_answer_overlaps_every_step_but_the_first():
    """``generate.decode.overlapped`` = steps - 1, and as many inter-token
    intervals in ``generate.decode.tick.ms``: the first read has none
    before it, and the last drains the device."""
    lm = _lm()
    sched = generation.GenerationScheduler(
        lm, slots=2, page_size=16, prefill_chunk=8, queue_limit=4
    )

    def tick_intervals():
        points = em.get_registry().histogram_points()
        return sum(p["count"] for p in points if p["name"] == "generate.decode.tick.ms")

    try:
        for _ in range(2):  # the second answer's first read follows a drain
            before, intervals = _scalars(), tick_intervals()
            out = sched.submit_ids([2, 7, 1, 8], max_new_tokens=11).result(timeout=120)
            assert len(out) == 11
            assert _grew(before, "generate.decode.steps") == 11
            assert _grew(before, "generate.decode.overlapped") == 10
            assert _grew(before, "generate.decode.wasted") == 0
            assert tick_intervals() - intervals == 10
    finally:
        sched.shutdown()


def test_top_shows_the_decode_line():
    from pathway_tpu.internals.top import render_top

    text = render_top(
        {
            "generation": {
                "generate.slots.total": 8.0,
                "generate.decode.steps": 640.0,
                "generate.decode.overlapped": 630.0,
                "generate.decode.wasted": 3.0,
                "generate.decode.tick.ms.p50": 18.74,
            }
        }
    )
    assert "decode: 640 step(s) · 98% ran ahead · 3 wasted · p50 18.7 ms a token" in text
