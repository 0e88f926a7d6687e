"""Device-path fault tolerance tests (ISSUE 13).

Five property groups, each load-bearing:

* **Classification** — only device-looking failures are wrapped in the
  typed :class:`DeviceJobError` hierarchy; host bugs propagate raw.
* **Retry / OOM degradation** — transients retry on the bounded jittered
  schedule; RESOURCE_EXHAUSTED splits onto smaller buckets and ratchets
  the callable's max-bucket cap instead of failing the stream.
* **Circuit breaker + host fallback** — K consecutive failures trip to
  the un-jitted CPU path with byte-identical outputs, half-open probing
  recovers, and a batch that fails device AND fallback is quarantined
  with a typed error (chaos-seeded via the ``device_error`` /
  ``device_oom`` / ``device_compile_fail`` fault kinds).
* **Dispatch-hang escalation** — a wedged dispatch job past the hard
  deadline fails its waiters and the dispatch thread is respawned
  (``device.dispatch.restarts``) while the epoch thread never slows.
* **Shutdown semantics** — ``submit()``/``run_batch()`` after ``close()``
  raise a clean typed error and in-flight waiters are failed, never
  stranded; the micro-batcher delivers the typed error to every
  cross-loop waiter exactly once.
"""

from __future__ import annotations

import asyncio
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from pathway_tpu.device import (
    BucketPolicy,
    DeviceExecutor,
    DeviceCompileError,
    DeviceDispatchHangError,
    DeviceJobError,
    DeviceOOMError,
    DeviceQuarantinedError,
    ExecutorClosedError,
    TransientDeviceError,
    render_device_snapshot,
)
from pathway_tpu.device import resilience as res
from pathway_tpu.engine import faults
from pathway_tpu.engine import flight_recorder as blackbox
from pathway_tpu.engine import metrics as em
from pathway_tpu.internals.top import render_top
from pathway_tpu.utils.batching import AsyncMicroBatcher

RNG = np.random.default_rng(13)


def _linear_executor(name="lin", max_bucket=8, **register_kwargs):
    """An executor around an elementwise kernel: jit and eager execution
    are bit-identical for it, which is what the fallback pins need."""
    ex = DeviceExecutor(collector_name=None)
    ex.register(
        name,
        lambda x: x * 2.0 + 1.0,
        policy=BucketPolicy(max_bucket=max_bucket),
        **register_kwargs,
    )
    return ex


def _counter(name: str, **labels) -> float:
    return em.get_registry().counter(name, **labels).value


def _events(kind: str) -> list[dict]:
    return [e for e in blackbox.get_recorder().events() if e["kind"] == kind]


@pytest.fixture(autouse=True)
def _clean_plan():
    faults.clear_plan()
    yield
    faults.clear_plan()


# --- classification ----------------------------------------------------------


def test_classify_maps_markers_to_typed_kinds():
    oom = res.classify(res.InjectedDeviceError("RESOURCE_EXHAUSTED: boom"))
    assert isinstance(oom, DeviceOOMError) and oom.kind == "oom"
    compile_ = res.classify(res.InjectedDeviceError("XLA compilation failed"))
    assert isinstance(compile_, DeviceCompileError) and compile_.kind == "compile"
    transient = res.classify(res.InjectedDeviceError("INTERNAL: link reset"))
    assert isinstance(transient, TransientDeviceError)
    assert transient.kind == "transient"
    # an unrecognized device error defaults to transient: retry is the
    # forgiving default and persistence still reaches the breaker
    assert isinstance(
        res.classify(res.InjectedDeviceError("something odd")),
        TransientDeviceError,
    )
    # "oom" only matches as a standalone word: an op/callable name that
    # merely embeds the letters must not route into the bucket ratchet
    assert isinstance(
        res.classify(res.InjectedDeviceError("INTERNAL: zoom_encoder died")),
        TransientDeviceError,
    )
    assert isinstance(
        res.classify(res.InjectedDeviceError("OOM while allocating 2GiB")),
        DeviceOOMError,
    )
    # a kernel's scoped-VMEM overrun is per program: a smaller batch
    # cannot fix it, so it must not enter the OOM ratchet
    assert isinstance(
        res.classify(res.InjectedDeviceError(
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem. "
            "Used 20.5M of 16.0M"
        )),
        DeviceCompileError,
    )
    assert isinstance(
        res.classify(res.InjectedDeviceError(
            "INTERNAL: Mosaic failed to compile TPU kernel: unsupported shape"
        )),
        DeviceCompileError,
    )


def test_classify_refuses_host_bugs_and_passes_typed_through():
    assert res.classify(ValueError("bad row")) is None
    assert res.classify(KeyError("missing")) is None
    already = DeviceOOMError("pre-typed")
    assert res.classify(already) is already


def test_retry_policy_delays_follow_the_shared_backoff():
    policy = res.RetryPolicy(retries=3, deadline_s=30.0, backoff_ms=100.0)
    delays = list(policy.delays())
    assert len(delays) == 3
    # exponential with jitter in [0, 50 ms): each base doubles
    assert 0.1 <= delays[0] < 0.15
    assert 0.2 <= delays[1] < 0.25
    assert 0.4 <= delays[2] < 0.45


def test_circuit_breaker_state_machine():
    b = res.CircuitBreaker(threshold=2, cooldown_s=0.05)
    assert b.admit() == "device" and b.state_name() == "closed"
    assert not b.record_failure()
    assert b.record_failure()  # second consecutive: trips
    assert b.state_name() == "open"
    assert b.admit() == "fallback"  # inside the cooldown
    deadline = time.monotonic() + 2.0
    while time.monotonic() < deadline:
        route = b.admit()
        if route != "fallback":
            break
        time.sleep(0.05)
    assert route == "probe"  # cooldown elapsed: one half-open probe
    assert b.admit() == "fallback"  # a second admit while probing
    assert b.record_success(probe=True)  # probe success closes
    assert b.state_name() == "closed"
    # a failed probe re-opens immediately
    b.record_failure()
    b.record_failure()
    deadline = time.monotonic() + 2.0
    while b.admit() != "probe" and time.monotonic() < deadline:
        time.sleep(0.05)
    assert b.record_failure(probe=True)
    assert b.state_name() == "open"
    assert b.snapshot()["trips"] == 3


# --- retry + OOM degradation -------------------------------------------------


@pytest.mark.chaos
def test_transient_failure_retries_and_recovers(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_RETRY_BACKOFF_MS", "1")
    ex = _linear_executor()
    rows = RNG.normal(size=(5, 4)).astype(np.float32)
    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "device_error", "source": "lin", "nth": 1}], seed=13
        )
    )
    before = _counter("device.retry.attempts")
    out = ex.run_batch("lin", (rows,))
    np.testing.assert_array_equal(out, rows * 2.0 + 1.0)
    assert _counter("device.retry.attempts") == before + 1
    st = ex.resilience_stats("lin")
    assert st["failures"] == {"transient": 1}
    assert st["breaker"]["state"] == "closed"
    assert st["fallback_batches"] == 0  # the retry healed it, no fallback
    assert [e for e in _events("device.failure") if e.get("callable") == "lin"]


@pytest.mark.chaos
def test_oom_mid_stream_ratchets_bucket_cap_and_completes(monkeypatch):
    """ISSUE 13 acceptance: a RESOURCE_EXHAUSTED chunk splits onto a
    smaller bucket, the per-callable cap ratchets, and the run completes
    with correct outputs — memory pressure shrinks footprint instead of
    crash-looping."""
    ex = _linear_executor(max_bucket=16)
    rows = RNG.normal(size=(16, 4)).astype(np.float32)
    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "device_oom", "source": "lin", "nth": 1}], seed=13
        )
    )
    before = _counter("device.oom.splits")
    out = ex.run_batch("lin", (rows,))
    np.testing.assert_array_equal(out, rows * 2.0 + 1.0)
    assert _counter("device.oom.splits") == before + 1
    st = ex.resilience_stats("lin")
    assert st["bucket_cap"] == 8  # one step below the OOMing 16 bucket
    assert st["oom_splits"] == 1
    # the ratchet persists: later batches plan under the cap (two chunks
    # of 8, never a 16 bucket again)
    dispatches_before = ex.stats("lin")["dispatches"]
    out2 = ex.run_batch("lin", (rows,))
    np.testing.assert_array_equal(out2, rows * 2.0 + 1.0)
    assert ex.stats("lin")["dispatches"] == dispatches_before + 2
    snap = ex.metrics_snapshot()
    assert snap["device.bucket.cap{callable=lin}"] == 8.0
    assert _events("device.oom.ratchet")


@pytest.mark.chaos
def test_oom_at_smallest_bucket_falls_back_to_host():
    ex = DeviceExecutor(collector_name=None)
    ex.register(
        "lin",
        lambda x: x * 2.0 + 1.0,
        policy=BucketPolicy(min_bucket=4, max_bucket=4),
    )
    rows = RNG.normal(size=(3, 4)).astype(np.float32)
    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "device_oom", "source": "lin", "from_nth": 1,
              "max_times": 99}],
            seed=13,
        )
    )
    out = ex.run_batch("lin", (rows,))
    np.testing.assert_array_equal(out, rows * 2.0 + 1.0)
    st = ex.resilience_stats("lin")
    assert st["bucket_cap"] is None  # nothing below bucket 4 to ratchet to
    assert st["fallback_batches"] == 1


# --- circuit breaker + host fallback -----------------------------------------


@pytest.mark.chaos
def test_breaker_trips_to_host_fallback_and_recovers_half_open(monkeypatch):
    """THE device-fault acceptance pin: seeded device errors trip the
    breaker after K consecutive failures, the un-jitted host fallback
    serves byte-identical outputs while it is open, and a half-open
    probe after the cooldown closes it again — with zero new compile
    keys, so the steady-state cache discipline survives recovery."""
    monkeypatch.setenv("PATHWAY_DEVICE_BREAKER_THRESHOLD", "2")
    monkeypatch.setenv("PATHWAY_DEVICE_BREAKER_COOLDOWN_S", "0.2")
    monkeypatch.setenv("PATHWAY_DEVICE_RETRIES", "0")
    ex = _linear_executor()
    rows = RNG.normal(size=(5, 4)).astype(np.float32)
    expected = ex.run_batch("lin", (rows,))  # healthy device output
    np.testing.assert_array_equal(expected, rows * 2.0 + 1.0)
    keys_after_warm = ex.stats("lin")["keys"]

    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "device_error", "source": "lin", "from_nth": 1,
              "max_times": 2}],
            seed=13,
        )
    )
    fb_before = _counter("device.fallback.batches")
    trips_before = _counter("device.breaker.trips")
    # failure 1: below threshold — fallback serves this batch, breaker
    # still closed; failure 2: trips it open
    out1 = ex.run_batch("lin", (rows,))
    out2 = ex.run_batch("lin", (rows,))
    np.testing.assert_array_equal(out1, expected)  # byte-identical
    np.testing.assert_array_equal(out2, expected)
    st = ex.resilience_stats("lin")
    assert st["breaker"]["state"] == "open"
    assert st["breaker"]["trips"] == 1
    assert _counter("device.breaker.trips") == trips_before + 1
    assert _counter("device.fallback.batches") == fb_before + 2
    assert [e for e in _events("device.breaker.open") if e["callable"] == "lin"]

    # open: the device is not attempted (the fault plan is exhausted, so
    # a device attempt would SUCCEED — fallback proves the open routing)
    out3 = ex.run_batch("lin", (rows,))
    np.testing.assert_array_equal(out3, expected)
    assert _counter("device.fallback.batches") == fb_before + 3
    assert ex.resilience_stats("lin")["breaker"]["state"] == "open"

    # after the cooldown the next dispatch is the half-open probe; the
    # device is healthy again, so it closes the breaker
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        out4 = ex.run_batch("lin", (rows,))
        np.testing.assert_array_equal(out4, expected)
        if ex.resilience_stats("lin")["breaker"]["state"] == "closed":
            break
        time.sleep(0.05)
    assert ex.resilience_stats("lin")["breaker"]["state"] == "closed"
    assert [e for e in _events("device.breaker.close") if e["callable"] == "lin"]
    # recovered steady state: same buckets, zero new compile keys — the
    # jax.cache.miss == 0 discipline is preserved through the episode
    assert ex.stats("lin")["keys"] == keys_after_warm
    fb_recovered = _counter("device.fallback.batches")
    out5 = ex.run_batch("lin", (rows,))
    np.testing.assert_array_equal(out5, expected)
    # closed again: the device serves, the fallback counter stops moving
    assert _counter("device.fallback.batches") == fb_recovered


@pytest.mark.chaos
def test_compile_failure_is_not_retried_and_serves_from_fallback(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_RETRIES", "3")
    monkeypatch.setenv("PATHWAY_DEVICE_RETRY_BACKOFF_MS", "1")
    ex = _linear_executor()
    rows = RNG.normal(size=(3, 4)).astype(np.float32)
    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "device_compile_fail", "source": "lin", "nth": 1}],
            seed=13,
        )
    )
    retries_before = _counter("device.retry.attempts")
    out = ex.run_batch("lin", (rows,))
    np.testing.assert_array_equal(out, rows * 2.0 + 1.0)
    # deterministic failure: zero retries spent, straight to fallback
    assert _counter("device.retry.attempts") == retries_before
    st = ex.resilience_stats("lin")
    assert st["failures"] == {"compile": 1}
    assert st["fallback_batches"] == 1


@pytest.mark.chaos
def test_poisoned_batch_quarantines_with_typed_error(monkeypatch):
    """A batch that fails device retries AND the host fallback is
    quarantined: bounded record, flight-recorder event, typed error to
    the waiter — one bad batch cannot wedge or crash-loop the stream."""
    monkeypatch.setenv("PATHWAY_DEVICE_RETRIES", "0")

    def poison_fallback(x):
        raise ValueError("poisoned row")

    ex = DeviceExecutor(collector_name=None)
    ex.register(
        "poison",
        lambda x: x * 2.0,
        policy=BucketPolicy(max_bucket=8),
        host_fallback=poison_fallback,
    )
    rows = RNG.normal(size=(3, 4)).astype(np.float32)
    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "device_error", "source": "poison", "from_nth": 1,
              "max_times": 99}],
            seed=13,
        )
    )
    q_before = _counter("device.quarantine.batches")
    with pytest.raises(DeviceQuarantinedError, match="quarantined"):
        ex.run_batch("poison", (rows,))
    assert _counter("device.quarantine.batches") == q_before + 1
    records = ex.quarantine_records()
    assert len(records) == 1
    assert records[0]["callable"] == "poison"
    assert records[0]["rows"] == 3
    assert "poisoned row" in records[0]["fallback_error"]
    assert "injected transient" in records[0]["device_error"]
    assert [e for e in _events("device.quarantine") if e["callable"] == "poison"]
    # the executor still works for the next (healthy) callable
    faults.clear_plan()
    ex.register("ok", lambda x: x + 1.0, policy=BucketPolicy(max_bucket=8))
    np.testing.assert_array_equal(
        ex.run_batch("ok", (rows,)), rows + 1.0
    )


def test_host_bug_during_probe_releases_the_slot(monkeypatch):
    """A raw host exception escaping a half-open probe must release the
    probe slot: pre-fix it latched _probe_inflight forever and every
    later dispatch served from the slow host fallback on a healthy
    device."""
    monkeypatch.setenv("PATHWAY_DEVICE_BREAKER_COOLDOWN_S", "0.05")
    monkeypatch.setenv("PATHWAY_DEVICE_RETRY_BACKOFF_MS", "1")
    ex = _linear_executor()
    rows = RNG.normal(size=(3, 4)).astype(np.float32)
    faults.install_plan(
        faults.FaultPlan(
            [
                {
                    "kind": "device_error",
                    "source": "lin",
                    "from_nth": 1,
                    "max_times": 15,
                }
            ],
            seed=13,
        )
    )
    for _ in range(6):
        ex.run_batch("lin", (rows,))
    entry = ex._callables["lin"]
    assert entry.breaker.state_name() == "open"
    faults.clear_plan()
    time.sleep(0.1)  # cooldown elapses: the next admit is the probe
    real_fixed = ex._dispatch_fixed
    fired = []

    def bomb(*args, **kwargs):
        if not fired:
            fired.append(True)
            raise ValueError("host bug, not a device failure")
        return real_fixed(*args, **kwargs)

    monkeypatch.setattr(ex, "_dispatch_fixed", bomb)
    with pytest.raises(ValueError):
        ex.run_batch("lin", (rows,))
    # the slot is free again: the next dispatch probes, succeeds, and
    # the breaker closes
    out = ex.run_batch("lin", (rows,))
    np.testing.assert_allclose(np.asarray(out), rows * 2.0 + 1.0)
    assert entry.breaker.state_name() == "closed"
    assert entry.breaker.snapshot()["trips"] == 1


def test_warmup_dispatches_take_the_typed_failure_path(monkeypatch):
    """warmup() sits under the same typed-failure contract as traffic:
    a transient during warmup retries away instead of failing startup,
    and a deterministic failure surfaces as a typed DeviceJobError —
    never a raw injected/XLA exception."""
    monkeypatch.setenv("PATHWAY_DEVICE_RETRY_BACKOFF_MS", "1")
    ex = _linear_executor()
    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "device_error", "source": "lin", "nth": 1}], seed=13
        )
    )
    entry = ex._callables["lin"]
    warmed = ex.warmup("lin", row_shapes=((4,),), dtypes=(np.float32,))
    assert warmed == len(entry.policy.buckets())  # transient retried away
    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "device_compile_fail", "source": "lin", "nth": 1}],
            seed=13,
        )
    )
    ex2 = _linear_executor()
    with pytest.raises(DeviceCompileError):
        ex2.warmup("lin", row_shapes=((4,),), dtypes=(np.float32,))


def test_host_bug_propagates_raw_and_skips_the_breaker():
    ex = DeviceExecutor(collector_name=None)

    def buggy(x):
        raise ValueError("bad row shape logic")

    ex.register("buggy", buggy, policy=BucketPolicy(max_bucket=8))
    with pytest.raises(ValueError, match="bad row shape logic"):
        ex.run_batch("buggy", (np.ones((2, 4), np.float32),))
    st = ex.resilience_stats("buggy")
    assert st["failures"] == {}  # never classified as a device failure
    assert st["breaker"]["consecutive_failures"] == 0


def test_resilience_kill_switch_reverts_to_raw_dispatch(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_RESILIENCE", "0")
    ex = _linear_executor()
    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "device_error", "source": "lin", "nth": 1}], seed=13
        )
    )
    with pytest.raises(res.InjectedDeviceError):
        ex.run_batch("lin", (np.ones((2, 4), np.float32),))


# --- dispatch-hang escalation ------------------------------------------------

HANG_MS = 10_000.0


@pytest.mark.chaos
def test_device_hang_restarts_dispatch_thread_while_epochs_stay_flat(
    monkeypatch,
):
    """ISSUE 13 acceptance: a wedged dispatch job past the hard deadline
    fails its waiters with a typed hang error and the dispatch thread is
    respawned (``device.dispatch.restarts`` moves, later jobs run) —
    while ``backlog.device.age.s`` grew and the epoch thread never saw a
    slow epoch (every duration bucket above 250 ms stays empty): a
    wedged DEVICE is distinguishable from a wedged WORKER."""
    monkeypatch.setenv("PATHWAY_DEVICE_DISPATCH_DEADLINE_S", "0.4")
    ex = DeviceExecutor(collector_name=None)
    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "device_hang", "source": "wedge", "nth": 1,
              "delay_ms": HANG_MS}],
            seed=13,
        )
    )
    restarts_before = _counter("device.dispatch.restarts")
    epoch_hist = em.get_registry().histogram(
        "epoch.duration.ms", buckets=em.MS_BUCKETS, chaos="device-hang"
    )
    try:
        fut = ex.submit(lambda: "never", name="wedge")
        ages: list[float] = []
        # the epoch thread keeps closing fast epochs while the dispatch
        # thread is wedged; only the device backlog ages
        while not fut.done():
            t0 = time.monotonic()
            ages.append(ex.metrics_snapshot()["backlog.device.age.s"])
            epoch_hist.observe((time.monotonic() - t0) * 1000.0)
            time.sleep(0.02)
        with pytest.raises(DeviceDispatchHangError, match="hard deadline"):
            fut.result(timeout=1.0)
        # the queue aged past the deadline before escalation fired
        assert max(ages) >= 0.2, max(ages)
        assert _counter("device.dispatch.restarts") == restarts_before + 1
        assert [e for e in _events("device.dispatch.restart")
                if e["job"] == "wedge"]
        assert _events("fault.device_hang")
        # the respawned dispatch thread serves new jobs
        assert ex.submit(lambda: "alive", name="after").result(timeout=5.0) == "alive"
        # in-flight accounting settled exactly once: nothing leaked
        deadline = time.monotonic() + 5.0
        while ex.metrics_snapshot()["backlog.device.queue"] and time.monotonic() < deadline:
            time.sleep(0.01)
        snap = ex.metrics_snapshot()
        assert snap["backlog.device.bytes"] == 0.0
        assert snap["backlog.device.queue"] == 0.0
        # the epoch thread NEVER saw the hang: no slow epoch buckets
        bounds, counts, _total, _n = epoch_hist.snapshot()
        slow = sum(
            c for bound, c in zip(list(bounds) + [float("inf")], counts)
            if bound > 250.0
        )
        assert slow == 0, (bounds, counts)
    finally:
        ex.close()


# --- shutdown semantics ------------------------------------------------------


def test_submit_after_close_raises_typed_error():
    ex = DeviceExecutor(collector_name=None)
    ex.close()
    with pytest.raises(ExecutorClosedError, match="closed"):
        ex.submit(lambda: 1, name="late")
    ex2 = _linear_executor()
    ex2.close()
    with pytest.raises(ExecutorClosedError, match="closed"):
        ex2.run_batch("lin", (np.ones((2, 4), np.float32),))


def test_close_fails_inflight_waiters_instead_of_stranding_them():
    """The shutdown pin: when the dispatch thread cannot drain within the
    close budget, the running job AND every queued job get a typed
    ExecutorClosedError — no waiter is left blocked forever."""
    ex = DeviceExecutor(collector_name=None)
    gate = threading.Event()
    started = threading.Event()

    def wedge():
        started.set()
        while not gate.wait(timeout=0.05):
            pass
        return "late"

    running = ex.submit(wedge, name="running", nbytes=100)
    queued = ex.submit(lambda: "queued", name="queued", nbytes=50)
    assert started.wait(timeout=5.0)
    ex.close(timeout_s=0.2)  # the wedge outlives the drain budget
    with pytest.raises(ExecutorClosedError):
        running.result(timeout=1.0)
    with pytest.raises(ExecutorClosedError):
        queued.result(timeout=1.0)
    gate.set()  # the abandoned thread finishes; its late result is dropped
    with pytest.raises(ExecutorClosedError):
        running.result(timeout=1.0)


def test_close_drains_queued_jobs_when_it_can():
    ex = DeviceExecutor(collector_name=None)
    fut = ex.submit(lambda: "done", name="quick")
    ex.close(timeout_s=5.0)
    assert fut.result(timeout=1.0) == "done"  # drained, not failed


def test_close_drains_queued_run_batch_jobs():
    """The drain window must admit jobs whose fn routes through
    run_batch (the AsyncMicroBatcher shape) — close() sets _closed
    before draining, and that guard must not fail work the dispatch
    thread can still finish."""
    ex = _linear_executor()
    rows = np.ones((3, 4), np.float32)
    gate = threading.Event()
    started = threading.Event()

    def wedge():
        started.set()
        gate.wait(timeout=10.0)
        return "gate"

    ex.submit(wedge, name="gate")
    fut = ex.submit(
        lambda: ex.run_batch("lin", (rows,)), name="batchy"
    )
    assert started.wait(timeout=5.0)
    closer = threading.Thread(target=lambda: ex.close(timeout_s=5.0))
    closer.start()
    deadline = time.monotonic() + 5.0
    while not ex._closed and time.monotonic() < deadline:
        time.sleep(0.01)
    assert ex._closed
    gate.set()  # drain proceeds with _closed already True
    closer.join(timeout=10.0)
    out = fut.result(timeout=1.0)
    np.testing.assert_allclose(np.asarray(out), rows * 2.0 + 1.0)


def test_close_during_retry_backoff_delivers_closed_error_not_fallback(
    monkeypatch,
):
    """close() interrupting a retry backoff must surface the typed
    closed error — not count a breaker failure, and never run the host
    fallback on a closed executor."""
    monkeypatch.setenv("PATHWAY_DEVICE_RETRY_BACKOFF_MS", "60000")
    ex = _linear_executor()
    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "device_error", "source": "lin", "from_nth": 1}],
            seed=13,
        )
    )
    rows = np.ones((2, 4), np.float32)
    caught: list[BaseException] = []
    started = threading.Event()

    def run():
        started.set()
        try:
            ex.run_batch("lin", (rows,))
        except BaseException as exc:  # noqa: BLE001 - asserted below
            caught.append(exc)

    t = threading.Thread(target=run)
    t.start()
    assert started.wait(timeout=5.0)
    time.sleep(0.3)  # let the dispatch fail once and enter backoff
    ex.close(timeout_s=2.0)
    t.join(timeout=10.0)
    assert not t.is_alive()
    assert len(caught) == 1 and isinstance(caught[0], ExecutorClosedError)
    entry = ex._callables["lin"]
    assert entry.fallback_batches == 0  # no compute after close()
    assert entry.breaker.snapshot()["trips"] == 0
    assert entry.breaker.snapshot()["consecutive_failures"] == 0


def test_budget_blocked_submit_fails_on_close_not_resurrects_thread():
    """A submit() parked on a full in-flight budget must fail with the
    typed closed error when close() frees the budget — not enqueue its
    job and respawn the dispatch thread on a closed executor."""
    ex = DeviceExecutor(collector_name=None, max_inflight_requests=1)
    gate = threading.Event()
    started = threading.Event()

    def wedge():
        started.set()
        gate.wait(timeout=10.0)
        return "gate"

    ex.submit(wedge, name="gate")
    assert started.wait(timeout=5.0)
    caught: list[BaseException] = []
    ran: list[str] = []
    waiting = threading.Event()

    def blocked_submit():
        waiting.set()
        try:
            ex.submit(lambda: ran.append("late"), name="late")
        except BaseException as exc:  # noqa: BLE001 - asserted below
            caught.append(exc)

    t = threading.Thread(target=blocked_submit)
    t.start()
    assert waiting.wait(timeout=5.0)
    time.sleep(0.2)  # park the submitter inside the budget wait
    # close() with the wedge still running: the drain budget elapses, the
    # running job is written off (freeing the budget) and the parked
    # submitter is woken — the window the re-check guards
    ex.close(timeout_s=0.2)
    t.join(timeout=10.0)
    gate.set()  # let the abandoned thread finish; late result is dropped
    assert not t.is_alive()
    assert len(caught) == 1 and isinstance(caught[0], ExecutorClosedError)
    deadline = time.monotonic() + 5.0
    while (
        ex._thread is not None
        and ex._thread.is_alive()
        and time.monotonic() < deadline
    ):
        time.sleep(0.02)
    assert ex._thread is None or not ex._thread.is_alive()
    assert ran == []  # the late job never executed


# --- the micro-batcher front-end ---------------------------------------------


def test_batcher_mid_coalesce_failure_fails_every_cross_loop_waiter_once(
    monkeypatch,
):
    """The satellite pin (extends the PR 11 result-count-mismatch pin): a
    batch whose process callback quarantines must deliver the typed
    error to EVERY waiter, across event loops, exactly once."""
    monkeypatch.setenv("PATHWAY_DEVICE_RETRIES", "0")

    def bad_fallback(x):
        raise ValueError("poisoned")

    ex = DeviceExecutor(collector_name=None)
    ex.register(
        "enc",
        lambda x: x * 2.0,
        policy=BucketPolicy(max_bucket=8),
        host_fallback=bad_fallback,
    )
    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "device_error", "source": "enc", "from_nth": 1,
              "max_times": 99}],
            seed=13,
        )
    )
    calls = []

    def process(items):
        calls.append(len(items))
        batch = np.stack([np.asarray(i, np.float32) for i in items])
        return list(ex.run_batch("enc", (batch,)))

    batcher = AsyncMicroBatcher(
        process, max_batch_size=64, flush_delay=0.01, executor=ex
    )
    # the flusher's first flush is immediate, so two loops sharing one
    # window is scheduler luck (never happens on a single core).  Hold
    # the window open until both loops' items sit in the ONE shared
    # pending list so the failure provably fans out across loops.
    real_flush = batcher.flush

    def gated_flush():
        with batcher._lock:
            n = len(batcher._pending)
        if n < 10:
            return
        real_flush()

    batcher.flush = gated_flush
    gate = threading.Event()
    try:
        # hold the dispatch thread so both loops' items coalesce
        ex.submit(lambda: gate.wait(timeout=5.0), name="gate")
        results: dict[str, list] = {}
        barrier = threading.Barrier(2, timeout=5.0)

        def run_loop(tag: str):
            async def main():
                barrier.wait()
                return await asyncio.gather(
                    *(batcher.submit(np.full(4, i, np.float32)) for i in range(5)),
                    return_exceptions=True,
                )

            results[tag] = asyncio.run(main())

        threads = [
            threading.Thread(target=run_loop, args=(tag,)) for tag in ("a", "b")
        ]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with batcher._lock:
                if not batcher._pending and len(batcher._flushers) == 0:
                    break
            time.sleep(0.01)
        gate.set()
        for t in threads:
            t.join(timeout=10.0)
    finally:
        gate.set()
        ex.close()
    assert calls == [10]  # ONE coalesced batch across both loops
    for tag in ("a", "b"):
        assert len(results[tag]) == 5
        for exc in results[tag]:
            # exactly once, typed: every waiter got the quarantine error
            assert isinstance(exc, DeviceQuarantinedError), exc
    with batcher._lock:
        assert not batcher._pending  # nothing stranded


def test_batcher_submit_failure_after_close_fails_waiters_not_hangs():
    ex = DeviceExecutor(collector_name=None)
    batcher = AsyncMicroBatcher(
        lambda items: items, max_batch_size=4, flush_delay=0.001, executor=ex
    )
    ex.close()

    async def main():
        return await asyncio.gather(
            batcher.submit(1), batcher.submit(2), return_exceptions=True
        )

    out = asyncio.run(main())
    assert all(isinstance(e, ExecutorClosedError) for e in out), out


# --- surfacing: snapshots, render, top ---------------------------------------


@pytest.mark.chaos
def test_device_snapshot_and_renders_carry_resilience_state(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_BREAKER_THRESHOLD", "1")
    monkeypatch.setenv("PATHWAY_DEVICE_RETRIES", "0")

    def bad_fallback(x):
        raise ValueError("still poisoned")

    ex = DeviceExecutor(collector_name=None)
    ex.register(
        "enc",
        lambda x: x * 2.0,
        policy=BucketPolicy(max_bucket=8),
        host_fallback=bad_fallback,
    )
    faults.install_plan(
        faults.FaultPlan(
            [{"kind": "device_error", "source": "enc", "from_nth": 1,
              "max_times": 99}],
            seed=13,
        )
    )
    with pytest.raises(DeviceQuarantinedError):
        ex.run_batch("enc", (np.ones((2, 4), np.float32),))
    snap = ex.device_snapshot()
    section = snap["resilience"]
    assert section["enabled"] is True
    assert section["callables"]["enc"]["breaker"]["state"] == "open"
    assert len(section["quarantine"]) == 1
    # JSON-able end to end (what rides a flight-recorder dump)
    import json

    json.dumps(snap)
    rendered = render_device_snapshot(snap)
    assert "breaker open" in rendered
    assert "quarantine: 1 poisoned batch(es)" in rendered
    # the `pathway_tpu top` device panel shows the same story from the
    # /status scalar section
    status = {
        "epochs": 3,
        "device": {
            "device.dispatch.batches": 4.0,
            "device.breaker.state{callable=enc}": 1.0,
            "device.bucket.cap{callable=enc}": 8.0,
            "device.oom.splits": 2.0,
            "device.fallback.batches": 3.0,
            "device.quarantine.batches": 1.0,
            "device.dispatch.restarts": 1.0,
        },
    }
    frame = render_top(status)
    assert "breaker: enc OPEN" in frame
    assert "oom ratchet: enc capped at bucket 8" in frame
    assert "degraded: 3 host-fallback batch(es) · 1 quarantined · 1 dispatch restart(s)" in frame


def test_quarantine_log_is_bounded(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_QUARANTINE_KEEP", "2")
    log = res.QuarantineLog.from_env()
    for i in range(5):
        log.add("enc", i, (np.ones((i + 1, 2)),), None, ValueError(f"e{i}"))
    assert len(log) == 2
    assert log.total == 5
    assert [r["rows"] for r in log.records()] == [3, 4]
