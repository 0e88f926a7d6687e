"""DeviceExecutor: the one sanctioned device-dispatch path.

Every jitted hot-path callable in this repo (encoder towers, rerankers,
the indexing top-k scan) used to shape its own batches ad hoc; this
module centralizes the three disciplines the device path needs
(ROADMAP "DeviceExecutor" arc; WindVE's collaborative CPU↔device queue
in PAPERS.md is the model):

1. **Fixed shapes** — :meth:`DeviceExecutor.run_batch` plans ragged row
   batches onto the declared power-of-two buckets
   (``device/bucketing.py``), pads with masked zero rows, and splits
   oversized batches, so a registered callable compiles once per bucket
   and steady-state ``jax.cache.miss`` stays at zero (the PR 8 dynamic
   counter is the pin, ``tests/test_jax_accounting.py``).

2. **Compile-cache discipline** — callables are registered once
   (:meth:`register`) and jitted once; every dispatch computes an
   explicit cache key (callable id, bucket shapes, dtypes, static args,
   backend) so cold compiles are *counted* (``device.cache.cold``) and
   can be paid ahead of traffic via :meth:`warmup`.  ``pathway_tpu
   lint`` enforces the other half: a direct ``jax.jit`` call site in
   ``xpacks/``/``stdlib/`` is a ``jit-outside-executor`` finding.

3. **Async dispatch with bounded in-flight budget** — :meth:`submit`
   queues host-side batch jobs onto a dispatch thread and hands a
   :class:`DeviceFuture` back, so device work overlaps epoch execution
   (the PR 3 async-committer overlap pattern applied to compute).  The
   budget is bytes + requests (``PATHWAY_DEVICE_INFLIGHT_MB`` /
   ``PATHWAY_DEVICE_INFLIGHT_REQUESTS``); a full queue backpressures the
   submitter and the stall is *counted* (``device.backpressure.s``).
   Queue depth/bytes/age export under ``backlog.device.*`` so a device
   stall is attributable next to every other wait point in the system
   (PR 9's backpressure namespace) — proven by the ``device_stall``
   chaos fault (``engine/faults.py``).

4. **Cost accounting at compile time** — every fresh cache key is
   compiled through the AOT path (``jitted.lower().compile()``; the
   executable is kept and reused, so it is still one backend compile
   per key) and its ``cost_analysis()``/``memory_analysis()`` feed the
   device observability layer (``device/telemetry.py``): flops totals,
   roofline utilization, per-bucket occupancy, padding waste, and the
   HBM live-bytes fallback — see docs/device_executor.md, "Cost
   accounting & roofline".

5. **Fault tolerance** (``device/resilience.py``) — every dispatch is
   wrapped in the typed failure classifier: transient XLA errors get
   bounded jittered retries (the udfs backoff policy), RESOURCE_EXHAUSTED
   splits the batch onto smaller buckets and ratchets the callable's
   max-bucket cap (``device.oom.splits``/``device.bucket.cap``), a
   per-callable circuit breaker trips to the un-jitted **host fallback**
   after K consecutive failures (``device.breaker.state``,
   ``device.fallback.*``) with half-open probing, a batch that fails
   retries AND fallback is quarantined with a typed error to its waiters
   (``device.quarantine.*``), and a job that blows the hard dispatch
   deadline fails its waiters while the wedged dispatch thread is torn
   down and respawned (``device.dispatch.restarts``).  Kill switch:
   ``PATHWAY_DEVICE_RESILIENCE=0``.  Contract: docs/fault_tolerance.md,
   "Device-path failures".

``AsyncMicroBatcher`` (``utils/batching.py``) is the coalescing
front-end over :meth:`submit`; model code reaches :meth:`run_batch`
from inside its batch callbacks.  The two layers compose: submit owns
the queue and the budget, run_batch owns shapes and the compile cache,
and run_batch is safe to call from a dispatch-thread job (it executes
inline, never re-enters the queue).
"""

from __future__ import annotations

import threading
import time
from contextvars import ContextVar
from typing import Any, Callable, Sequence

import jax
import numpy as np

from pathway_tpu.device import resilience as _res
from pathway_tpu.device import telemetry as _dtel
from pathway_tpu.device.compile_cache import ensure_compile_cache
from pathway_tpu.device.bucketing import (
    BucketPolicy,
    pad_batch_dim,
)
from pathway_tpu.engine import flight_recorder as _blackbox
from pathway_tpu.engine import metrics as _metrics
from pathway_tpu.engine import tracing as _tracing

__all__ = [
    "DeviceExecutor",
    "DeviceFuture",
    "default_executor_snapshot",
    "get_default_executor",
]

# request traces of the job currently executing on the dispatch thread —
# set by ``_run_job`` so ``run_batch``/``_run_chunk`` (inline, same
# thread) attribute their device spans to every coalesced waiter's trace
_JOB_TRACES: ContextVar[tuple] = ContextVar(
    "pathway_device_job_traces", default=()
)


def _current_traces() -> tuple:
    """Traces device spans should attach to: the running job's (batched
    submit path) or the ambient request trace (inline run_batch)."""
    traces = _JOB_TRACES.get()
    if traces:
        return traces
    trace = _tracing.current_trace()
    return (trace,) if trace is not None else ()


class DeviceFuture:
    """Thread-safe future for one queued device job.

    The epoch thread holds these while the dispatch thread works; waits
    are sliced (1 s) so a supervised worker blocked here still touches
    its progress beacon machinery rather than vanishing into an untimed
    wait."""

    __slots__ = ("_event", "_result", "_exc", "_callbacks", "_lock")

    def __init__(self):
        self._event = threading.Event()
        self._result: Any = None
        self._exc: BaseException | None = None
        self._callbacks: list[Callable[["DeviceFuture"], None]] = []
        self._lock = threading.Lock()

    def done(self) -> bool:
        return self._event.is_set()

    def set_result(self, value: Any) -> None:
        """Resolve once; a second resolution is ignored — an abandoned
        (hang-escalated) job that eventually completes on its zombie
        thread must not overwrite the typed error its waiters already
        consumed."""
        with self._lock:
            if self._event.is_set():
                return
            self._result = value
            self._event.set()
            callbacks, self._callbacks = self._callbacks, []
        for cb in callbacks:
            self._run_callback(cb)

    def set_exception(self, exc: BaseException) -> None:
        with self._lock:
            if self._event.is_set():
                return
            self._exc = exc
            self._event.set()
            callbacks, self._callbacks = self._callbacks, []
        for cb in callbacks:
            self._run_callback(cb)

    def _run_callback(self, cb: Callable[["DeviceFuture"], None]) -> None:
        try:
            cb(self)
        except Exception:  # noqa: BLE001 - a bad callback must not kill dispatch
            pass

    def add_done_callback(self, cb: Callable[["DeviceFuture"], None]) -> None:
        """Run ``cb(self)`` once resolved (immediately when already done).
        Callbacks run on the dispatch thread — keep them cheap."""
        with self._lock:
            if not self._event.is_set():
                self._callbacks.append(cb)
                return
        self._run_callback(cb)

    def result(self, timeout: float | None = None) -> Any:
        deadline = None if timeout is None else time.monotonic() + timeout
        while not self._event.is_set():
            remaining = 1.0
            if deadline is not None:
                remaining = min(1.0, deadline - time.monotonic())
                if remaining <= 0:
                    raise TimeoutError("device job did not complete in time")
            self._event.wait(timeout=remaining)
        if self._exc is not None:
            raise self._exc
        return self._result


# sentinel marking a compile-cache key whose AOT compile is in flight
_COMPILING = object()
# how long a concurrent dispatcher waits for another thread's in-flight
# compile before falling back to the jit path (a big TPU program can
# legitimately compile for minutes; waiting beats a duplicate compile)
_COMPILE_WAIT_S = 300.0


class _Registered:
    """One registered traceable: its jit wrapper + compile-key ledger +
    resilience state (breaker, retry policy, OOM bucket cap)."""

    __slots__ = (
        "name", "jitted", "policy", "seen_keys", "dispatches", "cold",
        "warmed", "lock", "cv", "compiled", "costs",
        "fn", "host_fallback", "breaker", "retry", "bucket_cap",
        "oom_splits", "fallback_batches", "failure_counts",
    )

    def __init__(
        self,
        name: str,
        jitted: Callable,
        policy: BucketPolicy,
        *,
        fn: Callable | None = None,
        host_fallback: Callable | None = None,
        breaker: "_res.CircuitBreaker | None" = None,
        retry: "_res.RetryPolicy | None" = None,
    ):
        self.name = name
        self.jitted = jitted
        self.policy = policy
        # the raw (un-jitted) callable: the host-fallback path executes
        # it eagerly on the SAME padded buffers, so a tripped breaker
        # serves bit-equivalent results from the CPU
        self.fn = fn
        self.host_fallback = host_fallback if host_fallback is not None else fn
        self.breaker = breaker
        self.retry = retry
        # OOM ratchet: the largest bucket this callable may still plan
        # (None = uncapped).  Only ever shrinks — sustained memory
        # pressure reduces footprint instead of crash-looping.
        self.bucket_cap: int | None = None
        self.oom_splits = 0
        self.fallback_batches = 0
        self.failure_counts: dict[str, int] = {}
        self.seen_keys: set[tuple] = set()
        # key -> AOT-compiled executable / compile-time cost dict
        # (device/telemetry.py): the fresh-key path compiles through
        # jitted.lower().compile() so cost_analysis() is captured at
        # compile time and the SAME executable serves every later
        # dispatch of the key — one backend compile either way.  While a
        # compile is in flight the key maps to the _COMPILING sentinel;
        # concurrent dispatchers of the same key wait on `cv` (bounded)
        # instead of paying a duplicate backend compile via the jit path
        self.compiled: dict[tuple, Any] = {}
        self.costs: dict[tuple, dict[str, float]] = {}
        self.dispatches = 0
        self.cold = 0
        self.warmed = 0
        # guards the ledger only (never held around the device call):
        # run_batch is legal from epoch, serving, and dispatch threads
        # concurrently, and a check-then-act race on seen_keys would
        # double-count cold compiles — tripping the "nonzero cold after
        # warmup is a bug" invariant spuriously
        self.lock = threading.Lock()
        # signaled when an in-flight AOT compile resolves (shares `lock`)
        self.cv = threading.Condition(self.lock)


class _Job:
    """One queued host-side batch job (the submit path)."""

    __slots__ = (
        "name", "fn", "future", "nbytes", "enqueued_at", "started_at",
        "abandoned", "finalized", "traces",
    )

    def __init__(
        self,
        name: str,
        fn: Callable[[], Any],
        nbytes: int,
        traces: tuple = (),
    ):
        self.name = name
        self.fn = fn
        self.future = DeviceFuture()
        self.nbytes = max(0, int(nbytes))
        # request traces this job serves (engine/tracing.py) — carried
        # explicitly across the submit→dispatch thread hop
        self.traces = traces
        self.enqueued_at = time.monotonic()
        # set by the dispatch loop when the job starts running — the
        # hang watchdog measures the dispatch deadline from here
        self.started_at: float | None = None
        # set by the hang escalation: the (wedged) thread running this
        # job has been written off; its eventual completion is ignored
        self.abandoned = False
        # in-flight byte accounting settled exactly once, whether by the
        # dispatch loop, the hang escalation, or close()
        self.finalized = False


def _donation_enabled() -> bool:
    """``PATHWAY_DEVICE_DONATE``: ``auto`` donates only where XLA
    implements donation (not the CPU backend, which would warn per
    call), ``on``/``off`` force it."""
    from pathway_tpu.internals.config import env_str

    mode = (env_str("PATHWAY_DEVICE_DONATE") or "auto").strip().lower()
    if mode in ("on", "1", "true"):
        return True
    if mode in ("off", "0", "false"):
        return False
    return jax.default_backend() not in ("cpu",)


class DeviceExecutor:
    """Bucketed, cache-disciplined, async device dispatch (one per
    process in practice — :func:`get_default_executor`)."""

    def __init__(
        self,
        *,
        max_inflight_mb: float | None = None,
        max_inflight_requests: int | None = None,
        collector_name: str | None = "device.executor",
    ):
        from pathway_tpu.internals.config import env_float, env_int

        ensure_compile_cache()
        if max_inflight_mb is None:
            max_inflight_mb = env_float("PATHWAY_DEVICE_INFLIGHT_MB")
        if max_inflight_requests is None:
            max_inflight_requests = env_int("PATHWAY_DEVICE_INFLIGHT_REQUESTS")
        # the default-policy cap THIS process runs with, stamped into the
        # exported gauges/snapshots so `pathway_tpu buckets` replays the
        # analyzed run's real configuration, not the analyst's shell env
        self._default_max_batch = int(env_int("PATHWAY_DEVICE_MAX_BATCH"))
        self.max_inflight_bytes = int(float(max_inflight_mb) * 1024 * 1024)
        self.max_inflight_requests = int(max_inflight_requests)
        from pathway_tpu.internals.config import env_bool

        self._callables: dict[str, _Registered] = {}
        self._queue: list[_Job] = []
        self._running: _Job | None = None
        self._inflight_bytes = 0
        self._cond = threading.Condition()
        self._thread: threading.Thread | None = None
        self._stop = False
        self._closed = False
        # bumped on every dispatch-thread (re)spawn: a loop whose gen is
        # superseded (hang escalation wrote it off) exits instead of
        # delivering into a queue a fresh thread now owns
        self._thread_gen = 0
        self._watchdog: threading.Thread | None = None
        # resilience rail (device/resilience.py): kill switch + the hard
        # per-job dispatch deadline (0 = hang escalation disabled)
        self._resilience = env_bool("PATHWAY_DEVICE_RESILIENCE")
        self._dispatch_deadline_s = float(
            env_float("PATHWAY_DEVICE_DISPATCH_DEADLINE_S") or 0.0
        )
        # never-set event: timed waits against it implement interruptible
        # retry backoff (close() sets it so shutdown never waits out a
        # backoff schedule)
        self._retry_interrupt = threading.Event()
        self._quarantine = _res.QuarantineLog.from_env()
        reg = _metrics.get_registry()
        self._m_batches = reg.counter(
            "device.dispatch.batches", "fixed-shape device batches dispatched"
        )
        self._m_rows = reg.counter(
            "device.dispatch.rows", "real rows dispatched through the executor"
        )
        self._m_pad = reg.counter(
            "device.pad.rows", "padding rows added by bucketing"
        )
        self._m_cold = reg.counter(
            "device.cache.cold", "first dispatches of a new compile-cache key"
        )
        self._m_warm = reg.counter(
            "device.warmup.compiles", "compile-cache keys paid ahead by warmup()"
        )
        self._m_jobs = reg.counter(
            "device.jobs", "async host-side batch jobs dispatched"
        )
        self._m_backpressure = reg.counter(
            "device.backpressure.s",
            "seconds submitters stalled on the in-flight budget",
        )
        self._m_dispatch_ms = reg.histogram(
            "device.dispatch.ms",
            "wall time of one dispatched device call (ms)",
            buckets=_metrics.MS_BUCKETS,
        )
        self._m_job_ms = reg.histogram(
            "device.job.ms",
            "wall time of one async host-side batch job (ms)",
            buckets=_metrics.MS_BUCKETS,
        )
        self._m_occupancy = reg.histogram(
            "device.bucket.occupancy",
            "real-row fraction of each dispatched bucket (1.0 = no padding)",
            buckets=_metrics.OCCUPANCY_BUCKETS,
        )
        # fault-tolerance counters (device/resilience.py)
        self._m_retries = reg.counter(
            "device.retry.attempts",
            "transient device failures retried by the dispatch wrapper",
        )
        self._m_oom_splits = reg.counter(
            "device.oom.splits",
            "RESOURCE_EXHAUSTED chunks split onto smaller buckets",
        )
        self._m_breaker_trips = reg.counter(
            "device.breaker.trips",
            "circuit-breaker open transitions (K consecutive device "
            "failures, or a failed half-open probe)",
        )
        self._m_fb_batches = reg.counter(
            "device.fallback.batches",
            "batches served by the un-jitted host-fallback path",
        )
        self._m_fb_rows = reg.counter(
            "device.fallback.rows", "real rows served by the host fallback"
        )
        self._m_fb_ms = reg.histogram(
            "device.fallback.ms",
            "wall time of one host-fallback batch execution (ms)",
            buckets=_metrics.MS_BUCKETS,
        )
        self._m_quarantine = reg.counter(
            "device.quarantine.batches",
            "poisoned batches quarantined (device retries AND host "
            "fallback failed)",
        )
        self._m_restarts = reg.counter(
            "device.dispatch.restarts",
            "dispatch threads torn down and respawned after a hard "
            "dispatch-deadline hang",
        )
        self._reg = reg
        # device-path cost ledger (device/telemetry.py): compile-time XLA
        # cost analysis x dispatch durations -> flops totals, roofline
        # utilization, and the batch-size distribution `pathway_tpu
        # buckets` replays
        self._accountant = _dtel.CostAccountant(registry=reg)
        # per-executor padding totals (the registry counters are shared
        # family children across executors, so the waste FRACTION must be
        # computed from this instance's own ledger)
        self._pad_rows = 0
        self._real_rows = 0
        # live-bytes fallback for backends without memory_stats(): the
        # argument+output+temp footprint of dispatches currently running
        self._mem_lock = threading.Lock()
        self._live_bytes = 0.0
        self._live_peak = 0.0
        if collector_name:
            reg.register_collector(collector_name, self.metrics_snapshot)

    # -- registration & compile-cache discipline -----------------------------

    def register(
        self,
        name: str,
        fn: Callable,
        *,
        static_argnames: Sequence[str] = (),
        donate_argnums: Sequence[int] = (),
        policy: BucketPolicy | None = None,
        host_fallback: Callable | None = None,
    ) -> str:
        """Register traceable ``fn`` under ``name`` and jit it ONCE.

        ``fn`` is called as ``fn(*operands, *arrays, **static)`` where
        the arrays carry the bucketed batch axis.  ``donate_argnums``
        name the array positions safe to donate (fresh padded buffers);
        donation is applied only where the backend implements it (see
        ``PATHWAY_DEVICE_DONATE``).  Re-registering a name replaces the
        callable and resets its compile ledger.

        ``host_fallback`` overrides the CPU path a tripped circuit
        breaker routes to; the default is ``fn`` itself executed
        un-jitted on the same padded buffers (bit-equivalent by the
        padding-mask contract).  Resilience state (breaker, retry
        policy) is created from the ``PATHWAY_DEVICE_*`` knobs at
        registration time; ``PATHWAY_DEVICE_RESILIENCE=0`` at executor
        construction disables the whole rail."""
        if policy is None:
            from pathway_tpu.internals.config import env_int

            policy = BucketPolicy(max_bucket=env_int("PATHWAY_DEVICE_MAX_BATCH"))
        jitted = self._jit_wrap(fn, tuple(static_argnames), tuple(donate_argnums))
        self._callables[name] = _Registered(
            name,
            jitted,
            policy,
            fn=fn,
            host_fallback=host_fallback,
            breaker=_res.CircuitBreaker.from_env() if self._resilience else None,
            retry=_res.RetryPolicy.from_env() if self._resilience else None,
        )
        return name

    def _jit_wrap(
        self,
        fn: Callable,
        static_argnames: tuple[str, ...],
        donate_argnums: tuple[int, ...],
    ) -> Callable:
        kwargs: dict[str, Any] = {}
        if static_argnames:
            kwargs["static_argnames"] = static_argnames
        if donate_argnums and _donation_enabled():
            kwargs["donate_argnums"] = donate_argnums
        return jax.jit(fn, **kwargs)

    def set_resilience(self, on: bool) -> None:
        """Toggle the fault-tolerance rail at runtime — the benchmark /
        test lever mirroring ``metrics.set_enabled``.  Turning it off
        bypasses routing only (breaker state, caps and ledgers are
        kept); turning it on creates resilience state for callables
        registered while it was off."""
        self._resilience = bool(on)
        if on:
            for entry in self._callables.values():
                if entry.breaker is None:
                    entry.breaker = _res.CircuitBreaker.from_env()
                if entry.retry is None:
                    entry.retry = _res.RetryPolicy.from_env()

    def registered(self, name: str) -> bool:
        return name in self._callables

    def jitted(self, name: str) -> Callable:
        """The raw compiled wrapper of a registered callable — for
        benchmarks/tests that feed pre-padded fixed shapes directly.
        Production code goes through :meth:`run_batch`, which is what
        keeps the shapes on-bucket."""
        return self._callables[name].jitted

    def cache_keys(self, name: str) -> set[tuple]:
        """The compile-cache keys this executor has dispatched (or
        warmed) for ``name`` — the discipline ledger, for tests and
        ``warmup`` planning."""
        entry = self._callables[name]
        with entry.lock:
            return set(entry.seen_keys)

    def executables(self, name: str) -> dict[tuple, Any]:
        """The AOT-compiled executable behind each cache key of ``name``
        — for inspecting what was actually compiled (``as_text()``,
        ``cost_analysis()``), e.g. that a kernel made it into the
        program."""
        entry = self._callables[name]
        with entry.lock:
            return {
                key: compiled
                for key, compiled in entry.compiled.items()
                if compiled is not _COMPILING
            }

    def stats(self, name: str) -> dict[str, int]:
        entry = self._callables[name]
        with entry.lock:
            return {
                "dispatches": entry.dispatches,
                "cold": entry.cold,
                "warmed": entry.warmed,
                "keys": len(entry.seen_keys),
            }

    @staticmethod
    def _cache_key(
        operands: tuple, arrays: tuple, static: dict[str, Any] | None
    ) -> tuple:
        """Explicit cache key: every leaf's (shape, dtype) + static args
        + backend.  Mirrors what jit keys on, so ``seen_keys`` tracks
        the real compile cache one-to-one."""
        leaves: list[tuple] = []
        for leaf in jax.tree_util.tree_leaves((operands, arrays)):
            leaves.append(
                (tuple(getattr(leaf, "shape", ())), str(getattr(leaf, "dtype", type(leaf).__name__)))
            )
        static_key = tuple(sorted((static or {}).items()))
        return (tuple(leaves), static_key, jax.default_backend())

    @staticmethod
    def _cost_analysis_enabled() -> bool:
        from pathway_tpu.internals.config import env_bool

        return env_bool("PATHWAY_DEVICE_COST_ANALYSIS")

    def _compile_key(
        self,
        entry: _Registered,
        key: tuple,
        operands: tuple,
        arrays: tuple,
        static: dict[str, Any] | None,
    ) -> Any:
        """AOT-compile a fresh cache key and capture its XLA cost.

        ``jitted.lower().compile()`` and a plain jit call do NOT share a
        compile cache, so the executable compiled here is kept and
        reused for every later dispatch of the key — paying one backend
        compile AND getting ``cost_analysis()``/``memory_analysis()`` at
        compile time.  A compile failure propagates to the dispatch that
        asked for it: it is classified and counted like any other device
        failure (``device/resilience.py``), never absorbed into a second,
        unaccounted compile on the jit path.  The caller has already
        claimed the key with the ``_COMPILING`` sentinel inside the
        freshness critical section."""
        try:
            lowered = entry.jitted.lower(*operands, *arrays, **(static or {}))
            compiled = lowered.compile()
            cost = _dtel.extract_cost(compiled)
            with entry.cv:
                entry.compiled[key] = compiled
                entry.costs[key] = cost
                entry.cv.notify_all()
            return compiled
        except BaseException:
            # un-claim the key: its next dispatch is fresh again and
            # re-attempts this compile instead of taking the jit path
            with entry.cv:
                entry.seen_keys.discard(key)
            raise
        finally:
            # ANY exit that left the sentinel behind (including a
            # BaseException unwinding through the compile) must clear it,
            # or concurrent dispatchers of this key would block on a
            # compile that is never coming
            with entry.cv:
                if entry.compiled.get(key) is _COMPILING:
                    entry.compiled.pop(key, None)
                entry.cv.notify_all()

    def _dispatch_fixed(
        self,
        entry: _Registered,
        operands: tuple,
        arrays: tuple,
        static: dict[str, Any] | None,
        *,
        warmup: bool = False,
        note: dict[str, Any] | None = None,
    ) -> Any:
        key = self._cache_key(operands, arrays, static)
        aot = False
        with entry.lock:
            fresh = key not in entry.seen_keys
            if fresh:
                entry.seen_keys.add(key)
                if warmup:
                    entry.warmed += 1
                else:
                    entry.cold += 1
                # resolved only on fresh keys (an env read per dispatch
                # would tax the warm path for nothing)
                aot = self._cost_analysis_enabled()
                if aot:
                    # claim the key IN the same critical section that
                    # decided freshness: a concurrent dispatcher must see
                    # the sentinel (and wait below), never a gap in which
                    # it pays a duplicate backend compile via the jit path
                    entry.compiled[key] = _COMPILING
            entry.dispatches += 1
            compiled = entry.compiled.get(key)
            cost = entry.costs.get(key)
        if note is not None:
            note["cache"] = "cold" if fresh else "warm"
        if fresh:
            (self._m_warm if warmup else self._m_cold).inc()
            compiled = (
                self._compile_key(entry, key, operands, arrays, static)
                if aot
                else None
            )
            with entry.lock:
                cost = entry.costs.get(key)
        elif compiled is _COMPILING:
            # another thread is AOT-compiling this key right now: wait
            # for its executable (timed slices, never unbounded) rather
            # than paying a DUPLICATE backend compile through the jit
            # path — the jit and AOT caches are separate
            deadline = time.monotonic() + _COMPILE_WAIT_S
            with entry.cv:
                while (
                    entry.compiled.get(key) is _COMPILING
                    and time.monotonic() < deadline
                ):
                    entry.cv.wait(timeout=1.0)
                compiled = entry.compiled.get(key)
                cost = entry.costs.get(key)
            if compiled is _COMPILING:  # compiler thread wedged/too slow
                compiled = None
                cost = None
        # live-bytes tracking is part of the accounting rail: the kill
        # switch (PATHWAY_METRICS_DISABLED) drops its lock sections too
        footprint = 0.0
        if self._accountant.enabled:
            footprint = (
                cost["argument_bytes"]
                + cost["output_bytes"]
                + cost["temp_bytes"]
                if cost
                else float(sum(getattr(a, "nbytes", 0) for a in arrays))
            )
            with self._mem_lock:
                self._live_bytes += footprint
                self._live_peak = max(self._live_peak, self._live_bytes)
        t0 = time.monotonic()
        try:
            # fault injection sits INSIDE the dispatch so an injected
            # failure flows through the same classify/retry/breaker
            # machinery a real XLA error would (engine/faults.py)
            self._maybe_inject_failure(entry.name)
            if compiled is not None:
                # statics are baked into the AOT executable at lowering
                out = compiled(*operands, *arrays)
            else:
                out = entry.jitted(*operands, *arrays, **(static or {}))
            out = jax.tree_util.tree_map(np.asarray, out)
        finally:
            if footprint:
                with self._mem_lock:
                    self._live_bytes -= footprint
        duration = time.monotonic() - t0
        self._m_dispatch_ms.observe(duration * 1000.0)
        self._m_batches.inc()
        self._accountant.record_dispatch(cost, duration)
        return out

    # -- fault classification, retry, fallback, quarantine --------------------

    def _maybe_inject_failure(self, name: str) -> None:
        """``device_error`` / ``device_oom`` / ``device_compile_fail``
        fault injection (``engine/faults.py``): raised HERE, inside the
        dispatch, so injected failures take the exact classify / retry /
        breaker / fallback path real XLA failures do."""
        from pathway_tpu.engine import faults

        plan = faults.active_plan()
        if plan is None:
            return
        if plan.check("device_error", source=name) is not None:
            raise _res.InjectedDeviceError(
                f"INTERNAL: injected transient device failure ({name})"
            )
        if plan.check("device_oom", source=name) is not None:
            raise _res.InjectedDeviceError(
                f"RESOURCE_EXHAUSTED: injected device OOM ({name})"
            )
        if plan.check("device_compile_fail", source=name) is not None:
            raise _res.InjectedDeviceError(
                f"injected XLA compilation failure ({name})"
            )

    def _count_failure(
        self, entry: _Registered, kind: str, exc: BaseException
    ) -> None:
        with entry.lock:
            entry.failure_counts[kind] = entry.failure_counts.get(kind, 0) + 1
        self._reg.counter(
            "device.failures",
            "classified device-path failures observed (kind label)",
            kind=kind,
        ).inc()
        _blackbox.record(
            "device.failure",
            callable=entry.name,
            failure=kind,
            error=str(exc)[:200],
        )

    def _dispatch_with_retry(
        self,
        entry: _Registered,
        operands: tuple,
        arrays: tuple,
        static: dict[str, Any] | None,
        *,
        warmup: bool = False,
        note: dict[str, Any] | None = None,
    ) -> Any:
        """One fixed-shape dispatch under the typed-failure contract:
        non-device exceptions propagate raw (a deterministic host bug
        must not be retried into invisibility); device failures are
        classified, counted, and — for transients only — retried on the
        bounded jittered udfs backoff schedule, capped by the retry
        deadline."""
        retry = entry.retry
        # the schedule is materialized lazily, on the FIRST failure: the
        # happy path must not pay a strategy object + generator per
        # dispatch (the ≤2%-of-dispatch-cost pin,
        # benchmarks/device_fault_recovery.py)
        delays = None
        deadline = 0.0
        attempt = 0
        while True:
            try:
                return self._dispatch_fixed(
                    entry, operands, arrays, static, warmup=warmup, note=note
                )
            except Exception as exc:  # noqa: BLE001 - classified below
                typed = _res.classify(exc)
                if typed is None:
                    raise  # host bug, not a device failure
                self._count_failure(entry, typed.kind, exc)
                if typed is exc:
                    raise  # already typed by a nested layer
                if retry is None or typed.kind != "transient":
                    raise typed from exc
                if delays is None:
                    delays = retry.delays()
                    deadline = time.monotonic() + retry.deadline_s
                attempt += 1
                if note is not None:
                    note["retries"] = attempt
                remaining = deadline - time.monotonic()
                if attempt > retry.retries or remaining <= 0:
                    raise typed from exc
                self._m_retries.inc()
                # interruptible timed wait (never a bare sleep): close()
                # sets the event so shutdown never waits out a backoff
                self._retry_interrupt.wait(
                    timeout=min(next(delays), max(0.0, remaining))
                )
                if self._closed:
                    raise _res.ExecutorClosedError(
                        "device executor closed during retry backoff"
                    ) from exc

    def _ratchet(
        self, entry: _Registered, cap: int, exc: BaseException
    ) -> None:
        """OOM graceful degradation: shrink the callable's max-bucket
        cap (only ever downward) so sustained memory pressure reduces
        device footprint instead of crash-looping."""
        with entry.lock:
            entry.bucket_cap = (
                cap if entry.bucket_cap is None else min(entry.bucket_cap, cap)
            )
            entry.oom_splits += 1
            new_cap = entry.bucket_cap
        self._m_oom_splits.inc()
        self._reg.gauge(
            "device.bucket.cap",
            "largest bucket a callable may plan after OOM ratcheting",
            callable=entry.name,
        ).set(float(new_cap))
        _blackbox.record(
            "device.oom.ratchet",
            callable=entry.name,
            cap=new_cap,
            error=str(exc)[:200],
        )

    def _run_host_fallback(
        self,
        entry: _Registered,
        operands: tuple,
        padded: tuple,
        static: dict[str, Any] | None,
    ) -> Any:
        """Un-jitted CPU execution of the registered callable on the
        SAME padded buffers — the padding-mask contract that makes
        bucketing correct also makes this bit-equivalent."""
        fb = entry.host_fallback
        if fb is None:
            raise RuntimeError(
                f"no host fallback registered for {entry.name!r}"
            )
        t0 = time.monotonic()
        out = jax.tree_util.tree_map(
            np.asarray, fb(*operands, *padded, **(static or {}))
        )
        self._m_fb_ms.observe((time.monotonic() - t0) * 1000.0)
        return out

    def _quarantine_batch(
        self,
        entry: _Registered,
        padded: tuple,
        count: int,
        device_exc: BaseException | None,
        fallback_exc: BaseException,
    ) -> None:
        record = self._quarantine.add(
            entry.name, count, padded, device_exc, fallback_exc
        )
        self._m_quarantine.inc()
        _blackbox.record(
            "device.quarantine",
            callable=entry.name,
            rows=count,
            device_error=record["device_error"],
            fallback_error=record["fallback_error"],
        )

    def _ledger(self, count: int, bucket: int) -> None:
        """Padding/occupancy accounting for one chunk that actually
        served (device or fallback) at ``bucket``."""
        self._m_rows.inc(count)
        self._m_pad.inc(bucket - count)
        self._m_occupancy.observe(count / bucket)
        # locked: run_batch is legal from epoch, serving, and dispatch
        # threads concurrently, and an unguarded += would lose increments
        # and understate padding waste
        with self._mem_lock:
            self._real_rows += count
            self._pad_rows += bucket - count

    def _run_chunk(
        self,
        entry: _Registered,
        operands: tuple,
        rows: tuple,
        count: int,
        bucket: int,
        static: dict[str, Any] | None,
    ) -> list[Any]:
        """Dispatch one planned chunk; when request traces are in scope
        (a traced job on the dispatch thread, or an ambient trace on an
        inline ``run_batch``), the chunk records a ``device.dispatch``
        span per trace — bucket, rows, cache cold/warm, retries and
        fallback attributes filled by the layers below via ``note``."""
        traces = _current_traces()
        # the timeline's interval, from the call to the result on the host
        call = _tracing.begin(
            "executor", "device.call",
            callable=entry.name, bucket=bucket, rows=count,
        )
        note: dict[str, Any] | None = {} if traces else None
        started = time.time()
        t0 = time.monotonic()
        try:
            return self._run_chunk_inner(
                entry, operands, rows, count, bucket, static, note
            )
        finally:
            _tracing.end(call)
            duration_s = time.monotonic() - t0
            for trace in traces:
                trace.add_span(
                    "device.dispatch",
                    started,
                    duration_s,
                    callable=entry.name,
                    bucket=bucket,
                    rows=count,
                    **note,
                )

    def _run_chunk_inner(
        self,
        entry: _Registered,
        operands: tuple,
        rows: tuple,
        count: int,
        bucket: int,
        static: dict[str, Any] | None,
        note: dict[str, Any] | None,
    ) -> list[Any]:
        """Dispatch one planned chunk under the resilience contract;
        returns the (unpadded) outputs, possibly from several smaller
        dispatches after an OOM ratchet."""
        padded = tuple(pad_batch_dim(r, bucket)[0] for r in rows)
        breaker = entry.breaker if self._resilience else None
        if breaker is None:
            # resilience rail off: PR-11 behavior, raw errors to callers
            out = self._dispatch_fixed(entry, operands, padded, static, note=note)
            self._ledger(count, bucket)
            return [_slice_rows(out, count)]
        route = breaker.admit()
        probe = route == "probe"
        device_exc: BaseException | None = None
        if route != "fallback":
            try:
                out = self._dispatch_with_retry(
                    entry, operands, padded, static, note=note
                )
            except _res.ExecutorClosedError:
                # close() interrupted a retry backoff: not a device
                # failure — no breaker count, no fallback compute on a
                # closed executor; the waiter gets the typed closed error
                if probe:
                    breaker.abort_probe()
                raise
            except _res.DeviceOOMError as exc:
                smaller = entry.policy.next_smaller(bucket)
                if smaller is not None:
                    # the device answered — it is responsive, just out of
                    # memory: the ratchet (not the breaker) owns this
                    breaker.record_success(probe=probe)
                    self._ratchet(entry, smaller, exc)
                    return self._run_rows(entry, operands, rows, count, static)
                # already at the smallest bucket: a persistent failure
                device_exc = exc
                if breaker.record_failure(probe=probe):
                    self._on_breaker_trip(entry)
            except _res.DeviceJobError as exc:
                device_exc = exc
                if breaker.record_failure(probe=probe):
                    self._on_breaker_trip(entry)
            except BaseException:
                # a host bug escaping raw (classify() refused to wrap
                # it): the probe's outcome will never be reported — the
                # slot must be released or the breaker latches into
                # permanent fallback with a healthy device
                if probe:
                    breaker.abort_probe()
                raise
            else:
                if breaker.record_success(probe=probe):
                    _blackbox.record(
                        "device.breaker.close", callable=entry.name
                    )
                self._ledger(count, bucket)
                return [_slice_rows(out, count)]
        # degraded mode: the un-jitted host path serves this batch
        if note is not None:
            note["fallback"] = True
        try:
            out = self._run_host_fallback(entry, operands, padded, static)
        except Exception as exc:  # noqa: BLE001 - the poisoned-batch terminus
            self._quarantine_batch(entry, padded, count, device_exc, exc)
            device_part = (
                f"device failed ({device_exc})"
                if device_exc is not None
                else "device not attempted (breaker open)"
            )
            raise _res.DeviceQuarantinedError(
                f"batch quarantined for {entry.name!r}: {device_part}; "
                f"host fallback failed ({exc})"
            ) from exc
        with entry.lock:
            entry.fallback_batches += 1
        self._m_fb_batches.inc()
        self._m_fb_rows.inc(count)
        self._ledger(count, bucket)
        return [_slice_rows(out, count)]

    def _on_breaker_trip(self, entry: _Registered) -> None:
        self._m_breaker_trips.inc()
        _blackbox.record(
            "device.breaker.open",
            callable=entry.name,
            threshold=entry.breaker.threshold if entry.breaker else 0,
        )

    def _run_rows(
        self,
        entry: _Registered,
        operands: tuple,
        arrays: tuple,
        n_rows: int,
        static: dict[str, Any] | None,
    ) -> list[Any]:
        """Plan ``n_rows`` under the callable's current OOM bucket cap
        and dispatch every chunk; re-entered when a mid-stream ratchet
        re-plans a failing chunk at a smaller cap."""
        outs: list[Any] = []
        with entry.lock:
            cap = entry.bucket_cap
        for chunk in entry.policy.plan(n_rows, cap=cap):
            rows = tuple(
                a[chunk.start : chunk.start + chunk.count] for a in arrays
            )
            outs.extend(
                self._run_chunk(
                    entry, operands, rows, chunk.count, chunk.bucket, static
                )
            )
        return outs

    # -- the fixed-shape inline path -----------------------------------------

    def run_batch(
        self,
        name: str,
        arrays: Sequence[np.ndarray],
        n_rows: int | None = None,
        *,
        operands: Sequence[Any] = (),
        static: dict[str, Any] | None = None,
    ) -> Any:
        """Run a ragged batch through the registered callable on warm
        bucketed shapes; returns outputs with padding sliced off.

        ``arrays`` share a leading batch axis of ``n_rows`` (defaulting
        to the first array's).  Batches above the policy's largest
        bucket are split; each chunk is padded to its bucket with zero
        rows.  Outputs (a single array or a tuple/list of arrays, each
        leading with the batch axis) are unpadded and concatenated back
        to ``n_rows``.  Executes inline on the calling thread — safe
        from a dispatch-thread job; use :meth:`submit` for async.

        Failure semantics (``device/resilience.py``): transient device
        errors are retried, OOM splits onto smaller buckets and ratchets
        the callable's cap, persistent failures trip the per-callable
        breaker to the host fallback, and a batch that fails device AND
        fallback raises :class:`DeviceQuarantinedError`.  Host bugs in
        the callable itself always propagate raw."""
        if self._closed and not (
            self._thread is not None
            and threading.current_thread() is self._thread
        ):
            # external callers are refused after close(); the dispatch
            # thread itself stays admitted so close()'s drain window can
            # finish queued jobs whose fn routes through run_batch (the
            # AsyncMicroBatcher path) instead of failing them at the door
            raise _res.ExecutorClosedError(
                "run_batch() on a closed device executor"
            )
        entry = self._callables[name]
        arrays = tuple(np.asarray(a) for a in arrays)
        if n_rows is None:
            n_rows = arrays[0].shape[0]
        if n_rows == 0:
            raise ValueError("cannot dispatch an empty batch")
        for a in arrays:
            if a.shape[0] != n_rows:
                raise ValueError(
                    f"batch arrays disagree on row count: {a.shape[0]} != {n_rows}"
                )
        operands = tuple(operands)
        self._accountant.record_batch(n_rows)
        chunk_outs = self._run_rows(entry, operands, arrays, n_rows, static)
        if len(chunk_outs) == 1:
            return chunk_outs[0]
        return _concat_rows(chunk_outs)

    def warmup(
        self,
        name: str,
        row_shapes: Sequence[tuple[int, ...]],
        dtypes: Sequence[Any],
        *,
        operands: Sequence[Any] = (),
        static: dict[str, Any] | None = None,
        buckets: Sequence[int] | None = None,
    ) -> int:
        """Pay every bucket's compile before traffic arrives.

        ``row_shapes``/``dtypes`` describe one row of each array (the
        trailing shape, without the batch axis).  Returns the number of
        cache keys compiled.  Warmed keys count under
        ``device.warmup.compiles``, not ``device.cache.cold`` — after a
        full warmup, any nonzero cold counter is a discipline bug."""
        entry = self._callables[name]
        if buckets is None:
            buckets = entry.policy.buckets()
        before = len(entry.seen_keys)
        for bucket in buckets:
            arrays = tuple(
                np.zeros((bucket,) + tuple(shape), dtype=dtype)
                for shape, dtype in zip(row_shapes, dtypes)
            )
            if self._resilience:
                # warmup dispatches sit under the same typed-failure
                # contract as traffic: transients retry on the bounded
                # schedule instead of failing startup, and anything
                # persistent surfaces as a typed DeviceJobError (the
                # breaker/fallback stay out of it — warming the host
                # path would compile nothing)
                self._dispatch_with_retry(
                    entry, tuple(operands), arrays, static, warmup=True
                )
            else:
                self._dispatch_fixed(
                    entry, tuple(operands), arrays, static, warmup=True
                )
        return len(entry.seen_keys) - before

    # -- the async host-job path ---------------------------------------------

    def submit(
        self,
        fn: Callable[[], Any],
        *,
        name: str = "host",
        nbytes: int = 0,
        timeout_s: float | None = None,
        traces: tuple = (),
    ) -> DeviceFuture:
        """Queue ``fn()`` onto the dispatch thread; returns its future.

        Blocks (bounded, counted) while the in-flight budget — requests
        and bytes — is exhausted: that stall IS the backpressure signal,
        surfaced as ``device.backpressure.s`` and attributable live via
        ``backlog.device.*``.  Never call from the dispatch thread (a
        dispatch-thread job that needs device work calls
        :meth:`run_batch` inline instead)."""
        if (
            self._thread is not None
            and threading.current_thread() is self._thread
        ):
            raise RuntimeError(
                "submit() called from the dispatch thread — run_batch() "
                "is the inline API for dispatch-side device work"
            )
        if self._closed:
            raise _res.ExecutorClosedError(
                "submit() on a closed device executor"
            )
        # serving deadline propagation (shed-before-work): a request whose
        # budget already lapsed must not queue a device dispatch — the
        # client has been (or is being) answered 504 (engine/serving.py)
        from pathway_tpu.engine import serving as _serving

        _serving.shed_if_expired("device")
        if not traces:
            # direct submit (no batcher in front): the ambient request
            # trace of the submitting context is the one to carry over
            traces = _current_traces()
        job = _Job(name, fn, nbytes, traces=traces)
        deadline = (
            None if timeout_s is None else time.monotonic() + timeout_s
        )
        stalled = 0.0
        try:
            with self._cond:
                while self._over_budget():
                    if deadline is not None and time.monotonic() >= deadline:
                        raise TimeoutError(
                            "device executor in-flight budget full past deadline"
                        )
                    if self._closed:
                        raise _res.ExecutorClosedError(
                            "device executor closed while submit() waited "
                            "on the in-flight budget"
                        )
                    t0 = time.monotonic()
                    self._cond.wait(timeout=0.1)
                    stalled += time.monotonic() - t0
                if self._closed:
                    # close() may free the budget (failing leftovers) and
                    # wake this waiter with the loop condition now false —
                    # enqueueing here would resurrect the dispatch thread
                    # on a closed executor
                    raise _res.ExecutorClosedError(
                        "device executor closed while submit() waited "
                        "on the in-flight budget"
                    )
                self._inflight_bytes += job.nbytes
                self._queue.append(job)
                self._ensure_thread()
                self._cond.notify_all()
        finally:
            # a timed-out submit stalled too — the count must not hide it
            if stalled:
                self._m_backpressure.inc(stalled)
        return job.future

    def _over_budget(self) -> bool:
        inflight = len(self._queue) + (1 if self._running is not None else 0)
        return (
            inflight >= self.max_inflight_requests
            or self._inflight_bytes >= self.max_inflight_bytes
        )

    def _ensure_thread(self) -> None:
        """(Re)spawn the dispatch thread — caller holds ``_cond``."""
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop = False
        self._thread_gen += 1
        self._thread = threading.Thread(
            target=self._dispatch_loop,
            args=(self._thread_gen,),
            name="device-dispatch",
            daemon=True,
        )
        self._thread.start()
        if (
            self._dispatch_deadline_s > 0
            and (self._watchdog is None or not self._watchdog.is_alive())
        ):
            self._watchdog = threading.Thread(
                target=self._watchdog_loop,
                name="device-dispatch-watchdog",
                daemon=True,
            )
            self._watchdog.start()

    # pathway-lint: context=device
    def _dispatch_loop(self, gen: int) -> None:
        while True:
            with self._cond:
                while (
                    not self._queue
                    and not self._stop
                    and self._thread_gen == gen
                ):
                    self._cond.wait(timeout=1.0)
                if self._thread_gen != gen:
                    # superseded: a hang escalation wrote this thread off
                    # and a fresh loop owns the queue now
                    return
                if self._stop and not self._queue:
                    return
                job = self._queue.pop(0)
                job.started_at = time.monotonic()
                self._running = job
            try:
                self._run_job(job)
            finally:
                with self._cond:
                    # settle the in-flight accounting exactly once: the
                    # hang escalation (or close) may already have
                    # finalized an abandoned job on this zombie thread
                    if not job.finalized:
                        job.finalized = True
                        self._inflight_bytes -= job.nbytes
                    if self._running is job:
                        self._running = None
                    superseded = self._thread_gen != gen
                    self._cond.notify_all()
                if superseded:
                    return

    def _run_job(self, job: _Job) -> None:
        self._maybe_stall(job)
        self._maybe_hang(job)
        t0 = time.monotonic()
        started = time.time()
        token = _JOB_TRACES.set(job.traces) if job.traces else None
        try:
            result = job.fn()
        except BaseException as exc:  # noqa: BLE001 - delivered to the waiter
            job.future.set_exception(exc)
            return
        finally:
            if token is not None:
                _JOB_TRACES.reset(token)
            if job.traces:
                duration_s = time.monotonic() - t0
                queue_wait_s = max(0.0, t0 - job.enqueued_at)
                for trace in job.traces:
                    trace.add_span(
                        "device.job",
                        started,
                        duration_s,
                        job=job.name,
                        queue_wait_s=round(queue_wait_s, 6),
                    )
        if job.abandoned:
            # the watchdog already failed this job's waiters and
            # respawned the dispatch thread; the late result is dropped
            # (DeviceFuture resolves once) — just don't count it
            return
        # a host job's wall time (tokenize + inner run_batch calls) is a
        # different quantity from one device call — separate histogram
        self._m_job_ms.observe((time.monotonic() - t0) * 1000.0)
        self._m_jobs.inc()
        job.future.set_result(result)

    def _maybe_stall(self, job: _Job) -> None:
        """``device_stall`` fault injection: delay dispatch, no error —
        only ``backlog.device.*`` and the freshness layer can see it."""
        from pathway_tpu.engine import faults

        spec = faults.check("device_stall", source=job.name)
        if spec is None:
            return
        deadline = time.monotonic() + spec.delay_ms / 1000.0
        while time.monotonic() < deadline and not self._stop:
            time.sleep(0.05)

    def _maybe_hang(self, job: _Job) -> None:
        """``device_hang`` fault injection: WEDGE the dispatch thread on
        this job (bounded by ``delay_ms``, default 60 s) — a stuck
        device call / driver deadlock stand-in.  The job makes no
        progress and raises nothing: only the hard dispatch deadline
        (``PATHWAY_DEVICE_DISPATCH_DEADLINE_S``) can end it, by failing
        the job and respawning the dispatch thread — exactly what its
        chaos test proves."""
        from pathway_tpu.engine import faults

        spec = faults.check("device_hang", source=job.name)
        if spec is None:
            return
        _blackbox.record("fault.device_hang", job=job.name)
        limit = time.monotonic() + (spec.delay_ms or 60_000.0) / 1000.0
        while (
            time.monotonic() < limit
            and not self._stop
            and not job.abandoned
        ):
            time.sleep(0.05)

    # pathway-lint: context=watchdog
    def _watchdog_loop(self) -> None:
        """Hard dispatch-deadline enforcement: a running job older than
        ``PATHWAY_DEVICE_DISPATCH_DEADLINE_S`` gets failed with a typed
        hang error and the (wedged) dispatch thread is written off and
        respawned, so one stuck device call cannot freeze the whole
        dispatch queue behind it."""
        while True:
            with self._cond:
                if self._stop:
                    return
                job = self._running
                started = job.started_at if job is not None else None
                self._cond.wait(timeout=0.1)
            if (
                job is not None
                and started is not None
                and time.monotonic() - started > self._dispatch_deadline_s
            ):
                self._escalate_hang(job)

    def _escalate_hang(self, job: _Job) -> None:
        with self._cond:
            # re-check under the lock: the job may have finished (or a
            # concurrent escalation handled it) while we decided
            if job.finalized or self._running is not job:
                return
            job.abandoned = True
            job.finalized = True
            self._running = None
            self._inflight_bytes -= job.nbytes
            age = time.monotonic() - (job.started_at or job.enqueued_at)
            # write the wedged thread off and hand the queue to a fresh
            # one (unless we are shutting down anyway)
            self._thread = None
            if not self._stop and not self._closed:
                self._ensure_thread()
            else:
                self._thread_gen += 1
            self._cond.notify_all()
        self._m_restarts.inc()
        self._reg.counter(
            "device.failures",
            "classified device-path failures observed (kind label)",
            kind="hang",
        ).inc()
        _blackbox.record(
            "device.dispatch.restart",
            job=job.name,
            age_s=round(age, 3),
            deadline_s=self._dispatch_deadline_s,
        )
        job.future.set_exception(
            _res.DeviceDispatchHangError(
                f"dispatch of job {job.name!r} exceeded the hard deadline "
                f"({self._dispatch_deadline_s:g} s); the dispatch thread "
                "was restarted"
            )
        )

    def close(self, timeout_s: float = 5.0) -> None:
        """Shut the executor down: refuse new work, drain what the
        dispatch thread can finish within ``timeout_s``, and FAIL (never
        strand) every waiter still in flight with a typed
        :class:`ExecutorClosedError`."""
        with self._cond:
            self._closed = True
            self._stop = True
            self._retry_interrupt.set()
            self._cond.notify_all()
            thread = self._thread
        if thread is not None:
            thread.join(timeout=timeout_s)
        leftovers: list[_Job] = []
        with self._cond:
            if thread is not None and thread.is_alive():
                # wedged mid-job past the drain budget: write the thread
                # off and fail its job — a stranded waiter is worse than
                # an abandoned thread
                self._thread_gen += 1
                running = self._running
                if running is not None and not running.finalized:
                    running.abandoned = True
                    running.finalized = True
                    self._inflight_bytes -= running.nbytes
                    leftovers.append(running)
                    self._running = None
            while self._queue:
                job = self._queue.pop(0)
                if not job.finalized:
                    job.finalized = True
                    self._inflight_bytes -= job.nbytes
                leftovers.append(job)
            self._cond.notify_all()
        for job in leftovers:
            job.future.set_exception(
                _res.ExecutorClosedError(
                    f"device executor closed before job {job.name!r} "
                    "completed"
                )
            )

    # -- observability -------------------------------------------------------

    def _queue_snapshot(self) -> dict[str, float]:
        """The ``backlog.device.*`` slice: queue depth/bytes/oldest age."""
        with self._cond:
            jobs = list(self._queue)
            if self._running is not None:
                jobs.append(self._running)
            inflight_bytes = self._inflight_bytes
        now = time.monotonic()
        out = {
            "backlog.device.queue": float(len(jobs)),
            "backlog.device.bytes": float(inflight_bytes),
        }
        if jobs:
            out["backlog.device.age.s"] = max(
                0.0, now - min(j.enqueued_at for j in jobs)
            )
        else:
            out["backlog.device.age.s"] = 0.0
        return out

    def _padding_snapshot(self) -> dict[str, float]:
        with self._mem_lock:
            pad, real = self._pad_rows, self._real_rows
        total = pad + real
        return {
            "pad_rows": float(pad),
            "real_rows": float(real),
            "fraction": (pad / total) if total else 0.0,
        }

    def _hbm_snapshot(self) -> dict[str, Any]:
        """Real allocator stats where the backend keeps them, else this
        executor's tracked in-flight footprint (the CPU-rig fallback)."""
        stats = _dtel.hbm_stats()
        if stats is not None:
            return {**stats, "source": "memory_stats"}
        with self._mem_lock:
            return {
                "bytes_in_use": self._live_bytes,
                "peak": self._live_peak,
                "source": "executor",
            }

    def metrics_snapshot(self) -> dict[str, float]:
        """Registry collector: ``backlog.device.*`` plus the device cost
        gauges — utilization, padding waste, HBM — and the resilience
        state (per-callable breaker + OOM bucket cap, quarantine depth),
        so one scrape covers the whole device story."""
        out = self._queue_snapshot()
        out.update(self._accountant.gauges())
        out["device.batch.max"] = float(self._default_max_batch)
        padding = self._padding_snapshot()
        out["device.padding.waste.rows"] = padding["pad_rows"]
        out["device.padding.waste.fraction"] = padding["fraction"]
        hbm = self._hbm_snapshot()
        out["device.hbm.bytes_in_use"] = float(hbm["bytes_in_use"])
        out["device.hbm.peak"] = float(hbm["peak"])
        for name, entry in sorted(self._callables.items()):
            if entry.breaker is not None:
                out[f"device.breaker.state{{callable={name}}}"] = (
                    entry.breaker.state_value()
                )
            with entry.lock:
                cap = entry.bucket_cap
            if cap is not None:
                out[f"device.bucket.cap{{callable={name}}}"] = float(cap)
        out["device.quarantine.records"] = float(len(self._quarantine))
        return out

    def resilience_stats(self, name: str) -> dict[str, Any]:
        """The fault-tolerance ledger of one registered callable —
        breaker state, OOM ratchet, fallback/failure counts (tests and
        the snapshot below)."""
        entry = self._callables[name]
        with entry.lock:
            out: dict[str, Any] = {
                "bucket_cap": entry.bucket_cap,
                "oom_splits": entry.oom_splits,
                "fallback_batches": entry.fallback_batches,
                "failures": dict(entry.failure_counts),
            }
        out["breaker"] = (
            entry.breaker.snapshot() if entry.breaker is not None else None
        )
        return out

    def quarantine_records(self) -> list[dict[str, Any]]:
        return self._quarantine.records()

    def _attention_fallback_snapshot(self) -> dict[str, int]:
        """Shapes ``ops/attention.py`` routed to the XLA path instead of
        the Pallas kernel, with trace counts — empty on a healthy run."""
        family = self._reg.family("device.attention.xla_fallback")
        if family is None:
            return {}
        return {
            dict(key)["shape"]: int(counter.value)
            for key, counter in family.items()
        }

    def device_snapshot(self) -> dict[str, Any]:
        """The full device story as one JSON-able dict — what rides
        flight-recorder dumps (``set_device_supplier``) and feeds
        ``pathway_tpu buckets`` from a post-mortem root."""
        return {
            "cost": self._accountant.snapshot(),
            "default_max_batch": self._default_max_batch,
            "padding": self._padding_snapshot(),
            "hbm": self._hbm_snapshot(),
            "queue": self._queue_snapshot(),
            "callables": {
                name: self.stats(name) for name in sorted(self._callables)
            },
            "attention_xla_fallback": self._attention_fallback_snapshot(),
            "resilience": {
                "enabled": self._resilience,
                "dispatch_deadline_s": self._dispatch_deadline_s,
                "callables": {
                    name: self.resilience_stats(name)
                    for name in sorted(self._callables)
                },
                "quarantine": self.quarantine_records(),
            },
        }


def _slice_rows(out: Any, count: int) -> Any:
    if isinstance(out, (tuple, list)):
        return type(out)(np.asarray(o)[:count] for o in out)
    return np.asarray(out)[:count]


def _concat_rows(chunks: list[Any]) -> Any:
    first = chunks[0]
    if isinstance(first, (tuple, list)):
        return type(first)(
            np.concatenate([c[i] for c in chunks], axis=0)
            for i in range(len(first))
        )
    return np.concatenate(chunks, axis=0)


# ---------------------------------------------------------------------------
# Process-wide default executor
# ---------------------------------------------------------------------------

_default: DeviceExecutor | None = None
_default_lock = threading.Lock()


def get_default_executor() -> DeviceExecutor:
    """The process-wide executor every stock caller (encoder towers,
    indexing top-k, the micro-batcher front-end) shares — one queue, one
    budget, one ``backlog.device.*`` story."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = DeviceExecutor()
    return _default


def default_executor_snapshot() -> dict[str, Any] | None:
    """The default executor's :meth:`DeviceExecutor.device_snapshot`,
    WITHOUT instantiating one — the flight-recorder supplier
    (``internals/runner.py``): a run that never touched the device path
    dumps no device section rather than a zeroed one."""
    if _default is None:
        return None
    return _default.device_snapshot()
