"""Device-path fault tolerance: typed failure classes, retry policy,
circuit breaker, and poisoned-batch quarantine.

PR 11 built the DeviceExecutor and PR 12 made it measurable, but until
this module any exception raised by a device dispatch was delivered raw
to the waiter: one transient XLA error, HBM OOM, or wedged device call
failed the stream.  The host path earned its graceful-degradation spine
across PRs 1/2/5/10 (bounded retries, watchdogs, degraded modes); this
module is the device-path equivalent (WindVE in PAPERS.md legitimizes
CPU↔device collaborative execution as a degraded mode, VectorLiteRAG
motivates shrinking device footprint under pressure instead of dying):

* **Typed failure classes** — :class:`DeviceJobError` and its kinds
  (transient / oom / compile / hang / quarantined / closed).  The
  classifier (:func:`classify`) wraps only *device-looking* failures
  (XLA runtime errors, jax/jaxlib exceptions, injected device faults);
  a plain Python error from the callable is a deterministic host bug
  and propagates raw — retrying it would only mask it.

* **Retry policy** (:class:`RetryPolicy`) — bounded, jittered,
  deadline-capped retries for *transient* failures only, reusing the
  one backoff implementation the codebase has
  (``internals/udfs/retries.py``, the same policy the comm mesh and
  blob store use).  Knobs: ``PATHWAY_DEVICE_RETRIES`` /
  ``PATHWAY_DEVICE_RETRY_DEADLINE_S`` / ``PATHWAY_DEVICE_RETRY_BACKOFF_MS``.

* **Circuit breaker** (:class:`CircuitBreaker`) — per registered
  callable: ``PATHWAY_DEVICE_BREAKER_THRESHOLD`` consecutive device
  failures trip it OPEN and dispatches route to the registered
  **host fallback** (un-jitted CPU execution of the same callable on
  the same padded buffers — the padding-mask semantics that make
  bucketing correct also make the fallback bit-equivalent).  After
  ``PATHWAY_DEVICE_BREAKER_COOLDOWN_S`` one HALF-OPEN probe is admitted
  to the device; success closes the breaker, failure re-opens it.
  State exports as ``device.breaker.state{callable=}`` (0 closed,
  0.5 half-open, 1 open).

* **Poisoned-batch quarantine** — a batch that fails device retries AND
  the host fallback has nowhere left to go: it is recorded in a bounded
  quarantine log (``PATHWAY_DEVICE_QUARANTINE_KEEP``), a
  ``device.quarantine`` flight-recorder event is emitted, and its
  waiters get a typed :class:`DeviceQuarantinedError` — one bad row
  can fail its own batch but can never wedge the epoch thread or
  crash-loop the stream.

The executor (``executor.py``) wires these around every dispatch; the
whole rail is removable with ``PATHWAY_DEVICE_RESILIENCE=0`` (the
kill switch ``benchmarks/device_fault_recovery.py`` prices against).
Contract documented in docs/fault_tolerance.md, "Device-path failures".
"""

from __future__ import annotations

import dataclasses
import re
import threading
import time
from typing import Any

__all__ = [
    "CircuitBreaker",
    "DeviceCompileError",
    "DeviceDispatchHangError",
    "DeviceJobError",
    "DeviceOOMError",
    "DeviceQuarantinedError",
    "ExecutorClosedError",
    "InjectedDeviceError",
    "QuarantineLog",
    "RetryPolicy",
    "TransientDeviceError",
    "classify",
]


# ---------------------------------------------------------------------------
# Typed failure classes
# ---------------------------------------------------------------------------


class DeviceJobError(RuntimeError):
    """Base of every typed device-path failure the executor raises.

    ``kind`` is the stable machine-readable class (the label on
    ``device.failures{kind=}`` and flight-recorder events); subclasses
    pin it so ``except DeviceOOMError`` and ``exc.kind == "oom"`` agree.
    """

    kind = "device"


class TransientDeviceError(DeviceJobError):
    """A failure worth retrying: interconnect hiccup, preempted device,
    cancelled collective — the RPC-flavored XLA errors (UNAVAILABLE,
    INTERNAL, DEADLINE_EXCEEDED, ABORTED).  Also the *default* class for
    an unrecognized device error: retry is the forgiving default, and a
    genuinely persistent failure still lands in the breaker after the
    bounded retries are spent."""

    kind = "transient"


class DeviceCompileError(DeviceJobError):
    """XLA compilation/lowering failed for this cache key.  Deterministic
    — never retried at the same shape; counts toward the breaker and the
    batch goes to the host fallback."""

    kind = "compile"


class DeviceOOMError(DeviceJobError):
    """RESOURCE_EXHAUSTED / out-of-memory.  Not retried at the same
    shape: the executor *splits the batch* — drops the chunk to a
    smaller bucket and ratchets the callable's max-bucket cap
    (``device.oom.splits`` / ``device.bucket.cap``) so sustained memory
    pressure shrinks footprint instead of crash-looping."""

    kind = "oom"


class DeviceDispatchHangError(DeviceJobError):
    """A dispatched job blew through the hard dispatch deadline
    (``PATHWAY_DEVICE_DISPATCH_DEADLINE_S``).  The job's waiters get
    this error and the wedged dispatch thread is torn down and
    respawned (``device.dispatch.restarts``)."""

    kind = "hang"


class DeviceQuarantinedError(DeviceJobError):
    """The batch failed device retries AND the host fallback: it is
    poisoned.  Recorded in the quarantine log; the waiter decides
    whether to drop the rows or fail the stream."""

    kind = "quarantined"


class ExecutorClosedError(DeviceJobError):
    """``submit()``/``run_batch()`` after ``close()``, or a job failed
    because the executor shut down before running it — waiters are
    failed with this, never stranded."""

    kind = "closed"


class InjectedDeviceError(RuntimeError):
    """Raised only by the fault plan (``engine/faults.py``:
    ``device_error`` / ``device_oom`` / ``device_compile_fail``), never
    by real infrastructure.  Deliberately NOT a :class:`DeviceJobError`:
    it enters the classifier exactly like a raw XLA runtime error would,
    so chaos tests exercise the same classification path production
    failures take."""


# ---------------------------------------------------------------------------
# Classification
# ---------------------------------------------------------------------------

# message markers, checked in this order (most specific wins).  XLA
# surfaces backend failures as XlaRuntimeError with a grpc-style status
# prefix; these are the stable spellings across jaxlib versions.
_OOM_MARKERS = ("resource_exhausted", "out of memory")
# bare "oom" only as a standalone word — a callable or op name embedding
# the letters (zoom, bloom) must not route a transient into the ratchet
_OOM_WORD = re.compile(r"\boom\b")
_COMPILE_MARKERS = ("compil", "lowering", "mosaic", "unimplemented")
# a kernel that overruns its scoped VMEM is refused at compile time with
# RESOURCE_EXHAUSTED; the overrun is per program, so the OOM ratchet
# (which shrinks the batch) cannot fix it — it is a compile failure
_VMEM_MARKERS = ("vmem",)


def _looks_device(exc: BaseException) -> bool:
    """Only device-looking failures are classified; anything else is a
    host bug that must propagate raw (wrapping it in a retryable class
    would mask it)."""
    if isinstance(exc, InjectedDeviceError):
        return True
    t = type(exc)
    if t.__name__ == "XlaRuntimeError":
        return True
    module = t.__module__ or ""
    return module.startswith(("jaxlib", "jax"))


def classify(exc: BaseException) -> DeviceJobError | None:
    """The typed failure for ``exc``, or ``None`` when it is not a
    device failure (host bugs propagate raw).  An already-typed
    :class:`DeviceJobError` passes through unchanged."""
    if isinstance(exc, DeviceJobError):
        return exc
    if not _looks_device(exc):
        return None
    msg = str(exc)
    low = msg.lower()
    if any(m in low for m in _VMEM_MARKERS):
        return DeviceCompileError(msg)
    if any(m in low for m in _OOM_MARKERS) or _OOM_WORD.search(low):
        return DeviceOOMError(msg)
    if any(m in low for m in _COMPILE_MARKERS):
        return DeviceCompileError(msg)
    return TransientDeviceError(msg)


# ---------------------------------------------------------------------------
# Retry policy (the one backoff implementation, reused)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded jittered retry for transient device failures.

    ``retries`` extra attempts after the first, each preceded by a
    jittered exponential delay (the udfs backoff schedule), the whole
    affair capped by ``deadline_s`` of wall clock — a retry loop must
    never outlast the freshness SLO it exists to protect."""

    retries: int = 2
    deadline_s: float = 30.0
    backoff_ms: float = 50.0

    @classmethod
    def from_env(cls) -> "RetryPolicy":
        from pathway_tpu.internals.config import env_float, env_int

        return cls(
            retries=max(0, int(env_int("PATHWAY_DEVICE_RETRIES"))),
            deadline_s=float(env_float("PATHWAY_DEVICE_RETRY_DEADLINE_S")),
            backoff_ms=float(env_float("PATHWAY_DEVICE_RETRY_BACKOFF_MS")),
        )

    def delays(self):
        """The jittered schedule in seconds — one entry per retry,
        straight from the shared udfs backoff policy."""
        from pathway_tpu.internals.udfs.retries import (
            ExponentialBackoffRetryStrategy,
        )

        return ExponentialBackoffRetryStrategy(
            max_retries=self.retries,
            initial_delay=max(1, int(self.backoff_ms)),
            backoff_factor=2,
            jitter_ms=max(0, int(self.backoff_ms // 2)),
        ).delays()


# ---------------------------------------------------------------------------
# Circuit breaker
# ---------------------------------------------------------------------------

# gauge encoding of breaker state (device.breaker.state{callable=})
STATE_CLOSED = 0.0
STATE_HALF_OPEN = 0.5
STATE_OPEN = 1.0


class CircuitBreaker:
    """Per-callable device/host routing decision.

    CLOSED: dispatch to the device.  ``threshold`` *consecutive* device
    failures (retries already spent) trip it OPEN: dispatches route to
    the host fallback without touching the device.  After ``cooldown_s``
    the next admit becomes a single HALF-OPEN probe; its success closes
    the breaker, its failure re-opens it (fresh cooldown).  Thread-safe;
    decisions are made under one small lock and never held around work.
    """

    def __init__(self, threshold: int = 5, cooldown_s: float = 10.0):
        self.threshold = max(1, int(threshold))
        self.cooldown_s = float(cooldown_s)
        self._lock = threading.Lock()
        self._state = STATE_CLOSED
        self._consecutive = 0
        self._opened_at = 0.0
        self._probe_inflight = False
        self.trips = 0  # lifetime count, for snapshots

    @classmethod
    def from_env(cls) -> "CircuitBreaker":
        from pathway_tpu.internals.config import env_float, env_int

        return cls(
            threshold=int(env_int("PATHWAY_DEVICE_BREAKER_THRESHOLD")),
            cooldown_s=float(env_float("PATHWAY_DEVICE_BREAKER_COOLDOWN_S")),
        )

    def admit(self) -> str:
        """Route the next dispatch: ``"device"`` (closed), ``"probe"``
        (half-open trial — caller must report the outcome), or
        ``"fallback"`` (open / a probe is already in flight)."""
        # lock-free fast path: CLOSED is the steady state and a stale
        # read is benign (a breaker tripping concurrently lets one extra
        # dispatch reach the device, whose failure is then recorded) —
        # the happy path must not pay a lock per chunk
        if self._state == STATE_CLOSED:
            return "device"
        with self._lock:
            if self._state == STATE_CLOSED:
                return "device"
            if self._state == STATE_OPEN:
                if time.monotonic() - self._opened_at < self.cooldown_s:
                    return "fallback"
                self._state = STATE_HALF_OPEN
                self._probe_inflight = True
                return "probe"
            # half-open: exactly one probe at a time
            if self._probe_inflight:
                return "fallback"
            self._probe_inflight = True
            return "probe"

    def record_success(self, *, probe: bool = False) -> bool:
        """A device dispatch succeeded; True when this CLOSED a
        previously open breaker (the recovery transition)."""
        # lock-free fast path: nothing to reset in the steady state.  The
        # benign race (a concurrent failure bumping _consecutive that
        # this stale read misses resetting) only makes the breaker trip
        # marginally EARLIER under sustained mixed outcomes — the
        # conservative direction.
        if (
            not probe
            and self._state == STATE_CLOSED
            and self._consecutive == 0
        ):
            return False
        with self._lock:
            recovered = self._state != STATE_CLOSED
            self._state = STATE_CLOSED
            self._consecutive = 0
            if probe:
                self._probe_inflight = False
            return recovered

    def abort_probe(self) -> None:
        """The in-flight probe's outcome will never be reported (a host
        bug escaped the dispatch raw, or the executor closed mid-probe):
        release the slot so a later admit can probe again.  The state
        stays half-open — nothing was learned about the device."""
        with self._lock:
            self._probe_inflight = False

    def record_failure(self, *, probe: bool = False) -> bool:
        """A device dispatch failed (retries spent); True when this
        TRIPPED the breaker open (closed→open or a failed probe)."""
        with self._lock:
            self._consecutive += 1
            if probe:
                self._probe_inflight = False
                self._state = STATE_OPEN
                self._opened_at = time.monotonic()
                self.trips += 1
                return True
            if self._state == STATE_CLOSED and self._consecutive >= self.threshold:
                self._state = STATE_OPEN
                self._opened_at = time.monotonic()
                self.trips += 1
                return True
            return False

    def state_value(self) -> float:
        with self._lock:
            return self._state

    @staticmethod
    def _name_of(state: float) -> str:
        if state == STATE_OPEN:
            return "open"
        if state == STATE_HALF_OPEN:
            return "half-open"
        return "closed"

    def state_name(self) -> str:
        with self._lock:
            return self._name_of(self._state)

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            return {
                "state": self._name_of(self._state),
                "consecutive_failures": self._consecutive,
                "trips": self.trips,
                "threshold": self.threshold,
                "cooldown_s": self.cooldown_s,
            }


# ---------------------------------------------------------------------------
# Quarantine log
# ---------------------------------------------------------------------------


class QuarantineLog:
    """Bounded record of poisoned batches (newest kept).

    One entry per quarantined batch: the callable, the batch signature
    (rows, per-array shapes/dtypes), and both failure strings — enough
    to reproduce the poison offline without holding the actual row data
    (which may be large and may be the thing that OOMs)."""

    def __init__(self, keep: int = 32):
        from collections import deque

        self._records: "deque[dict[str, Any]]" = deque(maxlen=max(1, int(keep)))
        self._lock = threading.Lock()
        self.total = 0

    @classmethod
    def from_env(cls) -> "QuarantineLog":
        from pathway_tpu.internals.config import env_int

        return cls(keep=int(env_int("PATHWAY_DEVICE_QUARANTINE_KEEP")))

    def add(
        self,
        name: str,
        rows: int,
        arrays: tuple,
        device_error: BaseException | None,
        fallback_error: BaseException,
    ) -> dict[str, Any]:
        record = {
            "callable": name,
            "rows": int(rows),
            "shapes": [list(getattr(a, "shape", ())) for a in arrays],
            "dtypes": [str(getattr(a, "dtype", type(a).__name__)) for a in arrays],
            "device_error": (
                f"{type(device_error).__name__}: {device_error}"[:300]
                if device_error is not None
                else "(device not attempted: breaker open)"
            ),
            "fallback_error": f"{type(fallback_error).__name__}: {fallback_error}"[:300],
            "ts": time.time(),
        }
        with self._lock:
            self._records.append(record)
            self.total += 1
        return record

    def records(self) -> list[dict[str, Any]]:
        with self._lock:
            return list(self._records)

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)
