"""Request-scoped distributed tracing for the serving path.

PR 17/18 built admission control, deadlines and continuous batching; the
span story still stopped at the epoch (``pathway.epoch`` /
``pathway.commit``).  This module adds the per-request layer: a
:class:`RequestTrace` — W3C ``traceparent`` accepted on ingress, minted
otherwise — created by the admission controller
(``engine/serving.py``) and propagated through the REST handler
(``io/http/_server.py``), the connector row stamp (``_pw_trace`` next to
``_pw_deadline_ts``), the coalescing ``AsyncMicroBatcher``
(``utils/batching.py``), ``DeviceExecutor`` submit/dispatch
(``device/executor.py``) and the continuous-batching
``GenerationScheduler`` (``serving/generation.py``).

Every stage records a CHILD span with ids minted at creation (trace id,
span id, parent span id carried on the record — ``engine/telemetry.py``
exports them verbatim), so parent links in a collector are real and a
slow request decomposes into queue wait vs coalesce vs device dispatch
vs generation ticks.  Spans ride the existing bounded telemetry export
queue when an exporter is wired (:func:`set_exporter`); with zero
egress they still land in the in-process ring the ``pathway_tpu
requests`` CLI, the ``/status`` ``requests`` section and flight-recorder
dumps read.

Propagation is ambient (a contextvar scope, mirroring the serving
deadline's ``deadline_scope``) for same-thread stages, and explicit (the
trace rides the batcher entry / device job / generation request) across
thread hops — a coalesced batch serving waiters from two event loops
parents each waiter's spans to its own trace.

Beside the per-request traces the module keeps the process's **host
timeline** (:func:`begin` / :func:`end` / :func:`interval` /
:func:`switch`): closed intervals of work that belongs to a thread, not
to one request — the scheduler's tick phases, the dataflow epochs and
their async barrier, the executor's device calls, the stretches in which
the server holds no request.  Both are stamped with ``time.time()``, so
a request's spans and the threads' phases read as one stream on one
clock (:func:`timeline`, :func:`recent_requests`).

``PATHWAY_TRACE_REQUESTS=0`` turns the whole layer off (no trace
objects, no spans, no intervals, no ring writes) — the lever
``benchmarks/request_trace_overhead.py`` prices (≤ 2 % of request cost).
"""

from __future__ import annotations

import secrets
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from contextvars import ContextVar
from typing import Any

from pathway_tpu.engine import metrics as _metrics

__all__ = [
    "TRACE_STAMP",
    "RequestTrace",
    "active_trace",
    "begin",
    "begin_request",
    "current_trace",
    "enabled",
    "end",
    "epoch_run",
    "interval",
    "maybe_trace_storm",
    "recent_requests",
    "reset_for_tests",
    "set_exporter",
    "slowest_requests",
    "snapshot",
    "switch",
    "timeline",
    "trace_scope",
]

# the connector row stamp — rides REST rows next to ``_pw_deadline_ts``
# (io/_utils.DEADLINE_TS) so the trace survives the trip through the
# dataflow and an output-side consumer can attribute its row
TRACE_STAMP = "_pw_trace"

# per-trace span cap: a runaway stage (per-chunk prefill of a huge
# prompt, a retry storm) must not grow one trace without bound — overflow
# drops the newest span and counts it
MAX_SPANS_PER_TRACE = 64

# deep-tree shape of one ``trace_storm`` synthetic trace (chained
# parent→child spans), sized so a default burst overflows the bounded
# telemetry export queue (EXPORT_QUEUE_MAX=256) by construction
STORM_TREE_DEPTH = 12
STORM_DEFAULT_TRACES = 64


# the host timeline's ring: closed intervals, oldest dropped.  A decode
# tick writes six phases (≈ 6.3 records a tick with the epochs, waits and
# calls around them in a served window on a v5e), so this holds the last
# ≈ 5,000 ticks (≈ 20 s of uninterrupted 4 ms ticks) in ≈ 5 MB
TIMELINE_MAX = 32768

# how long the timeline trusts its cached reading of the switch: the
# registry lookup behind ``enabled()`` costs more than a whole interval
_SWITCH_TTL_S = 0.5


def enabled() -> bool:
    """Request tracing on? (``PATHWAY_TRACE_REQUESTS``, default on)."""
    from pathway_tpu.internals.config import env_bool

    on = env_bool("PATHWAY_TRACE_REQUESTS")
    _switch[0] = on  # every request's read of the switch also sets the timeline's
    return on


def _buffer_max() -> int:
    from pathway_tpu.internals.config import env_int

    return max(1, int(env_int("PATHWAY_TRACE_BUFFER")))


# held once: ``add_span`` runs for every span of every request
_M_SPANS = _metrics.get_registry().counter(
    "trace.spans", "request-scoped spans recorded"
)
_M_SPANS_DROPPED = _metrics.get_registry().counter(
    "trace.spans.dropped", "request spans dropped by the per-trace span cap"
)


class RequestTrace:
    """One request's trace: a trace id, a root span, and child spans.

    Created at admission (or at the REST front door when admission is
    off); every serving stage that touches the request records child
    spans on it.  ``finish()`` closes the root ``serve.request`` span
    and moves the trace into the bounded finished-request ring.
    """

    __slots__ = (
        "trace_id", "root_span_id", "parent_span_id", "route", "started",
        "spans", "duration_s", "status", "_lock", "_finished", "_dropped",
        "attributes", "committed_at",
    )

    def __init__(self, route: str, trace_parent: str | None = None):
        from pathway_tpu.engine.telemetry import (
            _parent_span_id,
            _root_trace_id,
        )

        # W3C traceparent accepted on ingress: the caller's trace id and
        # span id become ours / our root's parent; otherwise mint fresh
        self.trace_id = _root_trace_id(trace_parent) or secrets.token_hex(16)
        self.parent_span_id = _parent_span_id(trace_parent)
        self.root_span_id = secrets.token_hex(8)
        self.route = route
        self.started = time.time()
        self.spans: list[dict] = []
        self.duration_s: float | None = None
        self.status: Any = None
        self.attributes: dict[str, Any] = {}
        # when the REST handler handed the request's row to its connector
        # (io/http/_server.py): where ``serve.epoch.wait`` starts
        self.committed_at: float | None = None
        self._lock = threading.Lock()
        self._finished = False
        self._dropped = 0

    def traceparent(self) -> str:
        """The W3C header value downstream stages propagate — child spans
        of this request parent to ``root_span_id`` under ``trace_id``."""
        return f"00-{self.trace_id}-{self.root_span_id}-01"

    # -- span recording ----------------------------------------------------
    def add_span(
        self,
        name: str,
        start: float,
        duration_s: float,
        parent_span_id: str | None = None,
        **attributes: Any,
    ) -> str:
        """Record one finished child span (explicit timing — stages that
        batch many requests per tick reconstruct per-request timing).
        Returns the minted span id so a caller can chain children."""
        span_id = secrets.token_hex(8)
        record = {
            "name": name,
            "start": start,
            "duration_s": duration_s,
            "attributes": attributes,
            "trace_parent": self.traceparent(),
            "trace_id": self.trace_id,
            "span_id": span_id,
            "parent_span_id": (
                self.root_span_id if parent_span_id is None else parent_span_id
            ),
        }
        with self._lock:
            if len(self.spans) >= MAX_SPANS_PER_TRACE:
                self._dropped += 1
                _M_SPANS_DROPPED.inc()
                return span_id
            self.spans.append(record)
        _M_SPANS.inc()
        _export(record)
        return span_id

    @contextmanager
    def span(
        self, name: str, parent_span_id: str | None = None, **attributes: Any
    ):
        """Timed child-span scope for same-thread stages."""
        start = time.time()
        try:
            yield
        finally:
            self.add_span(
                name,
                start,
                time.time() - start,
                parent_span_id=parent_span_id,
                **attributes,
            )

    def finish(self, status: Any = None, **attributes: Any) -> None:
        """Close the root ``serve.request`` span and ring-buffer the
        trace.  Idempotent — the first close wins."""
        with self._lock:
            if self._finished:
                return
            self._finished = True
            self.duration_s = time.time() - self.started
            self.status = status
            self.attributes.update(attributes)
        record = {
            "name": "serve.request",
            "start": self.started,
            "duration_s": self.duration_s,
            "attributes": {
                "route": self.route,
                **({"status": status} if status is not None else {}),
                **self.attributes,
            },
            "trace_parent": self.traceparent(),
            "trace_id": self.trace_id,
            # the ROOT span: its id was minted at trace creation so every
            # child recorded before this close already parent-links to it
            "span_id": self.root_span_id,
            "parent_span_id": self.parent_span_id,
        }
        with self._lock:
            self.spans.append(record)
        _export(record)
        with _active_lock:
            _active.pop(self.trace_id, None)
        with _ring_lock:
            _ring.append(self.summary())

    def summary(self) -> dict[str, Any]:
        """JSON-able view of this trace (the ring/dump/CLI shape)."""
        with self._lock:
            spans = list(self.spans)
            dropped = self._dropped
        return {
            "trace_id": self.trace_id,
            "route": self.route,
            "start": self.started,
            "duration_s": self.duration_s,
            "status": self.status,
            "spans": spans,
            "spans_dropped": dropped,
        }


# ---------------------------------------------------------------------------
# Ambient propagation (the deadline_scope pattern, engine/serving.py)
# ---------------------------------------------------------------------------

_AMBIENT: ContextVar[RequestTrace | None] = ContextVar(
    "pathway_request_trace", default=None
)


def trace_scope(trace: RequestTrace | None):
    """Context manager binding ``trace`` as the ambient request trace
    (no-op for ``None`` — disabled tracing costs one branch)."""
    if trace is None:
        return nullcontext()
    return _scope(trace)


@contextmanager
def _scope(trace: RequestTrace):
    token = _AMBIENT.set(trace)
    try:
        yield trace
    finally:
        _AMBIENT.reset(token)


def current_trace() -> RequestTrace | None:
    """The ambient request trace of the calling context, if any."""
    return _AMBIENT.get()


def begin_request(
    route: str, trace_parent: str | None = None
) -> RequestTrace | None:
    """Mint (or adopt) a request trace — ``None`` while tracing is off."""
    if not enabled():
        return None
    trace = RequestTrace(route, trace_parent)
    with _active_lock:
        # bounded by admission (in-flight + queue); the cap is a backstop
        # against a leak ever growing the index without bound
        if len(_active) < _ACTIVE_MAX:
            _active[trace.trace_id] = trace
    _metrics.get_registry().counter(
        "trace.requests", "request traces created by the serving path"
    ).inc()
    return trace


# in-flight traces by trace id: lets a stage that only holds the row
# stamp (connector staging, the device executor on the epoch thread)
# attribute its span to the right trace without an ambient hop
_ACTIVE_MAX = 4096
_active: dict[str, RequestTrace] = {}
_active_lock = threading.Lock()


def active_trace(trace_parent: str | None) -> RequestTrace | None:
    """The in-flight trace a ``_pw_trace`` row stamp refers to, if any."""
    if not trace_parent:
        return None
    from pathway_tpu.engine.telemetry import _root_trace_id

    trace_id = _root_trace_id(trace_parent)
    if not trace_id:
        return None
    with _active_lock:
        return _active.get(trace_id)


# in-flight traces by REQUEST ROW KEY: the REST ingress binds its row's
# key so the dataflow's async-UDF node (engine/dataflow.py) can re-enter
# the request's trace scope on the epoch thread — the hop that connects
# ingress spans to batcher/device/generation spans for pipeline-served
# requests
_by_key: dict[int, RequestTrace] = {}


def bind_key(key: int, trace: RequestTrace | None) -> None:
    if trace is None:
        return
    with _active_lock:
        if len(_by_key) < _ACTIVE_MAX:
            _by_key[key] = trace


def unbind_key(key: int) -> None:
    if not _by_key:
        return
    with _active_lock:
        _by_key.pop(key, None)


def trace_for_key(key: int) -> RequestTrace | None:
    """The trace bound to a request row key — ultra-cheap when serving
    is inactive (one falsy dict check, the ``fail_request`` pattern)."""
    if not _by_key:
        return None
    with _active_lock:
        return _by_key.get(key)


# ---------------------------------------------------------------------------
# Finished-request ring + export hook
# ---------------------------------------------------------------------------

_ring: deque[dict] = deque(maxlen=256)
_ring_lock = threading.Lock()
_exporter: Any = None  # engine.telemetry.Telemetry for this run, if any


def set_exporter(telemetry: Any) -> None:
    """Wire (or clear, with ``None``) the run's Telemetry instance so
    request spans ride its bounded export queue (internals/runner.py —
    same lifetime contract as the flight-recorder suppliers)."""
    global _exporter
    _exporter = telemetry
    _staged.clear()  # a run's epochs start over: nothing waits for the last run's
    # the ring size knob is read when a run wires tracing up, not per
    # request — resizing preserves the newest entries
    global _ring
    with _ring_lock:
        size = _buffer_max()
        if _ring.maxlen != size:
            _ring = deque(list(_ring)[-size:], maxlen=size)


def _export(record: dict) -> None:
    exporter = _exporter
    if exporter is not None:
        try:
            exporter.emit_span(record)
        except Exception:  # noqa: BLE001 - tracing must never fail a request
            pass


def recent_requests(n: int = 20) -> list[dict]:
    """The newest ``n`` finished request traces, newest first."""
    with _ring_lock:
        items = list(_ring)
    return list(reversed(items))[:n]


def slowest_requests(n: int = 10) -> list[dict]:
    """The ``n`` slowest finished request traces, slowest first."""
    with _ring_lock:
        items = list(_ring)
    return sorted(items, key=lambda t: -(t.get("duration_s") or 0.0))[:n]


def requests_state() -> dict[str, float]:
    """Scalar gauges for the ``/status`` ``requests`` section."""
    with _ring_lock:
        items = list(_ring)
    out = {"trace.requests.buffered": float(len(items))}
    if items:
        durations = [t.get("duration_s") or 0.0 for t in items]
        out["trace.requests.slowest.ms"] = max(durations) * 1000.0
        out["trace.requests.newest.ms"] = (
            items[-1].get("duration_s") or 0.0
        ) * 1000.0
    return out


# ---------------------------------------------------------------------------
# The epoch boundary: what a request waits between its commit and the epoch
# ---------------------------------------------------------------------------

# requests whose row the connector has staged into an epoch that has not
# started yet, as (epoch time, trace); filled and emptied on the engine
# thread alone (io/_utils.py stages, internals/runner.py starts epochs)
_staged: list[tuple[int, RequestTrace]] = []


def note_staged(trace_parent: str | None, epoch_time: int) -> None:
    """The connector staged a request's row into ``epoch_time``."""
    trace = active_trace(trace_parent)
    if trace is not None and len(_staged) < _ACTIVE_MAX:
        _staged.append((epoch_time, trace))


def epoch_run(epoch_time: int, index: int):
    """Scope of one ``run_epoch`` on the engine thread: closes the
    ``serve.epoch.wait`` span of every request whose row this epoch holds
    (handler's commit → now; observed into ``serve.epoch.wait.ms`` for
    every request, those that waited nothing included) and returns the
    ``epoch.run`` interval of the timeline."""
    if _staged:
        now = time.time()
        held = [tr for t, tr in _staged if t <= epoch_time]
        _staged[:] = [(t, tr) for t, tr in _staged if t > epoch_time]
        for trace in held:
            start = trace.committed_at if trace.committed_at is not None else now
            waited_s = max(0.0, now - start)
            trace.add_span("serve.epoch.wait", start, waited_s, epoch=epoch_time)
            _metrics.get_registry().histogram(
                "serve.epoch.wait.ms",
                "REST row committed -> start of the epoch that holds it (ms)",
                buckets=_metrics.MS_BUCKETS,
                route=trace.route,
            ).observe(waited_s * 1000.0, trace_id=trace.trace_id)
    return interval("engine", "epoch.run", epoch=epoch_time, index=index)


# ---------------------------------------------------------------------------
# The host timeline: intervals of the threads' own work, on the spans' clock
# ---------------------------------------------------------------------------


class _Interval:
    """One open interval; closing it (``end`` or leaving its ``with``)
    writes the record."""

    __slots__ = ("track", "name", "start", "attributes", "_annotation")

    def __init__(self, track: str, name: str, start: float, attributes: dict | None):
        self.track = track
        self.name = name
        self.start = start
        self.attributes = attributes
        # an annotation only while a profiler session listens (a check of
        # 20 ns against the 300 ns of an annotation nobody records)
        annotation = _TraceAnnotation or _load_annotation()
        self._annotation = annotation(name) if annotation.is_enabled() else None

    def __enter__(self):
        return self

    def __exit__(self, *_exc) -> None:
        end(self)


_TraceAnnotation = None  # jax.profiler's, imported at the first interval


def _load_annotation():
    global _TraceAnnotation
    from jax.profiler import TraceAnnotation

    _TraceAnnotation = TraceAnnotation
    return TraceAnnotation


_OFF = nullcontext()
_switch = [True, 0.0]  # [tracing on?, wall time of the next look at the knob]
_timeline: deque[tuple] = deque(maxlen=TIMELINE_MAX)
_totals: dict[tuple[str, str], list[float]] = {}  # (track, name) -> [seconds, count]
_timeline_lock = threading.Lock()


def begin(
    track: str, name: str, start: float | None = None, **attributes: Any
) -> _Interval | None:
    """Open an interval on ``track`` (a thread's lane: ``sched``,
    ``engine``, ``executor``, ``serve``) and return its handle for
    :func:`end` — ``None`` while tracing is off.  While a profiler session
    listens the same interval is a ``jax.profiler.TraceAnnotation``, so a
    capture with TraceMe on shows the host's phases above the device's
    operations."""
    now = time.time()
    if now >= _switch[1]:
        _switch[1] = now + _SWITCH_TTL_S
        enabled()
    if not _switch[0]:
        return None
    # ``None`` for no attributes: a record of strings and floats alone is
    # one the garbage collector stops tracking, and the ring holds 32k
    return _Interval(track, name, now if start is None else start, attributes or None)


def end(token: _Interval | None, **attributes: Any) -> float | None:
    """Close an interval (a no-op for ``None``); returns its end, which
    the next phase of the same thread may take as its start."""
    if token is None:
        return None
    if token._annotation is not None:
        token._annotation.__exit__(None, None, None)
    if attributes:
        token.attributes = {**(token.attributes or {}), **attributes}
    now = time.time()
    key = (token.track, token.name)
    with _timeline_lock:
        _timeline.append(
            (token.track, token.name, token.start, now, token.attributes)
        )
        total = _totals.get(key)
        if total is None:
            total = _totals[key] = [0.0, 0]
        total[0] += now - token.start
        total[1] += 1
    return now


def interval(track: str, name: str, **attributes: Any):
    """``with tracing.interval(track, name): ...`` — one interval around
    the block."""
    return begin(track, name, **attributes) or _OFF


def switch(token: _Interval | None, name: str, **attributes: Any) -> _Interval | None:
    """Close ``token`` and open ``name`` on the same track at the same
    instant: consecutive phases of one thread tile its time."""
    if token is None:
        return None
    return begin(token.track, name, start=end(token), **attributes)


def timeline(since: float | None = None, until: float | None = None) -> list[dict]:
    """The closed intervals that overlap the wall-clock window
    ``[since, until]``, oldest first."""
    with _timeline_lock:
        records = list(_timeline)
    return sorted(
        (
            {"track": t, "name": n, "start": s, "end": e, "attributes": a or {}}
            for t, n, s, e, a in records
            if (until is None or s <= until) and (since is None or e >= since)
        ),
        key=lambda r: r["start"],
    )


def phase_totals() -> dict[tuple[str, str], tuple[float, int]]:
    """``(track, name) -> (seconds, count)`` since process start."""
    with _timeline_lock:
        return {key: (total[0], int(total[1])) for key, total in _totals.items()}


def _phase_gauges() -> dict[str, float]:
    out: dict[str, float] = {}
    for (track, name), (seconds, count) in phase_totals().items():
        labels = f"{{track={track},name={name}}}"
        out[f"host.phase.seconds{labels}"] = seconds
        out[f"host.phase.count{labels}"] = float(count)
    return out


def snapshot() -> dict[str, Any]:
    """The tracing section of a flight-recorder dump: ring occupancy
    plus the slowest and newest traces WITH their span trees, so a
    post-mortem can render waterfalls offline."""
    with _ring_lock:
        buffered = len(_ring)
    return {
        "buffered": buffered,
        "slowest": slowest_requests(10),
        "recent": recent_requests(10),
        # the host timeline as per-name totals only: the ring itself is
        # megabytes, and a post-mortem asks where the threads' time went
        "timeline": {
            f"{track}/{name}": {"seconds": seconds, "count": count}
            for (track, name), (seconds, count) in phase_totals().items()
        },
    }


def reset_for_tests() -> None:
    global _exporter
    _exporter = None
    with _ring_lock:
        _ring.clear()
    with _active_lock:
        _active.clear()
        _by_key.clear()
    _staged.clear()
    with _timeline_lock:
        _timeline.clear()
        _totals.clear()
    _switch[1] = 0.0  # look at the knob again at the next interval


# the ring gauges ride every scrape (the /status ``requests`` section and
# the OTLP sample) — a plain-function collector, registered once at import
_metrics.get_registry().register_collector(
    "trace.requests.state", requests_state
)
_metrics.get_registry().register_collector("host.phase.state", _phase_gauges)


# ---------------------------------------------------------------------------
# trace_storm chaos hook (engine/faults.py)
# ---------------------------------------------------------------------------


def maybe_trace_storm(route: str) -> int:
    """``trace_storm`` fault injection: burst N synthetic traced
    requests, each with a deep chained span tree, through the bounded
    telemetry export queue — proving it drops oldest (counting
    ``telemetry.export.dropped``) without ever blocking the serving
    path.  Returns the number of synthetic traces emitted (0 = no
    fire)."""
    from pathway_tpu.engine import faults

    plan = faults.active_plan()
    if plan is None:
        return 0
    spec = plan.check("trace_storm", source=route)
    if spec is None:
        return 0
    n = int(spec.count or STORM_DEFAULT_TRACES)
    now = time.time()
    for i in range(n):
        trace = RequestTrace(route or "storm")
        parent: str | None = None
        for depth in range(STORM_TREE_DEPTH):
            parent = trace.add_span(
                f"storm.depth.{depth}",
                now,
                0.0,
                parent_span_id=parent,
                synthetic=True,
                storm_index=i,
            )
        trace.finish(status="storm", synthetic=True)
    _metrics.get_registry().counter(
        "trace.storm.synthetic",
        "synthetic traces injected by the trace_storm chaos fault kind",
    ).inc(float(n))
    return n
