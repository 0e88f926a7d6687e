"""Device-path observability: XLA cost accounting, roofline utilization,
padding efficiency, HBM tracking, and on-demand profiler traces.

PR 11 built the DeviceExecutor — fixed shapes, compile-cache discipline,
async dispatch — but left the device path a telemetry blind spot: we
counted dispatches and cache misses without knowing what fraction of
padded rows is waste, how many FLOPs each compiled callable moves, how
close the rig runs to roofline, or what lives in HBM.  This module is
the measurement rail every remaining [perf]/[scale] ROADMAP item pins
against (WindVE's CPU↔device queue-efficiency accounting and
VectorLiteRAG's per-stage device cost attribution in PAPERS.md are the
models):

* **XLA cost accounting** (:func:`extract_cost`, :class:`CostAccountant`).
  Every fresh compile-cache key the executor pays is compiled through the
  AOT path (``jitted.lower(...).compile()`` — ONE backend compile, the
  compiled executable is reused for dispatch), and its
  ``cost_analysis()`` / ``memory_analysis()`` are captured at compile
  time: flops, bytes accessed, argument/output/peak-temp bytes.  Each
  later dispatch of that key adds the known flops/bytes to
  ``device.flops.total`` / ``device.bytes.accessed`` and its wall time to
  the accountant's device-seconds ledger, yielding
  ``device.achieved.flops_per_s`` and a roofline **utilization
  estimate** against a configurable per-backend peak
  (:func:`peak_flops`: ``PATHWAY_DEVICE_PEAK_FLOPS`` overrides an
  auto-detected device-kind table; the CPU rig gets a measured-peak
  default so the layer is testable today).

* **Padding/bucket efficiency.**  The executor records every submitted
  ragged batch size here (:meth:`CostAccountant.record_batch`, bounded
  distinct-size map) and every chunk's occupancy
  (``device.bucket.occupancy`` histogram), so
  ``device.padding.waste.{rows,fraction}`` answer "how much of the
  padded work is waste" and ``pathway_tpu buckets`` can replay the
  observed distribution against a better bucket set
  (``bucketing.suggest_buckets``).

* **HBM / live-buffer accounting** (:func:`hbm_stats`).  Where the
  backend implements ``device.memory_stats()`` (TPU/GPU) the real
  allocator numbers are exported; elsewhere the executor's tracked
  live-bytes fallback (argument+output+temp bytes of in-flight
  dispatches) stands in — ``device.hbm.{bytes_in_use,peak}`` either way.

* **On-demand trace capture** (:func:`capture_trace`).  A
  ``jax.profiler`` start/stop hook reachable via ``GET /trace?seconds=N``
  on the monitoring HTTP server and the ``pathway_tpu trace`` CLI,
  dumping a TensorBoard-viewable trace directory under
  ``PATHWAY_DEVICE_TRACE_DIR``.  One capture at a time; captures are
  counted (``device.trace.captures``).

Everything flows through the unified registry (``engine/metrics.py``),
surfaces in ``/status`` / ``pathway_tpu top`` / Prometheus / OTLP, and
rides flight-recorder dumps (``set_device_supplier``) so post-mortems
say what the device was doing.  Steady-state cost is a few dict/float
ops per *dispatch* (not per row), priced by
``benchmarks/device_obs_overhead.py`` against the ≤2 %-of-a-1 ms-epoch
budget the profiler and freshness layers established.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any

import jax

from pathway_tpu.engine import metrics as _metrics
from pathway_tpu.engine import tracing as _tracing

__all__ = [
    "CostAccountant",
    "TraceBusy",
    "TraceUnavailable",
    "capture_trace",
    "extract_cost",
    "hbm_stats",
    "peak_flops",
    "render_device_snapshot",
]

# ---------------------------------------------------------------------------
# Roofline peak table
# ---------------------------------------------------------------------------

# Per-device-kind peak FLOP/s (dense, the marketed per-chip peak for the
# precision the serving path uses).  Matched case-insensitively as a
# substring of ``jax.devices()[0].device_kind``, most specific first.  A
# non-CPU kind missing here is an error (:func:`peak_flops`), not a default.
PEAK_FLOPS_TABLE: tuple[tuple[str, float], ...] = (
    ("tpu v5p", 459e12),
    ("tpu v5 lite", 197e12),
    ("tpu v5e", 197e12),
    ("tpu v6 lite", 918e12),
    ("tpu v6e", 918e12),
    ("tpu v4", 275e12),
    ("tpu v3", 123e12),
    ("tpu v2", 45e12),
)

# The CPU rig's measured-peak default, per core: a single f32 FMA port at
# a few GHz sustains ~8 GFLOP/s through numpy/XLA:CPU on this class of
# machine.  Deliberately conservative — a CPU "utilization" estimate is a
# smoke-test of the accounting plumbing, not a roofline claim; the table
# above is what a TPU run reports against.
CPU_PEAK_FLOPS_PER_CORE = 8e9


def device_kind() -> str:
    """The first local device's kind string."""
    return str(jax.local_devices()[0].device_kind)


def peak_flops() -> tuple[float, str]:
    """``(peak FLOP/s, provenance)`` for the roofline denominator.

    Priority: the ``PATHWAY_DEVICE_PEAK_FLOPS`` knob (an operator who
    benchmarked their part overrides any table), then the device-kind
    table; the CPU platform gets the measured-peak default scaled by core
    count.  An accelerator whose kind is not in the table raises: a
    utilization against another part's peak is a wrong number, not an
    estimate."""
    from pathway_tpu.internals.config import env_float

    configured = env_float("PATHWAY_DEVICE_PEAK_FLOPS")
    if configured:
        return float(configured), "PATHWAY_DEVICE_PEAK_FLOPS"
    kind = device_kind().lower()
    for needle, value in PEAK_FLOPS_TABLE:
        if needle in kind:
            return value, kind
    if jax.default_backend() != "cpu":
        raise ValueError(
            f"no peak FLOP/s known for device kind {kind!r}: add it to "
            "PEAK_FLOPS_TABLE or set PATHWAY_DEVICE_PEAK_FLOPS"
        )
    cores = os.cpu_count() or 1
    return CPU_PEAK_FLOPS_PER_CORE * cores, f"cpu-default ({cores} cores)"


# ---------------------------------------------------------------------------
# Cost extraction (one compiled executable -> one flat cost dict)
# ---------------------------------------------------------------------------


def extract_cost(compiled: Any) -> dict[str, float]:
    """Flatten an AOT-compiled executable's ``cost_analysis()`` +
    ``memory_analysis()`` into one plain-float dict.

    Keys: ``flops``, ``bytes_accessed`` (XLA's HBM traffic estimate),
    ``argument_bytes``, ``output_bytes``, ``temp_bytes`` (peak scratch),
    and ``analyzed`` (1.0 when ``cost_analysis()`` actually produced
    a cost dict).  Never raises; a backend without cost analysis yields
    zeros with ``analyzed = 0.0``, and the accountant counts that key's
    dispatches as *uncosted* — a gap in the accounting is visible, never
    read as a zero-FLOP device."""
    out = {
        "flops": 0.0,
        "bytes_accessed": 0.0,
        "argument_bytes": 0.0,
        "output_bytes": 0.0,
        "temp_bytes": 0.0,
        "analyzed": 0.0,
    }
    try:
        analysis = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - optional per backend
        analysis = None
    if analysis:
        out["analyzed"] = 1.0
        out["flops"] = max(0.0, float(analysis.get("flops", 0.0)))
        out["bytes_accessed"] = max(
            0.0, float(analysis.get("bytes accessed", 0.0))
        )
    try:
        mem = compiled.memory_analysis()
        out["argument_bytes"] = float(
            getattr(mem, "argument_size_in_bytes", 0) or 0
        )
        out["output_bytes"] = float(
            getattr(mem, "output_size_in_bytes", 0) or 0
        )
        out["temp_bytes"] = float(getattr(mem, "temp_size_in_bytes", 0) or 0)
    except Exception:  # noqa: BLE001 - optional per backend
        pass
    return out


# ---------------------------------------------------------------------------
# The accountant: per-executor cost/utilization/distribution ledger
# ---------------------------------------------------------------------------

# bounded distinct-size map: a pathological workload submitting thousands
# of distinct ragged sizes must not grow the accountant without bound —
# overflow sizes are still *counted*, just not individually keyed
MAX_DISTINCT_BATCH_SIZES = 512
# label cardinality cap for the exported ``device.batch.rows{rows=N}``
# gauges (the `pathway_tpu buckets` live feed): most-frequent sizes win
BATCH_SIZE_EXPORT_TOP = 32


class CostAccountant:
    """Cumulative device cost ledger for one :class:`DeviceExecutor`.

    Updated per *dispatch* (never per row) under one small lock; reads
    (collector gauges, ``pathway_tpu buckets``, flight-recorder
    snapshots) take consistent copies.  Honors the registry kill switch:
    with metrics disabled every update is an immediate return, which is
    the lever ``benchmarks/device_obs_overhead.py`` prices against."""

    def __init__(self, registry: "_metrics.MetricsRegistry | None" = None):
        reg = registry if registry is not None else _metrics.get_registry()
        self._registry = reg
        self._m_flops = reg.counter(
            "device.flops.total",
            "cost-analysis FLOPs moved by dispatched device batches",
        )
        self._m_bytes = reg.counter(
            "device.bytes.accessed",
            "cost-analysis bytes accessed by dispatched device batches",
        )
        self._lock = threading.Lock()
        self.flops_total = 0.0
        self.bytes_total = 0.0
        self.device_seconds = 0.0
        self.costed_dispatches = 0
        self.uncosted_dispatches = 0
        self.batch_sizes: dict[int, int] = {}
        self.batch_size_overflow = 0
        self.peak, self.peak_source = peak_flops()

    @property
    def enabled(self) -> bool:
        """Mirrors the registry kill switch — the executor gates its own
        accounting-side work (live-bytes locks) on this too."""
        return self._registry.enabled

    # -- writes (executor hot path) ----------------------------------------
    def record_batch(self, n_rows: int) -> None:
        """One submitted ragged batch of ``n_rows`` real rows — the
        distribution ``pathway_tpu buckets`` replays."""
        if not self._registry.enabled:
            return
        with self._lock:
            if n_rows in self.batch_sizes:
                self.batch_sizes[n_rows] += 1
            elif len(self.batch_sizes) < MAX_DISTINCT_BATCH_SIZES:
                self.batch_sizes[n_rows] = 1
            else:
                self.batch_size_overflow += 1

    def record_dispatch(
        self, cost: dict[str, float] | None, duration_s: float
    ) -> None:
        """One fixed-shape device call of a key whose compile-time cost
        is ``cost`` (None when the key could not be cost-analyzed; a
        cost dict whose ``analyzed`` flag is 0.0 — the AOT compile ran
        but the backend produced no cost analysis — counts as uncosted
        too, never as a zero-FLOP device)."""
        if not self._registry.enabled:
            return
        if cost is None or not cost.get("analyzed", 1.0):
            with self._lock:
                self.uncosted_dispatches += 1
                self.device_seconds += duration_s
            return
        flops = cost.get("flops", 0.0)
        accessed = cost.get("bytes_accessed", 0.0)
        with self._lock:
            self.costed_dispatches += 1
            self.flops_total += flops
            self.bytes_total += accessed
            self.device_seconds += duration_s
        if flops:
            self._m_flops.inc(flops)
        if accessed:
            self._m_bytes.inc(accessed)

    # -- reads --------------------------------------------------------------
    def achieved_flops_per_s(self) -> float:
        """Cumulative FLOPs over cumulative device-call wall seconds —
        the numerator of the roofline estimate."""
        with self._lock:
            if self.device_seconds <= 0.0:
                return 0.0
            return self.flops_total / self.device_seconds

    def utilization(self) -> float:
        """Achieved / peak: the roofline utilization estimate in [0, ~1]
        (an over-unity reading means the peak table or knob undershoots
        this part — fix the denominator, the numerator is measured)."""
        if self.peak <= 0.0:
            return 0.0
        return self.achieved_flops_per_s() / self.peak

    def gauges(self) -> dict[str, float]:
        """The collector-exported gauge slice of this ledger."""
        out = {
            "device.achieved.flops_per_s": self.achieved_flops_per_s(),
            "device.utilization": self.utilization(),
            "device.peak.flops_per_s": self.peak,
        }
        with self._lock:
            top = sorted(
                self.batch_sizes.items(), key=lambda kv: -kv[1]
            )[:BATCH_SIZE_EXPORT_TOP]
        for size, count in top:
            out[f"device.batch.rows{{rows={size}}}"] = float(count)
        return out

    def snapshot(self) -> dict[str, Any]:
        """The full ledger (flight-recorder / ``pathway_tpu buckets``
        form) — plain JSON-able values only."""
        with self._lock:
            sizes = dict(self.batch_sizes)
            out = {
                "flops_total": self.flops_total,
                "bytes_accessed_total": self.bytes_total,
                "device_seconds": self.device_seconds,
                "costed_dispatches": self.costed_dispatches,
                "uncosted_dispatches": self.uncosted_dispatches,
                "batch_size_overflow": self.batch_size_overflow,
            }
        out["achieved_flops_per_s"] = (
            out["flops_total"] / out["device_seconds"]
            if out["device_seconds"] > 0.0
            else 0.0
        )
        out["peak_flops_per_s"] = self.peak
        out["peak_source"] = self.peak_source
        out["utilization"] = (
            out["achieved_flops_per_s"] / self.peak if self.peak > 0.0 else 0.0
        )
        out["batch_sizes"] = {str(k): v for k, v in sorted(sizes.items())}
        return out


# ---------------------------------------------------------------------------
# HBM / allocator stats
# ---------------------------------------------------------------------------


def hbm_stats() -> dict[str, float] | None:
    """Real allocator numbers where the backend keeps them.

    ``device.memory_stats()`` is populated on TPU/GPU and ``None`` on
    CPU — callers (the executor's collector) fall back to the tracked
    live-bytes estimate there, so ``device.hbm.*`` is never silently
    absent."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 - optional per backend
        return None
    if not stats:
        return None
    in_use = float(stats.get("bytes_in_use", 0) or 0)
    return {
        "bytes_in_use": in_use,
        "peak": float(stats.get("peak_bytes_in_use", in_use) or in_use),
    }


# ---------------------------------------------------------------------------
# On-demand trace capture
# ---------------------------------------------------------------------------


class TraceUnavailable(RuntimeError):
    """Trace capture cannot run here (no trace dir configured) —
    rendered as a clean 503 / CLI message."""


class TraceBusy(TraceUnavailable):
    """A capture is already in progress (one at a time, by design: the
    underlying profiler session is process-global)."""


_MAX_TRACE_SECONDS = 120.0
_trace_lock = threading.Lock()
# uniquifies trace dir names: two captures within one wall-clock second
# must not merge into one TensorBoard session
_trace_seq = 0


def capture_trace(seconds: float, trace_dir: str | None = None) -> str:
    """Capture ``seconds`` of ``jax.profiler`` trace into a fresh
    directory under ``trace_dir`` (default: the
    ``PATHWAY_DEVICE_TRACE_DIR`` knob) and return its path.

    The result is a TensorBoard-viewable trace dir
    (``tensorboard --logdir <path>``).  Runs *in this process* — the
    monitoring HTTP server calls it so ``pathway_tpu trace`` captures
    the live worker, not the CLI process.  One capture at a time
    (:class:`TraceBusy`); duration is clamped to ``[0, 120] s`` so a
    typo'd request cannot pin the profiler for an hour."""
    from pathway_tpu.internals.config import env_str

    base = trace_dir or env_str("PATHWAY_DEVICE_TRACE_DIR")
    if not base:
        raise TraceUnavailable(
            "no trace directory configured — set PATHWAY_DEVICE_TRACE_DIR "
            "(or pass an explicit directory)"
        )
    seconds = max(0.0, min(float(seconds), _MAX_TRACE_SECONDS))
    if not _trace_lock.acquire(blocking=False):
        raise TraceBusy("a trace capture is already running in this process")
    try:
        global _trace_seq
        _trace_seq += 1  # under _trace_lock: one capture at a time
        path = os.path.join(
            base,
            f"trace-{time.strftime('%Y%m%d-%H%M%S')}"
            f"-pid{os.getpid()}-{_trace_seq:03d}",
        )
        os.makedirs(path, exist_ok=True)
        # device events and TraceMe annotations (the host timeline's
        # intervals, engine/tracing.py) only: with the Python tracer on,
        # a serving process's answers were delayed by seconds while the
        # capture ran and the hand-over took minutes (PERF.md section 6)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        # the trace's clock starts at the session's start (the trace keeps
        # its Unix time as ``profile_start_time``); this interval puts the
        # traced seconds on the timeline's clock too
        capture = _tracing.begin("profiler", "trace.capture", path=path)
        jax.profiler.start_trace(path, profiler_options=options)
        try:
            deadline = time.monotonic() + seconds
            # sliced wait: a supervised worker capturing a long trace
            # still touches its progress machinery at sub-second cadence
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                time.sleep(min(0.05, remaining))
        finally:
            _tracing.end(capture)
            jax.profiler.stop_trace()
        _metrics.get_registry().counter(
            "device.trace.captures", "on-demand jax.profiler traces captured"
        ).inc()
        return path
    finally:
        _trace_lock.release()


# ---------------------------------------------------------------------------
# Snapshot rendering (CLI / post-mortem)
# ---------------------------------------------------------------------------


def format_utilization(util: float) -> str:
    """One spelling for the roofline utilization everywhere it renders
    (`pathway_tpu top`, the blackbox/profile device section): percent for
    human-scale readings, scientific for the CPU rig's ~1e-6-of-peak
    territory where a row of \"0.00%\" says nothing."""
    return f"{util:.2%}" if util >= 0.0005 else f"{util:.2e}"


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0
    return f"{n:.1f} TiB"


def render_device_snapshot(snapshot: dict[str, Any]) -> str:
    """Human-readable device section of a flight-recorder dump (the
    ``pathway_tpu blackbox`` / ``profile`` render).  ``.get()``
    everywhere: this renders foreign or cross-version dumps — a partial
    snapshot must render best-effort, never traceback."""
    cost = snapshot.get("cost") or {}
    lines = ["device:"]
    util = cost.get("utilization")
    if util is not None:
        lines.append(
            f"  utilization {format_utilization(util)} of "
            f"{cost.get('peak_flops_per_s', 0.0):.3g} FLOP/s peak "
            f"({cost.get('peak_source', '?')}) · achieved "
            f"{cost.get('achieved_flops_per_s', 0.0):.3g} FLOP/s"
        )
        lines.append(
            f"  flops {cost.get('flops_total', 0.0):.3g} · bytes accessed "
            f"{_fmt_bytes(cost.get('bytes_accessed_total', 0.0))} over "
            f"{cost.get('costed_dispatches', 0)} costed dispatch(es)"
            + (
                f" ({cost.get('uncosted_dispatches')} uncosted)"
                if cost.get("uncosted_dispatches")
                else ""
            )
        )
    padding = snapshot.get("padding") or {}
    if padding:
        lines.append(
            f"  padding waste {padding.get('fraction', 0.0):.2%} "
            f"({int(padding.get('pad_rows', 0))} pad / "
            f"{int(padding.get('real_rows', 0))} real rows)"
        )
    hbm = snapshot.get("hbm") or {}
    if hbm:
        lines.append(
            f"  hbm {_fmt_bytes(hbm.get('bytes_in_use', 0.0))} in use · "
            f"peak {_fmt_bytes(hbm.get('peak', 0.0))} "
            f"({hbm.get('source', '?')})"
        )
    queue = snapshot.get("queue") or {}
    if queue:
        lines.append(
            f"  queue {int(queue.get('backlog.device.queue', 0))} job(s) · "
            f"{_fmt_bytes(queue.get('backlog.device.bytes', 0.0))} in flight "
            f"· oldest {queue.get('backlog.device.age.s', 0.0):.2f} s"
        )
    callables = snapshot.get("callables") or {}
    for name in sorted(callables):
        st = callables[name] or {}
        lines.append(
            f"  {name}: {st.get('dispatches', 0)} dispatch(es), "
            f"{st.get('keys', 0)} compile key(s) "
            f"(cold {st.get('cold', 0)} / warmed {st.get('warmed', 0)})"
        )
    for shape, traces in sorted(
        (snapshot.get("attention_xla_fallback") or {}).items()
    ):
        lines.append(
            f"  attention {shape}: {traces} trace(s) on the XLA path "
            "(Pallas kernel does not support the shape)"
        )
    resilience = snapshot.get("resilience") or {}
    for name in sorted(resilience.get("callables") or {}):
        st = (resilience["callables"].get(name) or {})
        breaker = st.get("breaker") or {}
        failures = st.get("failures") or {}
        interesting = (
            breaker.get("state") not in (None, "closed")
            or breaker.get("trips")
            or st.get("bucket_cap") is not None
            or st.get("fallback_batches")
            or failures
        )
        if not interesting:
            continue  # healthy callables say nothing — failures stand out
        parts = [f"breaker {breaker.get('state', '?')}"]
        if breaker.get("trips"):
            parts.append(f"{breaker['trips']} trip(s)")
        if st.get("fallback_batches"):
            parts.append(f"{st['fallback_batches']} fallback batch(es)")
        if st.get("bucket_cap") is not None:
            parts.append(
                f"OOM-capped at bucket {st['bucket_cap']} "
                f"({st.get('oom_splits', 0)} split(s))"
            )
        if failures:
            parts.append(
                "failures "
                + ", ".join(f"{k}={v}" for k, v in sorted(failures.items()))
            )
        lines.append(f"  {name}: " + " · ".join(parts))
    quarantine = resilience.get("quarantine") or []
    if quarantine:
        lines.append(f"  quarantine: {len(quarantine)} poisoned batch(es)")
        for rec in quarantine[-3:]:
            lines.append(
                f"    {rec.get('callable', '?')}: {rec.get('rows', '?')} "
                f"row(s) — {rec.get('fallback_error', '?')}"
            )
    if len(lines) == 1:
        lines.append("  (no device activity recorded)")
    return "\n".join(lines)
