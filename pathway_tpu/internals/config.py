"""Env-var-driven runtime configuration + the declared knob registry.

Parity target: ``/root/reference/python/pathway/internals/config.py`` (173
LoC) + engine-side ``src/engine/dataflow/config.rs:88-127``.  Same env
variables, same context-local override mechanism.

Every ``PATHWAY_*`` environment knob the package reads is DECLARED here
in :data:`ENV_KNOBS` — name, type, default, one-line doc, owning
subsystem — and read through the typed accessors (:func:`env_bool`,
:func:`env_int`, :func:`env_float`, :func:`env_str`, :func:`env_raw`).
``pathway_tpu lint`` enforces both halves: a direct ``os.environ`` read
of a ``PATHWAY_*`` name outside this module is an ``env-direct-read``
finding, and an undeclared name anywhere is ``env-undeclared``.
``docs/configuration.md`` is GENERATED from this registry
(:func:`render_env_docs`; regenerate with ``pathway_tpu lint
--update-config-docs``) and pinned in sync by the lint gate.

Accessors read ``os.environ`` live (no caching): worker processes get
their knobs from the spawning supervisor's environment, and tests
monkeypatch freely between runs.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from contextvars import ContextVar
from typing import Any

# ---------------------------------------------------------------------------
# The declared environment-knob registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EnvKnob:
    """One declared ``PATHWAY_*`` environment knob."""

    name: str
    kind: str  # "bool" | "int" | "float" | "str"
    default: Any
    doc: str
    subsystem: str


def _k(name: str, kind: str, default: Any, doc: str, subsystem: str) -> EnvKnob:
    return EnvKnob(name, kind, default, doc, subsystem)


ENV_KNOBS: tuple[EnvKnob, ...] = (
    # -- core runtime (this module) -----------------------------------------
    _k("PATHWAY_IGNORE_ASSERTS", "bool", False,
       "skip `pw.assert_*` runtime checks", "core"),
    _k("PATHWAY_RUNTIME_TYPECHECKING", "bool", False,
       "enable runtime schema/type checking of expressions", "core"),
    _k("PATHWAY_TERMINATE_ON_ERROR", "bool", True,
       "terminate the run on the first operator error (else poison rows "
       "and continue)", "core"),
    _k("PATHWAY_REPLAY_STORAGE", "str", None,
       "persistence root for record/replay runs (enables persistence "
       "without an explicit `pw.persistence.Config`)", "core"),
    _k("PATHWAY_SNAPSHOT_ACCESS", "str", None,
       "`record` | `replay` — connector snapshot mode for record/replay "
       "runs", "core"),
    _k("PATHWAY_PERSISTENCE_MODE", "str", None,
       "persistence/replay pacing mode (`batch` | `speedrun`)", "core"),
    _k("PATHWAY_REPLAY_MODE", "str", None,
       "legacy alias of PATHWAY_PERSISTENCE_MODE written by "
       "`pathway_tpu replay --mode`", "core"),
    _k("PATHWAY_CONTINUE_AFTER_REPLAY", "bool", False,
       "keep consuming live connector data after a recorded stream "
       "drains", "core"),
    _k("PATHWAY_LICENSE_KEY", "str", None,
       "license key for entitlement checks (internals/license.py)", "core"),
    _k("PATHWAY_MONITORING_SERVER", "str", None,
       "OTLP/HTTP collector endpoint for telemetry export (zero egress "
       "when unset)", "core"),
    _k("PATHWAY_THREADS", "int", 1,
       "worker threads per spawned process (accepted for parity; the "
       "device mesh is what scales compute)", "core"),
    _k("PATHWAY_PROCESSES", "int", 1,
       "SPMD cluster size: identical processes forming one TCP mesh",
       "core"),
    _k("PATHWAY_PROCESS_ID", "int", 0,
       "this worker's id within the cluster, in [0, PATHWAY_PROCESSES)",
       "core"),
    _k("PATHWAY_FIRST_PORT", "int", 10000,
       "base port of the worker TCP mesh (worker i listens on "
       "FIRST_PORT + i)", "core"),
    _k("PATHWAY_PEER_HOSTS", "str", None,
       "comma-separated hostname per worker id for multi-host meshes "
       "(unset = localhost mesh)", "core"),
    _k("PATHWAY_RUN_ID", "str", None,
       "cluster run id minted by `pathway_tpu spawn` (one per run, kept "
       "across supervised restarts)", "core"),
    _k("PATHWAY_MONITORING_HTTP_PORT", "int", None,
       "serve `GET /metrics` + the HTML dashboard on this port (worker i "
       "uses port + i)", "core"),
    # -- comm mesh (engine/comm.py) -----------------------------------------
    _k("PATHWAY_COMM_SECRET", "str", "",
       "shared mesh handshake secret (`spawn` mints one per run); empty "
       "disables authentication and pickled frame values", "comm"),
    _k("PATHWAY_COMM_MAX_FRAME_MB", "int", 256,
       "frame-size cap in MiB — a corrupt or hostile length field must "
       "not OOM the worker", "comm"),
    _k("PATHWAY_COMM_RECV_TIMEOUT_S", "float", 300.0,
       "deadline for `recv()` waiting on a tagged frame", "comm"),
    _k("PATHWAY_COMM_HEARTBEAT_S", "float", 2.0,
       "heartbeat send interval per link", "comm"),
    _k("PATHWAY_COMM_HEARTBEAT_TIMEOUT_S", "float", 30.0,
       "force-fail a link whose peer was silent (or stopped acking) for "
       "this long", "comm"),
    _k("PATHWAY_COMM_RECONNECT_WINDOW_S", "float", 15.0,
       "window a failed link may reconnect + resync before the peer is "
       "declared dead and its inbox purged", "comm"),
    _k("PATHWAY_COMM_SEND_DEADLINE_S", "float", None,
       "SO_SNDTIMEO deadline on any single blocking socket write "
       "(default: the heartbeat timeout; 0 disables)", "comm"),
    _k("PATHWAY_COMM_SEND_BUFFER_MB", "float", 64.0,
       "per-link retransmit buffer in MiB (unacked frames kept for "
       "reconnect resync)", "comm"),
    # -- fault injection (engine/faults.py) ---------------------------------
    _k("PATHWAY_FAULT_PLAN", "str", None,
       "seeded fault-injection plan (JSON) for chaos/soak runs", "faults"),
    _k("PATHWAY_RESTART_ATTEMPT", "int", 0,
       "supervisor restart attempt announced to workers (fault `attempt` "
       "filters key off it)", "faults"),
    # -- metrics / telemetry ------------------------------------------------
    _k("PATHWAY_METRICS_DISABLED", "bool", False,
       "kill switch: turn every metric update into an immediate return "
       "(the benchmark lever)", "metrics"),
    _k("PATHWAY_TELEMETRY_PROTOCOL", "str", "otlp-json",
       "telemetry wire format: `otlp-json` | `pathway-json` (legacy line "
       "JSON)", "metrics"),
    _k("PATHWAY_SERVICE_INSTANCE_ID", "str", None,
       "OTel `service.instance.id` resource attribute (default: random "
       "per process)", "metrics"),
    _k("PATHWAY_SERVICE_NAMESPACE", "str", "local-dev",
       "OTel `service.namespace` resource attribute", "metrics"),
    # -- request tracing & SLOs (engine/tracing.py, engine/slo.py) ----------
    _k("PATHWAY_TRACE_REQUESTS", "bool", True,
       "request-scoped distributed tracing of the serving path (ingress/"
       "admission/batcher/device/generation child spans, histogram "
       "exemplars, the `pathway_tpu requests` waterfall); `0` removes "
       "the per-request span layer entirely", "tracing"),
    _k("PATHWAY_TRACE_BUFFER", "int", 256,
       "finished request traces retained in the in-process ring the "
       "`pathway_tpu requests` CLI, `/status` and flight-recorder dumps "
       "read", "tracing"),
    _k("PATHWAY_SLOS", "str", None,
       "extra SLO declarations (semicolon-separated "
       "`name: metric pNN < threshold over window`, e.g. "
       "`latency: serve.latency.ms p95 < 250ms over 5m`) merged over "
       "the built-in registry; a redeclared name overrides it", "tracing"),
    # -- per-operator profiler / device accounting (engine/profiler.py) -----
    _k("PATHWAY_PROFILE", "bool", False,
       "enable the per-operator epoch profiler (top-N attribution "
       "snapshots exported as `profiler.operator.*`)", "profiler"),
    _k("PATHWAY_PROFILE_SAMPLE_EVERY", "int", 16,
       "profiler sampling cadence: aggregate operator totals every N "
       "processed epochs", "profiler"),
    _k("PATHWAY_PROFILE_TOP", "int", 20,
       "operators kept per profiler snapshot (bounds metric cardinality "
       "and the CLI render)", "profiler"),
    _k("PATHWAY_PROFILE_OUTPUT", "str", None,
       "write the run's final profiler snapshot to this JSON path "
       "(render it with `pathway_tpu profile <path>`)", "profiler"),
    _k("PATHWAY_PROFILE_JAX", "bool", True,
       "install jax.monitoring listeners counting compilations, jit "
       "cache misses and compile seconds (`jax.compile.*`, "
       "`jax.cache.miss`)", "profiler"),
    _k("PATHWAY_PROFILE_TRANSFERS", "bool", False,
       "wrap jax.device_put/device_get to count explicit host<->device "
       "transfer bytes (`jax.transfer.*`)", "profiler"),
    # -- data-plane freshness & backpressure (engine/freshness.py) ----------
    _k("PATHWAY_FRESHNESS", "bool", True,
       "track ingest-time freshness (per-output `freshness.e2e.ms` / "
       "`output.staleness.s`) and `backlog.*` backpressure gauges; `0` "
       "removes the per-epoch watermark pass entirely", "freshness"),
    _k("PATHWAY_STATUS_REFRESH_S", "float", 1.0,
       "default poll interval of the `pathway_tpu top` live view "
       "(`GET /status` on the monitoring HTTP server)", "freshness"),
    # -- benchmark harness (benchmarks/harness.py) --------------------------
    _k("PATHWAY_BENCH_BASELINE_DIR", "str", None,
       "directory of committed benchmark baselines (default: "
       "benchmarks/baselines/)", "bench"),
    _k("PATHWAY_BENCH_REPS", "int", None,
       "override the per-mode benchmark repetition count", "bench"),
    # -- persistence (engine/persistence.py) --------------------------------
    _k("PATHWAY_INCARNATION", "int", 0,
       "cluster incarnation lease this worker runs under (exported by "
       "the supervisor; fences zombie writers out of the root)",
       "persistence"),
    _k("PATHWAY_CHECKPOINT_GENERATIONS", "int", 3,
       "committed checkpoint generations retained (the deferred-GC "
       "fallback window)", "persistence"),
    _k("PATHWAY_CHECKPOINT_WRITERS", "int", 2,
       "background checkpoint writer threads; 0 = fully synchronous "
       "commits", "persistence"),
    _k("PATHWAY_CHECKPOINT_INFLIGHT_MB", "int", 256,
       "cap of in-flight snapshot bytes before commit staging "
       "backpressures the epoch thread", "persistence"),
    _k("PATHWAY_CHECKPOINT_PUBLISH_INTERVAL_MS", "float", 20.0,
       "minimum spacing between pipelined manifest publishes (staged "
       "frontiers conflate while the committer waits)", "persistence"),
    _k("PATHWAY_BLOB_RETRIES", "int", 3,
       "bounded retries for transient object-store errors", "persistence"),
    _k("PATHWAY_BLOB_RETRY_INITIAL_MS", "int", 200,
       "initial backoff of the blob retry schedule", "persistence"),
    _k("PATHWAY_PERSISTENT_STORAGE", "str", None,
       "filesystem root for the UDF DiskCache when no persistence config "
       "is active", "persistence"),
    # -- supervisor (engine/supervisor.py) ----------------------------------
    _k("PATHWAY_EPOCH_DEADLINE_S", "float", None,
       "hung-worker watchdog: no epoch progress for this long → SIGUSR1 "
       "(flight-recorder dump) → SIGTERM → SIGKILL into a supervised "
       "restart (unset or <= 0 disables)", "supervisor"),
    _k("PATHWAY_DEGRADED_SHRINK", "bool", False,
       "degraded-mode shrink (opt-in): when the same worker fails every "
       "attempt of a spent restart budget, rescale the supervised cluster "
       "to the surviving count instead of failing — checkpointed state "
       "re-partitions by shard range on resume", "supervisor"),
    _k("PATHWAY_STANDBY_COUNT", "int", 0,
       "warm-standby pool size (opt-in, `spawn --supervise --standbys`): "
       "K extra processes tail the persistence root so unplanned worker "
       "loss promotes a standby instead of restarting the group",
       "supervisor"),
    _k("PATHWAY_STANDBY_ID", "int", None,
       "exported by the supervisor into each standby process; its "
       "presence is what routes a spawned worker into standby-tailer "
       "mode instead of the event loop", "supervisor"),
    _k("PATHWAY_STANDBY_POLL_S", "float", 0.2,
       "standby tail cadence: how often a standby re-lists manifests, "
       "verifies newly committed generations, and refreshes its "
       "apply-cursor beacon", "supervisor"),
    _k("PATHWAY_STANDBY_PROMOTE_DEADLINE_S", "float", 20.0,
       "promotion deadline: if the standby + every survivor have not "
       "acked the PROMOTE request within this budget, the supervisor "
       "aborts the promotion and falls back to whole-group restart",
       "supervisor"),
    _k("PATHWAY_STANDBY_PROMOTIONS", "int", 8,
       "per-run promotion budget (separate from the restart budget): "
       "once spent, further worker deaths fall back to whole-group "
       "restart", "supervisor"),
    _k("PATHWAY_WORKER_FENCE", "int", 0,
       "per-worker fence token (exported by the supervisor to a promoted "
       "standby): commit-point writes carrying an older token than the "
       "lease's fence map are the dead worker's zombie and are rejected",
       "persistence"),
    # -- autoscaler (engine/autoscaler.py) ----------------------------------
    _k("PATHWAY_AUTOSCALE", "bool", False,
       "load-adaptive autoscaling (opt-in): the supervisor polls worker "
       "load beacons and grows/shrinks the cluster via live shard handoff "
       "under the PATHWAY_AUTOSCALE_* budgets below", "autoscaler"),
    _k("PATHWAY_AUTOSCALE_MIN_WORKERS", "int", 1,
       "shrink floor: the controller never targets fewer workers than "
       "this (and never below 1 regardless)", "autoscaler"),
    _k("PATHWAY_AUTOSCALE_MAX_WORKERS", "int", 8,
       "grow ceiling: the controller never targets more workers than "
       "this", "autoscaler"),
    _k("PATHWAY_AUTOSCALE_STALENESS_S", "float", 5.0,
       "grow trigger: worst per-worker output staleness above this for a "
       "full dwell window means the cluster is falling behind",
       "autoscaler"),
    _k("PATHWAY_AUTOSCALE_DWELL_S", "float", 10.0,
       "hysteresis dwell: the grow trigger must hold CONTINUOUSLY for "
       "this long before a rescale fires (one dip below threshold resets "
       "the clock) — oscillating load never flaps", "autoscaler"),
    _k("PATHWAY_AUTOSCALE_COOLDOWN_S", "float", 60.0,
       "post-rescale cooldown: no further scaling decision (either "
       "direction) for this long after a rescale fires", "autoscaler"),
    _k("PATHWAY_AUTOSCALE_IDLE_S", "float", 30.0,
       "shrink trigger: staleness comfortably low AND backlog ~empty "
       "continuously for this long shrinks the cluster one step",
       "autoscaler"),
    _k("PATHWAY_AUTOSCALE_BUDGET", "int", 4,
       "rescale budget: total grow/shrink decisions this supervisor run "
       "may fire; exhaustion logs loudly and pins the topology",
       "autoscaler"),
    _k("PATHWAY_AUTOSCALE_HANDOFF_DEADLINE_S", "float", 30.0,
       "live-handoff deadline: a posted handoff the workers have not "
       "fully acked within this window falls back to the restart-based "
       "rescale", "autoscaler"),
    # -- serving path (engine/serving.py, io/http/) -------------------------
    _k("PATHWAY_SERVE_ADMISSION", "bool", True,
       "`0` disables the serving admission controller entirely (every "
       "request is admitted immediately, no 429/queue/shedding — the "
       "unprotected mode `benchmarks/serving_overload.py` measures "
       "against)", "serving"),
    _k("PATHWAY_SERVE_DEADLINE_MS", "float", 30000.0,
       "default per-request deadline for REST queries (overridable per "
       "request via the `X-Pathway-Deadline-Ms` header); a request that "
       "cannot complete in budget is answered 504 and retracted before "
       "burning further work", "serving"),
    _k("PATHWAY_SERVE_INFLIGHT", "int", 64,
       "admission: max REST requests concurrently inside the pipeline "
       "(admitted, not yet answered); arrivals beyond it wait in the "
       "pending queue", "serving"),
    _k("PATHWAY_SERVE_INFLIGHT_MB", "float", 32.0,
       "admission: max summed request-body bytes in flight; the bytes "
       "axis of the same budget as `PATHWAY_SERVE_INFLIGHT`", "serving"),
    _k("PATHWAY_SERVE_QUEUE", "int", 128,
       "admission: max requests waiting for an in-flight slot; overflow "
       "is answered 429 + Retry-After immediately (shed newest, never a "
       "stranded socket)", "serving"),
    _k("PATHWAY_SERVE_QUEUE_DELAY_MS", "float", 250.0,
       "load shedding: CoDel-style target queue delay — admission waits "
       "(or output staleness) sustained above this arm the shedder",
       "serving"),
    _k("PATHWAY_SERVE_SHED_DWELL_S", "float", 1.0,
       "load shedding: queue delay must stay above target this long "
       "before degraded mode engages (any dip resets the clock — the "
       "`ScaleController` hysteresis shape)", "serving"),
    _k("PATHWAY_SERVE_RECOVER_S", "float", 5.0,
       "load shedding: queue delay must stay back under target this "
       "long before degraded mode disengages", "serving"),
    _k("PATHWAY_SERVE_DRAIN_S", "float", 10.0,
       "graceful drain budget: on shutdown/live-handoff the webserver "
       "stops accepting (503) and waits up to this long for in-flight "
       "requests to complete before the handoff fence proceeds",
       "serving"),
    # -- generation serving (pathway_tpu/serving/) --------------------------
    _k("PATHWAY_GENERATE_SLOTS", "int", 8,
       "generation slot count — the fixed device batch width of the "
       "continuous decode step; finished rows free their slot every "
       "tick", "generate"),
    _k("PATHWAY_GENERATE_PAGE_SIZE", "int", 16,
       "tokens per KV page; KV memory is allocated and freed in pages, "
       "so footprint tracks live tokens instead of slots x max_cache",
       "generate"),
    _k("PATHWAY_GENERATE_PAGES", "int", 0,
       "KV pool size in pages (page 0 is the reserved null page); 0 "
       "auto-sizes to half the dense worst case, floored so one "
       "full-cache request always fits", "generate"),
    _k("PATHWAY_GENERATE_PREFILL_CHUNK", "int", 512,
       "the most prompt tokens one prefill program holds, i.e. the "
       "longest one prefill may hold up a decode tick; a waiting prompt "
       "is prefilled alone at the smallest width of a short ladder "
       "derived from this (512 -> 32 / 256 / 512) that covers what is "
       "left of it, and slots with no more left than the narrowest "
       "width share one program", "generate"),
    _k("PATHWAY_GENERATE_QUEUE", "int", 128,
       "max requests queued for a generation slot; overflow is "
       "answered 429 + Retry-After (page-pool exhaustion backpressures "
       "here, never an OOM)", "generate"),
    # -- device executor (pathway_tpu/device/) ------------------------------
    _k("PATHWAY_DEVICE_MAX_BATCH", "int", 512,
       "largest batch bucket of the DeviceExecutor's default bucketing "
       "policy (bigger batches split; smaller round up to powers of two)",
       "executor"),
    _k("PATHWAY_DEVICE_INFLIGHT_MB", "float", 256.0,
       "in-flight byte budget of the async device-dispatch queue; a full "
       "budget backpressures submitters (counted as "
       "`device.backpressure.s`)", "executor"),
    _k("PATHWAY_DEVICE_INFLIGHT_REQUESTS", "int", 64,
       "in-flight request budget of the async device-dispatch queue",
       "executor"),
    _k("PATHWAY_DEVICE_DONATE", "str", "auto",
       "donate padded input buffers to jitted device calls: `auto` "
       "(backends with donation support), `on`, `off`", "executor"),
    _k("PATHWAY_DEVICE_COST_ANALYSIS", "bool", True,
       "capture XLA cost_analysis/memory_analysis per compile-cache key "
       "(AOT compile path) feeding device.flops.total / "
       "device.utilization; `0` falls back to plain jit dispatch with "
       "uncosted accounting", "executor"),
    _k("PATHWAY_DEVICE_PEAK_FLOPS", "float", None,
       "per-device peak FLOP/s for the roofline utilization estimate "
       "(default: auto-detected from the device kind — an accelerator "
       "kind missing from the table is an error until this is set; the "
       "CPU rig gets a measured-peak default so the layer is testable "
       "without a TPU)",
       "executor"),
    _k("PATHWAY_DEVICE_TRACE_DIR", "str", None,
       "base directory for on-demand jax.profiler traces (`GET "
       "/trace?seconds=N` on the monitoring HTTP server, `pathway_tpu "
       "trace`); unset disables capture", "executor"),
    _k("PATHWAY_DEVICE_RESILIENCE", "bool", True,
       "device-path fault tolerance rail (typed failure classes, "
       "retries, OOM bucket ratchet, circuit breaker, quarantine); `0` "
       "reverts to raw PR-11 dispatch where any device error fails the "
       "caller", "executor"),
    _k("PATHWAY_DEVICE_RETRIES", "int", 2,
       "bounded retries for TRANSIENT device failures per dispatch "
       "(jittered exponential backoff, the shared udfs policy); compile "
       "failures and OOM are never retried at the same shape", "executor"),
    _k("PATHWAY_DEVICE_RETRY_DEADLINE_S", "float", 30.0,
       "wall-clock cap on one dispatch's whole retry affair — the retry "
       "loop must never outlast the freshness SLO it protects",
       "executor"),
    _k("PATHWAY_DEVICE_RETRY_BACKOFF_MS", "float", 50.0,
       "initial backoff before the first device retry (doubles per "
       "attempt, jittered by half the initial)", "executor"),
    _k("PATHWAY_DEVICE_BREAKER_THRESHOLD", "int", 5,
       "consecutive device failures (retries already spent) that trip a "
       "callable's circuit breaker OPEN — dispatches then route to the "
       "un-jitted host fallback (`device.breaker.state`, "
       "`device.fallback.*`)", "executor"),
    _k("PATHWAY_DEVICE_BREAKER_COOLDOWN_S", "float", 10.0,
       "open-breaker cooldown before one half-open probe is admitted "
       "back to the device (success closes, failure re-opens)",
       "executor"),
    _k("PATHWAY_DEVICE_DISPATCH_DEADLINE_S", "float", 0.0,
       "hard per-job dispatch deadline: a queued batch job running "
       "longer is failed with a typed hang error and the dispatch "
       "thread is respawned (`device.dispatch.restarts`); 0 disables "
       "hang escalation (long LLM-generation jobs use their own "
       "threads)", "executor"),
    _k("PATHWAY_DEVICE_QUARANTINE_KEEP", "int", 32,
       "poisoned-batch quarantine records retained per executor "
       "(newest kept; the total is still counted by "
       "`device.quarantine.batches`)", "executor"),
    # -- devices (parallel/mesh.py, internals/runner.py) --------------------
    _k("PATHWAY_JAX_DISTRIBUTED", "bool", False,
       "form a multi-host JAX device mesh too (`spawn "
       "--jax-distributed`): each process joins one global mesh",
       "devices"),
    _k("PATHWAY_DEVICE_COORDINATOR", "str", None,
       "host:port of the jax.distributed coordinator (default derived "
       "from worker 0's host and the mesh ports)", "devices"),
    # -- models / native kernels --------------------------------------------
    _k("PATHWAY_FUSED_ENCODER", "bool", True,
       "use the fused/packed encoder inference path", "models"),
    _k("PATHWAY_ENCODER_QUANTIZE", "str", None,
       "`int8` enables weight-only-quantized encoder inference", "models"),
    _k("PATHWAY_NATIVE", "bool", True,
       "`0` disables the native C++ kernels (numpy/python fallback)",
       "models"),
    _k("PATHWAY_COLUMNAR", "bool", True,
       "`0` forces every operator onto the row-wise reference evaluator "
       "(disables the columnar fast paths; see docs/columnar.md)",
       "models"),
    # -- CLI ----------------------------------------------------------------
    _k("PATHWAY_SPAWN_ARGS", "str", None,
       "arguments for `pathway_tpu spawn-from-env` (the k8s-operator "
       "hook)", "cli"),
)

ENV_REGISTRY: dict[str, EnvKnob] = {k.name: k for k in ENV_KNOBS}

_SUBSYSTEM_TITLES = (
    ("core", "Core runtime (`internals/config.py`)"),
    ("comm", "Worker mesh (`engine/comm.py`)"),
    ("faults", "Fault injection (`engine/faults.py`)"),
    ("metrics", "Metrics & telemetry (`engine/metrics.py`, `engine/telemetry.py`)"),
    ("tracing", "Request tracing & SLOs (`engine/tracing.py`, `engine/slo.py`)"),
    ("profiler", "Profiler & device accounting (`engine/profiler.py`)"),
    ("freshness", "Freshness & backpressure (`engine/freshness.py`)"),
    ("bench", "Benchmark harness (`benchmarks/harness.py`)"),
    ("persistence", "Persistence (`engine/persistence.py`)"),
    ("supervisor", "Supervisor (`engine/supervisor.py`)"),
    ("autoscaler", "Autoscaler (`engine/autoscaler.py`)"),
    ("serving", "Serving path (`engine/serving.py`, `io/http/`)"),
    ("generate", "Generation serving (`pathway_tpu/serving/`)"),
    ("executor", "Device executor (`pathway_tpu/device/`)"),
    ("devices", "Device mesh (`parallel/mesh.py`)"),
    ("models", "Models & native kernels"),
    ("cli", "CLI (`pathway_tpu/cli.py`)"),
)

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


def _knob(name: str) -> EnvKnob:
    knob = ENV_REGISTRY.get(name)
    if knob is None:
        raise KeyError(
            f"{name} is not a declared environment knob — add it to "
            "internals/config.py:ENV_KNOBS (name, type, default, doc) and "
            "regenerate docs/configuration.md"
        )
    return knob


def env_raw(name: str) -> str | None:
    """The raw environment value of a DECLARED knob (None when unset).
    For knobs whose parse is deliberately custom (e.g. the watchdog
    deadline's positive-float-or-off semantics)."""
    _knob(name)
    return os.environ.get(name)


def env_str(name: str, default: Any = ...) -> Any:
    knob = _knob(name)
    raw = os.environ.get(name)
    if raw is None:
        return knob.default if default is ... else default
    return raw


def env_bool(name: str, default: Any = ...) -> bool:
    knob = _knob(name)
    fallback = knob.default if default is ... else default
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        # empty = unset (the `PATHWAY_NATIVE=` shell idiom keeps the
        # default), matching env_int/env_float — NOT falsy
        return bool(fallback)
    v = raw.strip().lower()
    if v in _TRUTHY:
        return True
    if v in _FALSY:
        return False
    return bool(fallback)


def env_int(name: str, default: Any = ...) -> Any:
    knob = _knob(name)
    fallback = knob.default if default is ... else default
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return fallback
    try:
        return int(raw)
    except ValueError:
        return fallback


def env_float(name: str, default: Any = ...) -> Any:
    knob = _knob(name)
    fallback = knob.default if default is ... else default
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return fallback
    try:
        return float(raw)
    except ValueError:
        return fallback


def render_env_docs() -> str:
    """``docs/configuration.md``, generated.  The lint gate pins the file
    byte-identical to this render (rule ``env-docs-stale``)."""
    lines = [
        "# Configuration knobs",
        "",
        "<!-- GENERATED FILE — do not edit. -->",
        "<!-- Source: pathway_tpu/internals/config.py:ENV_KNOBS. -->",
        "<!-- Regenerate: pathway_tpu lint --update-config-docs -->",
        "",
        "Every `PATHWAY_*` environment variable the runtime reads, in one",
        "declared registry (`internals/config.py:ENV_KNOBS`).  Code reads",
        "these through typed accessors (`config.env_bool` / `env_int` /",
        "`env_float` / `env_str` / `env_raw`); `pathway_tpu lint` rejects",
        "direct `os.environ` reads (`env-direct-read`) and undeclared",
        "names (`env-undeclared`), so this page is complete by",
        "construction.",
        "",
    ]
    for key, title in _SUBSYSTEM_TITLES:
        knobs = [k for k in ENV_KNOBS if k.subsystem == key]
        if not knobs:
            continue
        lines.append(f"## {title}")
        lines.append("")
        lines.append("| Variable | Type | Default | Meaning |")
        lines.append("|---|---|---|---|")
        for k in knobs:
            default = "—" if k.default is None else repr(k.default)
            lines.append(
                f"| `{k.name}` | {k.kind} | `{default}` | {k.doc} |"
            )
        lines.append("")
    return "\n".join(lines)


def _env_bool(name: str, default: bool = False) -> bool:
    return env_bool(name, default)


def _env_int(name: str, default: int) -> int:
    return env_int(name, default)


@dataclasses.dataclass
class PathwayConfig:
    # mirrors PathwayConfig (internals/config.py:57-97)
    ignore_asserts: bool = dataclasses.field(
        default_factory=lambda: _env_bool("PATHWAY_IGNORE_ASSERTS")
    )
    runtime_typechecking: bool = dataclasses.field(
        default_factory=lambda: _env_bool("PATHWAY_RUNTIME_TYPECHECKING")
    )
    terminate_on_error: bool = dataclasses.field(
        default_factory=lambda: _env_bool("PATHWAY_TERMINATE_ON_ERROR", True)
    )
    replay_storage: str | None = dataclasses.field(
        default_factory=lambda: env_str("PATHWAY_REPLAY_STORAGE")
    )
    snapshot_access: str | None = dataclasses.field(
        default_factory=lambda: env_str("PATHWAY_SNAPSHOT_ACCESS")
    )
    persistence_mode: str | None = dataclasses.field(
        default_factory=lambda: env_str("PATHWAY_PERSISTENCE_MODE")
    )
    continue_after_replay: bool = dataclasses.field(
        default_factory=lambda: _env_bool("PATHWAY_CONTINUE_AFTER_REPLAY")
    )
    license_key: str | None = dataclasses.field(
        default_factory=lambda: env_str("PATHWAY_LICENSE_KEY")
    )
    monitoring_server: str | None = dataclasses.field(
        default_factory=lambda: env_str("PATHWAY_MONITORING_SERVER")
    )
    # worker topology (config.rs:88-120)
    threads: int = dataclasses.field(default_factory=lambda: _env_int("PATHWAY_THREADS", 1))
    processes: int = dataclasses.field(default_factory=lambda: _env_int("PATHWAY_PROCESSES", 1))
    process_id: int = dataclasses.field(default_factory=lambda: _env_int("PATHWAY_PROCESS_ID", 0))
    first_port: int = dataclasses.field(
        default_factory=lambda: _env_int("PATHWAY_FIRST_PORT", 10000)
    )
    # multi-host clusters: comma-separated hostname per worker id
    # (PATHWAY_PEER_HOSTS=pod-0.svc,pod-1.svc,...); empty = localhost mesh
    peer_hosts: list | None = dataclasses.field(
        default_factory=lambda: (
            [h.strip() for h in env_str("PATHWAY_PEER_HOSTS", "").split(",")]
            if env_str("PATHWAY_PEER_HOSTS")
            else None
        )
    )
    run_id: str | None = dataclasses.field(
        default_factory=lambda: env_str("PATHWAY_RUN_ID")
    )
    monitoring_http_port: int | None = dataclasses.field(
        default_factory=lambda: env_int("PATHWAY_MONITORING_HTTP_PORT")
    )

    @property
    def worker_count(self) -> int:
        return self.threads * self.processes


_config_var: ContextVar[PathwayConfig | None] = ContextVar("pathway_config", default=None)
_global_config: PathwayConfig | None = None


def get_config() -> PathwayConfig:
    cfg = _config_var.get()
    if cfg is not None:
        return cfg
    global _global_config
    if _global_config is None:
        _global_config = PathwayConfig()
    return _global_config


def refresh_config() -> None:
    global _global_config
    _global_config = PathwayConfig()


@contextlib.contextmanager
def local_pathway_config(**overrides: Any):
    base = get_config()
    cfg = dataclasses.replace(base, **overrides)
    token = _config_var.set(cfg)
    try:
        yield cfg
    finally:
        _config_var.reset(token)


def set_license_key(key: str | None) -> None:
    get_config().license_key = key


def set_monitoring_config(*, server_endpoint: str | None = None) -> None:
    get_config().monitoring_server = server_endpoint


def pathway_config() -> PathwayConfig:
    return get_config()
