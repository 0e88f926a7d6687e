"""Benchmark regression harness: run, fingerprint, baseline, compare.

Every perf PR so far recorded its numbers as hand-edited prose in
``RESULTS.md`` — invisible to CI, unverifiable later, and on a rig whose
throughput swings 2-3x between identical runs (see the telemetry-overhead
section there), silently rot-prone.  This harness makes the benchmark
suite machine-checkable:

* ``run_suite`` runs the existing ``host_*.py`` / ``telemetry_overhead.py``
  scripts (each already prints one JSON line per metric) in **smoke**
  (small sizes, minutes) or **full** (committed RESULTS-scale) mode,
  repeats them, and reports per-metric **medians + IQR** plus an
  environment fingerprint (python/jax/platform/cpu) so numbers are never
  compared across incomparable rigs silently.
* ``update_baseline`` commits the summary to ``benchmarks/baselines/
  <mode>.json``; ``compare`` checks a fresh run against it with
  **noise-tolerant thresholds**: each baseline metric carries the ratio
  past which it counts as a regression, widened automatically when the
  baseline itself was noisy (IQR/median > 25%).  Ratio-type metrics
  (speedups, overhead percentages) are intrinsically noise-immune and
  keep tight thresholds; wall-clock throughputs on this shared-tenant
  rig get wide ones.  The policy is documented in
  ``docs/benchmarking.md`` and pinned by ``tests/test_bench_harness.py``.
* ``update_results_md`` regenerates the harness tables in ``RESULTS.md``
  between ``<!-- bench:harness:... -->`` markers, so committed prose and
  committed baselines can never drift apart.

CLI: ``pathway_tpu bench [--smoke|--full] [--check] [--update-baselines]
[--update-results] [--only NAME] [--reps N]`` (``pathway_tpu/cli.py``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import platform
import statistics
import subprocess
import sys
import time
from typing import Any, Callable

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
DEFAULT_BASELINE_DIR = os.path.join(BENCH_DIR, "baselines")
RESULTS_MD = os.path.join(BENCH_DIR, "RESULTS.md")

# Threshold policy (docs/benchmarking.md).  higher-better metrics regress
# when current/baseline drops below min_ratio; lower-better when it rises
# above max_ratio.  A noisy baseline (IQR/median > NOISY_CV) widens both —
# this rig's wall-clock throughputs swing 2-3x between identical runs, so
# tight thresholds there would only produce alarm fatigue.
DEFAULT_MIN_RATIO = 0.4
DEFAULT_MAX_RATIO = 2.5
NOISY_CV = 0.25
NOISY_MIN_RATIO = 0.25
NOISY_MAX_RATIO = 4.0

_HIGHER_TOKENS = ("per_sec", "per_s", "speedup", "recall", "mfu")
_LOWER_TOKENS = ("_pct", "_ms", "_us", "cost", "latency", "_s")


class HarnessError(RuntimeError):
    pass


@dataclasses.dataclass(frozen=True)
class Bench:
    """One benchmark script and its per-mode argv."""

    name: str
    script: str
    smoke_args: tuple[str, ...]
    full_args: tuple[str, ...]
    in_smoke: bool = True
    timeout_s: int = 900


SUITE: tuple[Bench, ...] = (
    Bench("host_wordcount", "host_wordcount.py", ("50000",), ("1000000",)),
    Bench("host_churn", "host_churn.py", ("50000", "3"), ("500000", "5")),
    Bench("host_window", "host_window.py", ("50000",), ("300000",)),
    Bench("host_join", "host_join.py", ("50000",), ("300000",)),
    # groupby/reduce hot path: columnar group-index + bulk reducer updates
    # vs the row-wise oracle (single- and multi-column group keys)
    Bench("host_groupby", "host_groupby.py", ("50000",), ("300000",)),
    # end-to-end + microbench cost of the instrumentation itself; its
    # interleaved-rep protocol is slow, so full mode only
    Bench("telemetry_overhead", "telemetry_overhead.py", (), (), in_smoke=False),
    Bench(
        "profiler_overhead", "profiler_overhead.py", ("smoke",), (),
    ),
    Bench(
        "freshness_overhead", "freshness_overhead.py", ("smoke",), (),
    ),
    # elastic rescale: time-to-recover of a repartitioning (N -> N')
    # resume vs a same-topology one, plus its read amplification
    Bench(
        "rescale_recovery", "rescale_recovery.py", ("smoke",), ("full",),
    ),
    # autoscaler actuators: live shard-handoff downtime vs the restart
    # fallback (backoff + rollback + redo) on identical roots — the
    # handoff must stay measurably cheaper (handoff_speedup > 1)
    Bench(
        "rescale_handoff", "rescale_handoff.py", ("smoke",), ("full",),
    ),
    # DeviceExecutor: bucketed dispatch vs ad-hoc per-shape jit + the
    # epoch-thread overlap won by async dispatch
    Bench(
        "device_executor", "device_executor.py", ("smoke",), ("full",),
    ),
    # device observability: per-dispatch cost of the PR 12 accounting
    # rail (cost analysis, occupancy, padding, live bytes) vs the
    # metrics kill switch — the ≤2%-of-a-1ms-epoch pin
    Bench(
        "device_obs_overhead", "device_obs_overhead.py", ("smoke",), ("full",),
    ),
    # device fault tolerance: happy-path cost of the classify/retry/
    # breaker wrapper vs the PATHWAY_DEVICE_RESILIENCE kill switch
    # (≤2% of dispatch cost pin) + breaker trip→host-fallback latency
    Bench(
        "device_fault_recovery", "device_fault_recovery.py",
        ("smoke",), ("full",),
    ),
    # serving-path overload: protected (admission wall) vs unprotected
    # (PATHWAY_SERVE_ADMISSION=0) goodput + admitted p99 at ~3x the
    # admitted budget — protection_speedup > 1 is the PR 17 pin
    Bench(
        "serving_overload", "serving_overload.py", ("smoke",), ("full",),
    ),
    # continuous batching + paged KV on a Poisson churn trace: goodput,
    # TTFT and request latency of the generation scheduler
    Bench(
        "serving_generation", "serving_generation.py", ("smoke",), ("full",),
    ),
    # request tracing: per-request cost of the PR 19 span/exemplar rail
    # vs the PATHWAY_TRACE_REQUESTS kill switch — the ≤2%-of-a-5ms-
    # request pin
    Bench(
        "request_trace_overhead", "request_trace_overhead.py",
        ("smoke",), ("full",),
    ),
    # unplanned worker loss: warm-standby promotion (fence + promote
    # protocol + dead-shard-only replay) vs the restart-all fallback
    # (backoff + incarnation bump + full replay + full tail redo) on
    # identical roots — promote_speedup >= 5 is the standby chaos pin
    Bench(
        "failover_downtime", "failover_downtime.py", ("smoke",), ("full",),
    ),
)

MODE_REPS = {"smoke": 3, "full": 3}


def metric_direction(name: str) -> str:
    """'higher' (throughput/quality) or 'lower' (cost/latency) — which way
    is better for this metric.  Throughput tokens win first so
    ``telemetry_overhead_rows_per_sec`` stays higher-better even though
    the family name says overhead.  An unclassifiable name is a loud
    error, never a silent guess: defaulting would let a future cost
    metric's regressions read as improvements."""
    if any(tok in name for tok in _HIGHER_TOKENS):
        return "higher"
    if any(tok in name for tok in _LOWER_TOKENS):
        return "lower"
    raise HarnessError(
        f"cannot classify metric {name!r} as higher- or lower-better — "
        f"rename it to carry one of {_HIGHER_TOKENS + _LOWER_TOKENS} "
        "(see docs/benchmarking.md, 'Adding a benchmark')"
    )


def _child_env() -> dict[str, str]:
    """Environment of every child the harness starts: the suite is the
    CPU smoke harness unless the caller says otherwise."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env


_JAX_FINGERPRINT_CODE = (
    "import json, jax; d = jax.devices(); print(json.dumps({"
    "'jax': jax.__version__, 'jax_backend': jax.default_backend(), "
    "'jax_device_kind': str(d[0].device_kind), 'jax_device_count': len(d)}))"
)


def _jax_fingerprint() -> dict[str, Any]:
    """The JAX backend the benchmark children reach — asked of a child.

    The harness process itself must never initialise a backend: a chip
    belongs to one process at a time, so a parent that has called
    ``jax.devices()`` holds it and every benchmark child then fails or
    hangs.  The child gets the environment :func:`run_bench` gives them."""
    proc = subprocess.run(
        [sys.executable, "-c", _JAX_FINGERPRINT_CODE],
        capture_output=True, text=True, env=_child_env(), timeout=300,
    )
    if proc.returncode != 0:
        tail = "\n".join(proc.stderr.splitlines()[-15:])
        raise HarnessError(f"cannot fingerprint the JAX backend:\n{tail}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def environment_fingerprint() -> dict[str, Any]:
    """Where these numbers came from — compared (informationally) against
    the baseline's fingerprint so cross-rig comparisons are never silent."""
    cpu_model = ""
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    cpu_model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return {
        "python": platform.python_version(),
        **_jax_fingerprint(),
        "platform": platform.platform(),
        "machine": platform.machine(),
        "cpus": os.cpu_count() or 0,
        "cpu_model": cpu_model,
    }


def _parse_metric_lines(stdout: str) -> dict[str, float]:
    """{metric name: value} from a bench script's JSON-line protocol.
    Multi-mode scripts (telemetry_overhead prints one line per mode under
    the same metric name) get a ``.<mode>`` suffix."""
    out: dict[str, float] = {}
    for line in stdout.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        name = obj.get("metric")
        value = obj.get("value")
        if not isinstance(name, str) or not isinstance(value, (int, float)):
            continue
        if isinstance(obj.get("mode"), str):
            name = f"{name}.{obj['mode']}"
        out[name] = float(value)
    return out


def run_bench(bench: Bench, mode: str) -> dict[str, float]:
    """One subprocess run of one benchmark; returns its metrics."""
    args = bench.smoke_args if mode == "smoke" else bench.full_args
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH_DIR, bench.script), *args],
            capture_output=True,
            text=True,
            env=_child_env(),
            timeout=bench.timeout_s,
            cwd=REPO_ROOT,
        )
    except subprocess.TimeoutExpired as exc:
        raise HarnessError(
            f"benchmark {bench.name} exceeded its {bench.timeout_s} s "
            "timeout (hung or pathologically slow)"
        ) from exc
    if proc.returncode != 0:
        tail = "\n".join(proc.stderr.splitlines()[-15:])
        raise HarnessError(
            f"benchmark {bench.name} exited {proc.returncode}:\n{tail}"
        )
    metrics = _parse_metric_lines(proc.stdout)
    if not metrics:
        raise HarnessError(
            f"benchmark {bench.name} printed no metric lines"
        )
    return metrics


def _iqr(values: list[float]) -> float:
    if len(values) < 2:
        return 0.0
    q = statistics.quantiles(values, n=4, method="inclusive")
    return q[2] - q[0]


def run_suite(
    *,
    mode: str = "smoke",
    reps: int | None = None,
    only: list[str] | None = None,
    suite: tuple[Bench, ...] | None = None,
    echo: Callable[[str], Any] | None = None,
) -> dict[str, Any]:
    """Run the suite ``reps`` times and summarize medians + IQR."""
    if mode not in MODE_REPS:
        raise HarnessError(f"unknown mode {mode!r}")
    if reps is None:
        from pathway_tpu.internals.config import env_int

        reps = env_int("PATHWAY_BENCH_REPS") or MODE_REPS[mode]
    say = echo or (lambda _msg: None)
    all_benches = list(suite if suite is not None else SUITE)
    benches = [
        b
        for b in all_benches
        if (mode == "full" or b.in_smoke) and (not only or b.name in only)
    ]
    if only:
        known = {b.name for b in all_benches}
        unknown = set(only) - known
        if unknown:
            raise HarnessError(f"unknown benchmark(s): {sorted(unknown)}")
        unavailable = set(only) - {b.name for b in benches}
        if unavailable:
            raise HarnessError(
                f"benchmark(s) {sorted(unavailable)} are not part of "
                f"{mode} mode (run with --full)"
            )
    if not benches:
        raise HarnessError("no benchmarks selected")
    samples: dict[str, list[float]] = {}
    for rep in range(reps):
        for bench in benches:
            say(f"[bench] rep {rep + 1}/{reps}: {bench.name}")
            for name, value in run_bench(bench, mode).items():
                samples.setdefault(name, []).append(value)
    metrics = {
        name: {
            "median": statistics.median(values),
            "iqr": _iqr(values),
            "samples": values,
            "direction": metric_direction(name),
        }
        for name, values in sorted(samples.items())
    }
    return {
        "mode": mode,
        "created_at": time.time(),
        "reps": reps,
        "only": sorted(only) if only else None,
        "fingerprint": environment_fingerprint(),
        "metrics": metrics,
    }


# ---------------------------------------------------------------------------
# Baselines
# ---------------------------------------------------------------------------


def _baseline_dir(override: str | None = None) -> str:
    if override:
        return override
    try:
        from pathway_tpu.internals.config import env_str

        configured = env_str("PATHWAY_BENCH_BASELINE_DIR")
    except Exception:  # noqa: BLE001 - harness must run without the package
        configured = None
    return configured or DEFAULT_BASELINE_DIR


def baseline_entry(summary: dict[str, Any]) -> dict[str, Any]:
    """Baseline record for one metric summary, threshold chosen by the
    noise policy above."""
    median = summary["median"]
    noisy = bool(median) and (summary.get("iqr", 0.0) / abs(median)) > NOISY_CV
    entry = {
        "median": median,
        "iqr": summary.get("iqr", 0.0),
        "direction": summary["direction"],
    }
    if summary["direction"] == "higher":
        entry["min_ratio"] = NOISY_MIN_RATIO if noisy else DEFAULT_MIN_RATIO
    else:
        entry["max_ratio"] = NOISY_MAX_RATIO if noisy else DEFAULT_MAX_RATIO
    return entry


def update_baseline(
    results: dict[str, Any], *, baseline_dir: str | None = None
) -> str:
    """Write (or, for ``--only`` subset runs, MERGE into) the mode's
    baseline.  A subset run must never wipe the other benchmarks' entries
    — that would silently erase their regression coverage, since
    ``compare`` only iterates baseline metrics."""
    directory = _baseline_dir(baseline_dir)
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"{results['mode']}.json")
    metrics = {
        name: baseline_entry(summary)
        for name, summary in results["metrics"].items()
    }
    if results.get("only"):
        existing = load_baseline(results["mode"], baseline_dir=baseline_dir)
        if existing is not None:
            merged = dict(existing.get("metrics", {}))
            merged.update(metrics)
            metrics = merged
    payload = {
        "mode": results["mode"],
        "created_at": results["created_at"],
        "reps": results["reps"],
        "fingerprint": results["fingerprint"],
        "metrics": metrics,
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def load_baseline(
    mode: str, *, baseline_dir: str | None = None
) -> dict[str, Any] | None:
    """The committed baseline for ``mode``, or ``None`` when absent.  A
    PRESENT-but-unparseable file is a loud :class:`HarnessError` — silently
    treating a corrupt baseline as missing would skip the check."""
    path = os.path.join(_baseline_dir(baseline_dir), f"{mode}.json")
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except OSError:
        return None
    except ValueError as exc:
        raise HarnessError(
            f"baseline {path} is not valid JSON ({exc}) — fix or delete it"
        ) from exc


def compare(results: dict[str, Any], baseline: dict[str, Any]) -> dict[str, Any]:
    """Noise-tolerant regression check of ``results`` against ``baseline``.

    A baseline metric absent from the results is reported (``missing``)
    but only fails the check on an unfiltered run — ``--only`` subsets
    legitimately skip benches.  Fingerprint differences are reported,
    never fatal: a new rig needs new baselines, not a red gate.
    """
    regressions: list[dict[str, Any]] = []
    improvements: list[dict[str, Any]] = []
    missing: list[str] = []
    for name, base in baseline.get("metrics", {}).items():
        current = results["metrics"].get(name)
        if current is None:
            missing.append(name)
            continue
        base_median = base.get("median") or 0.0
        if not base_median:
            continue
        ratio = current["median"] / base_median
        direction = base.get("direction", metric_direction(name))
        record = {
            "metric": name,
            "current": current["median"],
            "baseline": base_median,
            "ratio": ratio,
            "direction": direction,
        }
        if direction == "higher":
            threshold = base.get("min_ratio", DEFAULT_MIN_RATIO)
            record["threshold"] = threshold
            if ratio < threshold:
                regressions.append(record)
            elif ratio > 1.0 / threshold:
                improvements.append(record)
        else:
            threshold = base.get("max_ratio", DEFAULT_MAX_RATIO)
            record["threshold"] = threshold
            if ratio > threshold:
                regressions.append(record)
            elif ratio < 1.0 / threshold:
                improvements.append(record)
    filtered = bool(results.get("only"))
    fingerprint_changed = sorted(
        key
        for key in set(results.get("fingerprint", {}))
        | set(baseline.get("fingerprint", {}))
        if results.get("fingerprint", {}).get(key)
        != baseline.get("fingerprint", {}).get(key)
    )
    return {
        "ok": not regressions and (filtered or not missing),
        "regressions": regressions,
        "improvements": improvements,
        "missing": missing,
        "filtered": filtered,
        "fingerprint_changed": fingerprint_changed,
        "mode": results.get("mode"),
    }


def render_report(report: dict[str, Any]) -> str:
    lines = []
    for reg in report["regressions"]:
        lines.append(
            f"REGRESSION {reg['metric']}: {reg['current']:g} vs baseline "
            f"{reg['baseline']:g} (ratio {reg['ratio']:.2f}, "
            f"{'min' if reg['direction'] == 'higher' else 'max'} "
            f"{reg['threshold']:.2f})"
        )
    for imp in report["improvements"]:
        lines.append(
            f"improved   {imp['metric']}: {imp['current']:g} vs baseline "
            f"{imp['baseline']:g} (ratio {imp['ratio']:.2f})"
        )
    for name in report["missing"]:
        lines.append(
            f"missing    {name}"
            + (" (subset run, not failing)" if report["filtered"] else "")
        )
    if report["fingerprint_changed"]:
        lines.append(
            "note: environment fingerprint differs from the baseline on "
            + ", ".join(report["fingerprint_changed"])
            + " — consider --update-baselines on this rig"
        )
    lines.append(
        f"[bench] {report['mode']}: "
        + ("OK" if report["ok"] else "REGRESSION DETECTED")
        + f" ({len(report['regressions'])} regression(s), "
        f"{len(report['improvements'])} improvement(s))"
    )
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# RESULTS.md regeneration
# ---------------------------------------------------------------------------


def render_results_table(results: dict[str, Any]) -> str:
    fp = results["fingerprint"]
    stamp = time.strftime("%Y-%m-%d", time.gmtime(results["created_at"]))
    lines = [
        f"Generated by `pathway_tpu bench --{results['mode']}` on {stamp} "
        f"({results['reps']} rep(s); python {fp.get('python')}, "
        f"jax {fp.get('jax')} on backend **{fp.get('jax_backend', '?')}** "
        f"({fp.get('jax_device_count', '?')}x "
        f"{fp.get('jax_device_kind', '?')}), {fp.get('cpus')} cpu(s)).  "
        "Medians with IQR; do not hand-edit between the markers.",
        "",
        "| metric | median | IQR | better |",
        "|---|---|---|---|",
    ]
    for name, summary in results["metrics"].items():
        lines.append(
            f"| `{name}` | {summary['median']:g} | {summary['iqr']:g} "
            f"| {summary['direction']} |"
        )
    return "\n".join(lines)


def update_results_md(
    results: dict[str, Any], *, path: str | None = None
) -> str:
    """Replace (or append) the generated block for this mode in RESULTS.md."""
    if results.get("only"):
        raise HarnessError(
            "refusing to regenerate the RESULTS.md table from an --only "
            "subset run — it would drop the other benchmarks' rows; run "
            "the full suite for the mode"
        )
    path = path or RESULTS_MD
    begin = f"<!-- bench:harness:{results['mode']}:begin -->"
    end = f"<!-- bench:harness:{results['mode']}:end -->"
    block = f"{begin}\n{render_results_table(results)}\n{end}"
    try:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    except OSError:
        text = "# Benchmark results\n"
    if begin in text and end in text:
        head, _, rest = text.partition(begin)
        _, _, tail = rest.partition(end)
        text = head + block + tail
    else:
        text = (
            text.rstrip("\n")
            + f"\n\n## Harness results ({results['mode']} mode)\n\n{block}\n"
        )
    with open(path, "w", encoding="utf-8") as f:
        f.write(text)
    return path


def write_results(results: dict[str, Any], path: str) -> str:
    with open(path, "w", encoding="utf-8") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def main() -> None:
    """Thin argv entry so the harness runs standalone too:
    ``python benchmarks/harness.py [smoke|full] [--check] ...`` — the full
    option surface lives on ``pathway_tpu bench``."""
    mode = "smoke"
    check = False
    update = False
    for arg in sys.argv[1:]:
        if arg in ("smoke", "full"):
            mode = arg
        elif arg == "--check":
            check = True
        elif arg == "--update-baselines":
            update = True
        else:
            raise SystemExit(f"unknown argument {arg!r}")
    try:
        _main_inner(mode, check, update)
    except HarnessError as exc:
        raise SystemExit(f"bench: {exc}") from exc


def _main_inner(mode: str, check: bool, update: bool) -> None:
    # mirrors the `pathway_tpu bench` CLI ordering: baseline loaded before
    # the suite runs (fail fast when missing) and before any update, and
    # a FAILING check skips the baseline write — the committed file must
    # never end up holding the regressed numbers
    prior = load_baseline(mode) if check else None
    if check and prior is None and not update:
        raise SystemExit(f"no baseline for mode {mode!r}")
    results = run_suite(mode=mode, echo=print)
    print(json.dumps(results["metrics"], indent=2, sort_keys=True))
    report = compare(results, prior) if check and prior is not None else None
    if report is not None and not report["ok"]:
        print(render_report(report))
        print("regression detected — baseline update skipped")
        raise SystemExit(1)
    if update:
        print(f"baseline written to {update_baseline(results)}")
    if check:
        if report is None:
            print("check: OK (bootstrap — baseline created by this run)")
        else:
            print(render_report(report))
        raise SystemExit(0)


if __name__ == "__main__":
    main()
