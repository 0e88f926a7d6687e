"""Device-path observability tests (ISSUE 12 tentpole).

Five property groups:

* **Cost accounting** — a golden utilization pin against a faked
  ``cost_analysis()`` dict (the arithmetic, isolated from jax), plus the
  real end-to-end path: CPU dispatches produce nonzero
  cost-analysis-derived flops/utilization in the collector gauges.
* **Padding/bucket efficiency** — waste pins across the bucket edge
  cases (batch-of-1, oversize split), occupancy histogram, and the
  ``suggest_buckets`` DP against hand-checked distributions.
* **HBM accounting** — the executor live-bytes fallback on a backend
  without ``memory_stats()`` (this rig).
* **Trace capture** — ``GET /trace`` + ``pathway_tpu trace`` round trip:
  a TensorBoard-viewable trace dir appears (skip-marked when
  ``jax.profiler`` is unavailable); unconfigured/busy paths give clean
  non-200s.
* **Surfaces** — ``/status`` device section, the ``pathway_tpu top``
  device panel, flight-recorder dumps carrying the device snapshot, and
  the ``blackbox``/``profile``/``buckets`` CLI renders (including the
  pre-PR-12 empty state).
"""

from __future__ import annotations

import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax.numpy as jnp

from pathway_tpu.device import (
    BucketPolicy,
    CostAccountant,
    DeviceExecutor,
    replay_waste,
    suggest_buckets,
)
from pathway_tpu.device import telemetry as dtel
from pathway_tpu.engine import metrics as em

HAVE_JAX_PROFILER = False
try:  # pragma: no branch - probe once at import
    import jax.profiler  # noqa: F401

    HAVE_JAX_PROFILER = hasattr(jax.profiler, "start_trace")
except Exception:  # noqa: BLE001 - absence is the skip condition
    pass


def _executor(max_bucket=8, name="rowsum"):
    ex = DeviceExecutor(collector_name=None)
    ex.register(
        name,
        lambda x: jnp.sum(x * x, axis=1),
        policy=BucketPolicy(max_bucket=max_bucket),
    )
    return ex


# --- cost accounting ---------------------------------------------------------


def test_golden_utilization_from_faked_cost_analysis(monkeypatch):
    """THE utilization arithmetic pin: a faked cost dict and pinned peak
    must produce exactly flops/(seconds*peak) — no jax involved."""
    monkeypatch.setenv("PATHWAY_DEVICE_PEAK_FLOPS", "1e9")
    acc = CostAccountant(registry=em.MetricsRegistry(enabled=True))
    assert acc.peak == 1e9 and acc.peak_source == "PATHWAY_DEVICE_PEAK_FLOPS"
    fake_cost = {"flops": 2_000_000.0, "bytes_accessed": 4096.0}
    acc.record_dispatch(fake_cost, duration_s=0.001)  # 2 GFLOP/s achieved
    acc.record_dispatch(fake_cost, duration_s=0.003)  # 1 GFLOP/s cumulative
    assert acc.achieved_flops_per_s() == pytest.approx(1e9)
    assert acc.utilization() == pytest.approx(1.0)
    snap = acc.snapshot()
    assert snap["flops_total"] == 4_000_000.0
    assert snap["bytes_accessed_total"] == 8192.0
    assert snap["costed_dispatches"] == 2
    assert snap["utilization"] == pytest.approx(1.0)
    # an uncosted dispatch dilutes achieved (its seconds count, its
    # unknown flops cannot) and is itself counted — never silent
    acc.record_dispatch(None, duration_s=0.004)
    assert acc.utilization() == pytest.approx(0.5)
    assert acc.snapshot()["uncosted_dispatches"] == 1


def test_extract_cost_flattens_cost_and_memory_analysis():
    class FakeMem:
        argument_size_in_bytes = 128
        output_size_in_bytes = 32
        temp_size_in_bytes = 16

    class FakeCompiled:
        def cost_analysis(self):
            return {"flops": 15.0, "bytes accessed": 150.0}

        def memory_analysis(self):
            return FakeMem()

    cost = dtel.extract_cost(FakeCompiled())
    assert cost["flops"] == 15.0 and cost["bytes_accessed"] == 150.0
    assert cost["argument_bytes"] == 128.0 and cost["temp_bytes"] == 16.0
    assert cost["analyzed"] == 1.0

    class FakeCompiledNoMemory:
        def cost_analysis(self):
            # XLA reports -1 for a cost it cannot model: never a negative total
            return {"flops": -1.0, "bytes accessed": 70.0}

        def memory_analysis(self):
            raise RuntimeError("backend keeps no memory analysis")

    cost = dtel.extract_cost(FakeCompiledNoMemory())
    assert cost["flops"] == 0.0 and cost["bytes_accessed"] == 70.0
    assert cost["argument_bytes"] == 0.0

    class FakeCompiledBroken:
        def cost_analysis(self):
            raise RuntimeError("no cost analysis on this backend")

    broken = dtel.extract_cost(FakeCompiledBroken())
    assert broken["flops"] == 0.0 and broken["analyzed"] == 0.0
    # ...and an unanalyzed cost counts as UNCOSTED, not a zero-FLOP
    # device: the accounting gap stays visible
    acc = CostAccountant(registry=em.MetricsRegistry(enabled=True))
    acc.record_dispatch(broken, duration_s=0.001)
    snap = acc.snapshot()
    assert snap["uncosted_dispatches"] == 1 and snap["costed_dispatches"] == 0


def test_real_dispatches_yield_nonzero_cost_derived_gauges():
    """ISSUE 12 acceptance: on the CPU rig, real cost_analysis() values
    flow end to end — flops total, achieved FLOP/s and utilization are
    all nonzero after a few dispatches."""
    ex = _executor()
    rng = np.random.default_rng(5)
    for n in (1, 3, 7):
        ex.run_batch("rowsum", (rng.normal(size=(n, 4)).astype(np.float32),))
    snap = ex.metrics_snapshot()
    assert snap["device.achieved.flops_per_s"] > 0.0
    assert snap["device.utilization"] > 0.0
    assert snap["device.peak.flops_per_s"] > 0.0
    cost = ex.device_snapshot()["cost"]
    assert cost["flops_total"] > 0.0
    assert cost["costed_dispatches"] == 3
    assert cost["uncosted_dispatches"] == 0


def test_cost_analysis_kill_switch_falls_back_to_uncosted(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_COST_ANALYSIS", "0")
    ex = _executor()
    out = ex.run_batch("rowsum", (np.ones((3, 4), np.float32),))
    assert out.shape == (3,)  # dispatch still works, via the jit path
    cost = ex.device_snapshot()["cost"]
    assert cost["costed_dispatches"] == 0
    assert cost["uncosted_dispatches"] == 1
    assert cost["flops_total"] == 0.0


def test_aot_compile_failure_is_a_counted_device_failure_not_a_jit_detour():
    """A failed AOT compile used to be swallowed: the dispatch retraced
    through the plain jit path and was served *uncosted*, silently.  Now
    the failure is classified and counted like any other device failure,
    and the key is un-claimed so its next dispatch compiles AOT again."""
    from pathway_tpu.device.resilience import InjectedDeviceError

    traces = []

    def flaky(x):
        traces.append(1)
        if len(traces) == 1:
            raise InjectedDeviceError("Mosaic failed to compile TPU kernel")
        return jnp.sum(x * x, axis=1)

    ex = DeviceExecutor(collector_name=None)
    ex.register("flaky", flaky, policy=BucketPolicy(max_bucket=8))
    rows = np.ones((3, 4), np.float32)
    # the rail serves the batch from the host fallback — visibly
    assert ex.run_batch("flaky", (rows,)).tolist() == [4.0, 4.0, 4.0]
    st = ex.resilience_stats("flaky")
    assert st["failures"] == {"compile": 1} and st["fallback_batches"] == 1
    assert ex.cache_keys("flaky") == set()
    assert ex.run_batch("flaky", (rows,)).tolist() == [4.0, 4.0, 4.0]
    cost = ex.device_snapshot()["cost"]
    assert cost["costed_dispatches"] == 1 and cost["uncosted_dispatches"] == 0
    assert ex.resilience_stats("flaky")["fallback_batches"] == 1


def test_peak_flops_table_and_cpu_default(monkeypatch):
    monkeypatch.delenv("PATHWAY_DEVICE_PEAK_FLOPS", raising=False)
    monkeypatch.setattr(dtel, "device_kind", lambda: "TPU v4")
    peak, source = dtel.peak_flops()
    assert peak == 275e12 and source == "tpu v4"
    monkeypatch.setattr(dtel, "device_kind", lambda: "cpu")
    peak, source = dtel.peak_flops()
    assert peak == dtel.CPU_PEAK_FLOPS_PER_CORE * (os.cpu_count() or 1)
    assert source.startswith("cpu-default")


def test_unknown_kind_on_a_non_cpu_platform_raises(monkeypatch):
    """A TPU the table does not know must not be rated against the CPU
    default (or any other part's peak): it is an error naming the kind
    and the knob that settles it."""
    monkeypatch.delenv("PATHWAY_DEVICE_PEAK_FLOPS", raising=False)
    monkeypatch.setattr(dtel, "device_kind", lambda: "TPU v9 mega")
    monkeypatch.setattr(dtel.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="tpu v9 mega.*PATHWAY_DEVICE_PEAK_FLOPS"):
        dtel.peak_flops()
    monkeypatch.setenv("PATHWAY_DEVICE_PEAK_FLOPS", "3e14")
    assert dtel.peak_flops() == (3e14, "PATHWAY_DEVICE_PEAK_FLOPS")


def test_accounting_respects_the_metrics_kill_switch():
    ex = _executor()
    em.set_enabled(False)
    try:
        ex.run_batch("rowsum", (np.ones((3, 4), np.float32),))
    finally:
        em.set_enabled(True)
    cost = ex.device_snapshot()["cost"]
    assert cost["costed_dispatches"] == 0 and cost["device_seconds"] == 0.0
    assert ex.device_snapshot()["cost"]["batch_sizes"] == {}


# --- padding / bucket efficiency ---------------------------------------------
# (the padding-waste pins across bucket edge cases live next to the other
# bucket-edge tests in tests/test_device_executor.py)


def test_batch_size_distribution_is_recorded_and_bounded():
    ex = _executor(max_bucket=8)
    for n in (3, 3, 3, 5):
        ex.run_batch("rowsum", (np.ones((n, 4), np.float32),))
    sizes = ex.device_snapshot()["cost"]["batch_sizes"]
    assert sizes == {"3": 3, "5": 1}
    acc = CostAccountant(registry=em.MetricsRegistry(enabled=True))
    for n in range(dtel.MAX_DISTINCT_BATCH_SIZES + 10):
        acc.record_batch(n + 1)
    assert len(acc.batch_sizes) == dtel.MAX_DISTINCT_BATCH_SIZES
    assert acc.batch_size_overflow == 10  # counted, never silently dropped


def test_suggest_buckets_beats_pow2_on_a_skewed_distribution():
    # 100 batches of 33 rows: pow2 rounds every one up to 64
    counts = {33: 100, 1: 5}
    pow2_pad, real = replay_waste(counts, (1, 2, 4, 8, 16, 32, 64))
    assert pow2_pad == 31 * 100  # 33 -> 64 every time
    suggested = suggest_buckets(counts, max_buckets=4)
    assert 33 in suggested
    s_pad, s_real = replay_waste(counts, suggested)
    assert s_real == real and s_pad == 0
    # the DP prefers the smallest set reaching the optimum
    assert suggested == (1, 33)


def test_suggest_buckets_respects_the_budget_and_largest_size():
    counts = {2: 10, 7: 10, 15: 10, 100: 1}
    suggested = suggest_buckets(counts, max_buckets=2)
    assert len(suggested) == 2 and suggested[-1] == 100
    with pytest.raises(ValueError):
        suggest_buckets({}, max_buckets=4)


def test_replay_waste_splits_oversize_batches_like_the_planner():
    # 20 rows over largest bucket 8: chunks 8+8+4 → remainder bucket 4,
    # zero waste; 19 rows → remainder 3 pads to 4 (1 row)
    assert replay_waste({20: 1}, (4, 8)) == (0, 20)
    assert replay_waste({19: 1}, (4, 8)) == (1, 19)


# --- HBM fallback -------------------------------------------------------------


def test_hbm_fallback_tracks_live_dispatch_footprint(monkeypatch):
    # this rig has no allocator stats — force the executor fallback even
    # if a future backend grows memory_stats()
    monkeypatch.setattr(dtel, "hbm_stats", lambda: None)
    ex = _executor()
    ex.run_batch("rowsum", (np.ones((8, 4), np.float32),))
    hbm = ex._hbm_snapshot()
    assert hbm["source"] == "executor"
    assert hbm["bytes_in_use"] == 0.0  # nothing in flight now
    # peak covers the dispatched footprint: >= the 8x4 f32 argument
    assert hbm["peak"] >= 8 * 4 * 4
    snap = ex.metrics_snapshot()
    assert snap["device.hbm.peak"] == hbm["peak"]
    assert "device.hbm.bytes_in_use" in snap


def test_hbm_memory_stats_path_wins_when_available(monkeypatch):
    monkeypatch.setattr(
        dtel, "hbm_stats", lambda: {"bytes_in_use": 123.0, "peak": 456.0}
    )
    ex = _executor()
    hbm = ex._hbm_snapshot()
    assert hbm == {"bytes_in_use": 123.0, "peak": 456.0,
                   "source": "memory_stats"}


# --- trace capture ------------------------------------------------------------


@pytest.mark.skipif(not HAVE_JAX_PROFILER, reason="jax.profiler unavailable")
def test_trace_endpoint_and_cli_round_trip(tmp_path, monkeypatch):
    """ISSUE 12 satellite: GET /trace and `pathway_tpu trace` both leave
    a TensorBoard-viewable trace dir under PATHWAY_DEVICE_TRACE_DIR."""
    from click.testing import CliRunner

    from pathway_tpu.cli import cli
    from pathway_tpu.engine.http_server import MonitoringServer

    monkeypatch.setenv("PATHWAY_DEVICE_TRACE_DIR", str(tmp_path))
    server = MonitoringServer(
        port=0, run_id="r-trace", registry=em.MetricsRegistry(enabled=True)
    ).start()
    try:
        port = server._httpd.server_address[1]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/trace?seconds=0.05"
        ) as r:
            payload = json.loads(r.read())
        trace_dir = payload["trace_dir"]
        assert os.path.isdir(trace_dir)
        assert any(files for _, _, files in os.walk(trace_dir))
        result = CliRunner().invoke(
            cli,
            ["trace", "--seconds", "0.05",
             "--url", f"http://127.0.0.1:{port}/trace"],
        )
        assert result.exit_code == 0, result.output
        assert "trace written to" in result.output
        assert "tensorboard --logdir" in result.output
    finally:
        server.close()
    # two captures happened; both counted
    reg_val = em.get_registry().scalar_metrics().get("device.trace.captures")
    assert reg_val is not None and reg_val >= 2.0


def test_trace_endpoint_unconfigured_is_a_clean_503(monkeypatch):
    from pathway_tpu.engine.http_server import MonitoringServer

    monkeypatch.delenv("PATHWAY_DEVICE_TRACE_DIR", raising=False)
    server = MonitoringServer(
        port=0, run_id="r-no-trace", registry=em.MetricsRegistry(enabled=True)
    ).start()
    try:
        port = server._httpd.server_address[1]
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/trace?seconds=0.01")
        assert err.value.code == 503
        assert "PATHWAY_DEVICE_TRACE_DIR" in json.loads(err.value.read())["error"]
        # malformed duration: 400, not a traceback
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/trace?seconds=nope")
        assert err.value.code == 400
    finally:
        server.close()


def test_trace_cli_unreachable_endpoint_exits_cleanly():
    from click.testing import CliRunner

    from pathway_tpu.cli import cli

    result = CliRunner().invoke(
        cli, ["trace", "--seconds", "0.01", "--url", "http://127.0.0.1:1/trace"]
    )
    assert result.exit_code == 1
    assert "cannot reach" in result.output


def test_capture_trace_requires_a_dir(monkeypatch):
    monkeypatch.delenv("PATHWAY_DEVICE_TRACE_DIR", raising=False)
    from pathway_tpu.device import TraceUnavailable, capture_trace

    with pytest.raises(TraceUnavailable, match="PATHWAY_DEVICE_TRACE_DIR"):
        capture_trace(0.01)


# --- surfaces: /status, top, flight recorder, CLIs ---------------------------


def _device_status_payload():
    """A /status-shaped payload with a device section (render pins)."""
    return {
        "run_id": "r-dev",
        "epochs": 10,
        "backlog": {
            "backlog.device.queue": 2.0,
            "backlog.device.bytes": 4096.0,
            "backlog.device.age.s": 0.25,
        },
        "device": {
            "device.dispatch.batches": 20.0,
            "device.dispatch.rows": 512.0,
            "device.dispatch.ms.p95": 1.5,
            "device.cache.cold": 0.0,
            "device.warmup.compiles": 7.0,
            "jax.compile.count": 7.0,
            "jax.cache.miss": 7.0,
            "device.padding.waste.fraction": 0.125,
            "device.padding.waste.rows": 64.0,
            "device.utilization": 0.42,
            "device.peak.flops_per_s": 275e12,
            "device.achieved.flops_per_s": 115.5e12,
            "device.hbm.bytes_in_use": 2.0 * (1 << 20),
            "device.hbm.peak": 3.0 * (1 << 20),
        },
    }


def test_render_top_device_panel():
    from pathway_tpu.internals.top import render_top

    prev = {"epochs": 0, "device": {"device.dispatch.batches": 10.0}}
    out = render_top(_device_status_payload(), prev=prev, interval_s=2.0)
    assert "device" in out
    assert "dispatch 20 batch(es) (5.0/s)" in out
    assert "queue 2 job(s)" in out
    assert "cache: cold 0 / warmed 7" in out
    assert "jit 7 compile(s) / 7 cache miss(es)" in out
    assert "padding waste 12.5% (64 pad row(s))" in out
    assert "utilization 42.00%" in out
    assert "hbm 2.0 MiB in use · peak 3.0 MiB" in out
    # a pre-PR-12 server payload renders without the panel
    assert "device" not in render_top({"epochs": 1})


def test_status_endpoint_serves_the_device_section():
    from pathway_tpu.engine.http_server import MonitoringServer
    from pathway_tpu.engine.probes import ProberStats

    reg = em.MetricsRegistry(enabled=True)
    reg.counter("device.dispatch.batches", "").inc(4)
    reg.gauge("device.utilization", "").set(0.25)
    reg.gauge("device.hbm.bytes_in_use", "").set(1024.0)
    reg.gauge("device.padding.waste.fraction", "").set(0.5)
    server = MonitoringServer(port=0, run_id="r-ds", registry=reg).start()
    try:
        port = server._httpd.server_address[1]
        server.update(ProberStats(epochs=1))
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/status") as r:
            payload = json.loads(r.read())
    finally:
        server.close()
    assert payload["device"]["device.dispatch.batches"] == 4.0
    assert payload["device"]["device.utilization"] == 0.25
    assert payload["device"]["device.hbm.bytes_in_use"] == 1024.0
    assert payload["device"]["device.padding.waste.fraction"] == 0.5


def test_flight_recorder_dump_carries_device_snapshot(tmp_path):
    from pathway_tpu.engine.flight_recorder import FlightRecorder

    ex = _executor()
    ex.run_batch("rowsum", (np.ones((3, 4), np.float32),))
    rec = FlightRecorder()
    rec.configure(root=str(tmp_path), worker=0, run_id="r-fr")
    rec.set_device_supplier(ex.device_snapshot)
    rec.record("epoch", time_=1)
    path = rec.dump("test: device snapshot rides the dump")
    assert path is not None
    with open(path) as f:
        payload = json.load(f)
    device = payload["device"]
    assert device["cost"]["flops_total"] > 0.0
    assert device["padding"]["real_rows"] == 3.0
    assert "hbm" in device and "queue" in device
    assert device["callables"]["rowsum"]["dispatches"] == 1


def test_blackbox_cli_renders_device_section_and_empty_state(tmp_path):
    from click.testing import CliRunner

    from pathway_tpu.cli import cli
    from pathway_tpu.engine.flight_recorder import FlightRecorder

    ex = _executor()
    ex.run_batch("rowsum", (np.ones((5, 4), np.float32),))
    rec = FlightRecorder()
    rec.configure(root=str(tmp_path), worker=0, run_id="r-bb")
    rec.set_device_supplier(ex.device_snapshot)
    rec.record("epoch", time_=1)
    assert rec.dump("crash with device story") is not None
    # a pre-PR-12 dump: same root, no device key
    rec2 = FlightRecorder()
    rec2.configure(root=str(tmp_path), worker=1, run_id="r-bb")
    rec2.record("epoch", time_=1)
    assert rec2.dump("crash without device story") is not None

    result = CliRunner().invoke(cli, ["blackbox", str(tmp_path)])
    assert result.exit_code == 0, result.output
    assert "device:" in result.output
    assert "utilization" in result.output
    assert "padding waste" in result.output
    # the dump without a device key gets the explicit empty state
    assert "(no snapshot in this dump)" in result.output


def test_buckets_cli_from_dump_root_and_live_status(tmp_path):
    from click.testing import CliRunner

    from pathway_tpu.cli import cli
    from pathway_tpu.engine.flight_recorder import FlightRecorder
    from pathway_tpu.engine.http_server import MonitoringServer
    from pathway_tpu.engine.probes import ProberStats

    ex = _executor(max_bucket=64, name="bkt")
    rng = np.random.default_rng(9)
    for _ in range(20):
        ex.run_batch("bkt", (rng.normal(size=(33, 4)).astype(np.float32),))
    rec = FlightRecorder()
    rec.configure(root=str(tmp_path), worker=0, run_id="r-bkt")
    rec.set_device_supplier(ex.device_snapshot)
    assert rec.dump("bucket distribution dump") is not None

    runner = CliRunner()
    result = runner.invoke(cli, ["buckets", "--json", str(tmp_path)])
    assert result.exit_code == 0, result.output
    report = json.loads(result.output)
    assert report["batches"] == 20 and report["largest"] == 33
    assert 33 in report["suggested"]["buckets"]
    assert report["suggested"]["pad_rows"] < report["current"]["pad_rows"]

    # live path: the device.batch.rows{rows=N} gauges feed the same DP
    reg = em.MetricsRegistry(enabled=True)
    reg.gauge("device.batch.rows", "", rows=33).set(20.0)
    server = MonitoringServer(port=0, run_id="r-live", registry=reg).start()
    try:
        port = server._httpd.server_address[1]
        server.update(ProberStats(epochs=1))
        result = runner.invoke(
            cli,
            ["buckets", "--url", f"http://127.0.0.1:{port}/status"],
        )
    finally:
        server.close()
    assert result.exit_code == 0, result.output
    assert "suggested buckets" in result.output

    # an empty root: clean non-zero, never a traceback
    empty = tmp_path / "empty"
    empty.mkdir()
    result = runner.invoke(cli, ["buckets", str(empty)])
    assert result.exit_code == 1
    assert "no batch-size distribution" in result.output


def test_render_device_snapshot_best_effort_on_partial_payloads():
    from pathway_tpu.device import render_device_snapshot

    assert "(no device activity recorded)" in render_device_snapshot({})
    out = render_device_snapshot(
        {"cost": {"utilization": 0.5, "peak_flops_per_s": 1e12,
                  "achieved_flops_per_s": 5e11, "flops_total": 1e9,
                  "bytes_accessed_total": 1e6, "costed_dispatches": 3}}
    )
    assert "utilization 50.00%" in out
