"""The device trace joined to the program's host timeline
(``chipbench/trace_join.py``, ``chipbench/readings_idle.py``): the clock
checked from causality, each idle gap named by precedence, the idle
seconds by name, the device's wait between decode steps in flight and the
operations by program and scope, on traces and timelines built by hand;
and the two metric files that read the new tick phases."""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from chipbench import manifest, readings_idle, trace_join  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
T0 = 1_700_000_000.0  # seconds on the host's wall clock
MS = 1e-3
US = 1e-3  # in ms: a double holds the wall clock to a fifth of a microsecond


def record(name: str, start: float, end: float, track: str = "sched") -> dict:
    return {"track": track, "name": name, "start": T0 + start, "end": T0 + end, "attributes": {}}


def ticks(n: int = 4) -> list[dict]:
    """``n`` decode ticks 5 ms apart, each sending one step and reading the
    one before, then a tick that reads the last; one device.inflight."""
    records = [record("device.inflight", 0.9 * MS, (5 * n + 2.2) * MS)]
    for i in range(n + 1):
        at = 5 * i * MS
        records.append(record("tick.admit", at, at + 0.2 * MS))
        if i < n:
            records += [
                record("tick.decode.prepare", at + 0.2 * MS, at + 0.5 * MS),
                record("tick.decode.upload", at + 0.5 * MS, at + 1.0 * MS),
                record("tick.decode.enqueue", at + 1.0 * MS, at + 1.5 * MS),
            ]
            sync = (at + 1.5 * MS, at + 2.0 * MS)
        else:
            sync = (at + 0.2 * MS, at + 2.0 * MS)
        if i:  # the first tick has nothing in flight to read
            records.append(record("tick.decode.sync", *sync))
        records.append(record("tick.deliver", at + 2.0 * MS, at + 2.3 * MS))
    return records


def event(name: str, start_s: float, end_s: float, shift_s: float = 0.0, stats=None) -> tuple:
    return (name, (T0 + start_s + shift_s) * 1e9, (end_s - start_s) * 1e9, stats or {})


def planes(modules: list[tuple], ops: list[tuple] | None = None, span=(0.0, 30 * MS)) -> list[dict]:
    lines = [{"name": "XLA Modules", "events": modules}]
    if ops is not None:
        lines.append({"name": "XLA Ops", "events": ops})
    env = {"profile_start_time": (T0 + span[0]) * 1e9, "profile_stop_time": (T0 + span[1]) * 1e9}
    return [
        {"name": "/host:CPU", "stats": {}, "lines": []},
        {"name": "/device:TPU:0", "stats": {}, "lines": lines},
        {"name": "Task Environment", "stats": env, "lines": []},
    ]


def decode_trace(shift_s: float = 0.0, n: int = 4, span=(0.0, 30 * MS)) -> list[dict]:
    """Step ``i`` runs 1.2 to 5.8 ms after its tick starts, in two
    operations with half a millisecond between them; the device clock
    ``shift_s`` ahead of the host's."""
    modules, ops = [], []
    for i in range(n):
        at = 5 * i * MS
        modules.append(event("jit__decode(77)", at + 1.2 * MS, at + 5.8 * MS, shift_s))
        ops += [
            event("%fusion.1 = bf16[8] fusion(x)", at + 1.2 * MS, at + 3.2 * MS, shift_s),
            event("%fusion.2 = bf16[8] fusion(y)", at + 3.7 * MS, at + 5.8 * MS, shift_s),
        ]
    return planes(modules, ops, span)


def test_decode_reads_pair_each_step_with_the_read_a_tick_later():
    steps = trace_join.decode_reads(ticks())
    assert len(steps) == 4
    for i, (sent, read) in enumerate(steps):
        assert sent == pytest.approx((T0 + 5 * i * MS + 1.0 * MS) * 1e9, abs=1e3)
        assert read == pytest.approx((T0 + 5 * (i + 1) * MS + 2.0 * MS) * 1e9, abs=1e3)


@pytest.mark.parametrize("shift_ms,joins", [
    (0.0, True),
    (-0.25, True),   # the bounds miss 0 by 50 us: inside the tolerance
    (-1.25, False),  # by 1.05 ms
    (5.0, False),    # the device's clock 5 ms ahead: the bounds lie below 0
])
def test_offset_bounds_from_causality_and_the_unaligned_case(shift_ms, joins):
    records = ticks()
    times = trace_join.device_times(decode_trace(shift_ms * MS))
    bounds = trace_join.offset_bounds(times, records)
    # each run starts >= 0.2 ms after its enqueue began and ends 1.2 ms
    # before its read returns: host - device in [-0.2, 1.2] ms, less the shift
    assert bounds["lo_ns"] == pytest.approx((-0.2 - shift_ms) * 1e6, abs=1e3)
    assert bounds["hi_ns"] == pytest.approx((1.2 - shift_ms) * 1e6, abs=1e3)
    assert (bounds["runs"], bounds["shift"], bounds["candidates"]) == (4, 0, 1)
    offset = trace_join.join_offset(bounds)
    assert offset == (0.0 if joins else None)
    named = trace_join.name_gaps(times[0]["gaps"], records, offset)
    if joins:
        assert trace_join.UNALIGNED not in named
        assert trace_join.decode_gap_ms(times, records, offset) is not None
    else:  # every gap, and nothing joined
        assert list(named) == [trace_join.UNALIGNED]
        assert trace_join.held_idle_pct(named, 0.03) is None
        assert trace_join.decode_gap_ms(times, records, offset) is None
        # asked for by name at an offset the bounds allow, the join reads
        # what it reads on a shared clock
        inside = -shift_ms * 1e6
        assert bounds["lo_ns"] <= inside <= bounds["hi_ns"]
        wide = (-10 * MS, 40 * MS)  # the gaps between the steps, not the session's edges
        shifted = trace_join.device_times(decode_trace(shift_ms * MS, span=wide))[0]["gaps"][1:-1]
        plain = trace_join.device_times(decode_trace(span=wide))[0]["gaps"][1:-1]
        assert trace_join.name_gaps(shifted, records, inside) == pytest.approx(
            trace_join.name_gaps(plain, records), abs=1e-6
        )


def test_no_bounds_where_the_trace_has_more_steps_than_the_timeline():
    times = trace_join.device_times(decode_trace(n=4))
    assert trace_join.offset_bounds(times, ticks(2)) is None
    assert trace_join.join_offset(None) is None


def test_gaps_are_named_by_precedence_where_intervals_overlap():
    records = [
        record("serve.idle", 0.0, 1.0, "serve"),
        record("sched.idle", 0.0, 3.0),
        record("tick.admit", 2.0, 4.0),
        record("epoch.run", 3.0, 8.0, "engine"),
        record("epoch.async_wait", 5.0, 6.0, "engine"),  # inside its epoch's run
        record("device.call", 7.0, 9.0, "executor"),
        record("sched.idle", 8.5, 9.5),
        record("device.inflight", 0.0, 10.0),  # names no gap
    ]
    gaps = [((T0 + a) * 1e9, (T0 + b) * 1e9) for a, b in ((0.0, 6.5), (6.5, 10.0))]
    named = trace_join.name_gaps(gaps, records)
    assert named == pytest.approx({
        "serve.idle": 1.0, "sched.idle": 1.5, "tick.admit": 2.0, "epoch.async_wait": 1.0,
        "epoch.run": 3.0, "device.call": 1.0, "unattributed": 0.5,
    }, abs=1e-6)


def test_idle_gaps_are_seconds_summed_by_name_largest_first():
    named = {f"tick.phase{i}": float(i) for i in range(12)}
    assert trace_join.idle_gaps(named) == [[f"tick.phase{i}", float(i)] for i in range(11, 1, -1)]
    # a mean over the device planes
    assert trace_join.idle_gaps({"serve.idle": 3.0, "unattributed": 1.0}, planes=2) == [
        ["serve.idle", 1.5], ["unattributed", 0.5]
    ]
    assert trace_join.held_idle_pct({"serve.idle": 20.0, "tick.admit": 1.0, "unattributed": 0.5}, 30.0) \
        == pytest.approx(5.0)


def test_decode_gap_is_read_only_inside_one_device_inflight():
    modules = [
        event("jit__decode(1)", 0.0, 4 * MS), event("jit__prefill(2)", 4.2 * MS, 4.6 * MS),
        event("jit__decode(1)", 5 * MS, 9 * MS),
        # the device drained between these two: no wait of the host's
        event("jit__decode(1)", 20 * MS, 24 * MS), event("jit__decode(1)", 26 * MS, 29 * MS),
    ]
    records = [record("device.inflight", -1 * MS, 10 * MS), record("device.inflight", 19 * MS, 30 * MS)]
    times = trace_join.device_times(planes(modules))
    # 1 ms between the first two less the prefill's 0.4, then 2 ms
    assert trace_join.decode_gap_ms(times, records) == pytest.approx((0.6 + 2.0) / 2, abs=US)
    assert trace_join.decode_gap_ms(times, records[:1]) == pytest.approx(0.6, abs=US)
    assert trace_join.decode_gap_ms(times, []) is None


def test_device_events_counted_from_the_session_start_are_put_on_the_epoch():
    relative = planes([("jit__decode(1)", 2e6, 3e6, {})], span=(10.0, 10.03))
    assert trace_join.epoch_shift_ns(relative) == (T0 + 10.0) * 1e9
    (plane,) = trace_join.device_times(relative)
    assert plane["runs"] == [("jit__decode", (T0 + 10.0) * 1e9 + 2e6, (T0 + 10.0) * 1e9 + 5e6)]
    # before the first and after the last event, within the session
    assert [round((b - a) / 1e6, 3) for a, b in plane["gaps"]] == [2.0, 25.0]
    absolute = decode_trace()
    assert trace_join.epoch_shift_ns(absolute) == 0.0


def test_a_tick_is_read_from_one_read_to_the_next_while_in_flight():
    records = ticks()
    got = trace_join.tick_phases_ms(records, T0, T0 + 1.0)
    # reads return 5 ms apart; the first tick's read is the first there is
    assert got["tick"] == pytest.approx(5.0, abs=US)
    # the last tick sends nothing and waits 1.8 ms for its read
    assert got["tick.decode.upload"] == pytest.approx(1.0 / 3, abs=US)
    assert got["tick.decode.sync"] == pytest.approx((0.5 + 0.5 + 1.8) / 3, abs=US)
    # 2.7 ms between one tick's delivery and the next tick's admission
    assert got["between ticks"] == pytest.approx(2.7, abs=US)
    assert trace_join.tick_phases_ms(records, T0 + 1.0, T0 + 2.0) == {}


def test_device_ops_by_program_and_scope():
    stats = {"tf_op": "jit(_decode)/jit(main)/while/body/moe.experts/dot_general", "flops": 5}
    modules = [event("jit__decode(1)", 0.0, 4 * MS), event("jit__prefill(2)", 5 * MS, 9 * MS),
               event("jit__decode(1)", 10 * MS, 14 * MS)]
    ops = [
        event("%while.3 = (s32[]) while(p)", 0.0, 4 * MS),  # holds its children
        event("%dot.7 = bf16[8,64] dot(a, b)", 0.0, 1 * MS, stats=stats),
        event("%fusion.9 = bf16[8,64] fusion(c)", 1 * MS, 4 * MS),
        event("%dot.7 = bf16[8,64] dot(a, b)", 10 * MS, 11 * MS),  # stats read once a name
        event("%fusion.4 = bf16[512,64] fusion(d)", 5 * MS, 9 * MS),
        # the scope from the HLO line's own metadata
        event('%fusion.5 = bf16[512,64] fusion(e), metadata={op_name="jit(_prefill)/jit(main)/ssm.scan/mul"}',
              5 * MS, 6 * MS),
    ]
    got = trace_join.device_ops(planes(modules, ops))
    assert got[0][0] == "jit__prefill/%fusion.4 bf16[512,64] fusion"
    assert got[0][1:] == [pytest.approx(4 * MS), 1]
    assert got[1][0] == "jit__decode/%fusion.9 bf16[8,64] fusion"
    assert got[2] == ["jit__decode/moe.experts", pytest.approx(2 * MS), 2]
    assert got[3] == ["jit__prefill/ssm.scan", pytest.approx(1 * MS), 1]
    assert len(got) == 4
    assert trace_join.op_stat_keys(planes(modules, ops), n=2)[1]["tf_op"] == stats["tf_op"]


def test_the_script_joins_a_traced_run_built_by_hand():
    kept = {"planes": decode_trace(), "records": ticks() + [record("serve.idle", 21 * MS, 40 * MS, "serve")]}
    line = {"metrics": {"sched.decode_tick_ms": {"value": 5.0, "unit": "ms"}}}
    got = readings_idle.join(kept, line, trace_start_s=2.0)
    assert got["offset_bounds_us"] == pytest.approx([-200.0, 1200.0], abs=1.0)
    assert got["window_s"] == pytest.approx(0.03)
    # 30 ms: four steps of 4.1 ms busy; idle 0.4 ms between steps and
    # 0.5 ms inside each, 1.2 ms before the first and 9.2 ms after the last
    assert got["idle_s"] == pytest.approx(0.0136, abs=1e-6)
    joined = got["joined"]
    assert joined["offset_us"] == 0.0
    names = dict(joined["idle_gaps"])
    assert names["serve.idle"] == pytest.approx(0.009, abs=1e-6)
    assert sum(names.values()) == pytest.approx(got["idle_s"], abs=1e-6)
    assert joined["device.gap_ms.decode"] == pytest.approx(0.4, abs=US)
    assert [at["offset_us"] for at in got["at_bounds"]] == pytest.approx([-200.0, 1200.0], abs=1.0)
    assert got["phases_ms_per_sync"]["tick.decode.upload"] == pytest.approx(0.5, abs=US)
    assert got["phases_ms_per_sync"]["tick.decode.enqueue"] == pytest.approx(0.5, abs=US)
    assert got["tick_phases_ms"]["tick"] == pytest.approx(5.0, abs=US)
    assert got["ring"]["oldest_s"] == pytest.approx(2.0)
    assert got["decode_runs"] == 4 and got["decode_run_ms"] == pytest.approx(4.6, abs=US)
    # a device clock 5 ms ahead: unaligned by the rule, read at the bounds by name
    kept["planes"] = decode_trace(5 * MS)
    got = readings_idle.join(kept, line, trace_start_s=2.0)
    assert got["joined"]["idle_gaps"] == [[trace_join.UNALIGNED, pytest.approx(0.0136, abs=1e-6)]]
    assert got["joined"]["device.gap_ms.decode"] is None
    assert all(at["device.gap_ms.decode"] == pytest.approx(0.4, abs=US) for at in got["at_bounds"])


NEW_PHASE_METRICS = {"sched.upload_ms.decode": "tick.decode.upload", "sched.dispatch_ms.decode": "tick.decode.enqueue"}


@pytest.mark.parametrize("name", sorted(NEW_PHASE_METRICS))
def test_the_tick_phase_metrics_read_their_phase_per_decode_read(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    body = manifest.metric_file("per_layer", name)
    assert body["reader"] == "timeline" and body["stat"] == "per_ms"
    assert body["names"] == [NEW_PHASE_METRICS[name]] and body["per"] == "tick.decode.sync"
    # every cell whose mix traces from the file's second
    same_slice = [
        w["name"] for w in BENCH["workloads"]
        if manifest.traffic_mix(w["traffic"])["trace"]["start_s"] == body["from_s"]
    ]
    assert entry["workloads"] == same_slice and len(same_slice) == 3
    assert (entry["layer"], entry["moves"], entry["unit"]) == ("GenerationScheduler", "answer_p50_ms", "ms")
