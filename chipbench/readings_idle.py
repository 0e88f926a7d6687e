"""The chip's idle time named by what the host was doing in it: one traced
run of a cell, made as ``run.py --trace 1`` makes it, with the device
trace kept and joined to the program's host timeline (``trace_join``).
Not part of a benchmark run: it prints the readings of the join that the
harness's own line does not carry yet.

    python3 chipbench/readings_idle.py <cell> <seed> [<seconds> [<out_dir>]]

Prints run.py's result line, then one JSON line of the join: the clock
(the session's start, the device's first event as the trace gives it and
the host's clock around ``start_trace``), causality's bounds on the offset
between the two records, the idle seconds by name, the share of the traced
seconds idle with a request in the server, the device's mean wait between
two decode steps in flight, the tick's phases per decode read, the
timeline ring's reach and the device's operations by program and scope.
The same line, and the join's inputs (``save_join_inputs``), are written
under ``<out_dir>`` (``.chipbench/idle_join`` by default).  Needs the chip.
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest, run, trace_join, trace_reduce  # noqa: E402
from chipbench.readers import timeline as timeline_reader  # noqa: E402

PHASES = (
    "tick.admit", "tick.prefill.prepare", "tick.prefill.enqueue", "tick.decode.prepare",
    "tick.decode.upload", "tick.decode.enqueue", "tick.decode.sync", "tick.deliver",
)


def traced_run(cell: str, seed: int, seconds: float, **kw) -> tuple[dict, dict]:
    """``run.run_cell`` with ``--trace 1``; returns its line and what the
    run read and threw away: the trace's planes with their stats, the host
    timeline as it stood when the trace was read, and the host's clock
    around the profiler's start."""
    import jax

    from pathway_tpu.engine import tracing

    kept: dict = {}
    start_trace, read_xplane = jax.profiler.start_trace, trace_reduce.read_xplane

    def timed_start(*args, **kwargs):
        kept["start_trace_called_ns"] = time.time_ns()
        start_trace(*args, **kwargs)
        kept["start_trace_returned_ns"] = time.time_ns()

    def keep(trace_dir: str) -> list[dict]:
        kept["records"] = tracing.timeline()
        kept["planes"] = trace_join.read_planes(trace_dir)
        return [
            {**plane, "lines": [
                {"name": line["name"], "events": [e[:3] for e in line["events"]]}
                for line in plane["lines"]
            ]}
            for plane in kept["planes"]
        ]

    jax.profiler.start_trace, trace_reduce.read_xplane = timed_start, keep
    try:
        line = run.run_cell(cell, seed, seconds, 1, **kw)
    finally:
        jax.profiler.start_trace, trace_reduce.read_xplane = start_trace, read_xplane
    return line, kept


def joined_at(times: list[dict], records: list[dict], window_s: float, offset_ns: float | None) -> dict:
    """The joined readings at one offset (``None``: unaligned)."""
    named: dict[str, float] = {}
    for plane in times:
        for name, seconds in trace_join.name_gaps(plane["gaps"], records, offset_ns).items():
            named[name] = named.get(name, 0.0) + seconds
    n = len(times)
    idle_s = sum(named.values()) / n
    return {
        "offset_us": None if offset_ns is None else offset_ns * 1e-3,
        "idle_gaps": trace_join.idle_gaps(named, n),
        "unattributed_share": named.get(trace_join.UNATTRIBUTED, 0.0) / n / idle_s if idle_s else None,
        "device.idle_held_pct.answer": trace_join.held_idle_pct(named, window_s, n),
        "device.gap_ms.decode": trace_join.decode_gap_ms(times, records, offset_ns),
    }


def join(kept: dict, line: dict, trace_start_s: float) -> dict:
    """The readings of one traced run's join; ``trace_start_s`` is the
    traffic mix's ``trace.start_s`` (the ring's reach is given from the
    window's start).  ``joined`` is the join by its rule (offset 0 where
    causality allows it, else unaligned); ``at_bounds`` the same readings
    at each end of causality's bounds, asked for by name."""
    from pathway_tpu.engine import tracing

    planes, records = kept["planes"], kept["records"]
    span = trace_join.profile_span_ns(planes)
    times = trace_join.device_times(planes)
    bounds = trace_join.offset_bounds(times, records)
    since, until = span[0] * 1e-9, span[1] * 1e-9
    window_s = until - since
    idle_s = sum(e - s for plane in times for s, e in plane["gaps"]) * 1e-9 / len(times)
    decode = [
        e - s for plane in times for m, s, e in plane["runs"] if m in trace_join.DECODE_MODULES
    ]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    found = bounds is not None and bounds["lo_ns"] is not None
    return {
        "clock": {
            "profile_start_ns": span[0], "profile_stop_ns": span[1],
            "start_trace_called_ns": kept.get("start_trace_called_ns"),
            "start_trace_returned_ns": kept.get("start_trace_returned_ns"),
            "first_device_event_raw_ns": trace_join.first_event_ns(planes),
            "epoch_shift_ns": trace_join.epoch_shift_ns(planes),
            "plane_stats": {
                p["name"]: {k: str(v)[:200] for k, v in p["stats"].items()}
                for p in planes if p["stats"]
            },
        },
        "offset_bounds_us": [bounds["lo_ns"] * 1e-3, bounds["hi_ns"] * 1e-3] if found else None,
        "bounds": bounds,
        "window_s": window_s,
        "idle_s": idle_s,
        "idle_pct": 100.0 * idle_s / window_s,
        "joined": joined_at(times, records, window_s, trace_join.join_offset(bounds)),
        "at_bounds": [
            joined_at(times, records, window_s, bounds[end]) for end in ("lo_ns", "hi_ns")
        ] if found else None,
        "decode_run_ms": 1e-6 * sum(decode) / len(decode) if decode else None,
        "decode_runs": len(decode),
        "phases_ms_per_sync": {
            name: timeline_reader.measure(
                {"stat": "per_ms", "names": [name], "per": "tick.decode.sync"},
                records, since, until,
            )
            for name in PHASES
        },
        "tick_phases_ms": trace_join.tick_phases_ms(records, since, until),
        "sched.decode_tick_ms": metrics.get("sched.decode_tick_ms"),
        "ring": {
            "capacity": tracing.TIMELINE_MAX, "records": len(records),
            "records_in_trace": sum(1 for r in records if since <= r["start"] < until),
            # where the ring's oldest record lies, in seconds from the
            # window's start (the trace starts at trace_start_s)
            "oldest_s": min((r["start"] for r in records), default=since) - (since - trace_start_s),
        },
        "device_ops": trace_join.device_ops(planes),
        "op_stats": trace_join.op_stat_keys(planes),
    }


def save_join_inputs(kept: dict, stem: str) -> None:
    """What the join reads, small enough to bring back and read again:
    each device plane's gaps and program runs (``<stem>.npz``) and the
    timeline around the session (``<stem>-timeline.json``)."""
    import numpy as np

    span = trace_join.profile_span_ns(kept["planes"])
    times = trace_join.device_times(kept["planes"])
    arrays = {}
    for i, plane in enumerate(times):
        arrays[f"gaps{i}"] = np.array(plane["gaps"], dtype=np.float64)
        arrays[f"runs{i}"] = np.array([(s, e) for _m, s, e in plane["runs"]], dtype=np.float64)
        arrays[f"modules{i}"] = np.array([m for m, _s, _e in plane["runs"]])
    np.savez_compressed(f"{stem}.npz", span=np.array(span), **arrays)
    since, until = span[0] * 1e-9 - 60.0, span[1] * 1e-9 + 5.0
    with open(f"{stem}-timeline.json", "w") as f:
        json.dump(
            [r for r in kept["records"] if r["end"] >= since and r["start"] <= until], f, default=repr
        )


def main() -> None:
    cell, seed = sys.argv[1], int(sys.argv[2])
    bench = manifest.benchmark()
    seconds = float(sys.argv[3]) if len(sys.argv) > 3 else float(bench["run_seconds"])
    out = sys.argv[4] if len(sys.argv) > 4 else os.path.join(run.WORK_DIR, "idle_join")
    mix = manifest.traffic_mix(manifest.cell_entry(bench, cell)["traffic"])
    line, kept = traced_run(cell, seed, seconds)
    print(json.dumps(line), flush=True)
    os.makedirs(out, exist_ok=True)
    save_join_inputs(kept, os.path.join(out, f"{cell}-{seed}"))
    reading = {"cell": cell, "seed": seed, **join(kept, line, mix["trace"]["start_s"])}
    with open(os.path.join(out, f"{cell}-{seed}.json"), "w") as f:
        json.dump(reading, f, indent=1)
    joined = reading["joined"]["offset_us"] is not None
    run.note(
        f"clock: host - device offset in {reading['offset_bounds_us']} us over "
        f"{reading['decode_runs']} decode runs ({'joined at 0' if joined else 'unaligned'})"
    )
    print(json.dumps({k: v for k, v in reading.items() if k != "op_stats"}), flush=True)


if __name__ == "__main__":
    try:
        main()
        code = 0
    except BaseException:  # noqa: BLE001 - report, then leave as run.py does
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
