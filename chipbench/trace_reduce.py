"""From a profiler trace to numbers: the device's busy time, the device
time of each XLA module, the operations that took most time and the
longest idle gaps.

``read_xplane`` turns the ``.xplane.pb`` file ``jax.profiler`` writes into
plain planes, lines and events (``jax.profiler.ProfileData``, nothing but
JAX); ``reduce`` works on that plain form, which the tests also build by
hand.  A device plane is one whose name starts with ``/device:TPU:``.  On
it, line ``XLA Modules`` holds one event per run of a compiled program
(``jit__decode(123...)``) and line ``XLA Ops`` one per operation.  Busy
time is the union of the operation intervals (of the module intervals
where a trace has no operation line), averaged over the device planes.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def read_xplane(trace_dir: str) -> list[dict]:
    """Planes of the newest trace under ``trace_dir`` as
    ``{"name", "lines": [{"name", "events": [(name, start_ns, dur_ns)]}]}``."""
    from jax.profiler import ProfileData

    files = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for plane in ProfileData.from_file(files[-1]).planes:
        lines = []
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union_ns(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """Length of the union of ``(start, end)`` intervals, and the gaps
    between its pieces."""
    total, gaps = 0.0, []
    cur_start = cur_end = None
    for start, end in sorted(intervals):
        if cur_end is None:
            cur_start, cur_end = start, end
        elif start <= cur_end:
            cur_end = max(cur_end, end)
        else:
            total += cur_end - cur_start
            gaps.append((cur_end, start))
            cur_start, cur_end = start, end
    if cur_end is not None:
        total += cur_end - cur_start
    return total, gaps


def module_name(event_name: str) -> str:
    """``jit__decode(1234567)`` -> ``jit__decode``."""
    return re.sub(r"\(\d+\)$", "", event_name)


_HLO_LINE = re.compile(r"^(%?[\w.\-]+) = (.*?) ([\w\-]+)\(")


def op_name(event_name: str) -> tuple[str, str]:
    """A traced operation's name is its whole HLO line; returns a short
    form, its result's name, shape and opcode (``%fusion.199
    bf16[8,32,14336] fusion``), and the opcode."""
    match = _HLO_LINE.match(re.sub(r"\{[^}]*\}", "", event_name))
    if not match:
        return event_name[:96], ""
    name, shape, opcode = match.groups()
    return f"{name} {shape[:60]} {opcode}", opcode


def reduce(planes: list[dict], window_s: float | None = None) -> dict:
    """Busy seconds (mean over device planes), seconds and runs of each
    module (summed over planes), the ten operations with most time and the
    ten longest idle gaps.  ``window_s`` is the traced window; without it
    the span from the first to the last device event stands in."""
    devices = [p for p in planes if p["name"].startswith(DEVICE_PLANE)]
    if not devices:
        raise ValueError(f"no device plane among {[p['name'] for p in planes]}")
    busy, span, modules, ops, gaps = [], [], {}, {}, []
    for plane in devices:
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        for name, _start, dur in lines.get(MODULE_LINE, []):
            entry = modules.setdefault(module_name(name), {"seconds": 0.0, "runs": 0})
            entry["seconds"] += dur * 1e-9
            entry["runs"] += 1
        for name, _start, dur in lines.get(OP_LINE, []):
            short, opcode = op_name(name)
            if opcode not in ("while", "conditional", "call"):  # they hold their children
                ops[short] = ops.get(short, 0.0) + dur * 1e-9
        events = lines.get(OP_LINE) or lines.get(MODULE_LINE) or []
        intervals = [(start, start + dur) for _n, start, dur in events]
        total, plane_gaps = union_ns(intervals)
        busy.append(total * 1e-9)
        if intervals:
            span.append((max(e for _s, e in intervals) - min(s for s, _e in intervals)) * 1e-9)
        gaps += [(end - start) * 1e-9 for start, end in plane_gaps]
    if not ops:  # a trace without an operation line: the modules stand in
        ops = {name: m["seconds"] for name, m in modules.items()}
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s if window_s is not None else max(span, default=0.0),
        "modules": modules,
        "device_ops": [list(kv) for kv in sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [["unattributed", s] for s in sorted(gaps, reverse=True)[:10]],
    }
