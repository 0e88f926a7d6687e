"""The device trace and the program's host timeline on one clock: each
idle gap of the chip named by what the host was doing in it.

Two records of one traced window, both read in the process that served it:

- the device trace (``read_planes``: ``trace_reduce``'s plain planes, with
  each event's stats and each plane's stats beside them): every operation
  and every run of a compiled program on the device;
- the program's host timeline (``pathway_tpu.engine.tracing.timeline``):
  closed intervals of the threads' own work, stamped with ``time.time()``.

``device_times`` puts the device's events on the Unix epoch and keeps
what ``trace_reduce.reduce`` throws away: where each idle gap and each
program run lies.  ``offset_bounds`` checks the shared clock from
causality alone: a decode step cannot start on the device before the host
began the ``tick.decode.enqueue`` that sent it, and the
``tick.decode.sync`` that reads its tokens cannot return before it ended.
Where those bounds hold 0 (within ``TOLERANCE_NS``) the two records join
as they stand (``join_offset``); otherwise every gap is ``unaligned`` and
the joined numbers read nothing: the clock is never shifted to fit.  The
functions that join take the offset as an argument, so a reading can be
asked for at any offset the bounds allow, by name.  ``name_gaps``
cuts each gap against the timeline and names each piece by the first
interval that covers it, in the order of ``PRECEDENCE``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

import numpy as np

from chipbench import trace_reduce

DECODE_MODULES = ("jit__decode", "jit__decode_history")
TOLERANCE_NS = 100_000.0  # 100 us either side of 0 still joins at 0
DAY_NS = 86_400e9

# the host intervals a gap is named by, first match wins: no request in
# the server; the scheduler's tick phases; the epoch's barrier inside its
# run; the executor's device calls; a request in the server and the
# scheduler waiting for work
PRECEDENCE = (
    ("serve.idle",),
    "tick.",
    ("epoch.async_wait",),
    ("epoch.run",),
    ("device.call",),
    ("sched.idle",),
)
UNATTRIBUTED = "unattributed"
UNALIGNED = "unaligned"

# segments of an operation's name stack that JAX adds, not the program
_JAX_SEGMENTS = {"while", "body", "cond", "closed_call", "checkpoint", "remat", "branch"}


def read_planes(trace_dir: str) -> list[dict]:
    """Planes of the newest trace under ``trace_dir``: ``{"name", "stats",
    "lines": [{"name", "events": [(name, start_ns, dur_ns, stats)]}]}``;
    an event's stats are read for the first event of each name on a device
    plane's operation line (a program's operations repeat every run) and
    are empty elsewhere."""
    from jax.profiler import ProfileData

    files = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for plane in ProfileData.from_file(files[-1]).planes:
        device = plane.name.startswith(trace_reduce.DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            with_stats = device and line.name == trace_reduce.OP_LINE
            seen: set[str] = set()
            events = []
            for e in line.events:
                stats = {}
                if with_stats and e.name not in seen:
                    seen.add(e.name)
                    stats = dict(e.stats)
                events.append((e.name, float(e.start_ns), float(e.duration_ns), stats))
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "stats": dict(plane.stats), "lines": lines})
    return planes


def profile_span_ns(planes: list[dict]) -> tuple[float, float] | None:
    """The profiler session's start and stop on the Unix epoch, from the
    ``Task Environment`` plane, where the trace has one."""
    for plane in planes:
        stats = plane.get("stats") or {}
        if "profile_start_time" in stats and "profile_stop_time" in stats:
            return float(stats["profile_start_time"]), float(stats["profile_stop_time"])
    return None


def _device_lines(planes: list[dict]) -> list[dict[str, list]]:
    devices = [p for p in planes if p["name"].startswith(trace_reduce.DEVICE_PLANE)]
    if not devices:
        raise ValueError(f"no device plane among {[p['name'] for p in planes]}")
    return [{line["name"]: line["events"] for line in p["lines"]} for p in devices]


def first_event_ns(planes: list[dict]) -> float | None:
    """The earliest ``start_ns`` of any device event, as the trace gives it."""
    starts = [
        events[0][1] for lines in _device_lines(planes) for events in lines.values() if events
    ]
    return min(starts, default=None)


def epoch_shift_ns(planes: list[dict]) -> float:
    """What to add to a device event's ``start_ns`` to put it on the Unix
    epoch: nothing where it is there already, the session's start where
    the trace counts from it (as a host plane does)."""
    span = profile_span_ns(planes)
    first = first_event_ns(planes)
    if span is None or first is None or first > span[0] - DAY_NS:
        return 0.0
    return span[0]


def device_times(planes: list[dict]) -> list[dict]:
    """For each device plane, on the Unix epoch in ns: ``gaps``, every
    stretch of the session with no operation running (the stretches before
    the first and after the last included), and ``runs``, every run of a
    compiled program as ``(module, start, end)``, both sorted."""
    shift = epoch_shift_ns(planes)
    span = profile_span_ns(planes)
    out = []
    for lines in _device_lines(planes):
        runs = sorted(
            (trace_reduce.module_name(e[0]), e[1] + shift, e[1] + e[2] + shift)
            for e in lines.get(trace_reduce.MODULE_LINE, [])
        )
        events = lines.get(trace_reduce.OP_LINE) or lines.get(trace_reduce.MODULE_LINE) or []
        intervals = [(e[1] + shift, e[1] + e[2] + shift) for e in events]
        _busy, gaps = trace_reduce.union_ns(intervals)
        if span is not None and intervals:
            first = min(s for s, _e in intervals)
            last = max(e for _s, e in intervals)
            if first > span[0]:
                gaps.insert(0, (span[0], first))
            if span[1] > last:
                gaps.append((last, span[1]))
        out.append({"gaps": gaps, "runs": runs})
    return out


def _intervals(records: list[dict], name: str, offset_ns: float = 0.0) -> list[tuple[float, float]]:
    """The intervals ``name`` in ns on the device's clock: the host's less
    ``offset_ns`` (host - device)."""
    return sorted(
        (r["start"] * 1e9 - offset_ns, r["end"] * 1e9 - offset_ns)
        for r in records if r["name"] == name
    )


def decode_reads(records: list[dict]) -> list[tuple[float, float]]:
    """Each decode step's ``(enqueue start, end of the sync that read it)``
    on the host, in ns, in the order the steps were sent.  A step is read
    by the first sync after the tick that sent it: the sync that directly
    follows an enqueue reads the step before (the step runs one ahead)."""
    enqueues = _intervals(records, "tick.decode.enqueue")
    syncs = _intervals(records, "tick.decode.sync")
    starts = [s for s, _e in enqueues]
    steps = []
    for sync_start, sync_end in syncs:
        i = bisect.bisect_left(starts, sync_start) - 1
        if i >= 0 and enqueues[i][1] == sync_start:  # phases tile exactly
            i -= 1  # this tick's own step, read by the next tick's sync
        if i >= 0 and (not steps or steps[-1][0] < starts[i]):
            steps.append((starts[i], sync_end))
    return steps


def offset_bounds(times: list[dict], records: list[dict]) -> dict | None:
    """The offsets ``host - device`` (ns) that causality allows, over
    every decode step run on the device: each run starts after the host
    began its enqueue and ends before its read returned.  The runs of the
    trace are matched in order to the host's steps at the one shift of
    index under which the bounds are not empty (the one that holds 0 where
    several are); ``None`` where no step of the trace can be matched."""
    steps = decode_reads(records)
    best = None
    for plane in times:
        runs = [(s, e) for m, s, e in plane["runs"] if m in DECODE_MODULES]
        if not runs or len(steps) < len(runs):
            return None
        run_s = np.array([s for s, _e in runs])
        run_e = np.array([e for _s, e in runs])
        sent = np.array([s for s, _e in steps])
        read = np.array([e for _s, e in steps])
        n = len(runs)
        found = []
        for k in range(len(steps) - n + 1):
            lo = float(np.max(sent[k:k + n] - run_s))
            hi = float(np.min(read[k:k + n] - run_e))
            if lo <= hi:
                found.append((not lo <= 0.0 <= hi, abs(lo + hi), lo, hi, k))
        if not found:
            return {"lo_ns": None, "hi_ns": None, "runs": n, "shift": None, "candidates": 0}
        _outside, _centre, lo, hi, k = min(found)
        bounds = {"lo_ns": lo, "hi_ns": hi, "runs": n, "shift": k, "candidates": len(found)}
        if best is None or hi - lo < best["hi_ns"] - best["lo_ns"]:
            best = bounds
    return best


def join_offset(bounds: dict | None) -> float | None:
    """The offset the two records join at: 0 where causality's bounds hold
    it, else ``None`` (unaligned)."""
    if bounds and bounds["lo_ns"] is not None and (
        bounds["lo_ns"] - TOLERANCE_NS <= 0.0 <= bounds["hi_ns"] + TOLERANCE_NS
    ):
        return 0.0
    return None


def _levels(records: list[dict], offset_ns: float) -> list[tuple[list[tuple[float, float, str]], float]]:
    """The timeline's intervals by precedence, each level sorted by start,
    with its longest interval (to bound a search back from a gap)."""
    levels = []
    for names in PRECEDENCE:
        if isinstance(names, str):
            chosen = [r for r in records if r["track"] == "sched" and r["name"].startswith(names)]
        else:
            chosen = [r for r in records if r["name"] in names]
        intervals = sorted(
            (r["start"] * 1e9 - offset_ns, r["end"] * 1e9 - offset_ns, r["name"]) for r in chosen
        )
        longest = max((e - s for s, e, _n in intervals), default=0.0)
        levels.append((intervals, longest))
    return levels


def name_gaps(
    gaps: list[tuple[float, float]], records: list[dict], offset_ns: float | None = 0.0
) -> dict[str, float]:
    """Idle seconds by name: each piece of each gap takes the name of the
    first interval of ``records`` (moved to the device's clock by
    ``offset_ns``) that covers it, in the order of ``PRECEDENCE``; a piece
    none covers is ``unattributed``, and every gap is ``unaligned`` where
    there is no offset to join at."""
    named: dict[str, float] = {}

    def credit(name: str, ns: float) -> None:
        named[name] = named.get(name, 0.0) + ns * 1e-9

    if offset_ns is None:
        for start, end in gaps:
            credit(UNALIGNED, end - start)
        return named
    levels = [
        (level, [s for s, _e, _n in level], longest)
        for level, longest in _levels(records, offset_ns)
    ]
    for start, end in gaps:
        pieces = [(start, end)]
        for level, starts, longest in levels:
            if not pieces:
                break
            lo = bisect.bisect_left(starts, start - longest)
            hi = bisect.bisect_right(starts, end)
            for s, e, name in level[lo:hi]:
                left = []
                for a, b in pieces:
                    cover_a, cover_b = max(a, s), min(b, e)
                    if cover_a >= cover_b:
                        left.append((a, b))
                        continue
                    credit(name, cover_b - cover_a)
                    if a < cover_a:
                        left.append((a, cover_a))
                    if cover_b < b:
                        left.append((cover_b, b))
                pieces = left
        for a, b in pieces:
            credit(UNATTRIBUTED, b - a)
    return named


def idle_gaps(named: dict[str, float], planes: int = 1) -> list[list]:
    """At most ten ``[name, seconds]`` pairs, largest first: the idle
    seconds by name, a mean over the device planes."""
    ranked = sorted(named.items(), key=lambda kv: -kv[1])[:10]
    return [[name, seconds / planes] for name, seconds in ranked]


def held_idle_pct(named: dict[str, float], window_s: float, planes: int = 1) -> float | None:
    """Share of the traced seconds with no operation on the device and a
    request in the server: every named idle second less ``serve.idle``'s."""
    if not window_s or UNALIGNED in named:
        return None
    held = sum(s for name, s in named.items() if name != "serve.idle")
    return 100.0 * held / planes / window_s


def _idle_between(gaps: list[tuple[float, float]], starts: list[float], a: float, b: float) -> float:
    i = max(0, bisect.bisect_right(starts, a) - 1)
    idle = 0.0
    for s, e in gaps[i:]:
        if s >= b:
            break
        idle += max(0.0, min(e, b) - max(s, a))
    return idle


def decode_gap_ms(
    times: list[dict], records: list[dict], offset_ns: float | None = 0.0
) -> float | None:
    """Mean idle ms of the device between two consecutive decode-step runs
    that lie inside one ``device.inflight`` interval of the host: how long
    the chip waited for the host a step while it had work.  Nothing where
    there is no offset to join at."""
    if offset_ns is None:
        return None
    inflight = _intervals(records, "device.inflight", offset_ns)
    flight_starts = [s for s, _e in inflight]
    waits = []
    for plane in times:
        starts = [s for s, _e in plane["gaps"]]
        runs = [(s, e) for m, s, e in plane["runs"] if m in DECODE_MODULES]
        for (s1, e1), (s2, e2) in zip(runs, runs[1:]):
            i = bisect.bisect_right(flight_starts, s1) - 1
            if i >= 0 and e2 <= inflight[i][1]:
                waits.append(_idle_between(plane["gaps"], starts, e1, s2))
    if not waits:
        return None
    return 1e-6 * sum(waits) / len(waits)


def tick_phases_ms(records: list[dict], since: float, until: float) -> dict[str, float]:
    """What a decode tick is made of: each ``sched`` phase's mean ms over
    the intervals from the return of one decode read to the return of the
    next inside one ``device.inflight`` (the intervals the registry's
    ``generate.decode.tick.ms`` times), over the reads that returned in
    ``[since, until]`` (seconds); ``tick`` is the interval's own mean and
    ``between ticks`` what no phase covers."""
    inflight = _intervals(records, "device.inflight")
    flight_starts = [s for s, _e in inflight]

    def flight(at: float) -> int | None:
        i = bisect.bisect_right(flight_starts, at) - 1
        return i if i >= 0 and at < inflight[i][1] else None

    syncs = [
        (flight(s), e) for s, e in _intervals(records, "tick.decode.sync")
        if since * 1e9 <= e <= until * 1e9
    ]
    # a read starts inside the interval it drains, so two reads whose
    # starts share one had no drain between their returns
    ticks = [(a, b) for (fa, a), (fb, b) in zip(syncs, syncs[1:]) if fa is not None and fa == fb]
    if not ticks:
        return {}
    phases = sorted(
        (r["start"] * 1e9, r["end"] * 1e9, r["name"])
        for r in records if r["track"] == "sched" and r["name"].startswith("tick.")
    )
    starts = [s for s, _e, _n in phases]
    longest = max((e - s for s, e, _n in phases), default=0.0)
    total: dict[str, float] = {}
    for a, b in ticks:
        for s, e, name in phases[bisect.bisect_left(starts, a - longest):bisect.bisect_right(starts, b)]:
            if min(e, b) > max(s, a):
                total[name] = total.get(name, 0.0) + min(e, b) - max(s, a)
    tiled = sum(total.values())
    total["tick"] = sum(b - a for a, b in ticks)
    total["between ticks"] = total["tick"] - tiled
    return {name: 1e-6 * ns / len(ticks) for name, ns in total.items()}


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _scope(name: str, stats: dict) -> str | None:
    """The innermost named scope of an operation's name stack
    (``jit(_decode)/jit(main)/while/body/moe.experts/dot_general`` ->
    ``moe.experts``), from the HLO line's ``op_name`` metadata or whichever
    stat of the event carries the stack."""
    stacks = _OP_NAME.findall(name) + [
        v for v in stats.values() if isinstance(v, str) and "/" in v and " " not in v
    ]
    for stack in stacks:
        named = [
            s for s in stack.split("/")[:-1]
            if s and "(" not in s and s.split("_")[0] not in _JAX_SEGMENTS and not s.isdigit()
        ]
        if named:
            return named[-1]
    return None


def device_ops(planes: list[dict], top: int = 10) -> list[list]:
    """Operation seconds summed under ``<module>/<scope>``, the ten
    largest as ``[name, seconds, runs of the module]``; an operation with
    no scope in its stats keeps ``trace_reduce``'s short HLO name.  The
    operation belongs to the program run in which it starts."""
    totals: dict[str, float] = {}
    runs_of: dict[str, int] = {}
    for lines in _device_lines(planes):
        modules = sorted(
            (e[1], e[1] + e[2], trace_reduce.module_name(e[0]))
            for e in lines.get(trace_reduce.MODULE_LINE, [])
        )
        for _s, _e, module in modules:
            runs_of[module] = runs_of.get(module, 0) + 1
        module_starts = [s for s, _e, _m in modules]
        scope_of: dict[str, str] = {}
        for event in lines.get(trace_reduce.OP_LINE, []):
            name, start, dur = event[0], event[1], event[2]
            if name not in scope_of:
                short, opcode = trace_reduce.op_name(name)
                scope = None if opcode in ("while", "conditional", "call") else (
                    _scope(name, event[3] if len(event) > 3 else {}) or short
                )
                scope_of[name] = scope
            scope = scope_of[name]
            if scope is None:  # they hold their children
                continue
            i = bisect.bisect_right(module_starts, start) - 1
            module = modules[i][2] if i >= 0 and start < modules[i][1] else "-"
            key = f"{module}/{scope}"
            totals[key] = totals.get(key, 0.0) + dur * 1e-9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[key, seconds, runs_of.get(key.split("/", 1)[0], 0)] for key, seconds in ranked]


def op_stat_keys(planes: list[dict], n: int = 3) -> list[dict]:
    """The names (cut to 600 characters) and stats (each cut to 200) of the
    first ``n`` operations of the first device plane: what a trace carries
    to name them by."""
    lines = _device_lines(planes)[0]
    out = []
    for event in lines.get(trace_reduce.OP_LINE, [])[:n]:
        stats = event[3] if len(event) > 3 else {}
        out.append({"name": event[0][:600], **{k: str(v)[:200] for k, v in stats.items()}})
    return out

