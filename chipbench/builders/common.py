"""What every builder shares: a free port, waiting for the server, one
JSON POST, and the probe of the program's own ledgers."""

from __future__ import annotations

import json
import socket
import time
import urllib.error
import urllib.request


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_until_listening(port: int, server_thread, timeout_s: float = 120.0) -> None:
    """Block until the threaded server accepts connections; fail at once
    if its thread has died instead."""
    deadline = time.monotonic() + timeout_s
    while True:
        if not server_thread.is_alive():
            raise RuntimeError("the server thread exited before it listened")
        try:
            socket.create_connection(("127.0.0.1", port), timeout=1.0).close()
            return
        except OSError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.1)


def post(port: int, route: str, payload: dict, timeout_s: float = 900.0):
    """One JSON POST with the request deadline stretched to ``timeout_s``
    (the server's default is 30 s, less than one cold 7B-wide compile).
    Any HTTP error status raises."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}",
        data=json.dumps(payload).encode(),
        headers={
            "Content-Type": "application/json",
            "X-Pathway-Deadline-Ms": str(int(timeout_s * 1000)),
        },
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout_s + 30.0) as resp:
        return json.loads(resp.read())


def wait_for_route(port: int, route: str, payload: dict, timeout_s: float = 120.0):
    """The first POST to a route: the server listens a moment before its
    routes are mounted, so a 404 or a refused connection is tried again."""
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            return post(port, route, payload)
        except (urllib.error.HTTPError, urllib.error.URLError, OSError) as exc:
            if getattr(exc, "code", 404) != 404 or time.monotonic() > deadline:
                raise
            time.sleep(0.2)


def probe_program(scheduler=None) -> dict:
    """The program's own counters, as one dictionary the readers index:
    the executor's snapshot, the metric registry's histograms (sum and
    count) and scalars, and the generation scheduler's snapshot."""
    from pathway_tpu.device import default_executor_snapshot
    from pathway_tpu.engine.metrics import get_registry

    registry = get_registry()
    histograms: dict[str, dict] = {}
    for point in registry.histogram_points():
        entry = histograms.setdefault(point["name"], {"sum": 0.0, "count": 0})
        entry["sum"] += point["sum"]
        entry["count"] += point["count"]
    out = {
        "executor": default_executor_snapshot(),
        "histograms": histograms,
        "scalars": {
            k: v for k, v in registry.scalar_metrics().items()
            if isinstance(v, (int, float))
        },
    }
    if scheduler is not None:
        out["scheduler"] = scheduler.snapshot()
    return out


def warm_executor_buckets(name: str, buckets: tuple[int, ...]) -> int:
    """Compile every batch bucket of the executor's program ``name`` for
    each shape it has served so far (``DeviceExecutor.cache_keys``, the
    ledger the program keeps for planning warm-ups): the operands and
    static arguments as the program passed them, the batch axis varied.
    Returns the number of programs compiled."""
    import jax.numpy as jnp

    from pathway_tpu.device import get_default_executor

    executor = get_default_executor()
    if not executor.registered(name):
        return 0
    compiled = 0
    for leaves, static, _backend in executor.cache_keys(name):
        (_batch, *row), dtype = leaves[-1]
        operands = tuple(jnp.zeros(shape, dtype) for shape, dtype in leaves[:-1])
        compiled += executor.warmup(
            name, row_shapes=(tuple(row),), dtypes=(dtype,), operands=operands,
            static=dict(static), buckets=buckets,
        )
    return compiled


def seq_bucket(n: int) -> int:
    """The encoder's sequence bucket for ``n`` tokens."""
    for b in (16, 32, 64, 128, 256, 512):
        if n <= b:
            return b
    return 512


def device_path_misses(probe: dict) -> dict:
    """Counters that have to stay nought for every request to have been
    served by the device path (after ``chip_smoke.check_ledgers``): host
    fallbacks, failed or split dispatches, open breakers, attention on
    XLA where the kernel should be, failed scheduler ticks."""
    executor = probe["executor"]
    ledgers = list(executor["resilience"]["callables"].values())
    return {
        "uncosted_dispatches": executor["cost"]["uncosted_dispatches"],
        "failures": sum(sum(st["failures"].values()) for st in ledgers),
        "fallback_batches": sum(st["fallback_batches"] for st in ledgers),
        "oom_splits": sum(st["oom_splits"] for st in ledgers),
        "breaker_trips": sum(st["breaker"]["trips"] for st in ledgers),
        "quarantine": len(executor["resilience"]["quarantine"]),
        "attention_xla_fallback": sum(executor["attention_xla_fallback"].values()),
        "tick_failures": probe.get("scheduler", {}).get("tick_failures", 0),
    }
