"""chipbench: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

builds the cell's deployment from its configuration (``configs/``) in this
process, which holds the chip; ingests and warms up (set-up); drives the
cell's traffic (``traffic_mixes/``) at the server over loopback HTTP from
a client process for ``--seconds`` seconds; waits for every answer; reads
the metrics (``--trace 0``: the cell's end-to-end metrics, ``--trace 1``:
its per-layer metrics, with part of the window under the profiler); frees
the program's state and holds what was served against the plain references
(``checks/``).  The last line of standard output is the
result, one JSON object.  Without a TPU, or with fewer chips than the cell
asks for, it prints no result and exits with 2.
"""

from __future__ import annotations

import time

T0_WALL = time.time()  # process start, as near as Python lets us take it

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest, readers, stats, trace_reduce, traffic  # noqa: E402
from chipbench.builders import common  # noqa: E402

WORK_DIR = os.path.join(ROOT, ".chipbench")


def note(message: str) -> None:
    """A line on standard error, with the seconds since process start."""
    print(f"chipbench: [{time.time() - T0_WALL:7.1f} s] {message}", file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def find_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found platform {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s), JAX found {len(devices)}")
    return devices


def run_client(port: int, requests: list[dict], mix: dict, on_start=None) -> dict:
    """Drive ``requests`` from a client process; returns its report with
    each result beside its request."""
    spec = {"port": port, "requests": requests, **mix.get("client", {})}
    proc = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "client.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
    )
    try:
        proc.stdin.write(json.dumps(spec))
        proc.stdin.close()
        ready = proc.stdout.readline().split()
        if len(ready) != 2 or ready[0] != "READY":
            raise RuntimeError(f"the client did not start: {ready}")
        if on_start is not None:
            on_start(float(ready[1]))
        report = json.loads(proc.stdout.read())
    except BaseException:
        proc.kill()
        raise
    finally:
        proc.stdout.close()
        proc.wait()
    if proc.returncode != 0:
        raise RuntimeError(f"the client exited with {proc.returncode}")
    for request, result in zip(requests, report["results"]):
        result["request"] = request
    return report


def trace_window(start_wall: float, seconds: float, spec: dict, trace_dir: str, done: dict) -> None:
    """Profile the window, from ``spec["start_s"]`` after its start to
    ``spec["stop_before_close_s"]`` before its close (runs in a thread of
    its own)."""
    import jax

    # the device's own events are all that is read; the Python and host
    # tracers delay this server's answers by seconds while they are on
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    time.sleep(max(0.0, start_wall + spec["start_s"] - time.time()))
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.monotonic()
    time.sleep(max(0.0, start_wall + seconds - spec["stop_before_close_s"] - time.time()))
    done["window_s"] = time.monotonic() - t0
    jax.profiler.stop_trace()  # collecting the trace takes many seconds more


class CompileWatch:
    """Counts the programs JAX lowers (a compile, or a read from the
    persistent cache) while ``watching``, in any thread, and keeps their
    names from JAX's own log."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.watching = False
        self.count = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        watch = self

        class Handler(logging.Handler):
            def emit(self, record: logging.LogRecord) -> None:
                message = record.getMessage()
                if watch.watching and message.startswith("Compiling "):
                    watch.names.append(message[:160])

        logging.getLogger("jax._src.interpreters.pxla").addHandler(Handler())

    def _on_event(self, name: str, _seconds: float, **_kw) -> None:
        if self.watching and name == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax

        jax.config.update("jax_log_compiles", True)
        self.watching = True
        return self

    def __exit__(self, *_exc):
        import jax

        self.watching = False
        jax.config.update("jax_log_compiles", False)


class Bench:
    """One cell set up in this process: its files, its deployment warm."""

    def __init__(self, workload: str, seed: int, *, require_tpu: bool = True, tiny: bool = False):
        import jax

        self.bench = manifest.benchmark()
        self.workload = workload
        self.entry = manifest.cell_entry(self.bench, workload)
        self.require_tpu = require_tpu
        self.devices = find_chips(self.entry["chips"]) if require_tpu else jax.devices()
        self.config = manifest.config(self.bench, self.entry["config"], tiny=tiny)
        self.mix = manifest.traffic_mix(self.entry["traffic"])
        self.spec = self.config["chipbench"]
        self.compiles = CompileWatch()
        os.makedirs(WORK_DIR, exist_ok=True)
        builder = importlib.import_module(f"chipbench.builders.{self.spec['builder']}")
        self.deployment = builder.build(self.config, seed, WORK_DIR)
        note("built")
        self.deployment.start()
        note("listening")
        self.deployment.warm_up(self.mix)
        note("warm")

    def window(self, seed: int, seconds: float, trace: int = 0, rate_per_s: float | None = None) -> dict:
        """Drive one window of the cell's traffic; returns what was seen:
        the results, the ledgers around it, the trace's reduction."""
        deployment, mix = self.deployment, self.mix
        requests = traffic.make_schedule(
            mix, seed, seconds, deployment.documents, rate_per_s=rate_per_s
        )
        trace_dir = os.path.join(WORK_DIR, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        traced: dict = {}
        threads: list[threading.Thread] = []
        started: dict = {}

        def on_start(start_wall: float) -> None:
            started["wall"] = start_wall
            if trace:
                threads.append(threading.Thread(
                    target=trace_window, args=(start_wall, seconds, mix["trace"], trace_dir, traced)
                ))
                threads[0].start()

        before = deployment.probe()
        with self.compiles as watch:
            compiled_before = watch.count
            report = run_client(deployment.port, requests, mix, on_start)
            compiled = watch.count - compiled_before
        for t in threads:
            t.join()
        after = deployment.probe()
        if compiled:
            note(f"compiled in the window: {self.compiles.names[-compiled:]}")
        results = report["results"]
        timeout_ms = mix.get("client", {}).get("timeout_s", 120.0) * 1e3
        seen = {
            "results": results, "before": before, "after": after,
            "start_wall": started["wall"], "compiled": compiled, "seed": seed,
            "failed": sum(1 for r in results if r["status"] != 200),
            "latencies_ms": [
                r["latency_ms"] if r["status"] == 200 else max(r["latency_ms"], timeout_ms)
                for r in results
            ],
            "span_s": max(r["done_s"] for r in results),
            "trace": None,
        }
        late = [r["late_ms"] for r in results]
        note(
            f"{len(results)} requests, {seen['failed']} failed, sent late by "
            f"p50 {stats.percentile(late, 50):.2f} ms / max {max(late):.2f} ms, "
            f"last answer {seen['span_s']:.2f} s after the window opened"
        )
        seen["off_device_path"] = common.device_path_misses(after)
        if any(seen["off_device_path"].values()):
            note(f"not every request was served by the device path: {seen['off_device_path']}")
        note("latencies by arrival (s): " + " ".join(f"{r['latency_ms'] / 1e3:.2f}" for r in results))
        if seen["failed"]:
            kinds: dict = {}
            for r in results:
                if r["status"] != 200:
                    key = f"{r['status']} {r.get('error') or r.get('body', '')[:80]}"
                    kinds[key] = kinds.get(key, 0) + 1
            note(f"failed requests: {kinds}")
        if trace:
            try:
                seen["trace"] = trace_reduce.reduce(
                    trace_reduce.read_xplane(trace_dir), traced.get("window_s")
                )
            except ValueError:
                if self.require_tpu:
                    raise
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
        return seen

    def memory_peak(self) -> int:
        return max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in self.devices
        )

    def metrics(self, seen: dict, trace: int, setup_s: float) -> dict:
        """The cell's end-to-end metrics, or with ``trace`` its per-layer
        metrics: each read by its own file, those with nothing to read
        left out."""
        kind = self.devices[0].device_kind
        ctx = {
            **seen, "setup_s": setup_s, "work": self.deployment.work(seen["results"]),
            "sections": {**self.spec, "decoder": self.spec.get("decoder") or self.config},
            "peak": manifest.peak(kind) if self.require_tpu else {"flops_per_s": 1e12, "bytes_per_s": 1e11},
        }
        group = "per_layer" if trace else "end_to_end"
        out: dict = {}
        for m in manifest.metrics_of(self.bench, group, self.workload):
            value = readers.evaluate(manifest.metric_file(group, m["name"]), ctx)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    def check(self, seen: dict, *, control: bool = False, cache: dict | None = None) -> list[dict]:
        """The numbers ``correct`` is decided by, each beside its limit."""
        check = importlib.import_module(f"chipbench.checks.{self.spec['check']['module']}")
        return [
            {"name": "failed_requests", "value": float(seen["failed"]), "limit": 0.0},
            {"name": "compiles_in_window", "value": float(seen["compiled"]), "limit": 0.0},
            {"name": "off_device_path", "value": float(sum(seen["off_device_path"].values())), "limit": 0.0},
        ] + check.check({
            "config": self.config, "seed": seen["seed"],
            "deployment": self.deployment, "results": seen["results"],
            "control": control, "cache": cache if cache is not None else {},
        })


def run_cell(
    workload: str, seed: int, seconds: float, trace: int, *,
    require_tpu: bool = True, tiny: bool = False, control: bool = False,
    rate_per_s: float | None = None,
) -> dict:
    """One run; returns the result line as a dictionary.  ``require_tpu``,
    ``tiny`` (the configuration's CPU rehearsal sizes), ``control`` (the
    references computed in int8 in the program's place) and ``rate_per_s``
    (another rate than the cell's) are for the tests; the command never
    sets them."""
    bench = Bench(workload, seed, require_tpu=require_tpu, tiny=tiny)
    seen = bench.window(seed, seconds, trace, rate_per_s)
    setup_s = seen["start_wall"] - T0_WALL
    device = {
        "platform": bench.devices[0].platform,
        "kind": bench.devices[0].device_kind,
        "count": len(bench.devices),
        "memory_peak_bytes": bench.memory_peak(),
    }
    line = {
        "correct": False,
        "attempted": len(seen["results"]),
        "failed": seen["failed"],
        "metrics": bench.metrics(seen, trace, setup_s),
        "device": device,
    }
    if seen["trace"] is not None:
        reduction = seen["trace"]
        note("traced modules (s, runs): " + json.dumps(
            {k: [round(v["seconds"], 4), v["runs"]] for k, v in reduction["modules"].items()}
        ))
        device["busy_s"], device["window_s"] = reduction["busy_s"], reduction["window_s"]
        line["breakdown"] = {
            "device_ops": reduction["device_ops"], "idle_gaps": reduction["idle_gaps"]
        }
    numbers = bench.check(seen, control=control)
    note("checked")
    line["correct"] = all(n["value"] <= n["limit"] for n in numbers)
    line["checks"] = {n["name"]: {"value": n["value"], "limit": n["limit"]} for n in numbers}
    return line


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds, args.trace)
    except NoChip as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    for name, n in line["checks"].items():
        print(f"chipbench: {name} = {n['value']!r} (limit {n['limit']!r})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:  # noqa: BLE001 - report, then leave as below
        import traceback

        traceback.print_exc()
        code = 1
    # the server's threads are daemons and the client has been waited for:
    # leave without running the interpreter's teardown under a live server
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
