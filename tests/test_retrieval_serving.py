"""The serving-path latency harness (benchmarks/retrieval_serving.py).

Runs the full REST → embed → search → respond stack in a subprocess (the
engine thread it starts lives until process exit, so it must not share
this pytest process) at a tiny corpus and pins the artifact contract
``bench.py`` relies on.
"""

import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_serving_harness_contract():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [
            sys.executable,
            str(REPO / "benchmarks" / "retrieval_serving.py"),
            "500",
            "8",
        ],
        capture_output=True,
        text=True,
        timeout=420,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")][-1]
    out = json.loads(line)
    assert out["metric"] == "retrieval_serving_colocated_p50_ms"
    assert out["docs"] == 500 and out["n_queries"] == 8 and out["k"] == 10
    # stage accounting: every component measured and positive, and the
    # blocking device calls fit inside the end-to-end time
    for key in (
        "e2e_p50_ms",
        "host_other_p50_ms",
        "embed_call_p50_ms",
        "search_call_p50_ms",
        "embed_device_ms",
        "search_device_ms",
        "colocated_p50_ms",
    ):
        assert isinstance(out[key], (int, float)) and out[key] > 0, (key, out)
    assert out["host_other_p50_ms"] < out["e2e_p50_ms"], out
    assert out["colocated_p50_ms"] == round(
        out["host_other_p50_ms"] + out["embed_device_ms"] + out["search_device_ms"],
        3,
    ) or abs(
        out["colocated_p50_ms"]
        - (out["host_other_p50_ms"] + out["embed_device_ms"] + out["search_device_ms"])
    ) < 0.01, out
