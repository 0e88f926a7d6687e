"""A run end to end off the chip: the command refuses to run without a
TPU, and the rest of a run (builder, warm-up, client process, metrics,
checks, result line) works at the tiny presets with the look for a chip
skipped, sound and with the timed path broken underneath."""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]

REHEARSAL = """
import json, os, sys
sys.path.insert(0, {repo!r})
{fault}
from chipbench import run
line = run.run_cell({cell!r}, {seed}, 6.0, {trace}, require_tpu=False, tiny=True, control={control}, rate_per_s=1.5)
print(json.dumps(line), flush=True)
os._exit(0)
"""

def env() -> dict:
    out = dict(os.environ)
    out["JAX_PLATFORMS"] = "cpu"
    out["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return out


def rehearse(cell: str, seed: int, trace: int = 0, control: bool = False, fault: str = "") -> dict:
    script = REHEARSAL.format(
        repo=str(REPO), cell=cell, seed=seed, trace=trace, control=control, fault=fault
    )
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, env=env(), timeout=400
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_command_refuses_to_run_without_a_tpu(cell):
    proc = subprocess.run(
        [sys.executable, str(REPO / BENCH["command"][1]), "--workload", cell,
         "--seed", str(2**31 + 11), "--seconds", "5", "--trace", "0"],
        capture_output=True, text=True, env=env(), timeout=120, cwd=REPO,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_command_fails_where_only_the_benchmark_is(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(REPO / path, tmp_path / path, ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, BENCH["command"][1], "--workload", CELLS[0],
         "--seed", "3", "--seconds", "5", "--trace", "0"],
        capture_output=True, text=True, env=env(), timeout=120, cwd=tmp_path,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_prints_the_contracts_line(cell):
    line = rehearse(cell, seed=2**31 + 5, trace=0)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 4
    wanted = [m for m in BENCH["end_to_end"] if cell in m.get("workloads", [cell])]
    assert sorted(line["metrics"]) == sorted(m["name"] for m in wanted)
    for m in wanted:
        got = line["metrics"][m["name"]]
        assert list(got) == ["value", "unit"] and got["unit"] == m["unit"] and got["value"] > 0
    assert list(line["device"]) == ["platform", "kind", "count", "memory_peak_bytes"]
    assert all(list(n) == ["value", "limit"] and n["value"] <= n["limit"] for n in line["checks"].values())
    assert line["checks"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_control_run_reads_layers_and_is_not_correct(cell):
    """``--trace 1`` reports per-layer metrics (those that need the device
    trace find none here and are left out); with the references computed
    in int8 in the program's place ``correct`` comes out false."""
    line = rehearse(cell, seed=7, trace=1, control=True)
    assert line["correct"] is False
    failing = [k for k, n in line["checks"].items() if n["value"] > n["limit"]]
    assert failing and set(failing) <= {"logit_gap", "score_gap", "rank_gap"}
    per_layer = {m["name"]: m for m in BENCH["per_layer"] if cell in m.get("workloads", [cell])}
    assert line["metrics"] and set(line["metrics"]) <= set(per_layer)
    assert not [n for n in line["metrics"] if per_layer[n]["source"] == "device_trace"]
    assert all(v["value"] > 0 for v in line["metrics"].values())
