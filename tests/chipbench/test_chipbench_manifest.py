"""``BENCHMARK.json`` against its contract and against the files it names:
every configuration, traffic mix and metric is a file the harness finds by name."""

from __future__ import annotations

import json
import os
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = [("end_to_end", m) for m in BENCH["end_to_end"]] + [
    ("per_layer", m) for m in BENCH["per_layer"]
]


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert sorted(BENCH) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"]
    )
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert all(one_line(w) for w in BENCH["command"]) and len(BENCH["command"]) <= 32
    assert (REPO / BENCH["command"][1]).is_file()
    assert BENCH["command"][1].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert os.path.getsize(REPO / "BENCHMARK.json") <= 64 * 1024
    names = [m["name"] for _g, m in METRICS]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert sorted(config) == ["file", "name", "reduced", "source", "why"]
    assert NAME.match(config["name"]) and one_line(config["why"]) and one_line(config["source"])
    assert config["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    body = json.loads((REPO / config["file"]).read_text())
    spec = body["chipbench"]
    for key in ("builder", "check", "source", "reduced", "assumed", "guarantees", "limits", "tiny"):
        assert key in spec, key
    assert sorted(spec["reduced"]) == sorted(config["reduced"])
    assert all(NAME.match(k) for k in config["reduced"]) and len(config["reduced"]) <= 16
    assert (REPO / "chipbench" / "builders" / f"{spec['builder']}.py").is_file()
    assert (REPO / "chipbench" / "checks" / f"{spec['check']['module']}.py").is_file()
    assert spec["check"]["sample"] >= 6


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_entry_traffic_file_and_metrics(cell):
    assert sorted(cell) == ["chips", "config", "name", "traffic", "why"]
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and one_line(cell["why"])
    assert cell["chips"] in (1, 4)
    assert cell["config"] in [c["name"] for c in BENCH["configs"]]
    body = json.loads((REPO / "chipbench" / "traffic_mixes" / f"{cell['traffic']}.json").read_text())
    for key in ("route", "arrivals", "payload", "client", "trace"):
        assert key in body, key
    assert sorted(body["arrivals"]) == ["draw_seed", "rate_per_s"]
    # the traced slice is most of the window
    assert BENCH["run_seconds"] - body["trace"]["start_s"] - body["trace"]["stop_before_close_s"] >= 30
    reported = {
        group: [m["name"] for m in BENCH[group] if cell["name"] in m.get("workloads", [cell["name"]])]
        for group in ("end_to_end", "per_layer")
    }
    assert "setup_s" in reported["end_to_end"] and len(reported["end_to_end"]) >= 2
    assert reported["per_layer"]


@pytest.mark.parametrize("group,metric", METRICS, ids=lambda v: v if isinstance(v, str) else v["name"])
def test_metric_entry_and_file(group, metric):
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if group == "end_to_end" else {"layer", "moves"}
    )
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    cells = [w["name"] for w in BENCH["workloads"]]
    assert set(metric.get("workloads", cells)) <= set(cells)
    directory = "end_to_end" if group == "end_to_end" else "layer_metrics"
    body = json.loads((REPO / "chipbench" / directory / f"{metric['name']}.json").read_text())
    assert (REPO / "chipbench" / "readers" / f"{body['reader']}.py").is_file()
    if group == "end_to_end":
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        return
    assert one_line(metric["layer"])
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == metric["moves"])
    # each cell that reports the metric reports the end-to-end metric it moves
    assert set(metric["workloads"]) <= set(moved.get("workloads", cells))
    if metric["name"].endswith("_roofline") or "mfu" in re.split(r"[._]", metric["name"]):
        assert metric["unit"] == "%" and metric["better"] == "higher"
