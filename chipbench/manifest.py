"""``BENCHMARK.json`` and the files it names.  Everything that belongs to
one configuration, traffic mix or metric is a file found by its name:

    configs/<config>.json   traffic_mixes/<traffic>.json
    end_to_end/<metric>.json   layer_metrics/<metric>.json

A cell is an entry of ``workloads`` and nothing more: it names its
configuration and its traffic mix.
"""

from __future__ import annotations

import copy
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(root, "BENCHMARK.json")


def cell_entry(bench: dict, name: str) -> dict:
    for entry in bench["workloads"]:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: str = ROOT, *, tiny: bool = False) -> dict:
    """The configuration's file; with ``tiny`` its ``tiny`` block (the CPU
    rehearsal of the tests) laid over the ``chipbench`` block."""
    for entry in bench["configs"]:
        if entry["name"] == name:
            cfg = load_json(root, entry["file"])
            break
    else:
        raise KeyError(f"no config {name!r} in BENCHMARK.json")
    if tiny:
        cfg = copy.deepcopy(cfg)
        spec = cfg["chipbench"]
        for key, value in spec.pop("tiny").items():
            if isinstance(value, dict) and isinstance(spec.get(key), dict):
                spec[key] = {**spec[key], **value}
            else:
                spec[key] = value
    return cfg


def traffic_mix(name: str) -> dict:
    return load_json(HERE, "traffic_mixes", f"{name}.json")


def metrics_of(bench: dict, group: str, cell: str) -> list[dict]:
    """The metrics of ``group`` (``end_to_end`` or ``per_layer``) that
    ``cell`` reports: those that list it, and those that list no cells."""
    return [m for m in bench[group] if cell in m.get("workloads", [cell])]


def metric_file(group: str, name: str) -> dict:
    directory = "end_to_end" if group == "end_to_end" else "layer_metrics"
    return load_json(HERE, directory, f"{name}.json")


def peak(device_kind: str) -> dict:
    peaks = load_json(HERE, "peaks.json")
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in chipbench/peaks.json")
    return peaks[device_kind]
