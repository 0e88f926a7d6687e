"""Readings a configuration's ``logit_gap`` limits are set from, where its
check knows more controls than ``readings.py seeds`` runs: not part of a
benchmark run.

    python3 chipbench/readings_controls.py <cell> <seconds> <rate> <n> <seed> [<seed> ...]

Sets the cell up once, drives one window a seed at ``rate`` requests/s
(enough of them for the check's sample: rate x seconds answers), frees the
program and holds every window against the references as served (the lower
readings); then the first ``n`` windows again with each control in the
program's place: ``int8`` and, where the cell's check module names one
(``STATE_CONTROL``), the recurrent state's.  One JSON line a reading.
Needs the chip.
"""

from __future__ import annotations

import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import run  # noqa: E402


def main() -> None:
    cell, seconds, rate, n = sys.argv[1], float(sys.argv[2]), float(sys.argv[3]), int(sys.argv[4])
    seeds = [int(x) for x in sys.argv[5:]]
    bench = run.Bench(cell, seed=seeds[0])
    windows = [bench.window(seed, seconds, rate_per_s=rate) for seed in seeds]
    check = importlib.import_module(f"chipbench.checks.{bench.spec['check']['module']}")
    controls = [False, "int8"] + ([check.STATE_CONTROL] if hasattr(check, "STATE_CONTROL") else [])
    cache: dict = {}
    for control in controls:
        for seen in windows if control is False else windows[:n]:
            numbers = bench.check(seen, control=control, cache=cache)
            print(json.dumps({
                "seed": seen["seed"], "control": control, "answers": len(seen["results"]),
                **{number["name"]: number["value"] for number in numbers},
            }), flush=True)
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
