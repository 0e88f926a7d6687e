"""Readings the limits, the rate and the bounds were set from: not part
of a benchmark run, kept so that the next benchmark PR can take them again.

    python3 chipbench/readings.py sweep <cell> <seconds> <rate> [<rate> ...]
    python3 chipbench/readings.py seeds <cell> <seconds> <seed> [<seed> ...]

``sweep`` sets the cell up once and drives one window at each rate: the
knee is the highest rate whose answers keep up (the last answer comes
soon after the window closes, and the second half's latencies are no
worse than the first's).  ``seeds`` sets the cell up once, drives a short
window of the cell's own traffic for each seed, then frees the program
and holds every window against the references twice: as served (the lower
readings of each compared number) and with the references in int8 in the
program's place (the control's readings).  Each prints one JSON line per
window.  Both need the chip.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import run, stats  # noqa: E402


def sweep(cell: str, seconds: float, rates: list[float]) -> None:
    bench = run.Bench(cell, seed=1)
    for n, rate in enumerate(rates):
        seen = bench.window(100 + n, seconds, rate_per_s=rate)
        lat = seen["latencies_ms"]
        half = len(lat) // 2
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat), "failed": seen["failed"],
            "p50_ms": stats.percentile(lat, 50), "p90_ms": stats.percentile(lat, 90),
            "p50_first_half_ms": stats.percentile(lat[:half], 50),
            "p50_second_half_ms": stats.percentile(lat[half:], 50),
            "last_answer_after_close_s": seen["span_s"] - seconds,
            "compiled": seen["compiled"],
        }), flush=True)


def seeds(cell: str, seconds: float, seed_list: list[int]) -> None:
    bench = run.Bench(cell, seed=seed_list[0])
    windows = [bench.window(seed, seconds) for seed in seed_list]
    cache: dict = {}
    for control in (False, True):
        for seen in windows:
            numbers = bench.check(seen, control=control, cache=cache)
            extra = {}
            if "logit_gaps" in cache:
                import numpy as np

                gaps = np.asarray(cache.pop("logit_gaps"))
                extra = {
                    "gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
                    "gap_p99": float(np.quantile(gaps, 0.99)),
                    "gap_nonzero": int((gaps > 0).sum()), "gap_n": int(gaps.size),
                }
            lat = seen["latencies_ms"]
            print(json.dumps({
                "seed": seen["seed"], "control": control,
                "p50_ms": stats.percentile(lat, 50), "p90_ms": stats.percentile(lat, 90),
                **{n["name"]: n["value"] for n in numbers}, **extra,
            }), flush=True)


def main() -> None:
    mode, cell, seconds = sys.argv[1], sys.argv[2], float(sys.argv[3])
    if mode == "sweep":
        sweep(cell, seconds, [float(x) for x in sys.argv[4:]])
    elif mode == "seeds":
        seeds(cell, seconds, [int(x) for x in sys.argv[4:]])
    else:
        sys.exit(__doc__)
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
