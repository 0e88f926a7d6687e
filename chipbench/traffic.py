"""The one traffic generator: reads a traffic mix's file of parameters
(``traffic_mixes/<traffic>.json``) and makes an open-loop schedule.

A schedule is a list of requests, each with the second it is due (from the
start of the window), its route and its JSON payload.  The gaps between
arrivals are exponential, drawn once from the mix's own ``draw_seed`` and
scaled to the window, so every ``--seed`` meets the same arrivals: where
requests queue, the order of the gaps is part of the work, and the seed
may not change the work.  The seed draws the questions; their lengths are
the same set for every seed, in another order (``text.spread``).
"""

from __future__ import annotations

import random

from chipbench import text


def due_times(arrivals: dict, seconds: float) -> list[float]:
    """``rate_per_s x seconds`` arrivals: the first at 0, exponential gaps
    that add up, the trailing one included, to ``seconds``."""
    n = max(1, int(round(float(arrivals["rate_per_s"]) * seconds)))
    rng = random.Random(int(arrivals["draw_seed"]))
    gaps = [rng.expovariate(1.0) for _ in range(n)]
    scale = seconds / sum(gaps)
    due, t = [], 0.0
    for gap in gaps:
        due.append(t)
        t += gap * scale
    return due


def make_schedule(
    mix: dict, seed: int, seconds: float, documents: list[str] | None = None,
    *, rate_per_s: float | None = None,
) -> list[dict]:
    """The requests of one window of ``mix`` (its file of parameters)."""
    arrivals = dict(mix["arrivals"])
    if rate_per_s is not None:
        arrivals["rate_per_s"] = rate_per_s
    due = due_times(arrivals, seconds)
    payload = mix["payload"]
    questions = text.make_questions(
        len(due), seed, tuple(payload["words"]),
        documents if payload.get("about_documents") else None,
    )
    return [
        {
            "due_s": t,
            "route": mix["route"],
            "payload": {payload["field"]: q, **payload.get("extra", {})},
        }
        for t, q in zip(due, questions)
    ]
