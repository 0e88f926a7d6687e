"""The generation scheduler on a churning trace (CPU harness row).

One Poisson-arrival request trace (bimodal output lengths — many short
completions, a few long generations — the serving mix continuous batching
exists for) replayed through ``serving.generation.GenerationScheduler``:
finished rows are evicted and queued requests admitted every decode step,
over the paged KV pool.

Reported tokens/s counts requested tokens over the trace makespan; TTFT
and per-request latency come from the per-request timestamps.  The
scheduler is fully warmed on a replay of the trace before the timed pass.
These are CPU timings: what the chip does is the ledger's (``PERF.md``).

Usage: ``python benchmarks/serving_generation.py [smoke|full]``.
Prints harness-protocol JSON lines (benchmarks/harness.py).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pct(values, q):
    if not values:
        return 0.0
    return float(np.percentile(np.asarray(values, np.float64), q))


def build_trace(seed: int, n_requests: int, mean_gap_s: float):
    """(arrival offset s, prompt ids, max_new) — Poisson arrivals, mixed
    prompt lengths, bimodal output lengths (1 in 4 long)."""
    rng = np.random.default_rng(seed)
    trace = []
    t = 0.0
    for i in range(n_requests):
        prompt = [int(x) for x in rng.integers(1, 500, int(rng.integers(2, 24)))]
        max_new = 48 if i % 4 == 0 else int(rng.integers(4, 10))
        trace.append((t, prompt, max_new))
        t += float(rng.exponential(mean_gap_s))
    return trace


def run_continuous(sched, trace):
    reqs = []
    t0 = time.perf_counter()
    for offset, prompt, max_new in trace:
        now = time.perf_counter() - t0
        if now < offset:
            time.sleep(offset - now)
        reqs.append(sched.submit_request(list(prompt), max_new_tokens=max_new))
    for r in reqs:
        r.future.result(timeout=300)
    # request timestamps are time.monotonic(); compute the makespan on
    # them alone rather than mixing clocks with perf_counter
    start = min(r.submitted_at for r in reqs)
    makespan = max(r.finished_at for r in reqs) - start
    ttfts_ms = [r.ttft_s * 1e3 for r in reqs if r.ttft_s is not None]
    lats_ms = [(r.finished_at - r.submitted_at) * 1e3 for r in reqs]
    return makespan, ttfts_ms, lats_ms


def main() -> None:
    mode = sys.argv[1] if len(sys.argv) > 1 else "smoke"
    from pathway_tpu.models.decoder import DecoderLM
    from pathway_tpu.serving.generation import GenerationScheduler

    if mode == "full":
        n_requests, mean_gap, slots = 64, 0.02, 8
    else:
        n_requests, mean_gap, slots = 24, 0.02, 6

    # eos_id=None: every row emits exactly its requested budget
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    trace = build_trace(18, n_requests, mean_gap)
    requested = sum(mn for _, _, mn in trace)

    sched = GenerationScheduler(
        lm, slots=slots, page_size=16, prefill_chunk=16,
        queue_limit=max(2 * n_requests, 64),
    )
    try:
        # warm: replay the trace once untimed so every bucketed program
        # (prefill shapes, table widths) is compiled before measurement
        run_continuous(sched, trace)
        cont_span, cont_ttfts, cont_lats = run_continuous(sched, trace)
    finally:
        sched.shutdown()

    metrics = {
        "serving_continuous_tokens_per_sec": round(requested / cont_span, 1),
        "serving_continuous_ttft_p50_ms": round(_pct(cont_ttfts, 50), 2),
        "serving_continuous_ttft_p95_ms": round(_pct(cont_ttfts, 95), 2),
        "serving_continuous_request_p99_ms": round(_pct(cont_lats, 99), 2),
    }
    for name, value in metrics.items():
        print(json.dumps({"metric": name, "value": value}))
    print(
        json.dumps(
            {
                "trace": {
                    "requests": n_requests,
                    "requested_tokens": requested,
                    "mean_gap_s": mean_gap,
                    "slots": slots,
                    "continuous_median_lat_ms": round(
                        statistics.median(cont_lats), 2
                    ),
                }
            }
        )
    )


if __name__ == "__main__":
    main()
