"""Decoder-LLM serving throughput: prefill tokens/s and decode tokens/s.

Measures the two compiled programs JaxChat serving runs on
(``models/decoder.py``): bucketed prefill over a prompt batch, and
``decode_chunk`` — 16 sample→decode steps fused into one device program.
Decode is timed exactly as ``DecoderLM.generate_ids`` dispatches it:
chunk_len-step programs with one host sync per chunk, so the reported
tokens/s INCLUDES the per-chunk dispatch + sync cost serving pays.

Model shape: tinyllama-1.1b class on TPU (2.2 GB bf16 — deterministic
random weights, throughput is weight-independent); self-scales down on
CPU so CI can sanity-check the harness.

Prints one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax

    from pathway_tpu.device.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jax.numpy as jnp

    from pathway_tpu.models.decoder import (
        DecoderLM,
        decode_chunk,
        prefill,
        quantize_decoder_tree,
        speculative_decode_chunk,
    )

    platform = jax.devices()[0].platform
    if platform == "tpu":
        model, batch, prompt_len, steps, cache = "tinyllama-1.1b", 8, 512, 64, 1024
    else:
        model, batch, prompt_len, steps, cache = "pw-tiny-decoder", 4, 32, 16, 64

    lm = DecoderLM(model, max_cache=cache, eos_id=None)
    cfg = lm.config
    rng = np.random.default_rng(0)
    ids = rng.integers(1, cfg.vocab_size, size=(batch, prompt_len)).astype(np.int32)
    lens = jnp.full((batch,), prompt_len, jnp.int32)

    chunk_len = lm._chunk_len  # the bucket size generate_ids dispatches
    assert steps % chunk_len == 0
    pre = jax.jit(lambda t, i, l: prefill(t, i, l, cfg, cache))
    chunk = jax.jit(
        lambda t, kc, vc, lg, pos, done, key, temp: decode_chunk(
            t, kc, vc, lg, pos, done, key, temp, cfg, chunk_len, True, None
        )
    )

    # warm both programs, then time prefill with a scalar-fetch sync
    logits, kc, vc = pre(lm.params, jnp.asarray(ids), lens)
    float(logits.sum())
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        logits, kc, vc = pre(lm.params, jnp.asarray(ids), lens)
        float(logits.sum())
    prefill_tok_s = batch * prompt_len * reps / (time.perf_counter() - t0)

    # decode: chunk_len-step decode_chunk programs with one host sync per
    # chunk — exactly the dispatch pattern DecoderLM.generate_ids serves
    # through (so per-chunk dispatch + sync costs are measured, not hidden)
    done = jnp.zeros((batch,), bool)
    key = jax.random.PRNGKey(0)
    temp = jnp.float32(1.0)
    n_chunks = steps // chunk_len

    def time_decode(tree):
        """(tokens/s, wall) of the full chunked decode chain for ``tree``."""
        toks, *_ = chunk(tree, kc, vc, logits, lens, done, key, temp)
        np.asarray(toks)  # warm + sync
        lg, kc2, vc2, pos2, done2, key2 = logits, kc, vc, lens, done, key
        total = 0
        t0 = time.perf_counter()
        for _ in range(n_chunks):
            toks, valids, lg, kc2, vc2, pos2, done2, key2 = chunk(
                tree, kc2, vc2, lg, pos2, done2, key2, temp
            )
            np.asarray(toks), np.asarray(done2)  # per-chunk host sync
            total += int(toks.shape[0])
        dt = time.perf_counter() - t0
        assert total == steps
        return batch * total / dt, dt

    decode_tok_s, dt = time_decode(lm.params)
    # weight-only int8: same chunked dispatch, half the HBM weight bytes
    # per decode sweep
    qtree = quantize_decoder_tree(lm.params)
    decode_tok_s_int8, _ = time_decode(qtree)

    # self-speculative greedy: int8 draft, float verify — exact float
    # chain at (ideally) near-int8 cost; tokens/round is data-dependent,
    # so run rounds until `steps` tokens/row are accepted
    n_draft = 8
    spec = jax.jit(
        lambda t, d, c1, c2, lg, ps: speculative_decode_chunk(
            t, d, c1, c2, lg, ps, cfg, n_draft
        )
    )
    toks, n, *_ = spec(lm.params, qtree, kc, vc, logits, lens)
    np.asarray(toks)  # warm + sync
    lg, kc2, vc2, pos2 = logits, kc, vc, lens
    # bound rounds so even a row accepting n_draft every round stays
    # inside the cache (overflow writes would be silently dropped and
    # corrupt the measurement)
    max_rounds = min(steps // n_draft, (cache - prompt_len) // n_draft - 1)
    assert max_rounds >= 1
    accepted = rounds = 0
    t0 = time.perf_counter()
    while accepted < steps * batch and rounds < max_rounds:
        toks, n, lg, kc2, vc2, pos2 = spec(lm.params, qtree, kc2, vc2, lg, pos2)
        accepted += int(np.asarray(n).sum())
        rounds += 1
    spec_tok_s = accepted / (time.perf_counter() - t0)
    mean_accept = accepted / max(rounds * batch, 1)

    n_params = lm.n_params()
    print(
        json.dumps(
            {
                "metric": "decoder_serving_throughput",
                "model": model,
                "n_params": n_params,
                "batch": batch,
                "prefill_tokens_per_sec": round(prefill_tok_s, 1),
                "decode_tokens_per_sec": round(decode_tok_s, 1),
                "decode_tokens_per_sec_int8": round(decode_tok_s_int8, 1),
                "decode_tokens_per_sec_speculative": round(spec_tok_s, 1),
                "speculative_mean_accept": round(mean_accept, 2),
                "decode_ms_per_token_per_seq": round(dt / steps * 1000.0, 3),
                "platform": platform,
            }
        )
    )
    # harness-protocol lines (benchmarks/harness.py): one {metric, value}
    # per number so the bench baseline carries decoder throughput too
    for name, value in (
        ("decoder_prefill_tokens_per_sec", prefill_tok_s),
        ("decoder_decode_tokens_per_sec", decode_tok_s),
        ("decoder_decode_int8_tokens_per_sec", decode_tok_s_int8),
        ("decoder_decode_speculative_tokens_per_sec", spec_tok_s),
        ("decoder_decode_ms_per_token", dt / steps * 1000.0),
    ):
        print(json.dumps({"metric": name, "value": round(value, 3)}))


if __name__ == "__main__":
    main()
