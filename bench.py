"""Headline benchmark: embeddings/sec/chip on the flagship sentence encoder.

BASELINE.md north star: >= 50k embeddings/sec/chip (MiniLM/BGE class).
Measures the sustained device throughput of the jit-compiled MiniLM-class
encoder on realistic chunk lengths (seq bucket 64, the document-chunk
regime the RAG pipeline runs in), after warmup, pre-tokenized — matching
how the reference separates host tokenization from model forward
(sentence-transformers tokenizes on CPU there too).

Also reports MFU: analytic encoder FLOPs (derived from the config) over
the chip's peak bf16 FLOP/s (``pathway_tpu.device.telemetry.peak_flops``;
an unknown device kind is an error there, not a default).

One process, one chip.  Prints exactly ONE JSON line, last:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "mfu": N,
   "platform": "tpu", "device_kind": ..., "device_count": N, ...extras}
and exits non-zero — with no JSON line — when JAX finds no TPU or when
any part of the measurement fails.
"""

from __future__ import annotations

import json
import os
import sys
import time

METRIC = "embeddings_per_sec_per_chip_minilm_seq64"
BASELINE_EMB_PER_SEC = 50_000.0
BATCH = 2048  # swept 512/1024/2048 on-chip: +9% sustained emb/s at 2048
# (same-window comparison, 2026-07-31); activations stay ~100 MB in HBM
SEQ = 64
WARMUP = 5
ITERS = 60
WINDOWS = 3  # report the best sustained window


def _analytic_flops_per_seq(cfg, seq: int) -> float:
    """Forward FLOPs for one padded sequence (2*m*n*k per matmul).

    Per token per layer: QKV+O projections 8*h^2, FFN 4*h*ffn, attention
    score/value einsums 4*seq*h. Embedding lookups/layernorms are noise.
    """
    h, ffn = cfg.hidden, cfg.intermediate
    per_token_layer = 8 * h * h + 4 * h * ffn + 4 * seq * h
    return float(cfg.layers * per_token_layer * seq)


def _measure_encoder(
    model_name: str, batch: int, iters: int, windows: int, warmup: int
):
    """Best-window throughput of the packed-bf16 jitted encoder.

    The production inference path: packed bf16 weights + pallas attention,
    tree passed as a runtime arg exactly like _JitModel does.  Every
    iteration hangs a scalar off the output and the window ends in one
    D2H fetch of their sum, so the timing covers execution, not enqueue.

    Returns (emb_per_sec, best_dt, cfg, fwd, params, ids, mask) — the jit
    artifacts are returned so callers (profile trace) can reuse them.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import (
        SentenceEncoderModule,
        config_for,
        fused_sentence_apply,
        pack_fast_params,
    )

    cfg = config_for(model_name)
    module = SentenceEncoderModule(cfg)
    params = module.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 16), jnp.int32),
        jnp.ones((1, 16), jnp.int32),
    )
    params = pack_fast_params(params, cfg)
    fwd = jax.jit(lambda t, i, m: fused_sentence_apply(t, i, m, cfg))

    host_rng = np.random.default_rng(0)
    ids = jnp.asarray(
        host_rng.integers(104, cfg.vocab_size, size=(batch, SEQ)), jnp.int32
    )
    mask = jnp.ones((batch, SEQ), jnp.int32)

    for _ in range(warmup):
        float(fwd(params, ids, mask).sum())

    emb_per_sec, best_dt = 0.0, 0.0
    for _ in range(windows):
        t0 = time.perf_counter()
        acc = None
        for _ in range(iters):
            out = fwd(params, ids, mask)
            s = out.sum()
            acc = s if acc is None else acc + s
        assert np.isfinite(float(acc))  # D2H of a scalar syncs the chain
        dt = time.perf_counter() - t0
        rate = batch * iters / dt
        if rate > emb_per_sec:
            emb_per_sec, best_dt = rate, dt
    return emb_per_sec, best_dt, cfg, fwd, params, ids, mask


def main() -> None:
    import jax

    from pathway_tpu.device.compile_cache import ensure_compile_cache
    from pathway_tpu.device.telemetry import peak_flops

    ensure_compile_cache()
    devs = jax.devices()
    print(f"devices: {devs}", file=sys.stderr)
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found platform {devs[0].platform!r}")
    peak, peak_source = peak_flops()

    emb_per_sec, best_dt, cfg, fwd, params, ids, mask = _measure_encoder(
        "all-MiniLM-L6-v2", BATCH, ITERS, WINDOWS, WARMUP
    )
    achieved = _analytic_flops_per_seq(cfg, SEQ) * emb_per_sec
    mfu = achieved / peak
    print(
        f"{BATCH}x{SEQ} x{ITERS} iters in {best_dt:.3f}s (best window) -> "
        f"{emb_per_sec:,.0f} emb/s, "
        f"{achieved/1e12:.1f} TFLOP/s on '{peak_source}' (peak {peak/1e12:.0f}) "
        f"-> MFU {mfu:.3f}",
        file=sys.stderr,
    )
    result = {
        "metric": METRIC,
        "value": round(emb_per_sec, 1),
        "unit": "embeddings/s",
        "vs_baseline": round(emb_per_sec / BASELINE_EMB_PER_SEC, 4),
        "mfu": round(mfu, 4),
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }
    # Secondary evidence.  A failing extra fails the run: a result with a
    # hole in it would read as a measurement.
    result["bge_mfu"] = _extra_bge_mfu(peak)
    result["retrieval_625k"] = _extra_retrieval_p50()
    result["profile_trace"] = _extra_profile_trace(fwd, params, ids, mask)
    result["int8_encoder"] = _extra_int8_encoder(fwd, params, ids, mask, emb_per_sec)
    # runs LAST: it starts a daemon engine thread that lives until
    # process exit, which must not sit under the other measurements
    result["retrieval_serving"] = _extra_retrieval_serving()
    print(json.dumps(result), flush=True)


def _extra_bge_mfu(peak: float) -> float:
    """Short BGE-base window: MFU of the bigger (compute-bound) encoder."""
    best, _, cfg, *_ = _measure_encoder(
        "bge-base-en-v1.5", batch=256, iters=20, windows=2, warmup=3
    )
    mfu = _analytic_flops_per_seq(cfg, SEQ) * best / peak
    print(f"bge-base: {best:,.0f} emb/s -> MFU {mfu:.3f}", file=sys.stderr)
    return round(mfu, 4)


def _extra_int8_encoder(fwd, params, ids, mask, bf16_emb_per_sec: float) -> dict:
    """W8A8 encoder window: int8×int8 matmuls run at 2× the bf16 MXU peak
    on v5e, so this measures the headroom past the bf16 headline — plus
    the embedding cosine agreement that prices the rounding.

    Reuses the HEADLINE jit and shapes: the float reference program is
    already warm, so the int8 program at the same shape is the only new
    compile this extra pays.
    """
    import time as _time

    import numpy as np

    from pathway_tpu.models.encoder import quantize_encoder_tree

    qtree = quantize_encoder_tree(params)
    got = np.asarray(fwd(qtree, ids, mask), np.float32)  # compiles int8 prog
    ref = np.asarray(fwd(params, ids, mask), np.float32)  # warm from headline
    cos = (ref * got).sum(-1)
    # sustained window, same shape as the headline
    iters = 30
    best = 0.0
    for _ in range(2):
        t0 = _time.perf_counter()
        acc = None
        for _ in range(iters):
            out = fwd(qtree, ids, mask)
            s = out[0, 0]
            acc = s if acc is None else acc + s
        assert np.isfinite(float(acc)), "non-finite int8 encoder output"
        dt = _time.perf_counter() - t0
        best = max(best, ids.shape[0] * iters / dt)
    print(
        f"int8 encoder: {best:,.0f} emb/s ({best / max(bf16_emb_per_sec, 1):.2f}x "
        f"bf16), cos min {cos.min():.4f}",
        file=sys.stderr,
    )
    return {
        "emb_per_sec": round(best, 1),
        "vs_bf16": round(best / max(bf16_emb_per_sec, 1.0), 3),
        "cos_min": round(float(cos.min()), 4),
        "cos_mean": round(float(cos.mean()), 4),
    }


def _extra_retrieval_p50() -> dict:
    """Top-k DEVICE time at the 625k-docs/chip north-star shard.

    The corpus matrix is generated ON DEVICE (bf16, the resident format):
    the per-query device time of the jitted masked-top-k kernel is the
    number the <20 ms north-star budget is about.  The serving-path wall
    latency is ``_extra_retrieval_serving``'s.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops import topk as topk_ops

    # mirror DeviceIndexCache's SINGLE-CHIP resident format: padded to the
    # next power of two (an unpadded 625k = 2^3·5^6 corpus would collapse
    # the two-stage block top-k's block size and silently time the
    # full-sort fallback), bf16.  This is the
    # per-chip shard of the north-star layout — the multi-chip path is a
    # different program (shard_map sharded_topk) and is exercised by the
    # sharded-retrieval tests and dryrun, not timed here.
    n_docs, cap = 625_000, 1 << 20
    key = jax.random.PRNGKey(0)
    docs = jax.random.normal(key, (cap, 384), jnp.bfloat16)
    mask = jnp.where(jnp.arange(cap) < n_docs, 0.0, -jnp.inf).astype(jnp.float32)
    qs = jax.random.normal(jax.random.PRNGKey(1), (64, 384), jnp.float32)
    qs = qs / jnp.linalg.norm(qs, axis=1, keepdims=True)
    kernel = topk_ops.masked_topk_jitted()
    dev_qs = [qs[j][None, :] for j in range(64)]
    np.asarray(kernel(docs, mask, dev_qs[0], metric="ip", k=10)[0])  # warm + compile
    t0 = time.perf_counter()
    outs = [kernel(docs, mask, q, metric="ip", k=10)[1] for q in dev_qs]
    np.asarray(jnp.concatenate(outs))  # one D2H sync for the whole chain
    device_ms = (time.perf_counter() - t0) * 1000.0 / len(dev_qs)
    print(
        f"retrieval at 625k docs: device {device_ms:.3f} ms/query",
        file=sys.stderr,
    )
    return {"device_ms_per_query": round(device_ms, 3)}


def _extra_retrieval_serving() -> dict:
    """Full serving-path latency at the 625k-docs/chip north-star shard:
    REST ingress → engine epoch → query embed → cached device search →
    k-merge → JSON response, stage-clocked on the serving host
    (benchmarks/retrieval_serving.py; VERDICT r4 weak #2)."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    from retrieval_serving import measure

    out = measure(625_000, n_queries=40, n_warmup=6)
    print(
        f"retrieval serving: colocated p50 {out['colocated_p50_ms']} ms "
        f"(host {out['host_other_p50_ms']} + embed {out['embed_device_ms']} "
        f"+ search {out['search_device_ms']})",
        file=sys.stderr,
    )
    return out


def _extra_profile_trace(fwd, params, ids, mask) -> str:
    """Capture a device profile of the headline loop as evidence."""
    import jax

    trace_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks", "traces", "bench"
    )
    os.makedirs(trace_dir, exist_ok=True)
    with jax.profiler.trace(trace_dir):
        for _ in range(5):
            float(fwd(params, ids, mask).sum())
    return trace_dir


if __name__ == "__main__":
    main()
