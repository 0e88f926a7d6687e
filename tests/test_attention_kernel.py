"""Pallas encoder-attention kernel and the fused inference forward.

The kernel runs compiled on TPU; under the CPU test mesh it is exercised in
interpret mode and the product wrapper falls back to the XLA path, so these
tests validate both implementations against each other and the fused
forward against the Flax module lowering.
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from pathway_tpu.models.encoder import (  # noqa: E402
    CrossEncoderModule,
    SentenceEncoder,
    SentenceEncoderModule,
    config_for,
    fused_cross_apply,
    fused_sentence_apply,
    pack_fast_params,
)
from pathway_tpu.ops.attention import (  # noqa: E402
    _supported,
    _xla_attention,
    encoder_attention,
)


def _rand_qkv(rng, B, S, H):
    q = jnp.asarray(rng.normal(size=(B, S, H)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, H)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, H)), jnp.bfloat16)
    return q, k, v


@pytest.mark.parametrize(
    "B,S,H,heads",
    [
        (4, 64, 384, 12),  # MiniLM chunk shape
        (2, 128, 768, 12),  # BGE-base
        (8, 16, 384, 12),  # tiny bucket
        (1, 256, 1024, 16),  # mxbai-large
        (3, 64, 384, 12),  # batch not divisible by block -> bb falls to 1
        # the shapes chip_smoke.py compiles for real on the TPU
        (1, 512, 768, 12),  # longest bucket: bb = 1, rows = 512
        (1, 512, 1024, 16),
        (1, 16, 768, 12),  # smallest block: one (16, H) tile
        (2, 256, 768, 12),
    ],
)
def test_kernel_matches_xla(B, S, H, heads):
    rng = np.random.default_rng(0)
    q, k, v = _rand_qkv(rng, B, S, H)
    mask = np.zeros((B, S), np.float32)
    mask[:, int(S * 0.8) :] = -1e9  # padded tail keys
    mask = jnp.asarray(mask)
    ref = _xla_attention(q, k, v, mask, heads)
    out = encoder_attention(q, k, v, mask, heads, interpret=True)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
    assert err < 0.05, err


def test_kernel_respects_key_mask():
    """A masked key must not influence any query's context."""
    rng = np.random.default_rng(1)
    B, S, H, heads = 2, 64, 384, 12
    q, k, v = _rand_qkv(rng, B, S, H)
    mask = np.zeros((B, S), np.float32)
    mask[:, 32:] = -1e9
    out1 = encoder_attention(q, k, v, jnp.asarray(mask), heads, interpret=True)
    # perturb masked-out keys/values wildly; output must be unchanged
    k2 = k.at[:, 32:, :].set(99.0)
    v2 = v.at[:, 32:, :].set(-99.0)
    out2 = encoder_attention(q, k2, v2, jnp.asarray(mask), heads, interpret=True)
    err = float(jnp.max(jnp.abs(out1.astype(jnp.float32) - out2.astype(jnp.float32))))
    assert err < 1e-3, err


def test_kernel_no_cross_sequence_leakage():
    """Kernel blocks pack several sequences; row s must only see keys of s."""
    rng = np.random.default_rng(2)
    B, S, H, heads = 8, 16, 384, 12  # bb packs 8 sequences per program
    q, k, v = _rand_qkv(rng, B, S, H)
    mask = jnp.zeros((B, S), jnp.float32)
    full = encoder_attention(q, k, v, mask, heads, interpret=True)
    # sequence 0 computed alone must equal sequence 0 computed in the batch
    solo = encoder_attention(q[:1], k[:1], v[:1], mask[:1], heads, interpret=True)
    err = float(
        jnp.max(jnp.abs(full[0].astype(jnp.float32) - solo[0].astype(jnp.float32)))
    )
    assert err < 1e-3, err


def test_unsupported_shape_is_counted_not_silent():
    """Where the kernel would run (TPU, or interpret mode here), a shape
    it rejects still computes through XLA — but the trace is counted, so
    a chip run cannot mistake the XLA path for the kernel."""
    from pathway_tpu.device import DeviceExecutor

    rng = np.random.default_rng(4)
    B, S, H, heads = 2, 16, 128, 8  # head_dim 16: not a supported width
    assert not _supported(S, H, heads)
    q, k, v = _rand_qkv(rng, B, S, H)
    mask = jnp.zeros((B, S), jnp.float32)
    ex = DeviceExecutor(collector_name=None)
    label = f"S{S}_H{H}_heads{heads}"
    before = ex.device_snapshot()["attention_xla_fallback"].get(label, 0)
    out = encoder_attention(q, k, v, mask, heads, interpret=True)
    ref = _xla_attention(q, k, v, mask, heads)
    assert jnp.array_equal(out, ref)
    after = ex.device_snapshot()["attention_xla_fallback"]
    assert after[label] == before + 1
    # off-TPU without interpret the XLA path is the only path: not counted
    encoder_attention(q, k, v, mask, heads)
    assert ex.device_snapshot()["attention_xla_fallback"][label] == before + 1


def test_supported_predicate():
    assert _supported(64, 384, 12)
    assert _supported(128, 768, 12)
    assert not _supported(64, 384, 5)  # H % heads != 0
    assert not _supported(64, 100, 4)  # H % 128 != 0


def test_fused_sentence_matches_module():
    cfg = config_for("all-MiniLM-L6-v2")
    module = SentenceEncoderModule(cfg)
    params = module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32), jnp.ones((1, 16), jnp.int32)
    )
    tree = pack_fast_params(params, cfg)
    rng = np.random.default_rng(0)
    B, S = 4, 64
    ids = jnp.asarray(rng.integers(104, cfg.vocab_size, size=(B, S)), jnp.int32)
    mask = np.ones((B, S), np.int32)
    mask[2, 40:] = 0
    mask[3, 10:] = 0
    mask = jnp.asarray(mask)
    ref = np.asarray(module.apply(params, ids, mask), np.float32)
    out = np.asarray(fused_sentence_apply(tree, ids, mask, cfg), np.float32)
    cos = np.sum(ref * out, axis=1) / (
        np.linalg.norm(ref, axis=1) * np.linalg.norm(out, axis=1)
    )
    assert cos.min() > 0.999, cos


def test_fused_cross_preserves_ranking():
    cfg = config_for("cross-encoder/ms-marco-MiniLM-L-6-v2")
    module = CrossEncoderModule(cfg)
    params = module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32), jnp.ones((1, 16), jnp.int32)
    )
    tree = pack_fast_params(params, cfg)
    rng = np.random.default_rng(3)
    B, S = 8, 32
    ids = jnp.asarray(rng.integers(104, cfg.vocab_size, size=(B, S)), jnp.int32)
    mask = jnp.ones((B, S), jnp.int32)
    ref = np.asarray(module.apply(params, ids, mask), np.float32)
    out = np.asarray(fused_cross_apply(tree, ids, mask, cfg), np.float32)
    assert np.max(np.abs(ref - out)) < 0.05 * (np.max(np.abs(ref)) + 1.0)


def test_sentence_encoder_end_to_end_uses_fused_path():
    enc = SentenceEncoder("all-MiniLM-L6-v2")
    embs = enc.encode(["hello world", "a longer sentence about streaming dataflow"])
    assert embs.shape == (2, 384)
    norms = np.linalg.norm(embs, axis=1)
    assert np.allclose(norms, 1.0, atol=1e-3)


def test_set_params_refreshes_fused_tree():
    """Weight replacement must reach the fused inference path, not serve a
    stale packed tree."""
    enc = SentenceEncoder("all-MiniLM-L6-v2")
    before = enc.encode(["a sentence"])
    new_params = enc.module.init(
        jax.random.PRNGKey(123),
        jnp.zeros((1, 16), jnp.int32),
        jnp.ones((1, 16), jnp.int32),
    )
    enc.set_params(new_params)
    after = enc.encode(["a sentence"])
    assert not np.allclose(before, after, atol=1e-3)


def test_encode_chunks_across_max_batch():
    """Batches beyond max_batch split into bucketed chunks whose results
    concatenate exactly (order preserved, no padding rows leaking)."""
    enc = SentenceEncoder("all-MiniLM-L6-v2", max_batch=8)
    texts = [f"sentence number {i} about topic {i % 5}" for i in range(19)]
    full = enc.encode(texts)
    assert full.shape == (19, 384)
    # per-chunk equality with one-at-a-time encodes
    for i in (0, 7, 8, 15, 18):
        solo = enc.encode([texts[i]])[0]
        cos = float(full[i] @ solo)
        assert cos > 0.9999, (i, cos)


def test_encode_mixed_lengths_bucket_by_longest():
    enc = SentenceEncoder("all-MiniLM-L6-v2")
    short = "hi"
    long = " ".join(["tok"] * 120)  # crosses into the 128 seq bucket
    both = enc.encode([short, long])
    solo_short = enc.encode([short])[0]
    # same text must embed identically regardless of batch companions up
    # to padding-bucket effects; cosine must stay essentially 1
    cos = float(both[0] @ solo_short)
    assert cos > 0.999, cos
