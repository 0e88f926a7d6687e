"""Weight-only int8 quantized decoder serving (models/decoder.py).

Pinned: quantized logits track the float model closely (per-output-
channel symmetric scales), generation runs end to end deterministically,
MoE expert weights quantize too, and the quantization round-trips the
weights within one scale step.
"""

import jax
import jax.numpy as jnp
import numpy as np

from pathway_tpu.models.decoder import (
    DecoderLM,
    causal_lm_logits,
    decoder_config_for,
    init_decoder_params,
    quantize_decoder_tree,
)
from tests.decoder_oracle import generate_ids, reference_greedy

CFG = decoder_config_for("pw-tiny-decoder")


def _rel_err(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12)


def test_quantized_weights_roundtrip_within_scale():
    tree = init_decoder_params(CFG, seed=0)
    q = quantize_decoder_tree(tree)
    w = np.asarray(tree["layers"]["wq"], np.float32)
    deq = np.asarray(q["layers"]["wq"]["q"], np.float32) * np.asarray(
        q["layers"]["wq"]["s"]
    )
    scale = np.asarray(q["layers"]["wq"]["s"])
    assert np.all(np.abs(deq - w) <= 0.5 * scale + 1e-8)
    # norms/embed stay untouched
    assert q["layers"]["ln0"] is tree["layers"]["ln0"]
    assert q["embed"] is tree["embed"]


def test_quantized_logits_track_float():
    tree = init_decoder_params(CFG, seed=1)
    q = quantize_decoder_tree(tree)
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(1, CFG.vocab_size, size=(4, 12)), jnp.int32)
    lens = jnp.full((4,), 12, jnp.int32)
    want = causal_lm_logits(tree, ids, lens, CFG)
    got = causal_lm_logits(q, ids, lens, CFG)
    assert _rel_err(got, want) < 0.05, _rel_err(got, want)
    # greedy next-token choice overwhelmingly agrees
    agree = (np.argmax(np.asarray(got), -1) == np.argmax(np.asarray(want), -1)).mean()
    assert agree > 0.9, agree


def test_quantized_moe_logits_track_float():
    cfg = decoder_config_for("pw-tiny-moe-decoder")
    tree = init_decoder_params(cfg, seed=2)
    q = quantize_decoder_tree(tree)
    assert isinstance(q["layers"]["wg"], dict)
    assert q["layers"]["moe_router"] is tree["layers"]["moe_router"]  # f32 routing
    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(2, 8)), jnp.int32)
    lens = jnp.full((2,), 8, jnp.int32)
    want = causal_lm_logits(tree, ids, lens, cfg, serving=True)[:, -1]
    got = causal_lm_logits(q, ids, lens, cfg, serving=True)[:, -1]
    assert _rel_err(got, want) < 0.07, _rel_err(got, want)


def test_quantized_generation_end_to_end():
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None, quantize="int8")
    assert lm.quantized
    prompts = [[5, 9, 3], [7]]
    out1 = generate_ids(lm, prompts, max_new_tokens=6)
    out2 = generate_ids(lm, prompts, max_new_tokens=6)
    assert out1 == out2
    assert all(len(o) == 6 for o in out1)
    # int8 weights through the scheduler's paged programs mostly track
    # the float tree's full-forward greedy chain
    ref = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    out_f = [reference_greedy(ref, p, 6) for p in prompts]
    matches = sum(
        a == b for qrow, frow in zip(out1, out_f) for a, b in zip(qrow, frow)
    )
    assert matches >= 8, (out1, out_f)  # 12 tokens total; greedy chains can drift


def test_quantize_rejects_unknown_mode():
    import pytest

    with pytest.raises(ValueError, match="int8"):
        DecoderLM("pw-tiny-decoder", quantize="fp4")
