"""LoRA adapters (models/lora.py).

Pinned: zero-init adapters leave the model EXACTLY equal to the base;
training moves only the adapters (base frozen bit-for-bit) and reduces
the loss; merge_lora folds the update back into plain weights; adapted
trees generate through the serving path unchanged.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pathway_tpu.models.decoder import (
    DecoderLM,
    causal_lm_logits,
    decoder_config_for,
    init_decoder_params,
)
from pathway_tpu.models.lora import (
    lora_decoder_tree,
    lora_mask,
    make_lora_train_step,
    merge_lora,
)
from pathway_tpu.parallel.mesh import make_mesh
from tests.decoder_oracle import generate_ids

CFG = decoder_config_for("pw-tiny-decoder")


def _ids(rng, b=4, s=10):
    ids = rng.integers(1, CFG.vocab_size, size=(b, s)).astype(np.int32)
    lens = np.full(b, s, np.int32)
    return jnp.asarray(ids), jnp.asarray(lens)


def test_zero_init_equals_base():
    base = init_decoder_params(CFG, seed=0)
    lora = lora_decoder_tree(base, CFG, rank=4)
    ids, lens = _ids(np.random.default_rng(0))
    np.testing.assert_array_equal(
        np.asarray(causal_lm_logits(lora, ids, lens, CFG)),
        np.asarray(causal_lm_logits(base, ids, lens, CFG)),
    )


def test_training_moves_only_adapters_and_learns():
    base = init_decoder_params(CFG, seed=1)
    mesh = make_mesh(8)
    init_state, run = make_lora_train_step(
        CFG, base, optax.adam(1e-2), mesh, rank=4, targets=("wq", "wv", "wo")
    )
    state = init_state()
    rng = np.random.default_rng(1)
    ids, lens = _ids(rng, b=8, s=12)
    losses = []
    for _ in range(8):
        state, loss = run(state, np.asarray(ids), np.asarray(lens))
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
    # frozen base identical; adapters moved
    for name in ("wq", "wv", "wo"):
        leaf = state.params["layers"][name]
        np.testing.assert_array_equal(
            np.asarray(leaf["w"]), np.asarray(base["layers"][name])
        )
        assert float(np.abs(np.asarray(leaf["b"])).max()) > 0.0
    np.testing.assert_array_equal(
        np.asarray(state.params["layers"]["wk"]), np.asarray(base["layers"]["wk"])
    )
    np.testing.assert_array_equal(
        np.asarray(state.params["embed"]), np.asarray(base["embed"])
    )


def test_merge_matches_adapted_forward():
    base = init_decoder_params(CFG, seed=2)
    lora = lora_decoder_tree(base, CFG, rank=4, seed=3)
    # give the adapters a real update so the merge is non-trivial
    lora["layers"]["wq"]["b"] = (
        jax.random.normal(jax.random.PRNGKey(4), lora["layers"]["wq"]["b"].shape)
        * 0.02
    ).astype(lora["layers"]["wq"]["b"].dtype)
    ids, lens = _ids(np.random.default_rng(2))
    want = causal_lm_logits(lora, ids, lens, CFG)
    merged = merge_lora(lora)
    assert not isinstance(merged["layers"]["wq"], dict)
    got = causal_lm_logits(merged, ids, lens, CFG)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_adapted_tree_serves_through_generate():
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    want = generate_ids(lm, [[3, 5, 7]], max_new_tokens=5)
    lm.params = lora_decoder_tree(lm.params, CFG, rank=4)
    got = generate_ids(lm, [[3, 5, 7]], max_new_tokens=5)
    assert got == want  # zero-init adapters: identical serving behavior


def test_mask_marks_only_adapters():
    base = init_decoder_params(CFG, seed=5)
    lora = lora_decoder_tree(base, CFG, rank=2)
    mask = lora_mask(lora)
    assert mask["layers"]["wq"]["a"] is True
    assert mask["layers"]["wq"]["b"] is True
    assert mask["layers"]["wq"]["w"] is False
    assert mask["embed"] is False


def test_quantize_rejects_adapted_trees():
    from pathway_tpu.models.decoder import quantize_decoder_tree

    base = init_decoder_params(CFG, seed=7)
    lora = lora_decoder_tree(base, CFG, rank=2)
    with pytest.raises(ValueError, match="merge_lora"):
        quantize_decoder_tree(lora)
    # merged trees quantize fine
    assert isinstance(quantize_decoder_tree(merge_lora(lora))["layers"]["wq"], dict)


def test_moe_mlp_targets_rejected():
    cfg = decoder_config_for("pw-tiny-moe-decoder")
    tree = init_decoder_params(cfg, seed=6)
    with pytest.raises(ValueError, match="MoE"):
        lora_decoder_tree(tree, cfg, targets=("wq", "wd"))
    # attention-only targets work on MoE configs
    adapted = lora_decoder_tree(tree, cfg, targets=("wq", "wv"))
    assert isinstance(adapted["layers"]["wq"], dict)
