"""Mixtral-class MoE decoder family (models/decoder.py, cfg.experts > 0).

Parity target: the reference's Adaptive RAG serves the dense Mistral
sibling via HFPipelineChat (xpacks/llm/llms.py:314); the MoE variant is
TPU-native here.  Pinned:
  * identical experts degenerate exactly to the dense decoder,
  * generation is deterministic and finite,
  * the paged programs' cache holds the full forward's logits for MoE
    layers,
  * the causal-LM train step (with load-balance aux) learns,
  * expert-parallel serving (tp specs over a "model" axis) matches
    unsharded execution.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding

from pathway_tpu.models.decoder import (
    DecoderLM,
    causal_lm_logits,
    causal_lm_logits_and_aux,
    decoder_config_for,
    init_decoder_params,
    tp_param_specs,
)
from tests.decoder_oracle import generate_ids, paged_logits

MOE_CFG = decoder_config_for("pw-tiny-moe-decoder")


def _ids(rng, b=4, s=10, cfg=MOE_CFG):
    ids = rng.integers(1, cfg.vocab_size, size=(b, s)).astype(np.int32)
    lengths = rng.integers(s // 2, s + 1, size=(b,)).astype(np.int32)
    return jnp.asarray(ids), jnp.asarray(lengths)


def test_identical_experts_match_dense_decoder():
    cfg = dataclasses.replace(MOE_CFG, expert_capacity_factor=16.0)
    dense_cfg = dataclasses.replace(cfg, experts=0)
    dense = init_decoder_params(dense_cfg, seed=0)
    moe = init_decoder_params(cfg, seed=0)
    # share attention/embed weights; collapse every expert onto the dense MLP
    for name in ("embed", "final_norm", "lm_head"):
        moe[name] = dense[name]
    for name in ("ln0", "ln1", "wq", "wk", "wv", "wo"):
        moe["layers"][name] = dense["layers"][name]
    for name in ("wg", "wu", "wd"):
        moe["layers"][name] = jnp.broadcast_to(
            dense["layers"][name][:, None], moe["layers"][name].shape
        )
    ids, lengths = _ids(np.random.default_rng(0))
    want = causal_lm_logits(dense, ids, lengths, dense_cfg)
    got, aux = causal_lm_logits_and_aux(moe, ids, lengths, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
    assert np.isfinite(float(aux)) and float(aux) > 0.0


def test_moe_prefill_decode_cache_consistency():
    """Paged prefill, then decode steps, must equal the full forward at
    every position they cover."""
    tree = init_decoder_params(MOE_CFG, seed=1)
    rng = np.random.default_rng(1)
    B, S = 2, 8
    full = rng.integers(1, MOE_CFG.vocab_size, size=(B, S + 1)).astype(np.int32)
    want = causal_lm_logits(
        tree, jnp.asarray(full), jnp.full((B,), S + 1, jnp.int32), MOE_CFG, serving=True
    )
    got = paged_logits(tree, MOE_CFG, full, S - 2)
    np.testing.assert_allclose(
        got, np.asarray(want)[:, S - 3:], rtol=2e-4, atol=2e-4
    )


def test_moe_decoder_generates_deterministically():
    lm = DecoderLM("pw-tiny-moe-decoder", max_cache=64)
    assert lm.config.experts == 4
    out1 = generate_ids(lm, [[5, 9, 3], [7]], max_new_tokens=6)
    out2 = generate_ids(lm, [[5, 9, 3], [7]], max_new_tokens=6)
    assert out1 == out2
    assert all(len(o) <= 6 for o in out1)
    assert all(0 <= t < lm.config.vocab_size for o in out1 for t in o)


def test_moe_train_step_learns():
    from pathway_tpu.parallel.mesh import make_mesh
    from pathway_tpu.parallel.train import make_causal_lm_train_step

    init_state, run = make_causal_lm_train_step(
        MOE_CFG, optax.adam(1e-2), make_mesh(1)
    )
    state = init_state(seed=0)
    rng = np.random.default_rng(2)
    ids, lengths = _ids(rng, b=8, s=12)
    losses = []
    for _ in range(8):
        state, loss = run(state, np.asarray(ids), np.asarray(lengths))
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_moe_expert_parallel_serving_matches_unsharded():
    tree = init_decoder_params(MOE_CFG, seed=3)
    ids, lengths = _ids(np.random.default_rng(3), b=2, s=6)
    forward = jax.jit(
        lambda t, i, l: causal_lm_logits(t, i, l, MOE_CFG, serving=True)
    )
    want = forward(tree, ids, lengths)

    # axis size 2: divides the experts
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("model",))
    specs = tp_param_specs(MOE_CFG)
    sharded = jax.tree_util.tree_map(
        lambda t, s: jax.device_put(t, NamedSharding(mesh, s)), tree, specs
    )
    got = forward(sharded, ids, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
    # the serving programs over the expert-parallel tree: prefill, then
    # two decode steps
    steps = paged_logits(sharded, MOE_CFG, np.asarray(ids), 4)
    np.testing.assert_allclose(
        steps, paged_logits(tree, MOE_CFG, np.asarray(ids), 4), rtol=2e-4, atol=2e-4
    )
