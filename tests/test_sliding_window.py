"""Sliding-window attention (DecoderConfig.sliding_window, Mistral v0.1).

Pinned: window ≥ sequence degenerates to full causal attention, a tight
window actually changes (and localizes) attention, the paged programs'
cache holds the full forward's logits under the window, and the pipelined
trunk applies the same mask.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np

from pathway_tpu.models.decoder import (
    DecoderConfig,
    causal_lm_logits,
    init_decoder_params,
)
from tests.decoder_oracle import paged_logits

BASE = DecoderConfig(
    vocab_size=128, hidden=32, layers=2, heads=4, kv_heads=2,
    intermediate=64, max_len=64, dtype=jnp.float32,
)


def _ids(rng, b=2, s=16):
    ids = rng.integers(1, BASE.vocab_size, size=(b, s)).astype(np.int32)
    lens = np.full(b, s, np.int32)
    return jnp.asarray(ids), jnp.asarray(lens)


def test_wide_window_equals_full_attention():
    cfg = dataclasses.replace(BASE, sliding_window=64)
    tree = init_decoder_params(BASE, seed=0)
    ids, lens = _ids(np.random.default_rng(0))
    full = causal_lm_logits(tree, ids, lens, BASE)
    windowed = causal_lm_logits(tree, ids, lens, cfg)
    np.testing.assert_allclose(np.asarray(windowed), np.asarray(full), rtol=1e-6)


def test_tight_window_changes_and_localizes():
    cfg = dataclasses.replace(BASE, sliding_window=4)
    tree = init_decoder_params(BASE, seed=1)
    rng = np.random.default_rng(1)
    ids, lens = _ids(rng)
    full = np.asarray(causal_lm_logits(tree, ids, lens, BASE))
    win = np.asarray(causal_lm_logits(tree, ids, lens, cfg))
    assert not np.allclose(win[:, -1], full[:, -1], atol=1e-3)
    # locality: with one layer of window-4 attention, position 10's output
    # cannot see position <= 6 — perturbing position 2 leaves it unchanged
    one_layer = dataclasses.replace(cfg, layers=1)
    tree1 = init_decoder_params(one_layer, seed=2)
    ids2 = np.asarray(ids).copy()
    ids2[:, 2] = (ids2[:, 2] + 7) % 120 + 1
    a = np.asarray(causal_lm_logits(tree1, ids, lens, one_layer))
    b = np.asarray(causal_lm_logits(tree1, jnp.asarray(ids2), lens, one_layer))
    np.testing.assert_allclose(a[:, 10], b[:, 10], rtol=1e-6)
    assert not np.allclose(a[:, 3], b[:, 3], atol=1e-4)


def test_swa_prefill_decode_consistency():
    cfg = dataclasses.replace(BASE, sliding_window=5)
    tree = init_decoder_params(cfg, seed=3)
    rng = np.random.default_rng(3)
    B, S = 2, 12
    full = rng.integers(1, cfg.vocab_size, size=(B, S + 1)).astype(np.int32)
    want = causal_lm_logits(
        tree, jnp.asarray(full), jnp.full((B,), S + 1, jnp.int32), cfg, serving=True
    )
    # a prefix longer than the window prefilled, the rest decoded
    got = paged_logits(tree, cfg, full, 7)
    np.testing.assert_allclose(got, np.asarray(want)[:, 6:], rtol=2e-4, atol=2e-4)


def test_swa_pipelined_trunk_matches():
    from pathway_tpu.parallel.pipeline import (
        make_pipelined_causal_lm,
        make_pp_mesh,
        place_pp_params,
    )

    cfg = dataclasses.replace(BASE, sliding_window=6)
    mesh = make_pp_mesh(2)
    tree = init_decoder_params(cfg, seed=4)
    pp_tree = place_pp_params(tree, mesh)
    ids, lens = _ids(np.random.default_rng(4), b=4)
    want = causal_lm_logits(tree, ids, lens, cfg)
    import jax

    got = jax.jit(make_pipelined_causal_lm(cfg, mesh, n_micro=2))(pp_tree, ids, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
