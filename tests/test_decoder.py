"""Decoder LLM (models/decoder.py): paged-cache correctness, causality,
generation, tensor-parallel sharding, and the JaxChat serving UDF.

Parity target: the reference's local chat serving
(xpacks/llm/llms.py HFPipelineChat / the Mistral-7B Adaptive RAG
template), re-designed as paged prefill + single-token decode behind the
continuous-batching scheduler, held to the full causal forward.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pathway_tpu.models.decoder import (
    DecoderLM,
    causal_lm_logits,
    decoder_config_for,
    init_decoder_params,
    tp_param_specs,
)
from tests.decoder_oracle import generate_ids, paged_logits, reference_greedy

CFG = decoder_config_for("pw-tiny-decoder")
TREE = init_decoder_params(CFG, seed=3)


def _last_logits(tree, ids, lengths):
    """The full forward's logits at each row's final real token."""
    logits = np.asarray(
        causal_lm_logits(tree, jnp.asarray(ids), jnp.asarray(lengths), CFG, serving=True)
    )
    return logits[np.arange(len(lengths)), np.asarray(lengths) - 1]


def test_decode_step_matches_prefill():
    """Incremental decode over the paged cache reproduces full-forward
    logits."""
    rng = np.random.default_rng(0)
    B, S = 2, 12
    ids = rng.integers(1, CFG.vocab_size, size=(B, S)).astype(np.int32)

    # prefill on a PREFIX, then feed the remaining real tokens one by one
    cut = 5
    got = paged_logits(TREE, CFG, ids, cut)
    full = np.asarray(
        causal_lm_logits(TREE, jnp.asarray(ids), jnp.full((B,), S), CFG, serving=True)
    )
    np.testing.assert_allclose(got, full[:, cut - 1:], rtol=2e-4, atol=2e-4)


def test_prefill_is_causal():
    """Changing tokens at/after a row's final position cannot change the
    logits read at earlier lengths."""
    rng = np.random.default_rng(1)
    ids = rng.integers(1, CFG.vocab_size, size=(1, 10)).astype(np.int32)
    lens = np.asarray([6], np.int32)
    base = _last_logits(TREE, ids, lens)
    ids2 = ids.copy()
    ids2[0, 6:] = rng.integers(1, CFG.vocab_size, size=4)
    pert = _last_logits(TREE, ids2, lens)
    np.testing.assert_allclose(base, pert, atol=1e-6)


def test_ragged_batch_rows_independent():
    """A row's logits don't depend on other rows in the padded batch."""
    rng = np.random.default_rng(2)
    a = rng.integers(1, CFG.vocab_size, size=8).astype(np.int32)
    b = rng.integers(1, CFG.vocab_size, size=3).astype(np.int32)
    ids = np.zeros((2, 8), np.int32)
    ids[0] = a
    ids[1, :3] = b
    both = _last_logits(TREE, ids, np.asarray([8, 3], np.int32))
    solo = _last_logits(TREE, b[None, :], np.asarray([3], np.int32))
    np.testing.assert_allclose(both[1], solo[0], atol=1e-5)


def test_generate_greedy_deterministic():
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    out1 = generate_ids(lm, [[5, 9, 17]], max_new_tokens=8)
    out2 = generate_ids(lm, [[5, 9, 17]], max_new_tokens=8)
    assert out1 == out2
    assert len(out1[0]) == 8
    assert all(0 <= t < CFG.vocab_size for t in out1[0])


def test_generate_matches_token_by_token_prefill():
    """Greedy generation through the cache equals greedy re-forward argmax."""
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    prompt = [3, 7, 11, 2, 19]
    got = generate_ids(lm, [prompt], max_new_tokens=5)[0]
    assert got == reference_greedy(lm, prompt, 5)


def test_generate_batch_ragged():
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    outs = generate_ids(lm, [[5, 9, 17, 4], [8]], max_new_tokens=4)
    assert len(outs) == 2 and all(len(o) == 4 for o in outs)
    solo = generate_ids(lm, [[8]], max_new_tokens=4)[0]
    assert outs[1] == solo


def test_eos_stops_row():
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    forced = generate_ids(lm, [[5, 9, 17]], max_new_tokens=3)[0]
    eos = forced[1]
    lm2 = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=eos)
    out = generate_ids(lm2, [[5, 9, 17]], max_new_tokens=8)[0]
    assert out == forced[: forced.index(eos)]


def test_temperature_sampling_seeded():
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    a = generate_ids(lm, [[5, 9]], max_new_tokens=6, temperature=0.8, seed=1)
    b = generate_ids(lm, [[5, 9]], max_new_tokens=6, temperature=0.8, seed=1)
    c = generate_ids(lm, [[5, 9]], max_new_tokens=6, temperature=0.8, seed=2)
    greedy = generate_ids(lm, [[5, 9]], max_new_tokens=6)
    assert a == b
    # sampling at T=0.8 over 512 random logits matching greedy argmax on
    # all 6 tokens for BOTH seeds has negligible probability
    assert a != greedy or c != greedy


def test_long_prompt_keeps_tail_and_runs():
    """Prompts past the cache budget work: the tail is kept and prefills
    in chunks."""
    lm = DecoderLM("pw-tiny-decoder", max_cache=128, eos_id=None)
    rng = np.random.default_rng(7)
    long_prompt = rng.integers(1, CFG.vocab_size, size=600).tolist()
    out = generate_ids(lm, [long_prompt], max_new_tokens=4)[0]
    assert len(out) == 4
    # equivalent to generating from the kept tail directly
    tail = long_prompt[-(128 - 4):]
    assert out == generate_ids(lm, [tail], max_new_tokens=4)[0]


def test_max_new_tokens_budget_validated():
    lm = DecoderLM("pw-tiny-decoder", max_cache=32, eos_id=None)
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate_ids(lm, [[1, 2, 3]], max_new_tokens=32)


def test_unknown_model_name_raises():
    with pytest.raises(ValueError, match="unknown decoder model"):
        decoder_config_for("mistral-7b")  # typo'd preset name


@pytest.mark.parametrize("sampling", [
    {}, {"temperature": 0.8, "top_k": 5, "repetition_penalty": 1.3},
], ids=["plain", "top_k_and_penalty"])
def test_jax_chat_routes_through_continuous_scheduler(monkeypatch, sampling):
    """Every chat row, whatever its sampling, is submitted to the shared
    continuous scheduler of its model, options passed through."""
    import asyncio

    from pathway_tpu.serving import generation
    from pathway_tpu.xpacks.llm import llms

    chat = llms.JaxChat(model="pw-tiny-decoder", max_new_tokens=3, max_cache=64)
    submitted = []
    real_submit = generation.GenerationScheduler.submit

    def spy_submit(self, prompt, **kw):
        submitted.append((self, kw))
        return real_submit(self, prompt, **kw)

    monkeypatch.setattr(generation.GenerationScheduler, "submit", spy_submit)

    async def run():
        return await asyncio.gather(
            *(chat.__wrapped__(f"question {i}", **sampling) for i in range(3))
        )

    try:
        answers = asyncio.run(run())
        shared = generation.shared_scheduler("pw-tiny-decoder", max_cache=64)
    finally:
        generation.reset_shared_schedulers()
    assert len(answers) == 3 and all(isinstance(a, str) for a in answers)
    assert [s for s, _kw in submitted] == [shared] * 3
    for _s, kw in submitted:
        assert kw["max_new_tokens"] == 3
        assert kw["top_k"] == sampling.get("top_k")
        assert kw["repetition_penalty"] == sampling.get("repetition_penalty")
        assert kw["temperature"] == sampling.get("temperature", 0.0)


def test_tensor_parallel_decode_matches_single_device():
    """Params sharded over a model axis produce the same logits; XLA
    inserts the all-reduces from the shardings alone."""
    from jax.sharding import Mesh, NamedSharding

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("model",))
    specs = tp_param_specs(CFG)
    # tiny config: heads=4 < 8, so shard over 2 devices instead
    mesh2 = Mesh(np.array(jax.devices()[:2]).reshape(2), ("model",))
    place = lambda t, s: jax.device_put(t, NamedSharding(mesh2, s))
    tree_sh = jax.tree_util.tree_map(
        place, TREE, specs, is_leaf=lambda x: isinstance(x, jnp.ndarray)
    )
    rng = np.random.default_rng(4)
    ids = jnp.asarray(rng.integers(1, CFG.vocab_size, size=(1, 9)).astype(np.int32))
    lens = jnp.asarray([9], jnp.int32)
    ref_logits = causal_lm_logits(TREE, ids, lens, CFG)
    logits = causal_lm_logits(tree_sh, ids, lens, CFG)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), atol=1e-5)
    assert mesh.size == 8  # the 8-device mesh exists; 2 used for 4 heads


def test_jax_chat_udf_end_to_end():
    """JaxChat answers a question column through the dataflow."""
    import pathway_tpu as pw
    from pathway_tpu.xpacks.llm import llms

    chat = llms.JaxChat(model="pw-tiny-decoder", max_new_tokens=4, max_cache=64)
    t = pw.debug.table_from_markdown(
        """
        q
        hello
        """
    )
    res = t.select(a=chat(llms.prompt_chat_single_qa(pw.this.q)))
    rows = pw.debug.table_to_pandas(res)
    (answer,) = rows["a"].tolist()
    assert isinstance(answer, str) and len(answer) > 0


def test_hf_config_dir_roundtrip(tmp_path):
    import json

    d = tmp_path / "ckpt"
    d.mkdir()
    (d / "config.json").write_text(
        json.dumps(
            dict(
                model_type="llama",
                vocab_size=1000,
                hidden_size=128,
                num_hidden_layers=3,
                num_attention_heads=8,
                num_key_value_heads=4,
                intermediate_size=256,
                rope_theta=5e5,
                rms_norm_eps=1e-6,
            )
        )
    )
    cfg = decoder_config_for(str(d))
    assert (cfg.hidden, cfg.layers, cfg.kv_heads) == (128, 3, 4)
    assert cfg.rope_theta == 5e5 and cfg.norm_eps == 1e-6


def test_causal_lm_train_step_overfits_tiny_batch():
    """dp×tp next-token training: loss strictly decreases on a fixed batch
    over the 8-device virtual mesh, and the trained tree still serves
    through generate (train/serve share the TP placement)."""
    import optax

    from pathway_tpu.models.decoder import DecoderConfig
    from pathway_tpu.parallel import make_causal_lm_train_step, make_mesh

    cfg = DecoderConfig(
        vocab_size=64, hidden=32, layers=2, heads=4, kv_heads=2,
        intermediate=64, max_len=32, dtype=jnp.float32,
    )
    mesh = make_mesh(8)  # (data=4, model=2)
    init_state, run = make_causal_lm_train_step(cfg, optax.adam(3e-3), mesh)
    state = init_state(seed=0)
    rng = np.random.default_rng(0)
    ids = rng.integers(1, 64, size=(8, 16)).astype(np.int32)
    lengths = np.full(8, 16, np.int32)
    losses = []
    for _ in range(8):
        state, loss = run(state, ids, lengths)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.1, losses
    assert np.isfinite(losses).all()


def test_causal_lm_loss_masks_padding():
    """Pad positions beyond a row's length contribute nothing to the loss."""
    import optax

    from pathway_tpu.models.decoder import DecoderConfig
    from pathway_tpu.parallel import make_causal_lm_train_step, make_mesh

    cfg = DecoderConfig(
        vocab_size=64, hidden=32, layers=2, heads=4, kv_heads=2,
        intermediate=64, max_len=32, dtype=jnp.float32,
    )
    mesh = make_mesh(8)
    init_state, run = make_causal_lm_train_step(cfg, optax.adam(0.0), mesh)
    state = init_state(seed=1)
    rng = np.random.default_rng(1)
    ids = rng.integers(1, 64, size=(8, 16)).astype(np.int32)
    lengths = np.full(8, 10, np.int32)
    _, loss_a = run(state, ids, lengths)
    ids2 = ids.copy()
    ids2[:, 10:] = rng.integers(1, 64, size=(8, 6))  # perturb only padding
    _, loss_b = run(state, ids2, lengths)
    assert abs(float(loss_a) - float(loss_b)) < 1e-6


def test_generation_batch_invariance():
    """A row's greedy chain must not depend on what it is co-batched
    with (other slots are fully masked; the prefill program's shape
    changes shapes, not math)."""
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    solo = generate_ids(lm, [[5, 9, 3]], max_new_tokens=10)
    batched = generate_ids(
        lm, [[5, 9, 3], [7, 11, 2, 8, 1], [4]], max_new_tokens=10
    )
    assert batched[0] == solo[0]
    # and independent of row order
    shuffled = generate_ids(lm, [[4], [5, 9, 3]], max_new_tokens=10)
    assert shuffled[1] == solo[0]


def test_models_import_nothing_of_serving():
    """The scheduler drives the model, never the other way round: whoever
    wants text calls ``GenerationScheduler(lm).generate`` or
    ``shared_scheduler``."""
    import pathlib
    import re

    import pathway_tpu.models as models

    for path in pathlib.Path(models.__file__).parent.glob("*.py"):
        assert not re.search(
            r"^\s*(from|import)\s+pathway_tpu\.serving", path.read_text(), flags=re.M
        ), path.name
