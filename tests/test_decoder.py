"""Decoder LLM (models/decoder.py): KV-cache correctness, causality,
generation, tensor-parallel sharding, and the JaxChat serving UDF.

Parity target: the reference's local chat serving
(xpacks/llm/llms.py HFPipelineChat / the Mistral-7B Adaptive RAG
template), re-designed as jitted prefill + cached single-token decode.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pathway_tpu.models.decoder import (
    DecoderLM,
    decode_step,
    decoder_config_for,
    init_decoder_params,
    prefill,
    tp_cache_specs,
    tp_param_specs,
)

CFG = decoder_config_for("pw-tiny-decoder")
TREE = init_decoder_params(CFG, seed=3)


def _full_logits(tree, ids, lengths, cache_len):
    """Reference: logits at every position via repeated prefill."""
    outs = []
    for t in range(1, int(lengths.max()) + 1):
        lens = np.minimum(lengths, t).astype(np.int32)
        logits, _, _ = prefill(tree, ids, jnp.asarray(lens), CFG, cache_len)
        outs.append(np.asarray(logits))
    return np.stack(outs, axis=1)  # [B, T, V]


def test_decode_step_matches_prefill():
    """Incremental decode over the cache reproduces full-forward logits."""
    rng = np.random.default_rng(0)
    B, S, C = 2, 12, 32
    ids = rng.integers(1, CFG.vocab_size, size=(B, S)).astype(np.int32)
    lengths = np.array([12, 7], np.int32)

    # prefill on a PREFIX, then feed the remaining real tokens one by one
    cut = 5
    logits, kc, vc = prefill(
        TREE, jnp.asarray(ids), jnp.asarray(np.full(B, cut, np.int32)), CFG, C
    )
    pos = jnp.asarray(np.full(B, cut, np.int32))
    for t in range(cut, S):
        token = jnp.asarray(ids[:, t])
        logits, kc, vc = decode_step(TREE, kc, vc, token, pos, CFG)
        full, _, _ = prefill(
            TREE,
            jnp.asarray(ids),
            jnp.asarray(np.full(B, t + 1, np.int32)),
            CFG,
            C,
        )
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full), rtol=2e-4, atol=2e-4
        )
        pos = pos + 1


def test_prefill_is_causal():
    """Changing tokens at/after a row's final position cannot change the
    logits read at earlier lengths."""
    rng = np.random.default_rng(1)
    ids = rng.integers(1, CFG.vocab_size, size=(1, 10)).astype(np.int32)
    lens = jnp.asarray([6], jnp.int32)
    base, _, _ = prefill(TREE, jnp.asarray(ids), lens, CFG, 16)
    ids2 = ids.copy()
    ids2[0, 6:] = rng.integers(1, CFG.vocab_size, size=4)
    pert, _, _ = prefill(TREE, jnp.asarray(ids2), lens, CFG, 16)
    np.testing.assert_allclose(np.asarray(base), np.asarray(pert), atol=1e-6)


def test_ragged_batch_rows_independent():
    """A row's logits don't depend on other rows in the padded batch."""
    rng = np.random.default_rng(2)
    a = rng.integers(1, CFG.vocab_size, size=8).astype(np.int32)
    b = rng.integers(1, CFG.vocab_size, size=3).astype(np.int32)
    ids = np.zeros((2, 8), np.int32)
    ids[0] = a
    ids[1, :3] = b
    lens = jnp.asarray([8, 3], jnp.int32)
    both, _, _ = prefill(TREE, jnp.asarray(ids), lens, CFG, 16)
    solo, _, _ = prefill(TREE, jnp.asarray(b[None, :]), jnp.asarray([3]), CFG, 16)
    np.testing.assert_allclose(np.asarray(both)[1], np.asarray(solo)[0], atol=1e-5)


def test_generate_greedy_deterministic():
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    out1 = lm.generate_ids([[5, 9, 17]], max_new_tokens=8)
    out2 = lm.generate_ids([[5, 9, 17]], max_new_tokens=8)
    assert out1 == out2
    assert len(out1[0]) == 8
    assert all(0 <= t < CFG.vocab_size for t in out1[0])


def test_generate_matches_token_by_token_prefill():
    """Greedy generation through the cache equals greedy re-prefill argmax."""
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    prompt = [3, 7, 11, 2, 19]
    got = lm.generate_ids([prompt], max_new_tokens=5)[0]
    seq = list(prompt)
    for _ in range(5):
        ids = np.asarray([seq], np.int32)
        logits, _, _ = prefill(
            lm.params, jnp.asarray(ids), jnp.asarray([len(seq)]), CFG, 64
        )
        nxt = int(np.argmax(np.asarray(logits)[0]))
        seq.append(nxt)
    assert got == seq[len(prompt):]


def test_generate_batch_ragged():
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    outs = lm.generate_ids([[5, 9, 17, 4], [8]], max_new_tokens=4)
    assert len(outs) == 2 and all(len(o) == 4 for o in outs)
    solo = lm.generate_ids([[8]], max_new_tokens=4)[0]
    assert outs[1] == solo


def test_eos_stops_row():
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    forced = lm.generate_ids([[5, 9, 17]], max_new_tokens=3)[0]
    eos = forced[1]
    lm2 = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=eos)
    out = lm2.generate_ids([[5, 9, 17]], max_new_tokens=8)[0]
    assert out == forced[: forced.index(eos)]


def test_temperature_sampling_seeded():
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    a = lm.generate_ids([[5, 9]], max_new_tokens=6, temperature=0.8, seed=1)
    b = lm.generate_ids([[5, 9]], max_new_tokens=6, temperature=0.8, seed=1)
    c = lm.generate_ids([[5, 9]], max_new_tokens=6, temperature=0.8, seed=2)
    greedy = lm.generate_ids([[5, 9]], max_new_tokens=6)
    assert a == b
    # sampling at T=0.8 over 512 random logits matching greedy argmax on
    # all 6 tokens for BOTH seeds has negligible probability
    assert a != greedy or c != greedy


def test_long_prompt_keeps_tail_and_runs():
    """Prompts past the 512 shared bucket cap and past the cache budget
    work: the tail is kept and prefill buckets up to the cache size."""
    lm = DecoderLM("pw-tiny-decoder", max_cache=128, eos_id=None)
    rng = np.random.default_rng(7)
    long_prompt = rng.integers(1, CFG.vocab_size, size=600).tolist()
    out = lm.generate_ids([long_prompt], max_new_tokens=4)[0]
    assert len(out) == 4
    # equivalent to generating from the kept tail directly
    tail = long_prompt[-(128 - 4):]
    assert out == lm.generate_ids([tail], max_new_tokens=4)[0]


def test_max_new_tokens_budget_validated():
    lm = DecoderLM("pw-tiny-decoder", max_cache=32, eos_id=None)
    with pytest.raises(ValueError, match="max_new_tokens"):
        lm.generate_ids([[1, 2, 3]], max_new_tokens=32)


def test_unknown_model_name_raises():
    with pytest.raises(ValueError, match="unknown decoder model"):
        decoder_config_for("mistral-7b")  # typo'd preset name


def test_jax_chat_microbatches_concurrent_rows(monkeypatch):
    """Concurrent rows of one epoch run as a single generate_many batch.

    Pins the STATIC fallback path (the one top_k / repetition_penalty
    configs take) — the default continuous route is pinned below.
    """
    import asyncio

    from pathway_tpu.xpacks.llm import llms

    monkeypatch.setenv("PATHWAY_GENERATE_CONTINUOUS", "0")
    chat = llms.JaxChat(model="pw-tiny-decoder", max_new_tokens=3, max_cache=64)
    batch_sizes = []
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    real = lm.generate_many

    def spy(prompts, **kw):
        batch_sizes.append(len(prompts))
        return real(prompts, **kw)

    lm.generate_many = spy
    chat._model = lm

    async def run():
        return await asyncio.gather(
            *(chat.__wrapped__(f"question {i}") for i in range(5))
        )

    answers = asyncio.run(run())
    assert len(answers) == 5 and all(isinstance(a, str) for a in answers)
    assert max(batch_sizes) > 1  # rows actually coalesced
    assert sum(batch_sizes) == 5


def test_jax_chat_routes_through_continuous_scheduler(monkeypatch):
    """Default config serves chat through the shared continuous scheduler;
    the static per-config batcher is never touched."""
    import asyncio

    from pathway_tpu.serving import generation
    from pathway_tpu.xpacks.llm import llms

    chat = llms.JaxChat(model="pw-tiny-decoder", max_new_tokens=3, max_cache=64)
    static_calls = []
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    lm.generate_many = lambda *a, **kw: static_calls.append(a) or []
    chat._model = lm

    sched_calls = []
    real_shared = generation.shared_scheduler

    def spy_shared(*a, **kw):
        sched_calls.append(a)
        return real_shared(*a, **kw)

    monkeypatch.setattr(generation, "shared_scheduler", spy_shared)

    async def run():
        return await asyncio.gather(
            *(chat.__wrapped__(f"question {i}") for i in range(3))
        )

    try:
        answers = asyncio.run(run())
    finally:
        generation.reset_shared_schedulers()
    assert len(answers) == 3 and all(isinstance(a, str) for a in answers)
    assert len(sched_calls) == 3
    assert not static_calls  # static batcher bypassed entirely


def test_tensor_parallel_decode_matches_single_device():
    """Params/cache sharded over an 8-way model axis produce the same
    logits; XLA inserts the all-reduces from the shardings alone."""
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
    ids = rng.integers(1, CFG.vocab_size, size=(1, 8)).astype(np.int32)
    lens = jnp.asarray([8], jnp.int32)
    ref_logits, ref_kc, ref_vc = prefill(TREE, jnp.asarray(ids), lens, CFG, 16)
    logits, kc, vc = prefill(tree_sh, jnp.asarray(ids), lens, CFG, 16)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), atol=1e-5)

    kc = jax.device_put(kc, NamedSharding(mesh2, tp_cache_specs()))
    vc = jax.device_put(vc, NamedSharding(mesh2, tp_cache_specs()))
    tok = jnp.asarray([7], jnp.int32)
    pos = jnp.asarray([8], jnp.int32)
    step_ref, _, _ = decode_step(TREE, ref_kc, ref_vc, tok, pos, CFG)
    step_tp, _, _ = decode_step(tree_sh, kc, vc, tok, pos, CFG)
    np.testing.assert_allclose(np.asarray(step_tp), np.asarray(step_ref), atol=1e-5)
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
    with (padding rows are fully masked; the prefill bucket only changes
    shapes, not math)."""
    from pathway_tpu.models.decoder import DecoderLM

    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    solo = lm.generate_ids([[5, 9, 3]], max_new_tokens=10)
    batched = lm.generate_ids(
        [[5, 9, 3], [7, 11, 2, 8, 1], [4]], max_new_tokens=10
    )
    assert batched[0] == solo[0]
    # and independent of row order
    shuffled = lm.generate_ids([[4], [5, 9, 3]], max_new_tokens=10)
    assert shuffled[1] == solo[0]
