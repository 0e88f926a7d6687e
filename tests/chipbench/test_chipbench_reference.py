"""The plain references against the program at the tiny presets, on the
CPU: the same seeded weights without taking any from the program, the
same logits and embeddings, the same tokens and prompt."""

from __future__ import annotations

import json
import pathlib
import sys

import numpy as np

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench import text  # noqa: E402
from chipbench.reference import decoder as ref_decoder  # noqa: E402
from chipbench.reference import encoder as ref_encoder  # noqa: E402
from chipbench.reference import topk as ref_topk  # noqa: E402

TINY = json.loads((REPO / "chipbench/configs/mistral7b-bge-rag.json").read_text())["chipbench"]["tiny"]


def test_reference_decoder_draws_the_programs_weights_and_logits():
    import jax.numpy as jnp

    from pathway_tpu.models import decoder as program

    cfg = program.decoder_config_for(TINY["decoder_model"])
    theirs = program.init_decoder_params(cfg, 0)
    mine = ref_decoder.init_weights(TINY["decoder"], 0)
    for name in ("wq", "wk", "wo", "wg", "wd"):
        assert np.array_equal(np.asarray(theirs["layers"][name]), np.asarray(mine["layers"][name]))
    assert np.array_equal(np.asarray(theirs["lm_head"]), np.asarray(mine["lm_head"]))
    rng = np.random.default_rng(0)
    ids = rng.integers(104, 512, (3, 40)).astype(np.int32)
    lengths = np.array([40, 25, 33], np.int32)
    positions = np.stack([np.arange(n - 5, n) for n in lengths]).astype(np.int32)
    want = np.asarray(program.causal_lm_logits(theirs, jnp.asarray(ids), jnp.asarray(lengths), cfg))
    want = np.take_along_axis(want, positions[:, :, None], axis=1)
    got = ref_decoder.logits_at(mine, TINY["decoder"], ids, lengths, positions)
    assert np.max(np.abs(got - want)) < 1e-4
    low = ref_decoder.logits_at(mine, TINY["decoder"], ids, lengths, positions, weight_bits=8)
    assert 1e-3 < np.max(np.abs(low - want)) < 0.5  # int8 weights move it, and not far


def test_reference_encoder_draws_the_programs_weights_and_embeddings():
    from pathway_tpu.models import encoder as program

    enc = {**TINY["encoder"], "vocab_size": 30522, "max_position_embeddings": 512}
    model = program.SentenceEncoder(enc["model"])
    mine = ref_encoder.init_weights({**enc, "dtype": "float32"})
    theirs = model.params["params"]["Encoder_0"]["TransformerBlock_2"]["Dense_0"]["kernel"]
    assert np.array_equal(
        np.asarray(theirs), np.asarray(mine["params"]["Encoder_0"]["TransformerBlock_2"]["Dense_0"]["kernel"])
    )
    texts = text.make_questions(6, 1, (5, 30))
    want = model.encode(texts)  # the fused bf16 path the server runs
    tok = text.HashTokenizer(30522)
    got = ref_encoder.embed(enc, ref_encoder.init_weights(enc), [tok.encode(t, 512) for t in texts])
    cos = np.sum(want * got, axis=1)
    assert cos.min() > 0.999 and np.allclose(np.linalg.norm(got, axis=1), 1.0, atol=1e-5)
    low = ref_encoder.embed(enc, ref_encoder.init_weights(enc, weight_bits=8), [tok.encode(t, 512) for t in texts])
    assert np.sum(low * got, axis=1).min() < cos.min()  # int8 is farther off than the program


def test_tokenizer_and_prompt_copies_match_the_program():
    from pathway_tpu.models.tokenizer import HashTokenizer
    from pathway_tpu.xpacks.llm import llms, prompts

    sample = "Document 7 : shard, epoch! what's the p95?"
    assert text.HashTokenizer(32000).encode(sample, 64) == HashTokenizer(32000, 64).encode(sample)
    ids = [5, 101, 7, 0, 102, 9]
    assert text.parse_served_tokens(HashTokenizer(512).decode(ids)) == [5, 7, 9]
    assert set(ids) - set(text.DROPPED_IDS) == {5, 7, 9}
    docs = [{"text": "alpha beta", "metadata": {}}, {"text": "gamma", "metadata": {}}]
    built = (
        "Please provide an answer based solely on the provided sources. "
        "When referencing information from a source, cite it. "
        "If none of the sources are helpful, respond with: No information found. "
        f"\nSources:\n{prompts._docs_to_context(docs)}\nQuestion: q1 why\nAnswer:"
    )
    theirs = llms._messages_to_prompt([{"role": "user", "content": built}])
    assert text.rag_prompt(["alpha beta", "gamma"], "q1 why") == theirs


def test_reference_topk_is_exact():
    rng = np.random.default_rng(3)
    corpus = rng.normal(size=(500, 32)).astype(np.float32)
    queries = rng.normal(size=(4, 32)).astype(np.float32)
    scores = ref_topk.scores(corpus, queries)
    unit = lambda a: a / np.linalg.norm(a, axis=1, keepdims=True)  # noqa: E731
    assert np.allclose(scores, unit(queries) @ unit(corpus).T, atol=1e-5)
    ids, vals = ref_topk.topk(scores, 7)
    assert np.array_equal(ids, np.argsort(-scores, axis=1)[:, :7])
    assert np.array_equal(vals, np.take_along_axis(scores, ids, axis=1))
