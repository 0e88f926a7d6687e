"""``correct`` for a RAG answer cell whose decoder is the Laguna language
model: ``checks/rag_answer_mimo.py``'s numbers (every answered request held
to the retrieval reference; a seeded sample of the finished answers, the
longest among them, replayed through the reference decoder in one full
forward each; ``logit_gap`` the root mean square and ``logit_gap_mean`` the
mean, over the sample's served tokens, of the gap by which a served token's
float32 logit lies under the reference's best) with
``reference/laguna_decoder.py`` as the decoder's reference, given the same
share of the experts as the program.

The prompts here are longer than a prefill program and than a window
layer's ring: the program reached each served position through several
chunks, each attending to a ring that the chunks before it had wrapped, and
through the full layers' growing tables; the reference reads the whole
sequence at once, so a chunk that read a stale ring entry, missed one or
read one twice shows here as a gap at its positions and, through the
cache, at every later one.

``control=True`` computes both references in int8 in the program's place
(no served tokens are needed: at each position the token int8 puts first
is read against the float32 logits).
"""

from __future__ import annotations

import numpy as np

from chipbench import text
from chipbench.checks.common import (
    answered, number, retrieval_numbers, seeded_sample, served_documents,
)
from chipbench.reference import laguna_decoder as ref_decoder

BLOCK_ROWS = 6  # the reference's forward runs over this many answers at a time, so that it fits


def check(ctx: dict) -> list[dict]:
    spec = ctx["config"]["chipbench"]
    limits, serving = spec["limits"], spec["serving"]
    documents = ctx["deployment"].documents
    row_of = {doc: i for i, doc in enumerate(documents)}
    k, new_tokens = serving["search_topk"], serving["max_new_tokens"]
    control = ctx.get("control", False)

    cache = ctx.setdefault("cache", {})
    done = answered(ctx["results"])
    malformed = 0
    for r in done:
        found = served_documents(r["json"].get("context_docs"), row_of, k)
        if found is None or not isinstance(r["json"].get("response"), str):
            malformed += 1
        r["rows"], r["served_scores"] = found or (None, None)
    done = [r for r in done if r["rows"] is not None]
    out = [number("malformed_answers", malformed, 0)]
    if not done:
        return out + [number("answers_unchecked", 1, 0)]

    ctx["deployment"].release()

    out += retrieval_numbers(spec, documents, [
        {"question": r["request"]["payload"]["prompt"], "rows": r["rows"], "scores": r["served_scores"]}
        for r in done
    ], cache, control)

    dec_config = spec.get("decoder") or ctx["config"]
    dec_tok = text.HashTokenizer(dec_config["vocab_size"])
    max_len = min(dec_config.get("max_position_embeddings", 4096), 8192)
    limit = min(serving.get("max_cache", 1024), max_len) - new_tokens
    replayable = []
    for r in done:
        served = text.parse_served_tokens(r["json"]["response"])
        if len(served) != new_tokens:
            continue  # an id the tokenizer's decode drops, or EOS: not in the text
        prompt = text.rag_prompt([documents[row] for row in r["rows"]], r["request"]["payload"]["prompt"])
        ids = dec_tok.encode(prompt, max_len)[-limit:]
        replayable.append({"prompt_ids": ids, "served": served})
    longest = max(replayable, key=lambda s: len(s["prompt_ids"]), default=None)
    rows = spec["check"]["sample"]
    sample = seeded_sample(replayable, rows, ctx["seed"], longest)
    out.append(number("answers_unchecked", 0 if sample else 1, 0))
    if not sample:
        return out
    if "dec_weights" not in cache:
        cache["dec_weights"] = ref_decoder.init_weights(dec_config)
    gaps = _logit_gaps(
        cache["dec_weights"], dec_config, sample, rows, new_tokens, 8 if control else None
    )
    cache["logit_gaps"] = gaps  # for readings.py: the spread of the gaps
    out.append(number("logit_gap", float(np.sqrt(np.mean(gaps**2))), limits["logit_gap"]))
    out.append(number("logit_gap_mean", float(gaps.mean()), limits["logit_gap_mean"]))
    return out


def _logit_gaps(weights, dec_config, sample, rows, new_tokens, bits):
    """The gap, at each of the sample's served positions, by which the
    served token's float32 logit lies under the best.  For the control the
    "served" token is the one the int8 forward puts first at each position
    of the same prompts and served tokens."""
    # one shape for every seed (blocks of BLOCK_ROWS rows, a multiple of 256
    # tokens wide), so that the reference's programs come from the compile cache
    real = len(sample)
    sample = (sample * rows)[:rows]
    width = -(-(max(len(s["prompt_ids"]) for s in sample) + new_tokens) // 256) * 256
    gaps = []
    for start in range(0, real, BLOCK_ROWS):
        block = (sample[start:start + BLOCK_ROWS] * BLOCK_ROWS)[:BLOCK_ROWS]
        ids = np.zeros((BLOCK_ROWS, width), np.int32)
        lengths = np.zeros(BLOCK_ROWS, np.int32)
        positions = np.zeros((BLOCK_ROWS, new_tokens), np.int32)
        for i, s in enumerate(block):
            n = len(s["prompt_ids"])
            ids[i, :n] = s["prompt_ids"]
            ids[i, n:n + new_tokens] = s["served"]
            lengths[i] = n + new_tokens
            positions[i] = np.arange(n - 1, n - 1 + new_tokens)
        logits = ref_decoder.logits_at(weights, dec_config, ids, lengths, positions)
        if bits is None:
            served = ids[np.arange(BLOCK_ROWS)[:, None], positions + 1]
        else:
            served = ref_decoder.logits_at(
                weights, dec_config, ids, lengths, positions, weight_bits=bits
            ).argmax(-1)
        picked = np.take_along_axis(logits, served[:, :, None], axis=2)[:, :, 0]
        gaps.append((logits.max(-1) - picked)[: min(BLOCK_ROWS, real - start)].ravel())
    return np.concatenate(gaps)
