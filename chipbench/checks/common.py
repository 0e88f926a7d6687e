"""Shared by the checks: one compared number, and the seeded sample."""

from __future__ import annotations

import json
import random

from chipbench import text
from chipbench.reference import encoder as ref_encoder
from chipbench.reference import topk as ref_topk


def number(name: str, value: float, limit: float) -> dict:
    return {"name": name, "value": float(value), "limit": float(limit)}


def answered(results: list[dict]) -> list[dict]:
    """The requests that came back 200 with a JSON body kept."""
    out = []
    for r in results:
        if r["status"] == 200 and "body" in r:
            try:
                out.append({**r, "json": json.loads(r["body"])})
            except ValueError:
                pass
    return out


def seeded_sample(items: list, n: int, seed: int, first=None) -> list:
    """``n`` of ``items`` drawn from the seed, ``first`` (if given) among
    them."""
    rng = random.Random(seed * 1_000_003 + 53)
    rest = [x for x in items if x is not first]
    rng.shuffle(rest)
    head = [first] if first is not None else []
    return (head + rest)[:n]


def served_documents(docs, row_of: dict, k: int):
    """(rows, scores) of a served list of ``{"text", "dist"}`` documents,
    or None where it is not ``k`` distinct documents of the corpus."""
    if not isinstance(docs, list) or len(docs) != k:
        return None
    rows = [row_of.get(d.get("text")) if isinstance(d, dict) else None for d in docs]
    if None in rows or len(set(rows)) != k:
        return None
    try:
        return rows, [-float(d["dist"]) for d in docs]
    except (KeyError, TypeError, ValueError):
        return None


def _int8(vectors):
    """Each vector rounded to int8 with a scale of its own."""
    import numpy as np

    scale = np.maximum(np.abs(vectors).max(axis=1, keepdims=True) / 127.0, 1e-12)
    return np.clip(np.round(vectors / scale), -127, 127) * scale


def retrieval_numbers(
    spec: dict, documents: list[str], items: list[dict], cache: dict, control: bool
) -> list[dict]:
    """``score_gap`` and ``rank_gap`` of ``items`` (each a ``question`` with
    the ``rows`` and ``scores`` it was served) against the reference
    encoder and the reference top-k over the whole corpus.  With
    ``control`` the reference in int8 (encoder weights, index rows and query
    vectors) stands in the program's place."""
    enc, k = spec["encoder"], len(items[0]["rows"])
    tokenizer = text.HashTokenizer(enc.get("vocab_size", 30522))
    max_len = enc.get("max_position_embeddings", 512)
    doc_ids = [tokenizer.encode(d, max_len) for d in documents]
    if "doc_vecs" not in cache:
        cache["enc_weights"] = ref_encoder.init_weights(enc)
        cache["doc_vecs"] = ref_encoder.embed(enc, cache["enc_weights"], doc_ids)
    q_ids = [tokenizer.encode(item["question"], max_len) for item in items]
    ref_scores = ref_topk.scores(
        cache["doc_vecs"], ref_encoder.embed(enc, cache["enc_weights"], q_ids)
    )
    served = [(item["rows"], item["scores"]) for item in items]
    if control:
        if "low_doc_vecs" not in cache:
            cache["low_weights"] = ref_encoder.init_weights(enc, weight_bits=8)
            cache["low_doc_vecs"] = ref_encoder.embed(enc, cache["low_weights"], doc_ids)
        low = ref_topk.scores(  # the index and the queries held in int8 too
            _int8(cache["low_doc_vecs"]),
            _int8(ref_encoder.embed(enc, cache["low_weights"], q_ids)),
        )
        served = list(zip(*ref_topk.topk(low, k)))
    kth = ref_topk.topk(ref_scores, k)[1][:, -1]
    score_gap = rank_gap = 0.0
    for i, (rows, scores) in enumerate(served):
        for row, score in zip(rows, scores):
            score_gap = max(score_gap, abs(float(score) - ref_scores[i, row]))
            rank_gap = max(rank_gap, float(kth[i] - ref_scores[i, row]))
    limits = spec["limits"]
    return [
        number("score_gap", score_gap, limits["score_gap"]),
        number("rank_gap", rank_gap, limits["rank_gap"]),
    ]
