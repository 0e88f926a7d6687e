"""Retrieval-quality evaluation harness (parity: the reference's
``integration_tests/rag_evals`` RAGAS-style end-to-end eval).

A deterministic corpus of real PDF documents flows through the FULL
product path — fs-format bytes → parser → splitter → embedder → index —
and a query set with known target documents measures **recall@k** and
**MRR** per retriever (BM25 / dense / hybrid RRF).

Run: ``python benchmarks/rag_eval.py`` — prints one JSON line per
retriever.  ``tests/test_rag_eval.py`` asserts thresholds on the same
functions (CPU-runnable; the dense path uses the deterministic
seeded encoder, or a golden-weights checkpoint directory if given).
"""

from __future__ import annotations

import json
import os
import random
import sys
from typing import Any

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOPICS = {
    "volcanoes": "magma eruption lava basalt caldera ash vent crater",
    "beekeeping": "hive honey pollen queen drone nectar apiary swarm",
    "sailing": "mast rudder keel spinnaker tack jib regatta harbor",
    "astronomy": "nebula quasar telescope parallax supernova orbit comet",
    "baking": "dough yeast proofing sourdough crumb gluten oven knead",
    "chess": "gambit endgame castling zugzwang checkmate bishop rook",
    "cryptography": "cipher entropy nonce keypair signature hash lattice",
    "gardening": "compost mulch pruning seedling trellis perennial soil",
    "railways": "locomotive gauge signal ballast junction freight track",
    "weaving": "loom warp weft shuttle heddle tapestry yarn selvage",
}


def build_corpus(docs_per_topic: int = 3, queries_per_doc: int = 2):
    """Deterministic (text, path) docs + (query, target_path) pairs.

    Each document mixes its topic's distinctive vocabulary with common
    filler; each query is a phrase of distinctive words drawn from its
    target document, so both lexical and embedding retrievers have a
    recoverable signal.
    """
    rng = random.Random(7)
    filler = "the report describes how a process can slowly change over time".split()
    docs: list[tuple[str, str]] = []
    queries: list[tuple[str, str]] = []
    for topic, vocab_str in TOPICS.items():
        vocab = vocab_str.split()
        shared, specific_pool = vocab[:2], vocab[2:]
        per_doc = max(1, len(specific_pool) // docs_per_topic)
        for d in range(docs_per_topic):
            # each doc owns a disjoint slice of the topic vocabulary, so a
            # query naming those words has ONE right answer (siblings share
            # only the two topic-common words)
            own = specific_pool[d * per_doc : (d + 1) * per_doc] or [
                specific_pool[d % len(specific_pool)]
            ]
            doc_vocab = shared + own
            words = []
            for _ in range(6):  # six sentences
                sent = rng.sample(doc_vocab, min(3, len(doc_vocab))) + rng.sample(
                    filler, 4
                )
                rng.shuffle(sent)
                words.append(" ".join(sent) + ".")
            path = f"/{topic}/doc{d}.pdf"
            docs.append(("\n".join(words), path))
            for _q in range(queries_per_doc):
                q_words = rng.sample(own, min(2, len(own))) + [rng.choice(shared)]
                queries.append((" ".join(q_words), path))
    return docs, queries


def _docs_table(docs, render: str = "pdf"):
    import pathway_tpu as pw
    from pathway_tpu.engine.types import Json
    from pathway_tpu.io._utils import make_static_input_table
    from tests.doc_fixtures import make_pdf

    rows = []
    for text, path in docs:
        data = make_pdf([text]) if render == "pdf" else text.encode()
        rows.append({"data": data, "_metadata": Json({"path": path})})
    return make_static_input_table(
        pw.schema_from_types(data=bytes, _metadata=Json), rows
    )


def make_retriever(kind: str, embedder_model: str | None = None) -> Any:
    from pathway_tpu.stdlib.indexing import (
        BruteForceKnnFactory,
        HybridIndexFactory,
        TantivyBM25Factory,
    )
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    if kind == "bm25":
        return TantivyBM25Factory()
    embedder = SentenceTransformerEmbedder(
        model=embedder_model or "all-MiniLM-L6-v2"
    )
    dense = BruteForceKnnFactory(embedder=embedder)
    if kind == "dense":
        return dense
    if kind == "hybrid":
        return HybridIndexFactory([TantivyBM25Factory(), dense])
    raise ValueError(f"unknown retriever kind {kind!r}")


def run_eval(
    retriever_factory: Any,
    *,
    docs_per_topic: int = 3,
    queries_per_doc: int = 2,
    k: int = 5,
    render: str = "pdf",
) -> dict:
    """recall@1 / recall@k / MRR of the full DocumentStore path."""
    import pathway_tpu as pw
    from pathway_tpu.debug import _capture_table
    from pathway_tpu.io._utils import make_static_input_table
    from pathway_tpu.xpacks.llm import DocumentStore
    from pathway_tpu.xpacks.llm.parsers import PypdfParser, Utf8Parser
    from pathway_tpu.xpacks.llm.splitters import TokenCountSplitter

    pw.G.clear()
    docs, queries = build_corpus(docs_per_topic, queries_per_doc)
    store = DocumentStore(
        _docs_table(docs, render),
        retriever_factory,
        parser=PypdfParser() if render == "pdf" else Utf8Parser(),
        splitter=TokenCountSplitter(min_tokens=10, max_tokens=60),
    )
    query_table = make_static_input_table(
        DocumentStore.RetrieveQuerySchema,
        [
            {
                "query": q,
                "k": k,
                "metadata_filter": None,
                "filepath_globpattern": None,
                "_pw_key": i,
            }
            for i, (q, _t) in enumerate(queries)
        ],
    )
    cap = _capture_table(store.retrieve_query(query_table))
    rows = cap.final_rows()

    hits_at_1 = hits_at_k = 0
    rr_total = 0.0
    for key, (result,) in rows.items():
        target = queries[key.value if hasattr(key, "value") else int(key)][1]
        ranked_paths = [
            (hit.get("metadata") or {}).get("path") for hit in result.value
        ]
        if ranked_paths and ranked_paths[0] == target:
            hits_at_1 += 1
        if target in ranked_paths:
            hits_at_k += 1
            rr_total += 1.0 / (ranked_paths.index(target) + 1)
    n = len(queries)
    return {
        "queries": n,
        "docs": len(docs),
        "k": k,
        "recall_at_1": round(hits_at_1 / n, 4),
        f"recall_at_{k}": round(hits_at_k / n, 4),
        "mrr": round(rr_total / n, 4),
    }


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    for kind in ("bm25", "dense", "hybrid"):
        metrics = run_eval(make_retriever(kind))
        metrics["metric"] = f"rag_eval_{kind}"
        print(json.dumps(metrics))


if __name__ == "__main__":
    main()
