"""Builder ``rag_server``: the RAG server a user would build from model
names (after ``chip_smoke.build_rag_server``, which ran on the chip in
PR 21),

    QARestServer(BaseRAGQuestionAnswerer(JaxChat(decoder), DocumentStore(
        docs, BruteForceKnnFactory(SentenceTransformerEmbedder(encoder)))))

run threaded with every scheduler, executor and batcher knob at its
default.  Set-up ingests the seeded corpus through the real path and
warms every route the server has and every shape the cell's traffic uses.
"""

from __future__ import annotations

import json
import os

from chipbench import text
from chipbench.builders import common

# every request is an epoch of its own, but requests that wait behind an
# answer can share one (PERF.md, Open questions): the batch buckets their
# queries can coalesce to are warmed, since a compile in the window fails the run
QUERY_BUCKETS = (1, 2, 4, 8)


class RagServer:
    def __init__(self, config: dict, seed: int, work_dir: str):
        import pathway_tpu as pw
        from pathway_tpu.engine.types import Json
        from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
        from pathway_tpu.xpacks.llm.document_store import DocumentStore
        from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
        from pathway_tpu.xpacks.llm.llms import JaxChat
        from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer
        from pathway_tpu.xpacks.llm.servers import QARestServer

        spec = config["chipbench"]
        self.config = config
        self.spec = spec
        self.serving = spec["serving"]
        self.encoder_model = spec["encoder"]["model"]
        self.decoder_model = spec.get("decoder_model") or self._write_decoder_dir(
            config, work_dir
        )
        self.documents = text.make_documents(
            spec["corpus"]["documents"], seed, tuple(spec["corpus"]["words"])
        )
        self.port = common.free_port()
        docs = pw.debug.table_from_rows(
            pw.schema_from_types(data=bytes, _metadata=Json),
            [
                (doc.encode(), Json({"path": f"/docs/{i}.txt"}))
                for i, doc in enumerate(self.documents)
            ],
        )
        store = DocumentStore(
            docs,
            BruteForceKnnFactory(embedder=SentenceTransformerEmbedder(self.encoder_model)),
        )
        chat = JaxChat(
            model=self.decoder_model, max_new_tokens=self.serving["max_new_tokens"]
        )
        self.server = QARestServer(
            "127.0.0.1", self.port, BaseRAGQuestionAnswerer(chat, store)
        )
        self.thread = None
        self.released = False

    @staticmethod
    def _write_decoder_dir(config: dict, work_dir: str) -> str:
        """The decoder is named to the program by a directory holding its
        ``config.json``: the configuration file's top-level keys."""
        path = os.path.join(work_dir, "models", "decoder")
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump({k: v for k, v in config.items() if k != "chipbench"}, f)
        return path

    # -- set-up --------------------------------------------------------------

    def start(self) -> None:
        self.thread = self.server.run_server(threaded=True, with_cache=False)
        common.wait_until_listening(self.port, self.thread)

    def warm_up(self, mix: dict) -> None:
        """Ingest, then every route and every shape of the traffic mix."""
        from pathway_tpu.models import shared_sentence_encoder

        port, k = self.port, self.serving["search_topk"]
        stats = common.wait_for_route(port, "/v1/statistics", {})
        if stats["file_count"] != len(self.documents):
            raise RuntimeError(f"indexed {stats['file_count']} of {len(self.documents)}")
        # the encoder's own warm-up: each batch bucket a query epoch can
        # coalesce to, at the sequence buckets the questions fall in
        tokenizer = text.HashTokenizer(self.spec["encoder"].get("vocab_size", 30522))
        lo, hi = mix["payload"]["words"]
        probes = text.make_questions(2, 0, (lo, lo)) + text.make_questions(2, 0, (hi, hi))
        seqs = sorted({common.seq_bucket(len(tokenizer.encode(q, 512))) for q in probes})
        shared_sentence_encoder(self.encoder_model).warmup(
            seq_lens=tuple(seqs), buckets=QUERY_BUCKETS
        )
        question = probes[-1]
        common.post(port, "/v1/retrieve", {"query": question, "k": k})
        # the index's top-k compiles one program per query-batch bucket
        common.warm_executor_buckets("indexing:masked_topk", QUERY_BUCKETS)
        # one answer alone walks the scheduler through every block-table
        # width of prefill and the one of decode; then the other routes
        out = common.post(port, "/v2/answer", {"prompt": question})
        if not out["response"].strip():
            raise RuntimeError("the warm-up answer is empty")
        common.post(port, "/v1/pw_ai_answer", {"prompt": question})
        common.post(port, "/v1/pw_list_documents", {})

    # -- the window ------------------------------------------------------------

    def scheduler(self):
        from pathway_tpu.serving import generation

        return generation.shared_scheduler(
            self.decoder_model, max_cache=self.serving.get("max_cache", 1024)
        )

    def probe(self) -> dict:
        return common.probe_program(self.scheduler())

    def work(self, results: list[dict]) -> dict:
        """Useful work in answered requests: the tokens the decoder had to
        process (each prompt, rebuilt from the documents the response names,
        and each generated token)."""
        tokenizer = text.HashTokenizer((self.spec.get("decoder") or self.config)["vocab_size"])
        prompts, generated = [], 0
        for r in results:
            if r["status"] != 200 or "body" not in r:
                continue
            body = json.loads(r["body"])
            docs = [d["text"] for d in body.get("context_docs") or []]
            prompt = text.rag_prompt(docs, r["request"]["payload"]["prompt"])
            prompts.append(len(tokenizer.encode(prompt, 8192)))
            generated += len(body["response"].split())
        if not prompts:
            return {}
        mean = sum(prompts) / len(prompts)
        return {
            "decoder_tokens": sum(prompts) + generated,
            "prompt_tokens": sum(prompts),
            "context_tokens_mean": mean + generated / len(prompts) / 2.0,
            "prefill_context_mean": mean / 2.0,
        }

    def release(self) -> None:
        """Free the decoder's weights and KV pools on the device, so that
        the reference has the chip's memory (the server stays up, unused)."""
        import jax
        from pathway_tpu.serving import generation

        if self.released:
            return
        self.released = True
        sched = self.scheduler()
        leaves = jax.tree_util.tree_leaves(
            (sched.lm.params, sched._k_pool, sched._v_pool, sched._logits)
        )
        generation.reset_shared_schedulers()
        for leaf in leaves:
            leaf.delete()


def build(config: dict, seed: int, work_dir: str) -> RagServer:
    return RagServer(config, seed, work_dir)
