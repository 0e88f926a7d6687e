"""Chip smoke: the embed -> index -> retrieve -> generate serving path on a TPU.

One process that holds the chip builds the RAG server a user would build,

    QARestServer(BaseRAGQuestionAnswerer(JaxChat(model=...), DocumentStore(
        docs, BruteForceKnnFactory(embedder=SentenceTransformerEmbedder(...)))))

runs it (``run_server(threaded=True, with_cache=False)``), answers a few
``POST /v1/retrieve`` and concurrent ``POST /v2/answer`` requests over
loopback HTTP, then reads the executor's and the scheduler's own ledgers
and fails unless every request was served by the device path: no host
fallback, no jit-instead-of-AOT dispatch, no numpy top-k, no XLA attention
where the Pallas kernel should be.  It prints two JSON lines: the report
(versions, models, counts, seconds, cache entries, memory, counters), then
as the last stdout line the verdict, exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

A run that fails prints neither and exits non-zero.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # index sharded over four chips

It exits non-zero, with one line saying why, unless
``jax.devices()[0].platform == "tpu"``; there is no CPU mode.  Tier-1
rehearses the same control flow on CPU by calling :func:`build_rag_server`
and :func:`drive` with the tiny presets (``tests/test_chip_smoke.py``).

Models: ``bge-base-en-v1.5`` whole and ``Mistral-7B-Instruct-v0.2`` at its
published widths, cut in depth only (``configs/`` holds the config file
with the source and the cut).  Weights are seeded random init and the
tokenizer is the hashing stand-in — the image has no checkpoints and no
network — and the JSON says so.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import random
import socket
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
ENCODER_MODEL = "bge-base-en-v1.5"
DECODER_MODEL = os.path.join(HERE, "configs", "mistral-7b-instruct-v0.2-depth24")
N_DOCS = 520  # > ops/topk.py::_JAX_MIN_ROWS, or every search is host numpy
MAX_NEW_TOKENS = 16
# sized for cold compiles: every new encoder (batch, seq) bucket and every
# new block-table width of the scheduler compiles another full-width program
REQUEST_TIMEOUT_S = 900.0
RETRIEVE_K = 5

_WORDS = (
    "stream table index shard epoch commit window join reduce filter key "
    "value batch device kernel page cache token vector query answer chunk "
    "source sink schema column row delta snapshot replay worker mesh chip "
    "memory bandwidth latency throughput embed retrieve rank prompt decode"
).split()


def make_documents(
    n: int, seed: int = 0, *, words: tuple[int, int] = (30, 90), long_words: int = 400
) -> list[str]:
    """``n`` distinct seeded documents of ``words`` words; document 0 has
    ``long_words`` (by default enough, ~400 tokens, to land in the
    encoder's 512 sequence bucket)."""
    rng = random.Random(seed)
    docs = []
    for i in range(n):
        count = long_words if i == 0 else rng.randint(*words)
        body = " ".join(rng.choice(_WORDS) for _ in range(count))
        docs.append(f"document {i} : {body}")
    return docs


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def build_rag_server(
    encoder_model: str,
    decoder_model: str,
    documents: list[str],
    *,
    host: str = "127.0.0.1",
    port: int,
    mesh=None,
    max_new_tokens: int = MAX_NEW_TOKENS,
):
    """The RAG server a user would build, from model names: documents are
    embedded by ``encoder_model`` into a brute-force device index (sharded
    over ``mesh`` when given) and questions are answered by
    ``decoder_model`` through ``JaxChat``.  Returns the ``QARestServer``;
    the caller runs it."""
    import pathway_tpu as pw
    from pathway_tpu.engine.types import Json
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.llms import JaxChat
    from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer
    from pathway_tpu.xpacks.llm.servers import QARestServer

    docs = pw.debug.table_from_rows(
        pw.schema_from_types(data=bytes, _metadata=Json),
        [
            (text.encode(), Json({"path": f"/docs/{i}.txt"}))
            for i, text in enumerate(documents)
        ],
    )
    store = DocumentStore(
        docs,
        BruteForceKnnFactory(
            embedder=SentenceTransformerEmbedder(encoder_model), mesh=mesh
        ),
    )
    chat = JaxChat(model=decoder_model, max_new_tokens=max_new_tokens)
    return QARestServer(host, port, BaseRAGQuestionAnswerer(chat, store))


def wait_until_listening(port: int, server_thread, timeout_s: float = 120.0) -> None:
    """Block until the threaded server accepts connections; fail at once
    if its thread has died instead."""
    deadline = time.monotonic() + timeout_s
    while True:
        if not server_thread.is_alive():
            raise RuntimeError("the server thread exited before it listened")
        try:
            socket.create_connection(("127.0.0.1", port), timeout=1.0).close()
            return
        except OSError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.2)


def post(port: int, route: str, payload: dict, timeout_s: float = REQUEST_TIMEOUT_S):
    """One JSON POST with the request deadline stretched to ``timeout_s``
    (the server's default is 30 s, less than one cold 7B-wide compile).
    Any HTTP error status raises."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}",
        data=json.dumps(payload).encode(),
        headers={
            "Content-Type": "application/json",
            "X-Pathway-Deadline-Ms": str(int(timeout_s * 1000)),
        },
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout_s + 30.0) as resp:
        return json.loads(resp.read())


def drive(
    port: int,
    documents: list[str],
    *,
    k: int = RETRIEVE_K,
    n_answers: int = 6,
    timeout_s: float = REQUEST_TIMEOUT_S,
) -> dict:
    """Send the smoke's requests to a running server and check the shape
    of what comes back: the index holds every document, every retrieve
    returns ``k`` hits (a query that IS a document finds it), every
    answer is a non-empty string.  Returns what was sent and received."""
    t0 = time.monotonic()
    stats = post(port, "/v1/statistics", {}, timeout_s)
    if stats["file_count"] != len(documents):
        raise AssertionError(f"indexed {stats['file_count']} of {len(documents)}")
    ingest_s = time.monotonic() - t0

    # document 0 is the long one: its query embeds in the 512 seq bucket
    queries = [documents[0], documents[7], "which worker owns the shard ?", documents[-1]]
    retrieved = []
    for query in queries:
        hits = post(port, "/v1/retrieve", {"query": query, "k": k}, timeout_s)
        if len(hits) != k:
            raise AssertionError(f"retrieve returned {len(hits)} hits, wanted {k}")
        for hit in hits:
            if not isinstance(hit["text"], str) or not hit["text"]:
                raise AssertionError(f"malformed hit {hit!r}")
        retrieved.append(hits)
    for query, hits in zip(queries, retrieved):
        if query in documents and query not in [h["text"] for h in hits]:
            raise AssertionError(
                f"document {documents.index(query)} is not among its own "
                f"top-{k}: {[h['text'][:24] for h in hits]}"
            )

    prompts = [
        f"what does document {3 + 11 * i} say about the {_WORDS[i]} ?"
        for i in range(n_answers)
    ]
    t1 = time.monotonic()
    first_answer_s = []

    def ask(prompt: str) -> str:
        out = post(port, "/v2/answer", {"prompt": prompt}, timeout_s)
        first_answer_s.append(time.monotonic() - t1)
        return out["response"]

    with concurrent.futures.ThreadPoolExecutor(max_workers=n_answers) as pool:
        answers = list(pool.map(ask, prompts))
    for answer in answers:
        if not isinstance(answer, str) or not answer.strip():
            raise AssertionError(f"empty answer {answer!r}")
    return {
        "queries": queries,
        "retrieved": retrieved,
        "prompts": prompts,
        "answers": answers,
        "ingest_s": ingest_s,
        "first_answer_s": min(first_answer_s),
        "answers_s": time.monotonic() - t1,
    }


def check_ledgers(
    device: dict, generation: dict, n_answers: int, *, executor_topk: bool
) -> dict:
    """Every request was served by the device path — asserted from the
    executor's and the scheduler's own snapshots, on any platform.
    ``executor_topk`` is False where the search legitimately bypasses the
    executor: an index sharded over a mesh runs its own shard_map program,
    and one under ``_JAX_MIN_ROWS`` rows is scored on the host.  Returns
    the counters it asserted zero."""
    callables = device["callables"]
    encoders = [n for n in callables if n.startswith("encoder:")]
    if not encoders or not all(callables[n]["dispatches"] > 0 for n in encoders):
        raise AssertionError(f"no encoder dispatches: {callables}")
    if executor_topk and not callables.get("indexing:masked_topk", {}).get("dispatches"):
        raise AssertionError(f"top-k never reached the executor: {callables}")
    ledgers = device["resilience"]["callables"].values()
    counters = {
        "uncosted_dispatches": device["cost"]["uncosted_dispatches"],
        "failures": sum(sum(st["failures"].values()) for st in ledgers),
        "fallback_batches": sum(st["fallback_batches"] for st in ledgers),
        "oom_splits": sum(st["oom_splits"] for st in ledgers),
        "breaker_trips": sum(st["breaker"]["trips"] for st in ledgers),
        "breakers_not_closed": sum(st["breaker"]["state"] != "closed" for st in ledgers),
        "quarantine": len(device["resilience"]["quarantine"]),
        "attention_xla_fallback": sum(device["attention_xla_fallback"].values()),
        "tick_failures": generation["tick_failures"],
    }
    if any(counters.values()):
        raise AssertionError(
            f"not every request was served by the device path: {counters}; "
            f"{device['resilience']}; {device['attention_xla_fallback']}; "
            f"{generation['last_tick_error']}"
        )
    if generation["tokens_total"] <= 0:
        raise AssertionError("no tokens generated")
    if generation["pages_used"] != 0 or generation["active"] != 0:
        raise AssertionError(f"pages not returned: {generation}")
    from pathway_tpu.engine.metrics import get_registry

    requests = int(get_registry().counter("generate.requests").value)
    if requests != n_answers:
        raise AssertionError(f"generate.requests={requests}, answers={n_answers}")
    return counters


# -- TPU-only checks ---------------------------------------------------------


def check_attention_kernel() -> dict:
    """``encoder_attention`` (Pallas, compiled by Mosaic) against
    ``_xla_attention`` on the chip, at every sequence bucket and the three
    encoder widths, to the tolerance ``tests/test_attention_kernel.py``
    uses in interpret mode."""
    import jax.numpy as jnp
    import numpy as np

    from pathway_tpu.ops.attention import _xla_attention, encoder_attention

    worst = {}
    rng = np.random.default_rng(0)
    for H, heads in ((384, 12), (768, 12), (1024, 16)):
        for S in (16, 32, 64, 128, 256, 512):
            for B in (1, 4):
                q, k, v = (
                    jnp.asarray(rng.normal(size=(B, S, H)), jnp.bfloat16)
                    for _ in range(3)
                )
                bias = np.zeros((B, S), np.float32)
                bias[:, int(S * 0.8):] = -1e9
                bias = jnp.asarray(bias)
                text = encoder_attention.lower(q, k, v, bias, heads).compile().as_text()
                if "tpu_custom_call" not in text:
                    raise AssertionError(f"no Mosaic call at B={B} S={S} H={H}")
                got = encoder_attention(q, k, v, bias, heads).astype(jnp.float32)
                ref = _xla_attention(q, k, v, bias, heads).astype(jnp.float32)
                err = float(jnp.max(jnp.abs(got - ref)))
                if not err < 0.05:
                    raise AssertionError(f"kernel off by {err} at B={B} S={S} H={H}")
                worst[f"H{H}"] = max(worst.get(f"H{H}", 0.0), err)
    return worst


def check_encoder_programs(executor, device: dict) -> list[int]:
    """Every compiled encoder program holds the Pallas kernel; returns the
    sequence buckets that were compiled (512 must be among them)."""
    seqs = set()
    for name in device["callables"]:
        if not name.startswith("encoder:"):
            continue
        for key, compiled in executor.executables(name).items():
            seq = key[0][-1][0][1]  # last leaf is the [bucket, seq] mask
            seqs.add(seq)
            if "tpu_custom_call" not in compiled.as_text():
                raise AssertionError(f"{name} seq {seq}: no Mosaic call compiled in")
    if 512 not in seqs:
        raise AssertionError(f"the 512 bucket never compiled: {sorted(seqs)}")
    return sorted(seqs)


def embed_again(encoder, documents: list[str], queries: list[str]):
    """``(document vectors, query vectors)`` straight from the encoder, for
    the reference checks.  Documents go in the embedder's batches of 256:
    the shapes ingestion already compiled, and a quarter of the activation
    memory of one 520 x 512 batch."""
    import numpy as np

    doc_vecs = np.concatenate(
        [encoder.encode(documents[i:i + 256]) for i in range(0, len(documents), 256)]
    )
    return doc_vecs, [encoder.encode([q])[0] for q in queries]


def check_retrieval_reference(doc_vecs, query_vecs, documents, run: dict, tol: float = 0.01) -> None:
    """The served scores against an f32 host reference over the same
    embeddings: each hit's score matches the reference score of that
    document, and no better document was missed, within bf16 rounding of
    the index (cos of unit vectors: |error| <~ 2^-8 per score)."""
    import numpy as np

    row = {text: i for i, text in enumerate(documents)}
    for query_vec, hits in zip(query_vecs, run["retrieved"]):
        ref = doc_vecs @ query_vec
        kth_best = np.sort(ref)[-len(hits)]
        for hit in hits:
            want = ref[row[hit["text"]]]
            if abs(-hit["dist"] - want) > tol or want < kth_best - tol:
                raise AssertionError(
                    f"hit {row[hit['text']]} scored {-hit['dist']:.4f}, reference "
                    f"{want:.4f}, reference k-th best {kth_best:.4f}"
                )


def check_sharded_index(mesh, doc_vecs, query_vecs, documents, run: dict) -> list[str]:
    """The live index's corpus sits on every chip of ``mesh``, and its
    answers equal the one-chip path's on the same vectors."""
    import gc

    from pathway_tpu.ops.topk import DeviceIndexCache
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        BruteForceKnnIndex,
        DistanceMetric,
    )

    live = [
        o for o in gc.get_objects()
        if isinstance(o, DeviceIndexCache) and o.mesh is mesh and o._padded is not None
    ]
    if not live:
        raise AssertionError("no live sharded index")
    # every route that searches builds an index of its own
    for cache in live:
        devices = sorted(str(s.device) for s in cache._padded.addressable_shards)
        if len(set(devices)) != mesh.size:
            raise AssertionError(f"corpus on {devices}, mesh has {mesh.size} chips")
    one_chip = BruteForceKnnIndex(DistanceMetric.COS)
    for i, vec in enumerate(doc_vecs):
        one_chip.add(i, vec)
    for query_vec, hits in zip(query_vecs, run["retrieved"]):
        want = [i for i, _ in one_chip.search(query_vec, len(hits))]
        got = [documents.index(h["text"]) for h in hits]
        if got != want:
            raise AssertionError(f"sharded ids {got} != one-chip ids {want}")
    return devices


def cache_entries(directory: str) -> int:
    return len(os.listdir(directory)) if os.path.isdir(directory) else 0


def verdict(ok: bool, devices: list) -> str:
    """The last stdout line: these keys and no others, the device as JAX
    reports it.  Everything else the run learned goes in the report line
    printed before it."""
    return json.dumps({
        "ok": bool(ok),
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    })


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = parser.parse_args()

    import jax

    device0 = jax.devices()[0]
    if device0.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found platform {device0.platform!r}")
    if len(jax.devices()) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but {len(jax.devices())} TPU device(s)")

    import jaxlib
    import numpy as np

    from pathway_tpu import native
    from pathway_tpu.device import default_executor_snapshot, get_default_executor
    from pathway_tpu.device.compile_cache import ensure_compile_cache
    from pathway_tpu.models import shared_sentence_encoder
    from pathway_tpu.serving import generation

    t_start = time.monotonic()
    cache_dir = ensure_compile_cache()
    entries_before = cache_entries(cache_dir)
    if native.get() is None:
        sys.exit("chip_smoke: native core did not build (see the warning above)")

    kernel_err = check_attention_kernel()

    mesh = None
    if args.chips > 1:
        from pathway_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(args.chips)
    documents = make_documents(N_DOCS)
    port = free_port()
    server = build_rag_server(ENCODER_MODEL, DECODER_MODEL, documents, port=port, mesh=mesh)
    wait_until_listening(port, server.run_server(threaded=True, with_cache=False))
    run = drive(port, documents)
    serving_peak = int(device0.memory_stats()["peak_bytes_in_use"])

    executor = get_default_executor()
    device = default_executor_snapshot()
    sched = generation.shared_scheduler(DECODER_MODEL)
    gen = sched.snapshot()
    counters = check_ledgers(device, gen, len(run["answers"]), executor_topk=mesh is None)
    seq_buckets = check_encoder_programs(executor, device)
    if not np.isfinite(np.asarray(sched._logits)).all():
        raise AssertionError("decoder logits are not finite")
    encoder = shared_sentence_encoder(ENCODER_MODEL)
    doc_vecs, query_vecs = embed_again(encoder, documents, run["queries"])
    check_retrieval_reference(doc_vecs, query_vecs, documents, run)
    index_devices = (
        check_sharded_index(mesh, doc_vecs, query_vecs, documents, run)
        if mesh is not None
        else [str(device0)]
    )
    kind = device0.device_kind
    if kind.lower() not in device["cost"]["peak_source"]:
        raise AssertionError(f"peak is {device['cost']['peak_source']!r}, chip is {kind!r}")
    if device["hbm"]["source"] != "memory_stats":
        raise AssertionError(f"hbm from {device['hbm']['source']!r}, not the allocator")

    lm = sched.lm
    import importlib.metadata

    report = {
        "platform": device0.platform,
        "device_kind": kind,
        "device_count": len(jax.devices()),
        "versions": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": importlib.metadata.version("libtpu"),
        },
        "encoder": ENCODER_MODEL,
        "decoder": os.path.basename(DECODER_MODEL),
        "decoder_layers": lm.config.layers,
        "weights": "pretrained" if lm.pretrained and encoder.pretrained else "random-init",
        "tokenizer": type(lm.tokenizer).__name__,
        "documents": len(documents),
        "chips": args.chips,
        "index_devices": index_devices,
        "encoder_devices": sorted(
            {str(d) for leaf in jax.tree_util.tree_leaves(encoder._infer_params)
             for d in leaf.devices()}
        ),
        "decoder_devices": sorted(
            {str(d) for leaf in jax.tree_util.tree_leaves(lm.params) for d in leaf.devices()}
        ),
        "requests_sent": len(run["queries"]) + len(run["prompts"]),
        "requests_answered": len(run["retrieved"]) + len(run["answers"]),
        "tokens_generated": gen["tokens_total"],
        "cold_compiles": sum(c["cold"] for c in device["callables"].values()),
        "encoder_seq_buckets": seq_buckets,
        "attention_kernel_max_abs_err": kernel_err,
        "ingest_s": round(run["ingest_s"], 1),
        "first_answer_s": round(run["first_answer_s"], 1),
        "answers_s": round(run["answers_s"], 1),
        "total_s": round(time.monotonic() - t_start, 1),
        "compile_cache": {
            "dir": cache_dir,
            "entries_before": entries_before,
            "entries_after": cache_entries(cache_dir),
        },
        # allocator high-water marks: after the last answer, and after
        # this script's own checks (which embed every document again)
        "serving_peak_bytes_in_use": serving_peak,
        "peak_bytes_in_use": int(device0.memory_stats()["peak_bytes_in_use"]),
        "bytes_limit": int(device0.memory_stats()["bytes_limit"]),
        "resilience": counters,
    }
    generation.reset_shared_schedulers()
    print(json.dumps(report), flush=True)
    print(verdict(True, jax.devices()), flush=True)


if __name__ == "__main__":
    main()
