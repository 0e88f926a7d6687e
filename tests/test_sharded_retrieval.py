"""The distributed device index wired into the *product* retrieval path.

VERDICT round-1 item 2: the corpus-sharded shard_map top-k
(``pathway_tpu/parallel/index.py``) must serve real retrieval —
DataIndex/DocumentStore — not just live beside it.  These tests run the
full dataflow path on the 8-virtual-device CPU mesh (conftest) and assert
the sharded answers are identical to the single-device ones, preserving
as-of-now retraction semantics (ExternalIndexNode).

Reference analog: index attached to the dataflow with as-of-now
retraction, src/engine/dataflow.rs:2694 + external_integration/mod.rs:40-50.
"""

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.debug import _capture_table
from pathway_tpu.engine.types import Json
from pathway_tpu.io._utils import make_static_input_table
from pathway_tpu.ops import topk as topk_ops
from pathway_tpu.parallel import (
    make_mesh,
    set_default_index_mesh,
    get_default_index_mesh,
)
from pathway_tpu.stdlib.indexing import BruteForceKnnFactory, UsearchKnnFactory
from pathway_tpu.stdlib.indexing.nearest_neighbors import (
    BruteForceKnnIndex,
    DistanceMetric,
)
from pathway_tpu.xpacks.llm import DocumentStore
from pathway_tpu.xpacks.llm.mocks import FakeEmbeddings


@pytest.fixture
def mesh():
    return make_mesh(8)


def _docs(entries):
    return make_static_input_table(
        pw.schema_from_types(data=bytes, _metadata=Json),
        [{"data": text.encode(), "_metadata": Json(meta)} for text, meta in entries],
    )


def _retrieval_results(factory, doc_entries, query, k):
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    docs = _docs(doc_entries)
    store = DocumentStore(docs, factory)
    queries = make_static_input_table(
        DocumentStore.RetrieveQuerySchema,
        [
            {
                "query": query,
                "k": k,
                "metadata_filter": None,
                "filepath_globpattern": None,
            }
        ],
    )
    cap = _capture_table(store.retrieve_query(queries))
    rows = list(cap.final_rows().values())
    assert len(rows) == 1
    return [(d["text"], d["dist"]) for d in rows[0][0].value]


def _assert_results_match(sharded, single, atol=0.02):
    """Same docs in the same order; scores within bf16-vs-f32 tolerance
    (the single-device path computes tiny corpora on host in f32)."""
    assert [t for t, _ in sharded] == [t for t, _ in single]
    for (_, a), (_, b) in zip(sharded, single):
        assert abs(a - b) <= atol, (sharded, single)


DOCS = [
    ("alpha beta gamma", {"path": "/a.txt", "modified_at": 1}),
    ("delta epsilon zeta", {"path": "/b.txt", "modified_at": 2}),
    ("alpha beta delta", {"path": "/c.txt", "modified_at": 3}),
    ("eta theta iota", {"path": "/d.txt", "modified_at": 4}),
    ("gamma gamma gamma", {"path": "/e.txt", "modified_at": 5}),
]


def test_document_store_mesh_matches_single_device(mesh):
    """Full DocumentStore retrieval: sharded answers == single-device answers."""
    single = _retrieval_results(
        BruteForceKnnFactory(embedder=FakeEmbeddings()), DOCS, "alpha beta gamma", 3
    )
    sharded = _retrieval_results(
        BruteForceKnnFactory(embedder=FakeEmbeddings(), mesh=mesh),
        DOCS,
        "alpha beta gamma",
        3,
    )
    _assert_results_match(sharded, single)
    assert sharded[0][0] == "alpha beta gamma"


def test_usearch_factory_mesh_matches_single_device(mesh):
    single = _retrieval_results(
        UsearchKnnFactory(embedder=FakeEmbeddings()), DOCS, "delta epsilon zeta", 2
    )
    sharded = _retrieval_results(
        UsearchKnnFactory(embedder=FakeEmbeddings(), mesh=mesh),
        DOCS,
        "delta epsilon zeta",
        2,
    )
    _assert_results_match(sharded, single)


def test_default_index_mesh_routes_document_store(mesh):
    """set_default_index_mesh() reroutes indexes built without explicit mesh."""
    single = _retrieval_results(
        BruteForceKnnFactory(embedder=FakeEmbeddings()), DOCS, "gamma", 2
    )
    set_default_index_mesh(mesh)
    try:
        assert get_default_index_mesh() is mesh
        sharded = _retrieval_results(
            BruteForceKnnFactory(embedder=FakeEmbeddings()), DOCS, "gamma", 2
        )
    finally:
        set_default_index_mesh(None)
    _assert_results_match(sharded, single)


def test_sharded_index_as_of_now_retraction(mesh):
    """Index mutation re-answers standing queries through the sharded path
    with retraction — the ExternalIndexNode semantics, now mesh-backed."""
    index = BruteForceKnnIndex(DistanceMetric.COS, mesh=mesh)
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(6, 8)).astype(np.float32)
    for i in range(3):
        index.add(i, vecs[i])
    first = index.search(vecs[0], k=2)
    assert first[0][0] == 0
    # add a duplicate of the query vector under a new key: it must take over
    index.add(77, vecs[0])
    second = index.search(vecs[0], k=2)
    assert {second[0][0], second[1][0]} == {0, 77}
    index.remove(77)
    third = index.search(vecs[0], k=2)
    assert third == first


@pytest.mark.parametrize("metric", ["cos", "ip", "l2sq"])
def test_sharded_topk_matches_single_device_all_metrics(mesh, metric):
    """The mesh path and the single-chip path share one metric definition
    (ops/topk.py score_block) — answers must agree exactly."""
    rng = np.random.default_rng(1)
    docs = rng.normal(size=(300, 16)).astype(np.float32)
    queries = rng.normal(size=(5, 16)).astype(np.float32)
    sharded_cache = topk_ops.DeviceIndexCache(mesh=mesh)
    idx, vals = topk_ops.topk_search_cached(
        docs, queries, 7, metric, cache=sharded_cache, version=0
    )
    single_cache = topk_ops.DeviceIndexCache()
    ref_idx, ref_vals = topk_ops.topk_search_cached(
        docs, queries, 7, metric, cache=single_cache, version=0
    )
    assert idx.shape == (5, 7)
    np.testing.assert_allclose(vals, ref_vals, rtol=1e-6, atol=1e-6)
    for row, ref_row in zip(idx, ref_idx):
        assert set(row.tolist()) == set(ref_row.tolist())
    # and the ranking is faithful to the host-side ground truth
    host_scores = topk_ops._score_numpy(docs, queries, metric)
    host_best = np.argmax(host_scores, axis=1)
    np.testing.assert_array_equal(idx[:, 0], host_best)


def test_million_row_padded_capacity(mesh):
    """>=1M-row corpus sharded over the mesh: padded capacity divides evenly
    across chips and planted nearest neighbours are found exactly."""
    n, dim = 1_000_000, 16
    rng = np.random.default_rng(2)
    docs = rng.normal(size=(n, dim)).astype(np.float32)
    docs /= np.linalg.norm(docs, axis=1, keepdims=True)
    # plant exact duplicates of the probe rows deep in the corpus
    probes = np.arange(4) * 249_999 + 13
    queries = docs[probes].copy()
    cache = topk_ops.DeviceIndexCache(mesh=mesh)
    idx, vals = topk_ops.topk_search_cached(
        docs, queries, 1, "cos", cache=cache, version=0
    )
    assert idx[:, 0].tolist() == probes.tolist()
    np.testing.assert_allclose(vals[:, 0], 1.0, atol=0.02)  # bf16 matmul
    # capacity is an equal multiple of the chip count
    cap = cache._padded.shape[0]
    assert cap >= n and cap % 8 == 0
    # warm-cache growth: adding rows within capacity reuses the same buffer shape
    docs2 = np.concatenate([docs, queries], axis=0)
    idx2, _ = topk_ops.topk_search_cached(
        docs2, queries, 2, "cos", cache=cache, version=1
    )
    assert cache._padded.shape[0] == cap  # same power-of-two bucket
    for row, planted in zip(idx2, probes):
        assert planted in row.tolist()


# ---------------------------------------------------------------------------
# 10M-doc north-star rehearsal (VERDICT r3 item 4; BASELINE.md: 10M docs on
# v5e-16, p50 retrieval < 20 ms, 625k x 384-dim bf16 per chip)
# ---------------------------------------------------------------------------


def test_north_star_capacity_model():
    """Pure capacity math for the 10M / v5e-16 layout — the documented
    model the full-scale rehearsal below executes."""
    from pathway_tpu.parallel.index import ShardedDeviceIndex

    class _FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 8, "model": 2}

    ix = ShardedDeviceIndex.__new__(ShardedDeviceIndex)
    ix.n_chips = 16
    ix.block = 1024
    n_docs = 10_000_000
    cap = ix._capacity(n_docs)
    # capacity grows in multiples of n_chips*block: equal slices per chip
    assert cap >= n_docs and cap % (16 * 1024) == 0
    per_chip = cap // 16
    assert per_chip == 625_664  # ceil(10M/16) rounded to the 1024 block
    # HBM budget at bf16: corpus slice per chip comfortably inside v5e 16GB
    hbm_bytes = per_chip * 384 * 2
    assert hbm_bytes < 500 * 1024 * 1024  # ~480 MB/chip
    # per-query work: one fused GEMM over the local slice, 2*N*D flops,
    # then top-k and an all_gather of 16*k (id, score) pairs — the only
    # payload crossing ICI
    flops_per_query_per_chip = 2 * per_chip * 384
    assert flops_per_query_per_chip < 1e9  # ~0.48 GFLOP: <<1ms of v5e MXU


def test_sharded_index_bf16_storage(mesh):
    """bf16 corpus storage (the north-star dtype): same top-1 answers as
    f32 at realistic dim, scores within bf16 rounding."""
    import jax.numpy as jnp

    from pathway_tpu.parallel.index import ShardedDeviceIndex

    n, dim = 4096, 384
    rng = np.random.default_rng(5)
    docs = rng.normal(size=(n, dim)).astype(np.float32)
    docs /= np.linalg.norm(docs, axis=1, keepdims=True)
    ix16 = ShardedDeviceIndex(mesh, dim=dim, block=256, dtype=jnp.bfloat16)
    ix32 = ShardedDeviceIndex(mesh, dim=dim, block=256)
    ix16.add(docs)
    ix32.add(docs)
    q = docs[:8]
    ids16, s16 = ix16.search(q, k=3)
    ids32, s32 = ix32.search(q, k=3)
    assert ids16[:, 0].tolist() == list(range(8))
    assert ids16[:, 0].tolist() == ids32[:, 0].tolist()
    np.testing.assert_allclose(s16[:, 0], s32[:, 0], atol=0.02)
    # the device buffer really is bf16 (half the HBM)
    assert ix16._docs.dtype == jnp.bfloat16


def test_sharded_index_flops_per_query(mesh):
    """Pin the per-query FLOP count of the compiled sharded top-k: one
    GEMM over the corpus (2*N*D per query) — no hidden recompute."""
    import jax

    from pathway_tpu.parallel.index import _sharded_topk_impl

    n, dim, n_q, k = 8192, 64, 4, 5
    rng = np.random.default_rng(0)
    docs = rng.normal(size=(n, dim)).astype(np.float32)
    mask = np.zeros((n,), np.float32)
    q = rng.normal(size=(n_q, dim)).astype(np.float32)
    axes = tuple(mesh.axis_names)
    lowered = _sharded_topk_impl.lower(
        docs, mask, q, k=k, mesh=mesh, axes=axes, metric="ip"
    )
    cost = lowered.compile().cost_analysis()
    if isinstance(cost, (list, tuple)):  # older jax: one dict per computation
        cost = cost[0] if cost else {}
    flops = cost.get("flops", 0.0)
    n_chips = 1
    for ax in axes:
        n_chips *= mesh.shape[ax]
    # XLA reports PER-PARTITION cost: each chip runs one GEMM over its
    # corpus slice — 2 * (N/n_chips) * D per query.  Within 2x rules out
    # any hidden recompute/doubled matmul; top-k/all_gather are the slack
    expected_per_chip = 2.0 * (n / n_chips) * dim * n_q
    assert expected_per_chip * 0.5 <= flops <= expected_per_chip * 2.0, (
        flops,
        expected_per_chip,
    )


@pytest.mark.skipif(
    "PATHWAY_SCALE_TESTS" not in __import__("os").environ,
    reason="full 10M rehearsal: ~16 GB host RAM and minutes of CPU "
    "(set PATHWAY_SCALE_TESTS=1); the capacity model above always runs",
)
def test_ten_million_doc_rehearsal(mesh):
    """The actual north-star shard layout executed on the virtual mesh:
    10M x 384 bf16 over 8 devices (each virtual device holds 2 v5e chips'
    worth), planted-neighbor exactness, padded-capacity math, p50 timing
    (CPU — the chip's latency at this shard size is bench.py's
    retrieval_625k extra)."""
    import time

    import jax.numpy as jnp

    from pathway_tpu.parallel.index import ShardedDeviceIndex

    n, dim = 10_000_000, 384
    rng = np.random.default_rng(7)
    ix = ShardedDeviceIndex(mesh, dim=dim, block=1024, dtype=jnp.bfloat16)
    # add in slabs to bound peak host memory
    slab = 1_000_000
    probes = []
    for s in range(0, n, slab):
        block = rng.normal(size=(slab, dim)).astype(np.float32)
        block /= np.linalg.norm(block, axis=1, keepdims=True)
        if s == 0:
            probes = block[:4].copy()
        ix.add(block)
    assert len(ix) == n
    t0 = time.perf_counter()
    ids, scores = ix.search(probes, k=10)
    build_and_first_query_s = time.perf_counter() - t0
    assert ids[:, 0].tolist() == [0, 1, 2, 3]
    np.testing.assert_allclose(scores[:, 0], 1.0, atol=0.02)
    cap = ix._docs.shape[0]
    assert cap % (8 * 1024) == 0 and cap >= n
    lat = []
    for i in range(5):
        t0 = time.perf_counter()
        ix.search(probes[i % 4 : i % 4 + 1], k=10)
        lat.append(time.perf_counter() - t0)
    lat.sort()
    print(
        f"10M rehearsal: first(incl sync) {build_and_first_query_s:.1f}s, "
        f"p50 query {lat[2]*1000:.0f} ms on CPU mesh"
    )
