"""Corpus-sharded device index: distributed exact top-k over the mesh.

TPU-native replacement for the reference's external-index-per-worker model
(``src/external_integration/``): the document embedding matrix is sharded
row-wise over *all* chips (each chip's slice is the analog of one worker's
key-shard), queries are replicated, and retrieval is

    local MXU einsum → local top-k → all_gather of k candidates/chip →
    final top-k

so the payload crossing ICI is ``n_chips × k`` (id, score) pairs per query —
vectors never leave HBM, matching SURVEY.md §5's "exchange channels carry
only row ids" mapping.  Written with ``jax.shard_map`` so the collective
schedule is explicit; everything inside is jit-compiled.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _flat_axis_index(axes: tuple[str, ...], mesh: Mesh):
    idx = lax.axis_index(axes[0])
    for ax in axes[1:]:
        idx = idx * mesh.shape[ax] + lax.axis_index(ax)
    return idx


@functools.partial(jax.jit, static_argnames=("k", "mesh", "axes", "metric"))
def _sharded_topk_impl(
    docs, mask, queries, *, k: int, mesh: Mesh, axes: tuple[str, ...], metric: str = "ip"
):
    n_chips = 1
    for ax in axes:
        n_chips *= mesh.shape[ax]
    # per-shard candidate count: k capped at the shard's row count; the
    # merge then sees n_chips * k_local >= k candidates (callers cap k at n)
    k_local = min(k, docs.shape[0] // n_chips)

    def local(docs_blk, mask_blk, q):
        # shared metric definition — scores match the single-chip path
        # (ops/topk.py score_block) bit-for-bit
        from pathway_tpu.ops.topk import exact_topk, score_block

        scores = score_block(docs_blk, q, metric)
        # keep the GEMM out of the top_k fusion (see ops/topk.py — 18x on
        # the CPU backend, harmless on TPU)
        scores = lax.optimization_barrier(scores) + mask_blk[None, :]
        # two-stage exact top-k: a full sort over the shard's megarow
        # (not the GEMM) is what dominates large-corpus latency
        vals, idx = exact_topk(scores, k_local)
        shard = _flat_axis_index(axes, mesh)
        idx = idx + shard * docs_blk.shape[0]
        vals_g = lax.all_gather(vals, axes, axis=1, tiled=True)
        idx_g = lax.all_gather(idx, axes, axis=1, tiled=True)
        best_vals, pos = lax.top_k(vals_g, k)
        return jnp.take_along_axis(idx_g, pos, axis=1), best_vals

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )(docs, mask, queries)


def sharded_topk(
    mesh: Mesh,
    docs: jax.Array,
    mask: jax.Array,
    queries: jax.Array,
    k: int,
    metric: str = "ip",
) -> tuple[jax.Array, jax.Array]:
    """(indices, scores) of the k best doc rows per query, across all chips."""
    axes = tuple(mesh.axis_names)
    return _sharded_topk_impl(
        docs, mask, queries, k=k, mesh=mesh, axes=axes, metric=metric
    )


class ShardedDeviceIndex:
    """A padded, corpus-sharded embedding index resident across chip HBM.

    Capacity grows in multiples of ``n_chips × block`` so every chip holds
    an equal slice and streaming growth hits a warm compile cache.  Padded
    rows carry a ``-inf`` score mask.  Cosine similarity assumes rows are
    L2-normalized (the encoders in ``models/encoder.py`` guarantee this).
    """

    def __init__(self, mesh: Mesh, dim: int, block: int = 1024, dtype=None):
        self.mesh = mesh
        self.dim = dim
        self.n_chips = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        self.block = block
        # north-star layout stores the corpus in bf16 (HBM: 2 bytes/dim —
        # 10M x 384 over 16 chips = 480 MB/chip); score_block casts to the
        # matmul dtype per backend, so storage dtype only sets memory
        self.dtype = np.float32 if dtype is None else dtype
        self._n = 0
        self._docs = None
        self._mask = None
        self._host_rows: list[np.ndarray] = []
        self._dirty = False

    def __len__(self) -> int:
        return self._n

    def add(self, vectors: np.ndarray) -> None:
        vectors = np.atleast_2d(np.asarray(vectors, np.float32))
        self._host_rows.append(vectors)
        self._n += vectors.shape[0]
        self._dirty = True

    def _capacity(self, n: int) -> int:
        unit = self.n_chips * self.block
        return max(unit, ((n + unit - 1) // unit) * unit)

    def _sync(self) -> None:
        if not self._dirty:
            return
        full = (
            np.concatenate(self._host_rows, axis=0)
            if self._host_rows
            else np.zeros((0, self.dim), np.float32)
        )
        cap = self._capacity(self._n)
        padded = np.zeros((cap, self.dim), self.dtype)
        padded[: self._n] = full
        mask = np.full((cap,), -np.inf, np.float32)
        mask[: self._n] = 0.0
        axes = tuple(self.mesh.axis_names)
        from pathway_tpu.parallel.mesh import put_global

        self._docs = put_global(padded, NamedSharding(self.mesh, P(axes, None)))
        self._mask = put_global(mask, NamedSharding(self.mesh, P(axes)))
        self._dirty = False

    def search(self, queries: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
        if self._n == 0:
            q = np.atleast_2d(queries)
            return (
                np.zeros((q.shape[0], 0), np.int64),
                np.zeros((q.shape[0], 0), np.float32),
            )
        self._sync()
        from pathway_tpu.parallel.mesh import put_global

        # queries are replicated; route through put_global so a mesh that
        # spans hosts still accepts them (device_put cannot target
        # non-addressable devices)
        q = put_global(
            np.atleast_2d(np.asarray(queries, np.float32)),
            NamedSharding(self.mesh, P(None, None)),
        )
        k_eff = min(k, self._n)
        idx, vals = sharded_topk(self.mesh, self._docs, self._mask, q, k_eff)
        return np.asarray(idx), np.asarray(vals)
