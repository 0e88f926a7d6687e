"""Dense similarity scoring / top-k — the TPU replacement for the
reference's ``mat_mul.rs`` + ``brute_force_knn_integration.rs`` dense scan.

Design (SURVEY.md §7, BASELINE north star): the index matrix lives on device
in HBM; queries are embedded on device; scores are one einsum on the MXU.
Shapes are bucketed to powers of two so streaming index growth hits a warm
XLA compile cache; the padded tail is masked to -inf.

Matrices under ``_JAX_MIN_ROWS`` rows are scored in numpy on the host:
device dispatch overhead dominates there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_JAX_MIN_ROWS = 256  # below this, host numpy beats dispatch overhead


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def score_block(matrix, queries, metric: str):
    """Traceable similarity scores [n_queries, n_rows]; larger = closer.

    The ONE device-side definition of each metric — used by both the
    single-chip jitted path below and the shard_map distributed top-k
    (``pathway_tpu/parallel/index.py``), so scores agree bit-for-bit
    between them.  cos/ip run the matmul in bfloat16 (MXU-native);
    l2sq stays float32 (catastrophic cancellation in bf16).
    """
    # bf16 is MXU-native; on CPU it is software-emulated and far slower
    # than f32, so the fallback path keeps the native dtype
    mm_dtype = jnp.bfloat16 if jax.default_backend() not in ("cpu",) else jnp.float32
    m = matrix.astype(mm_dtype)
    q = queries.astype(mm_dtype)
    if metric == "cos":
        mn = m / (jnp.linalg.norm(m, axis=1, keepdims=True).astype(mm_dtype) + 1e-6)
        qn = q / (jnp.linalg.norm(q, axis=1, keepdims=True).astype(mm_dtype) + 1e-6)
        return (qn @ mn.T).astype(jnp.float32)
    if metric == "ip":
        return (q @ m.T).astype(jnp.float32)
    # l2sq: return negative squared distance so that larger = closer
    m32 = matrix.astype(jnp.float32)
    q32 = queries.astype(jnp.float32)
    sq_m = jnp.sum(m32 * m32, axis=1)[None, :]
    sq_q = jnp.sum(q32 * q32, axis=1)[:, None]
    return -(sq_q + sq_m - 2.0 * (q32 @ m32.T))


_score_jax = functools.partial(jax.jit, static_argnames=("metric",))(score_block)


def exact_topk(scores, k: int):
    """Exact top-k over a large score row, two-stage.

    ``lax.top_k`` over a megarow is a full sort (~140 ms/query at 1M
    on v5e — it, not the GEMM, dominated retrieval latency).  Stage 1
    takes top-k within 1024-wide blocks (vectorized small sorts);
    stage 2 reduces the ``blocks × k`` candidates.  Exact: every
    global winner is by definition in its own block's top-k.
    """
    Q, N = scores.shape
    bs = 1024
    while N % bs:
        bs >>= 1
    blocks = N // bs
    if N <= 65536 or blocks < 2 or k > bs:
        return jax.lax.top_k(scores, k)
    vals, idx = jax.lax.top_k(scores.reshape(Q, blocks, bs), k)
    gidx = idx + (jnp.arange(blocks, dtype=idx.dtype) * bs)[None, :, None]
    v, pos = jax.lax.top_k(vals.reshape(Q, blocks * k), k)
    return v, jnp.take_along_axis(gidx.reshape(Q, blocks * k), pos, axis=1)


def masked_topk_block(matrix, mask, queries, *, metric: str, k: int):
    """Traceable masked top-k — registered on the DeviceExecutor
    (the sanctioned jit entry point), which buckets the query batch
    so churning query counts never recompile."""
    scores = score_block(matrix, queries, metric)
    # keep the dot out of the top_k fusion: XLA (notably on CPU) would
    # otherwise inline the GEMM into the sort fusion and lose the fast
    # matmul path — measured 18x slower without the barrier
    scores = jax.lax.optimization_barrier(scores)
    return exact_topk(scores + mask[None, :], k)


_TOPK_CALLABLE = "indexing:masked_topk"


def _topk_executor():
    """The default executor with the masked top-k registered once."""
    from pathway_tpu.device import get_default_executor

    ex = get_default_executor()
    if not ex.registered(_TOPK_CALLABLE):
        ex.register(
            _TOPK_CALLABLE,
            masked_topk_block,
            static_argnames=("metric", "k"),
        )
    return ex


def masked_topk_jitted():
    """The compiled masked top-k wrapper for pre-padded fixed shapes
    — the raw-kernel surface the retrieval benchmarks time.  Call
    with keyword ``metric=``/``k=``; production code goes through
    ``topk_search_cached`` (executor-bucketed)."""
    return _topk_executor().jitted(_TOPK_CALLABLE)


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_jax(scores, k: int):
    return jax.lax.top_k(scores, k)


class DeviceIndexCache:
    """Keeps the padded index matrix (and its padding mask) resident on
    device across queries.

    Rebuilds (re-pads, re-uploads) only when the index changed; the capacity
    grows in power-of-two buckets so streaming index growth hits a warm XLA
    compile cache instead of recompiling per row count.  Padded rows carry a
    -inf mask so they never win top-k.

    With a ``mesh``, the padded matrix is sharded row-wise over every chip
    (``NamedSharding(P(axes, None))``) and queries run through the shard_map
    distributed top-k (``pathway_tpu/parallel/index.py``) — the corpus never
    leaves HBM; only ``n_chips × k`` (id, score) pairs cross ICI.
    """

    def __init__(self, mesh=None):
        self.mesh = mesh
        self._version = -1
        self._metric = None
        self._padded = None
        self._mask = None
        self._n = 0

    def _n_chips(self) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for ax in self.mesh.axis_names:
            n *= self.mesh.shape[ax]
        return n

    def get(self, matrix: np.ndarray, version: int, metric: str = "raw"):
        n = matrix.shape[0]
        cap = _next_pow2(max(n, _JAX_MIN_ROWS))
        chips = self._n_chips()
        if cap % chips:  # non-power-of-two meshes: equal slices per chip
            cap = ((cap + chips - 1) // chips) * chips
        if (
            self._padded is None
            or version != self._version
            or metric != self._metric
            or self._padded.shape[0] != cap
            or self._padded.shape[1] != matrix.shape[1]
        ):
            padded = np.zeros((cap, matrix.shape[1]), dtype=np.float32)
            padded[:n] = matrix
            if metric == "cos":
                # normalize ONCE at build: the query kernel then runs a
                # plain inner product — re-normalizing the corpus per query
                # would add a full HBM sweep to every search
                norms = np.linalg.norm(padded[:n], axis=1, keepdims=True)
                padded[:n] /= np.maximum(norms, 1e-12)
            mask = np.full((cap,), -np.inf, dtype=np.float32)
            mask[:n] = 0.0
            # cos/ip score in bf16 on the MXU anyway — store the resident
            # matrix in bf16 there so every query sweeps half the HBM
            # bytes (and capacity doubles).  l2sq and the CPU backend keep
            # f32 (bf16 is software-emulated on CPU; l2sq cancels in bf16).
            store = padded
            if metric in ("cos", "ip") and jax.default_backend() not in ("cpu",):
                import ml_dtypes  # host-side cast; device_put ships bf16 bytes

                store = padded.astype(ml_dtypes.bfloat16)
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                axes = tuple(self.mesh.axis_names)
                self._padded = jax.device_put(
                    store, NamedSharding(self.mesh, P(axes, None))
                )
                self._mask = jax.device_put(mask, NamedSharding(self.mesh, P(axes)))
            else:
                self._padded = jax.device_put(jnp.asarray(store))
                self._mask = jax.device_put(jnp.asarray(mask))
            self._version = version
            self._metric = metric
            self._n = n
        return self._padded, self._mask, self._n


def topk_search_cached(
    matrix: np.ndarray,
    queries: np.ndarray,
    k: int,
    metric: str,
    *,
    cache: DeviceIndexCache,
    version: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Top-k against a device-resident padded index (warm across queries)."""
    n = matrix.shape[0]
    k_eff = min(k, n)
    if n < _JAX_MIN_ROWS and cache.mesh is None:
        scores = _score_numpy(
            matrix.astype(np.float32), queries.astype(np.float32), metric
        )
        idx = np.argsort(-scores, kind="stable", axis=1)[:, :k_eff]
        return idx, np.take_along_axis(scores, idx, axis=1)
    device_matrix, mask, _n = cache.get(matrix, version, metric)
    q = queries.astype(np.float32)
    kernel_metric = metric
    if metric == "cos":
        # the cached matrix is pre-normalized; normalize the (tiny) query
        # batch on host and run the kernel as a plain inner product
        q = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        kernel_metric = "ip"
    if cache.mesh is not None:
        from pathway_tpu.parallel.index import sharded_topk

        idx, vals = sharded_topk(
            cache.mesh,
            device_matrix,
            mask,
            jnp.asarray(q),
            k_eff,
            kernel_metric,
        )
        return np.asarray(idx), np.asarray(vals)
    vals, idx = _topk_executor().run_batch(
        _TOPK_CALLABLE,
        (q.astype(np.float32, copy=False),),
        operands=(device_matrix, mask),
        static={"metric": kernel_metric, "k": k_eff},
    )
    return np.asarray(idx), np.asarray(vals)


def _score_numpy(matrix: np.ndarray, queries: np.ndarray, metric: str) -> np.ndarray:
    if metric == "cos":
        mn = matrix / (np.linalg.norm(matrix, axis=1, keepdims=True) + 1e-12)
        qn = queries / (np.linalg.norm(queries, axis=1, keepdims=True) + 1e-12)
        return qn @ mn.T
    if metric == "ip":
        return queries @ matrix.T
    sq_m = np.sum(matrix * matrix, axis=1)[None, :]
    sq_q = np.sum(queries * queries, axis=1)[:, None]
    return -(sq_q + sq_m - 2.0 * (queries @ matrix.T))


def score_batch(matrix: np.ndarray, queries: np.ndarray, metric: str = "cos") -> np.ndarray:
    """Scores [n_queries, n_docs]; larger = closer for every metric."""
    if matrix.ndim != 2:
        matrix = np.atleast_2d(matrix)
    if queries.ndim != 2:
        queries = np.atleast_2d(queries)
    if matrix.shape[0] < _JAX_MIN_ROWS:
        return _score_numpy(
            matrix.astype(np.float32), queries.astype(np.float32), metric
        )
    scores = _score_jax(jnp.asarray(matrix), jnp.asarray(queries), metric)
    return np.asarray(scores)


def topk_search(
    matrix: np.ndarray, queries: np.ndarray, k: int, metric: str = "cos"
) -> tuple[np.ndarray, np.ndarray]:
    """(indices, scores) of the k best rows per query."""
    n = matrix.shape[0]
    k_eff = min(k, n)
    if n < _JAX_MIN_ROWS:
        scores = _score_numpy(
            matrix.astype(np.float32), queries.astype(np.float32), metric
        )
        idx = np.argsort(-scores, axis=1)[:, :k_eff]
        return idx, np.take_along_axis(scores, idx, axis=1)
    scores = _score_jax(jnp.asarray(matrix), jnp.asarray(queries), metric)
    vals, idx = _topk_jax(scores, k_eff)
    return np.asarray(idx), np.asarray(vals)
