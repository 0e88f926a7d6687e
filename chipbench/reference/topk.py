"""Plain reference of the index search: cosine scores of unit vectors as
one float32 matrix product at ``highest`` precision over every row, and
the k best of each full row of scores picked on the host.  No padding, no
mask, no blocks of candidates."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def scores(corpus, queries) -> np.ndarray:
    """[n_queries, n_rows] cosine scores; rows and queries are normalised
    here, whatever they came as."""
    with jax.default_matmul_precision("highest"):
        c = jnp.asarray(corpus, jnp.float32)
        q = jnp.asarray(queries, jnp.float32)
        c = c / jnp.maximum(jnp.linalg.norm(c, axis=1, keepdims=True), 1e-12)
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        return np.asarray(q @ c.T)


def topk(score_rows: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """(ids, scores) of the ``k`` best rows of each query, best first."""
    part = np.argpartition(-score_rows, k - 1, axis=1)[:, :k]
    order = np.argsort(-np.take_along_axis(score_rows, part, axis=1), axis=1, kind="stable")
    ids = np.take_along_axis(part, order, axis=1)
    return ids, np.take_along_axis(score_rows, ids, axis=1)
