"""Pallas TPU kernel: bidirectional (encoder) multi-head attention.

The embedding/rerank hot path runs BERT-family encoders at short sequence
lengths (document chunks, seq buckets 16..512).  XLA's stock lowering of
multi-head attention materializes four `[B, S, heads, hd]` relayout copies
per layer (q, k, v, ctx between the packed `[B*S, H]` matmul layout and the
`[B, heads, S, hd]` batched-matmul layout) plus fp32 score tensors — at
MiniLM shapes that is ~1.2 GB of pure copy traffic per 512x64 batch, more
HBM time than the matmuls themselves (measured: 3.8 ms copies + 3.3 ms
converts vs 3.7 ms of real fusions per step on v5e).

This kernel keeps q/k/v in their natural packed ``[B, S, H]`` lane layout
(exactly what the fused QKV projection produces), computes scores + softmax
+ context entirely in VMEM, and writes ctx back in packed layout — zero
relayouts, zero HBM score traffic.

Head/sequence packing: the MXU wants 128-lane contractions but ``hd`` is 32
(MiniLM) or 64 (BGE), and one sequence is only S<=512 rows.  Each program
takes ``bb`` sequences and, per 128-lane head group (G = 128//hd heads),
stacks the group's heads along MXU rows via a block-diagonal Q operand:

    Q_bd [G*bb*S, 128] = tile(q_rows, (G,1)) * head-block mask
    scores = Q_bd @ k_rows.T          # one full-width MXU matmul
    softmax over lanes (cross-sequence / cross-head lanes masked to -inf)
    ctx = probs @ v_rows              # second full-width matmul
    out = sum_h ctx[h-block] * lane-mask(h)

The zero blocks kill cross-head terms; masking kills cross-sequence terms.
FLOP waste is G*bb x on the attention einsums only — a few percent of
encoder FLOPs — in exchange for full MXU utilization, straight-line code
(no serial inner loops), and one-kernel fusion.

Reference analog: the reference runs attention inside torch/CUDA via
sentence-transformers (`/root/reference/python/pathway/xpacks/llm/
embedders.py:85-401`); this is the TPU-native equivalent of its fused
attention path.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE_GROUP = 128


def _attn_kernel(
    q_ref, k_ref, v_ref, bias_ref, out_ref, *, S: int, hd: int, scale: float
):
    """One program: bb sequences x all heads, softmax in VMEM (f32)."""
    rows, H = q_ref.shape  # rows = bb * S
    G = LANE_GROUP // hd  # heads per 128-lane group
    n_groups = H // LANE_GROUP

    # Structural validity of scores[r, c]: the q row r belongs to sequence
    # (r % rows) // S and the key column c to sequence c // S.
    r_seq = jax.lax.broadcasted_iota(jnp.int32, (G * rows, rows), 0) % rows // S
    c_seq = jax.lax.broadcasted_iota(jnp.int32, (G * rows, rows), 1) // S
    struct = jnp.where(r_seq == c_seq, 0.0, -1e9).astype(jnp.float32)

    # Q_bd head-block mask: row block h only keeps lanes of head h.
    qb_row = jax.lax.broadcasted_iota(jnp.int32, (G * rows, LANE_GROUP), 0)
    qb_col = jax.lax.broadcasted_iota(jnp.int32, (G * rows, LANE_GROUP), 1)
    qmask = (qb_row // rows == qb_col // hd).astype(jnp.bfloat16)

    # Per-head lane masks for the output fold.
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANE_GROUP), 1)

    bias_row = bias_ref[0, :, :].astype(jnp.float32)  # [1, rows] key bias

    for g in range(n_groups):
        lanes = pl.dslice(g * LANE_GROUP, LANE_GROUP)
        q_rows = q_ref[:, lanes]
        k_rows = k_ref[:, lanes]
        v_rows = v_ref[:, lanes]

        q_bd = jnp.tile(q_rows, (G, 1)) * qmask  # [G*rows, 128]
        scores = (
            jax.lax.dot_general(
                q_bd,
                k_rows,
                (((1,), (1,)), ((), ())),  # contract lanes: Q_bd @ k_rows.T
                preferred_element_type=jnp.float32,
            )
            * scale
            + struct
            + bias_row
        )  # [G*rows, rows] f32
        m = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - m)
        probs = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(jnp.bfloat16)
        ctx = jax.lax.dot(
            probs, v_rows, preferred_element_type=jnp.float32
        )  # [G*rows, 128]
        out = jnp.zeros((rows, LANE_GROUP), jnp.float32)
        for h in range(G):
            blk = ctx[h * rows : (h + 1) * rows, :]
            out = out + jnp.where((lane // hd) == h, blk, 0.0)
        out_ref[:, lanes] = out.astype(out_ref.dtype)


def _supported(S: int, H: int, heads: int) -> bool:
    if H % heads:
        return False
    hd = H // heads
    return hd in (32, 64, 128) and H % LANE_GROUP == 0 and S >= 16


def _note_xla_fallback(S: int, H: int, heads: int) -> None:
    """A shape the Pallas kernel rejects is served by the XLA path —
    counted and logged once per trace, so a TPU run that believes it is
    timing the kernel can see that it is not (``device_snapshot()``'s
    ``attention_xla_fallback``)."""
    from pathway_tpu.engine import metrics

    metrics.get_registry().counter(
        "device.attention.xla_fallback",
        "encoder-attention traces served by the XLA path because the "
        "Pallas kernel does not support the shape",
        shape=f"S{S}_H{H}_heads{heads}",
    ).inc()
    logging.getLogger(__name__).warning(
        "encoder_attention: Pallas kernel does not support S=%d H=%d "
        "heads=%d; this trace uses the XLA attention path",
        S, H, heads,
    )


def _xla_attention(q, k, v, mask_bias, heads: int):
    """Reference path (and the only path off-TPU): plain XLA batched
    attention."""
    B, S, H = q.shape
    hd = H // heads
    scale = 1.0 / (hd**0.5)
    q4 = q.reshape(B, S, heads, hd)
    k4 = k.reshape(B, S, heads, hd)
    v4 = v.reshape(B, S, heads, hd)
    scores = jax.lax.dot_general(
        q4, k4, (((3,), (3,)), ((0, 2), (0, 2))), preferred_element_type=jnp.float32
    )  # [B, heads, S, S]
    scores = scores * scale + mask_bias[:, None, None, :].astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    ctx = jax.lax.dot_general(
        probs, v4, (((3,), (1,)), ((0, 1), (0, 2)))
    )  # [B, heads, S, hd]
    return jnp.swapaxes(ctx, 1, 2).reshape(B, S, H)


# ---------------------------------------------------------------------------
# Ragged paged attention (decoder serving path)
# ---------------------------------------------------------------------------
#
# The continuous-batching decode loop (pathway_tpu/serving/generation.py)
# keeps each request's KV in fixed-size PAGES of a preallocated pool
# instead of one dense [B, max_cache] block: cache memory scales with live
# tokens, and a per-slot block table maps logical positions onto pool
# pages (the Ragged Paged Attention layout — PAPERS.md).  The gather below
# is that layout in plain XLA, on the TPU as everywhere else (there is no
# hand-written kernel): every compiled shape is static (slot count fixed,
# page count bucketed by the scheduler), so a churning request mix replays
# one warm program per bucket — `jax.cache.miss == 0` in steady state.  It
# reads every page of every slot's table at the bucketed width, live or
# null; the scatter writes the new rows alone.  Both take the pool of ALL
# layers and the layer's index, so that a layer scan carries the pool
# whole and a layer's pages are read and written where they lie.


def _layer_pages(pool, layer):
    """``pool`` as pages ``[pages, page, KH, D]`` and the page at which the
    cache of ``layer`` starts in it.  ``layer`` None: ``pool`` is one
    layer's ``[P, page, KH, D]``.  Otherwise it is the whole stack ``[L, P,
    page, KH, D]``, flattened over layers and pages (a view, no copy), so
    that a layer scan which carries the stack reads and writes a layer's
    pages where they lie and never slices the layer's pool out of it."""
    if layer is None:
        return pool, 0
    L, P = pool.shape[:2]
    return pool.reshape((L * P,) + pool.shape[2:]), layer * P


def gather_kv_pages(pool, block_tables, layer=None):
    """Gather a slot-major KV view out of the page pool.

    ``pool`` is ``[P, page, KH, D]`` (one layer's pages), or the whole
    stack ``[L, P, page, KH, D]`` with ``layer`` the index (it may be
    traced) of the layer to read; ``block_tables`` ``[S, G]`` int32 page
    indices (entry 0 = the reserved null page for unallocated tail
    entries).  Returns ``[S, G*page, KH, D]`` — each slot's logical cache,
    contiguous again.  Garbage gathered through null-page entries sits at
    positions >= the slot's length and is masked out by the caller.
    """
    S, G = block_tables.shape
    pages, first = _layer_pages(pool, layer)
    g = pages[first + block_tables]  # [S, G, page, KH, D]
    return g.reshape(S, G * pages.shape[1], pages.shape[2], pages.shape[3])


def gqa_attention(q, k, v, mask, sink=None):
    """Grouped-query attention over a contiguous context: q ``[S, T, NH,
    D]``, k ``[S, C, KH, D]``, v ``[S, C, KH, Dv]`` (value heads may be
    narrower than key heads), mask ``[S, T, C]`` boolean (True = attend).
    ``sink`` ``[NH]``, where given, is a learned logit per query head that
    joins the softmax and carries no value: ``p_ij = exp(s_ij) / (exp(b_h)
    + sum_j' exp(s_ij'))``.  Softmax in float32.  Returns ``[S, T, NH *
    Dv]``."""
    S, T, NH, D = q.shape
    KH = k.shape[2]
    G = NH // KH
    qg = q.reshape(S, T, KH, G, D)
    scores = jnp.einsum(
        "stkgd,sckd->skgtc", qg, k, preferred_element_type=jnp.float32
    ) / (D**0.5)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e9)
    if sink is None:
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    else:
        logit = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, KH, G, 1, 1), scores.shape[:-1] + (1,)
        )
        probs = jax.nn.softmax(jnp.concatenate([scores, logit], axis=-1), axis=-1)
        probs = probs[..., :-1].astype(q.dtype)
    ctx = jnp.einsum("skgtc,sckd->stkgd", probs, v)
    return ctx.reshape(S, T, NH * v.shape[-1])


def paged_gqa_attention(q, k_pool, v_pool, block_tables, mask, sink=None, layer=None):
    """GQA attention against paged KV: q ``[S, T, NH, D]``, pools
    ``[P, page, KH, D]`` (or the whole stacks and ``layer``, as
    :func:`gather_kv_pages` takes them), block_tables ``[S, G]``, mask
    ``[S, T, G*page]`` boolean (True = attend).  Same math as the full
    forward (``models/decoder.py::_attend``) over the gathered context, so
    paged and dense generations agree token-for-token."""
    k = gather_kv_pages(k_pool, block_tables, layer)  # [S, C, KH, D]
    v = gather_kv_pages(v_pool, block_tables, layer)
    return gqa_attention(q, k, v, mask, sink)


def ring_mask(starts, positions, valid, window: int, cap: int):
    """Which of a slot's ring entries and of a program's own rows each row
    of the program attends to: ``[S, T, cap + T]`` boolean.

    A window layer keeps, a slot, ``R`` pages of its pool as a ring of
    ``cap = R * page`` entries: the token at position ``p`` lives at entry
    ``p % cap``, so the ring holds the last ``cap`` tokens and never
    grows.  Before this program the slot had written ``starts [S]``
    tokens, so entry ``j`` holds position ``j + cap * ((starts - 1 - j) //
    cap)`` (nothing yet where ``j >= starts``).  The program's rows sit at
    ``positions [S, T]`` (``valid`` marks those that hold a token) and may
    be more than the ring holds: each attends, causally and inside
    ``window``, to the ring's entries and the program's own rows by their
    positions.  The same for every window layer of a program."""
    entry = jnp.arange(cap, dtype=starts.dtype)[None, :]
    held = entry + cap * ((starts[:, None] - 1 - entry) // cap)
    nowhere = jnp.int32(-(2**30))
    key_pos = jnp.concatenate(
        [
            jnp.where(entry < starts[:, None], held, nowhere),
            jnp.where(valid, positions, -nowhere),
        ],
        axis=1,
    )[:, None, :]  # [S, 1, cap + T]
    q_pos = positions[:, :, None]
    return (key_pos <= q_pos) & (key_pos > q_pos - window) & valid[:, :, None]


def ring_gqa_attention(q, k_new, v_new, k_pool, v_pool, rings, mask, sink=None,
                       layer=None):
    """Sliding-window attention of a program's rows ``q`` / ``k_new`` /
    ``v_new`` ``[S, T, ...]`` against each slot's ring (the pages ``rings
    [S, R]`` of this layer's pools, as they were before this program) and
    the rows themselves, under ``mask`` (:func:`ring_mask`).  The caller
    writes the rows the ring keeps afterwards (:func:`ring_write_positions`),
    so nothing a row still needs has been overwritten."""
    k_old = gather_kv_pages(k_pool, rings, layer)  # [S, cap, KH, D]
    v_old = gather_kv_pages(v_pool, rings, layer)
    return gqa_attention(
        q, jnp.concatenate([k_old, k_new], axis=1),
        jnp.concatenate([v_old, v_new], axis=1), mask, sink,
    )


def ring_write_positions(positions, valid, lens, cap: int):
    """Where :func:`scatter_kv_pages` writes a program's rows into a ring
    of ``cap`` entries: row ``t`` of a slot's ``lens [S]`` rows at entry
    ``position % cap`` if it is among the slot's last ``cap`` (an earlier
    row would be overwritten by a later one of the same program), else,
    like a row that holds no token, past the table: the null page."""
    t = jnp.arange(positions.shape[1], dtype=lens.dtype)[None, :]
    keep = valid & (t >= lens[:, None] - cap)
    return jnp.where(keep, positions % cap, jnp.int32(2**30))


def scatter_kv_pages(pool, block_tables, positions, values, layer=None):
    """Write per-slot K or V rows into the page pool.

    ``pool`` ``[P, page, KH, D]``, or the whole stack ``[L, P, page, KH,
    D]`` with ``layer`` the index of the layer written (one scatter into
    the stack: in place where the caller's buffer may be reused);
    ``positions`` ``[S, T]`` logical token positions per slot (page = pos
    // page_size via the slot's block table); ``values`` ``[S, T, KH, D]``.
    Returns the updated pool.
    Positions whose block-table entry is 0 land in the reserved null page
    — by construction those are only padding rows (inactive slots, tail
    of a ragged prefill chunk), so null-page collisions are harmless: the
    null page is never unmasked by any slot's attention."""
    pages, first = _layer_pages(pool, layer)
    page = pages.shape[1]
    S, T = positions.shape
    G = block_tables.shape[1]
    slot_of = positions // page  # [S, T] block-table column per write
    page_idx = jnp.take_along_axis(
        block_tables, jnp.clip(slot_of, 0, G - 1), axis=1
    )  # [S, T]
    # positions past the table's width (ragged padding rows) must land in
    # the null page, NOT clip into the slot's last live page
    page_idx = jnp.where(slot_of >= G, 0, page_idx)
    flat = (first + page_idx) * page + positions % page  # [S, T] token rows
    rows = pages.reshape((pages.shape[0] * page,) + pages.shape[2:])
    rows = rows.at[flat.reshape(-1)].set(
        values.reshape(S * T, values.shape[2], values.shape[3]),
        mode="drop",
    )
    return rows.reshape(pool.shape)


@functools.partial(
    jax.jit, static_argnames=("heads", "block_seqs", "force_xla", "interpret")
)
def encoder_attention(
    q,
    k,
    v,
    mask_bias,
    heads: int,
    block_seqs: int | None = None,
    force_xla: bool = False,
    interpret: bool = False,
):
    """Bidirectional multi-head attention over packed-layout tensors.

    Args:
      q, k, v: ``[B, S, H]`` (heads packed in the lane dim, ``H = heads*hd``).
      mask_bias: ``[B, S]`` additive key bias (0 for valid, ``-1e9`` for pad).
      heads: number of attention heads.
      block_seqs: sequences per kernel program (default: tuned by S).
    Returns:
      ctx ``[B, S, H]`` in the same packed layout and dtype as ``q``.
    """
    B, S, H = q.shape
    if force_xla or not (interpret or jax.default_backend() == "tpu"):
        return _xla_attention(q, k, v, mask_bias, heads)
    if not _supported(S, H, heads):
        _note_xla_fallback(S, H, heads)
        return _xla_attention(q, k, v, mask_bias, heads)

    hd = H // heads
    # Padded score width bb*S of ~128 lanes measures fastest on v5e (larger
    # bb multiplies the masked-out score work; smaller starves the MXU).
    bb = block_seqs or max(1, min(B, 128 // S, 8))
    while B % bb:
        bb //= 2
    rows = bb * S
    grid = (B // bb,)
    # 2D refs keep every in-kernel access a plain (sublane, lane) slice —
    # collapsing [B, S, H] -> [B*S, H] is free outside the kernel.
    q2 = q.reshape(B * S, H)
    k2 = k.reshape(B * S, H)
    v2 = v.reshape(B * S, H)
    bias3 = mask_bias.astype(jnp.float32).reshape(B // bb, 1, rows)
    spec2 = pl.BlockSpec((rows, H), lambda i: (i, 0))
    bias_spec = pl.BlockSpec((1, 1, rows), lambda i: (i, 0, 0))
    kernel = functools.partial(_attn_kernel, S=S, hd=hd, scale=1.0 / (hd**0.5))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec2, spec2, spec2, bias_spec],
        out_specs=spec2,
        out_shape=jax.ShapeDtypeStruct((B * S, H), q.dtype),
        interpret=interpret,
    )(q2, k2, v2, bias3)
    return out.reshape(B, S, H)
