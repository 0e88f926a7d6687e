"""Ring attention: sequence/context parallelism for long sequences.

Shards the sequence axis of bidirectional (encoder) attention over the
device mesh: every chip holds one sequence block of Q/K/V in HBM, and the
K/V blocks rotate around the ring via ``jax.lax.ppermute`` while each chip
accumulates its queries' attention with the online-softmax (flash)
recurrence — running row-max ``m``, denominator ``l``, and weighted sum
``o`` are updated per incoming block, so the full ``[S, S]`` score matrix
never materializes and sequences scale with the number of chips.

The collectives ride ICI: per ring step each chip sends/receives one K
block + one V block + one bias block (its neighbors'), which XLA overlaps
with the local block's compute.  This is the long-context answer the
framework pairs with row-sharded SPMD dataflow: the host engine scales by
key shards, the device path scales batch via data parallelism
(``parallel/train.py``), corpora via the sharded index
(``parallel/index.py``), and sequence length via this module.

The reference has no sequence/context parallelism anywhere (its only axis
is key-shard data parallelism — SURVEY.md §2b/§5); this module is
TPU-native capability beyond the reference, required for long-context
workloads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _ring_attention_local(q, k, v, bias, *, heads: int, axis_name: str):
    """Per-device body: q/k/v [B, S_blk, H] packed-lanes, bias [B, S_blk]."""
    B, S_blk, H = q.shape
    hd = H // heads
    scale = 1.0 / (hd**0.5)
    n = jax.lax.psum(1, axis_name)

    # [B, heads, S_blk, hd] — local reshape only; S never gathers
    def split(x):
        return jnp.swapaxes(x.reshape(B, S_blk, heads, hd), 1, 2)

    q4 = split(q.astype(jnp.float32)) * scale
    perm = [(j, (j + 1) % n) for j in range(n)]

    def accumulate(k_blk, v_blk, b_blk, m, l, o):
        k4 = split(k_blk.astype(jnp.float32))
        v4 = split(v_blk.astype(jnp.float32))
        s = jnp.einsum("bhqd,bhkd->bhqk", q4, k4) + b_blk[:, None, None, :]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        o = o * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v4)
        return m_new, l, o

    def step(carry, _):
        k_blk, v_blk, b_blk, m, l, o = carry
        m, l, o = accumulate(k_blk, v_blk, b_blk, m, l, o)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        b_blk = jax.lax.ppermute(b_blk, axis_name, perm)
        return (k_blk, v_blk, b_blk, m, l, o), None

    # mark the accumulator carries device-varying along the ring axis up
    # front (they become varying after one ppermute'd step; scan requires
    # carry types to be loop-invariant)
    def varying(x):
        return jax.lax.pcast(x, (axis_name,), to="varying")

    m0 = varying(jnp.full((B, heads, S_blk), NEG_INF, jnp.float32))
    l0 = varying(jnp.zeros((B, heads, S_blk), jnp.float32))
    o0 = varying(jnp.zeros((B, heads, S_blk, hd), jnp.float32))
    # n-1 rotate-and-accumulate rounds; the final block accumulates without
    # the trailing ppermute round whose result would be discarded
    (k_blk, v_blk, b_blk, m, l, o), _ = jax.lax.scan(
        step, (k, v, bias.astype(jnp.float32), m0, l0, o0), None, length=n - 1
    )
    _, l, o = accumulate(k_blk, v_blk, b_blk, m, l, o)
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.swapaxes(out, 1, 2).reshape(B, S_blk, H).astype(q.dtype)


def ring_attention_traced(
    mesh: Mesh, q, k, v, mask_bias, heads: int, axis: str | None = None
):
    """Jit-traceable form: same computation as
    :func:`ring_encoder_attention` but without the eager ``device_put``
    calls, so it composes inside a larger jitted forward (shard_map
    splits the operands per ``in_specs`` itself).  Used by the
    long-context encoder (``models/long_context.py``)."""
    axis = axis or mesh.axis_names[0]
    B, S, H = q.shape
    n = mesh.shape[axis]
    if S % n:
        raise ValueError(f"sequence length {S} not divisible by mesh axis {n}")
    spec3 = P(None, axis, None)
    spec2 = P(None, axis)
    fn = jax.shard_map(
        functools.partial(_ring_attention_local, heads=heads, axis_name=axis),
        mesh=mesh,
        in_specs=(spec3, spec3, spec3, spec2),
        out_specs=spec3,
    )
    return fn(q, k, v, mask_bias)


def ring_encoder_attention(
    mesh: Mesh, q, k, v, mask_bias, heads: int, axis: str | None = None
):
    """Bidirectional multi-head attention with the sequence axis sharded.

    Args:
      mesh: device mesh; ``axis`` names the sequence axis (defaults to the
        mesh's first axis).
      q, k, v: ``[B, S, H]`` with heads packed in the lane dim; ``S`` must
        divide evenly by the axis size.
      mask_bias: ``[B, S]`` additive key bias (0 valid, ``-1e9`` padded).
    Returns:
      ctx ``[B, S, H]``, sharded like the inputs along ``S``.
    """
    axis = axis or mesh.axis_names[0]
    # eager entry point: pre-place the operands on the mesh, then run the
    # same traced computation.  Check divisibility BEFORE device_put so
    # the caller sees the actionable error, not a sharding failure.
    n = mesh.shape[axis]
    if q.shape[1] % n:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by mesh axis {n}"
        )
    sh3 = NamedSharding(mesh, P(None, axis, None))
    sh2 = NamedSharding(mesh, P(None, axis))
    return ring_attention_traced(
        mesh,
        jax.device_put(q, sh3),
        jax.device_put(k, sh3),
        jax.device_put(v, sh3),
        jax.device_put(mask_bias, sh2),
        heads,
        axis,
    )
