"""Mamba-2 (state-space duality) pieces of a mixer layer, in plain XLA.

A Mamba-2 head keeps a state ``S [P, N]`` (head width x state size) that
one token moves by ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`` and
reads as ``y_t = S_t C_t``.  Two forms of the same recurrence:

* :func:`ssd_chunked` -- a program's ``T`` tokens in chunks of ``L``:
  inside a chunk the recurrence unrolls into one masked ``[L, L]`` product
  (matrix work for the MXU), between chunks the state is carried by a short
  scan.  The prefill program's form.
* :func:`ssm_step` -- one token: the recurrence as written.  The decode
  program's form.

Both take and return the state in float32 and read it exactly (a product
with the state runs at ``HIGHEST`` precision or elementwise): a state kept
or multiplied in bfloat16 forgets what a slow head is there to remember.
A token whose ``dt`` is nought leaves the state as it was, bit for bit
(``exp(0) = 1``, and nought is added), which is how padding is kept out.

:func:`causal_conv` is the depthwise convolution before the scan, with the
tail of columns it needs from before the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_EXACT = lax.Precision.HIGHEST


def causal_conv(xbc, tail, weight, bias, lens):
    """Causal depthwise convolution over ``xbc [S, T, C]`` continued from
    ``tail [S, K-1, C]``, the last ``K-1`` columns before this program:
    ``out_t = bias + sum_k weight[k] * column[t - (K-1) + k]`` (tap ``K-1``
    meets the token's own column), then SiLU.  ``lens [S]`` counts the
    real columns of each row; the new tail is the last ``K-1`` columns of
    tail + real columns, so a row with none keeps its tail as it was.
    Returns ``(activated [S, T, C], new_tail [S, K-1, C])``."""
    K, T = weight.shape[0], xbc.shape[1]
    full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)  # [S, K-1+T, C]
    acc = bias.astype(jnp.float32)
    for k in range(K):
        acc = acc + full[:, k:k + T].astype(jnp.float32) * weight[k].astype(jnp.float32)
    at = lens[:, None] + jnp.arange(K - 1, dtype=lens.dtype)[None, :]  # [S, K-1]
    new_tail = jnp.take_along_axis(full, at[:, :, None], axis=1)
    return jax.nn.silu(acc).astype(xbc.dtype), new_tail.astype(tail.dtype)


def _by_group(t, groups: int):
    """``[..., NH, *rest]`` heads as ``[..., G, NH // G, *rest]`` at axis 3."""
    return t.reshape(t.shape[:3] + (groups, t.shape[3] // groups) + t.shape[4:])


def ssd_chunked(x, dt, A, B, C, state, chunk: int):
    """The recurrence over a program's tokens, chunk by chunk.

    ``x [S, T, NH, P]``; ``dt [S, T, NH]`` float32, nought where the token
    is padding; ``A [NH]`` float32 (negative); ``B``, ``C`` ``[S, T, G,
    N]`` (a group of ``NH // G`` heads shares them); ``state [S, NH, P,
    N]`` float32, as it stood before the first token.  Returns ``(y [S, T,
    NH, P] float32, state after the last token)``; ``y`` is ``S_t C_t``
    alone, the ``D x_t`` skip is the caller's."""
    S, T, NH, P = x.shape
    G, N = B.shape[2:]
    L = min(chunk, T)
    pad = -T % L
    if pad:
        # padding tokens: dt nought, so the state passes through them
        widen = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        x, dt, B, C = widen(x), widen(dt), widen(B), widen(C)
    n = (T + pad) // L
    x = _by_group(x.reshape(S, n, L, NH, P).astype(jnp.float32), G)  # [S,n,L,G,R,P]
    dt = _by_group(dt.reshape(S, n, L, NH), G)  # [S,n,L,G,R]
    B = B.reshape(S, n, L, G, N).astype(jnp.float32)
    C = C.reshape(S, n, L, G, N).astype(jnp.float32)
    cum = jnp.cumsum(dt * _by_group(A[None, None, None, :], G), axis=2)  # log-decay so far
    # inside a chunk: token l reads token m <= l through exp(cum_l - cum_m)
    reach = cum[:, :, :, None] - cum[:, :, None, :]  # [S,n,L(l),L(m),G,R]
    causal = jnp.tril(jnp.ones((L, L), bool))[None, None, :, :, None, None]
    weight = jnp.exp(jnp.where(causal, reach, -jnp.inf)) * dt[:, :, None]
    scores = jnp.einsum("snlgk,snmgk->snlmg", C, B)  # C_l . B_m, a group
    y = jnp.einsum("snlmgr,snmgrp->snlgrp", scores[..., None] * weight, x)
    # what each chunk adds to the state by its end
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dt  # [S,n,L,G,R]
    added = jnp.einsum("snlgrp,snlgk->sngrpk", x * to_end[..., None], B, precision=_EXACT)
    through = jnp.exp(cum[:, :, -1])  # a whole chunk's decay [S,n,G,R]

    def carry_over(held, chunk_):
        add, decay = chunk_
        return held * decay[..., None, None] + add, held

    state, before = lax.scan(
        carry_over, state.reshape(S, G, NH // G, P, N),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(through, 1, 0)),
    )
    before = jnp.moveaxis(before, 0, 1)  # the state each chunk started from
    y = y + jnp.einsum(
        "snlgk,sngrpk->snlgrp", C, before, precision=_EXACT
    ) * jnp.exp(cum)[..., None]
    return y.reshape(S, T + pad, NH, P)[:, :T], state.reshape(S, NH, P, N)


def ssm_step(x, dt, A, B, C, state):
    """One token of the recurrence: ``x [S, NH, P]``, ``dt [S, NH]``
    float32 (nought: the row holds no token), ``B``, ``C`` ``[S, G, N]``,
    ``state [S, NH, P, N]`` float32.  Returns ``(y [S, NH, P] float32, new
    state)``, all elementwise in float32."""
    NH, G = x.shape[1], B.shape[1]
    per_head = lambda t: jnp.repeat(t.astype(jnp.float32), NH // G, axis=1)  # [S,NH,N]
    decay = jnp.exp(dt * A[None, :])
    state = state * decay[..., None, None] + (
        (dt[..., None] * x.astype(jnp.float32))[..., None] * per_head(B)[:, :, None, :]
    )
    return jnp.sum(state * per_head(C)[:, :, None, :], axis=-1), state
