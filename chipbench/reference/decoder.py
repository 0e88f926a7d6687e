"""Plain reference of the decoder: a Mistral-family causal language model
(pre-norm blocks, RMS norm, rotary positions, grouped-query attention,
SwiGLU) as one full forward pass in ``jax.numpy`` float32 at ``highest``
matmul precision: no cache, no paging, no chunks, no batching tricks.

It imports nothing of the program and takes nothing the program made.  Its
weights are drawn anew from the seed by the published recipe the program
states (normal / sqrt(fan_in), stored in the configuration's dtype); the
layers are walked one at a time, each upcast to float32, so that a 7B-wide
model fits beside nothing else on a 16 GB chip.

``weight_bits=8`` is the control: the same forward with every matmul
weight rounded to int8 with one scale per output channel (the nearest
precision below the bfloat16 the configuration states).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MATMUL_WEIGHTS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


def sizes(config: dict) -> dict:
    """The sizes the forward needs, from a Hugging Face style config."""
    heads = config["num_attention_heads"]
    return {
        "vocab": config["vocab_size"],
        "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "heads": heads,
        "kv_heads": config.get("num_key_value_heads", heads),
        "head_dim": config["hidden_size"] // heads,
        "ffn": config["intermediate_size"],
        "theta": float(config.get("rope_theta", 10000.0)),
        "eps": float(config.get("rms_norm_eps", 1e-5)),
        "dtype": jnp.dtype(config.get("torch_dtype", "bfloat16")),
        "window": config.get("sliding_window"),
    }


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, divisor, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / divisor).astype(dtype)


def init_weights(config: dict, seed: int = 0) -> dict:
    """Scaled-normal weights from ``seed``: eleven keys split from it, one
    draw per stacked leaf, divided by sqrt(fan_in) and stored in the
    configuration's dtype; norms are ones."""
    s = sizes(config)
    H, L, F, V = s["hidden"], s["layers"], s["ffn"], s["vocab"]
    q, kv = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 11)

    def draw(key, shape, fan_in):
        return _draw(key, np.float32(np.sqrt(fan_in)), shape, s["dtype"])

    return {
        "embed": draw(keys[0], (V, H), H),
        "lm_head": draw(keys[1], (H, V), H),
        "final_norm": jnp.ones((H,), s["dtype"]),
        "layers": {
            "ln0": jnp.ones((L, H), s["dtype"]),
            "ln1": jnp.ones((L, H), s["dtype"]),
            "wq": draw(keys[2], (L, H, q), H),
            "wk": draw(keys[3], (L, H, kv), H),
            "wv": draw(keys[4], (L, H, kv), H),
            "wo": draw(keys[5], (L, q, H), q),
            "wg": draw(keys[6], (L, H, F), H),
            "wu": draw(keys[7], (L, H, F), H),
            "wd": draw(keys[8], (L, F, H), F),
        },
    }


def _round_weight(w, bits: int | None):
    """``w`` in float32, or as weight-only int-``bits`` would hold it: a
    symmetric scale per output channel over the contraction axis."""
    w = w.astype(jnp.float32)
    if bits is None:
        return w
    top = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True) / top, 1e-12)
    return jnp.clip(jnp.round(w / scale), -top, top) * scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """Rotary embedding, halves rotated; ``x`` is [B, S, heads, D]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(angle)[..., None, :], jnp.sin(angle)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("dims", "bits"))
def _layer(layers, index, x, lengths, dims, bits):
    """One block over the whole sequences ``x`` [B, S, H]."""
    heads, kv_heads, head_dim, theta, eps, window = dims
    w = {
        name: _round_weight(leaf[index], bits if name in MATMUL_WEIGHTS else None)
        for name, leaf in layers.items()
    }
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    mask = (positions[:, None, :] <= positions[:, :, None]) & (
        positions[:, None, :] < lengths[:, None, None]
    )
    if window is not None:
        mask = mask & (positions[:, None, :] > positions[:, :, None] - window)
    h = _rms(x, w["ln0"], eps)
    q = _rope((h @ w["wq"]).reshape(B, S, heads, head_dim), positions, theta)
    k = _rope((h @ w["wk"]).reshape(B, S, kv_heads, head_dim), positions, theta)
    v = (h @ w["wv"]).reshape(B, S, kv_heads, head_dim)
    group = heads // kv_heads
    qg = q.reshape(B, S, kv_heads, group, head_dim)
    scores = jnp.einsum("bskgd,bckd->bkgsc", qg, k) / np.sqrt(head_dim)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgsc,bckd->bskgd", probs, v).reshape(B, S, heads * head_dim)
    x = x + ctx @ w["wo"]
    h = _rms(x, w["ln1"], eps)
    return x + (jax.nn.silu(h @ w["wg"]) * (h @ w["wu"])) @ w["wd"]


@functools.partial(jax.jit, static_argnames=("eps", "bits"))
def _head(x, final_norm, lm_head, eps, bits):
    return _rms(x, final_norm.astype(jnp.float32), eps) @ _round_weight(lm_head, bits)


def logits_at(
    weights: dict, config: dict, ids: np.ndarray, lengths: np.ndarray,
    positions: np.ndarray, *, weight_bits: int | None = None,
) -> np.ndarray:
    """Next-token logits [B, P, vocab] of the full forward over ``ids``
    [B, S] (rows padded past ``lengths``) at ``positions`` [B, P]."""
    s = sizes(config)
    dims = (s["heads"], s["kv_heads"], s["head_dim"], s["theta"], s["eps"], s["window"])
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
        lengths = jnp.asarray(lengths, jnp.int32)
        for index in range(s["layers"]):
            x = _layer(weights["layers"], index, x, lengths, dims, weight_bits)
        picked = jnp.take_along_axis(x, jnp.asarray(positions)[:, :, None], axis=1)
        out = _head(picked, weights["final_norm"], weights["lm_head"], s["eps"], weight_bits)
    return np.asarray(out)
