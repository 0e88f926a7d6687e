"""Plain reference of the sentence encoder: a BERT-family trunk (learned
positions, post-norm blocks, exact GELU, LayerNorm eps 1e-12), CLS or
mean pooling and L2 normalisation, as a stock Flax module in float32 at
``highest`` matmul precision: no fused weights, no kernel, no buckets.

It imports nothing of the program.  The weights are Flax's own seeded
initialisation of this module tree (the module names below are the
parameter paths the seed is folded along), rounded to the dtype the
configuration serves them in.  ``weight_bits=8`` is the control: every dense kernel rounded to int8 with
one scale per output channel.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


class TransformerBlock(nn.Module):
    heads: int
    hidden: int
    intermediate: int

    @nn.compact
    def __call__(self, x, mask):
        attn = nn.MultiHeadDotProductAttention(
            num_heads=self.heads, qkv_features=self.hidden, deterministic=True
        )(x, x, mask=mask)
        x = nn.LayerNorm(epsilon=1e-12)(x + attn)
        h = nn.Dense(self.intermediate)(x)
        h = nn.Dense(self.hidden)(nn.gelu(h, approximate=False))
        return nn.LayerNorm(epsilon=1e-12)(x + h)


class Encoder(nn.Module):
    cfg: tuple

    @nn.compact
    def __call__(self, input_ids, attention_mask):
        vocab, hidden, layers, heads, intermediate, max_len = self.cfg
        positions = jnp.arange(input_ids.shape[1])[None, :]
        x = nn.Embed(vocab, hidden)(input_ids) + nn.Embed(max_len, hidden)(positions)
        x = nn.LayerNorm(epsilon=1e-12)(x)
        mask = attention_mask[:, None, None, :].astype(bool)
        for _ in range(layers):
            x = TransformerBlock(heads, hidden, intermediate)(x, mask)
        return x


class SentenceEncoderModule(nn.Module):
    cfg: tuple
    pooling: str

    @nn.compact
    def __call__(self, input_ids, attention_mask):
        x = Encoder(self.cfg)(input_ids, attention_mask)
        if self.pooling == "cls":
            pooled = x[:, 0, :]
        else:
            m = attention_mask[:, :, None].astype(x.dtype)
            pooled = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
        return pooled / (jnp.linalg.norm(pooled, axis=1, keepdims=True) + 1e-12)


def module_for(encoder: dict) -> SentenceEncoderModule:
    cfg = (
        encoder.get("vocab_size", 30522), encoder["hidden_size"],
        encoder["num_hidden_layers"], encoder["num_attention_heads"],
        encoder["intermediate_size"], encoder.get("max_position_embeddings", 512),
    )
    return SentenceEncoderModule(cfg, encoder.get("pooling", "mean"))


def _round_int8(w, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0, 1e-12)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def init_weights(encoder: dict, seed: int = 0, *, weight_bits: int | None = None):
    """Flax's seeded initialisation, rounded to the served dtype (and, for
    the control, every dense kernel to int8 per output channel)."""
    module = module_for(encoder)
    params = module.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 16), jnp.int32), jnp.ones((1, 16), jnp.int32)
    )
    served = jnp.dtype(encoder.get("dtype", "bfloat16"))

    def settle(path, leaf):
        leaf = leaf.astype(served).astype(jnp.float32)
        names = [getattr(p, "key", "") for p in path]
        if weight_bits == 8 and names[-1] == "kernel":
            # contraction axes: both head axes of the attention output
            # projection [heads, head_dim, hidden], else the first axis
            leaf = _round_int8(leaf, (0, 1) if names[-2] == "out" else 0)
        return leaf

    return jax.tree_util.tree_map_with_path(settle, params)


@functools.partial(jax.jit, static_argnums=(0,))
def _apply(module, params, ids, mask):
    return module.apply(params, ids, mask)


def embed(encoder: dict, weights, id_lists: list[list[int]], block: int = 256) -> np.ndarray:
    """Unit embeddings [n, hidden] of token id lists, in blocks of rows.
    Rows are padded (and masked) to a multiple of 32 tokens and blocks to
    a multiple of 64 rows, so that runs with other seeds find the same few
    programs in the compile cache; padding changes no embedding."""
    module = module_for(encoder)
    out = []
    with jax.default_matmul_precision("highest"):
        for start in range(0, len(id_lists), block):
            rows = id_lists[start:start + block]
            width = -(-max(len(r) for r in rows) // 32) * 32
            height = -(-len(rows) // 64) * 64
            ids = np.zeros((height, width), np.int32)
            mask = np.zeros((height, width), np.int32)
            mask[len(rows):, 0] = 1  # a padding row attends to one token, not to none
            for i, r in enumerate(rows):
                ids[i, : len(r)] = r
                mask[i, : len(r)] = 1
            out.append(np.asarray(_apply(module, weights, ids, mask))[: len(rows)])
    return np.concatenate(out)
