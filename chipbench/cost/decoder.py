"""Operations and bytes a Mistral-family decoder needs, from its sizes.

What the algorithm needs, not what a program happens to do: a padded slot,
a recomputed block or a gathered page the mathematics does not ask for is
not counted, so a later kernel leaves this yardstick alone.  Weights and
cache entries are counted at the bytes of the configuration's dtype.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _sizes(config: dict):
    H, L = config["hidden_size"], config["num_hidden_layers"]
    heads = config["num_attention_heads"]
    kv = config.get("num_key_value_heads", heads)
    D = H // heads
    return H, L, heads, kv, D, config["intermediate_size"], config["vocab_size"]


def layer_params(config: dict) -> int:
    """Matmul parameters of the blocks (norms left out)."""
    H, L, heads, kv, D, F, _V = _sizes(config)
    return L * (H * heads * D + 2 * H * kv * D + heads * D * H + 3 * H * F)


def matmul_params(config: dict) -> int:
    """Parameters every generated token is multiplied by: the blocks and
    the output head (the embedding is a lookup)."""
    return layer_params(config) + config["hidden_size"] * config["vocab_size"]


def kv_bytes_per_token(config: dict) -> int:
    _H, L, _heads, kv, D, _F, _V = _sizes(config)
    return 2 * L * kv * D * DTYPE_BYTES[config.get("torch_dtype", "bfloat16")]


def tokens(config: dict, *, tokens: float) -> dict:
    """Whole-step work of ``tokens`` tokens (prompt and generated alike):
    two operations per matmul parameter and token."""
    return {"flops": 2.0 * matmul_params(config) * tokens, "bytes": 0.0}


def decode_step(config: dict, *, rows: float, context: float) -> dict:
    """One decode step of ``rows`` sequences, each attending to
    ``context`` cached tokens: every weight is read once, every live
    cache entry once."""
    H, L, heads, _kv, D, _F, _V = _sizes(config)
    width = DTYPE_BYTES[config.get("torch_dtype", "bfloat16")]
    attention = 4.0 * L * heads * D * context * rows
    return {
        "flops": 2.0 * matmul_params(config) * rows + attention,
        "bytes": matmul_params(config) * width
        + rows * context * kv_bytes_per_token(config),
    }


def prefill_chunk(config: dict, *, rows: float, chunk: float, context: float) -> dict:
    """One chunk of ``chunk`` prompt tokens for each of ``rows`` sequences
    that already hold ``context`` tokens: the blocks' weights are read
    once (the output head only where a prompt ends: left out)."""
    H, L, heads, _kv, D, _F, _V = _sizes(config)
    width = DTYPE_BYTES[config.get("torch_dtype", "bfloat16")]
    attention = 4.0 * L * heads * D * (context + chunk / 2.0) * chunk * rows
    return {
        "flops": 2.0 * layer_params(config) * rows * chunk + attention,
        "bytes": layer_params(config) * width
        + rows * (context + chunk) * kv_bytes_per_token(config),
    }
