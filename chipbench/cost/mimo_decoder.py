"""Operations and bytes the MiMo-V2.5 language model needs (``model_type:
mimo_v2``), from its sizes and the routing's counters.

What the algorithm needs, not what a program happens to do: a padded row,
a recomputed block, an expert read and not used, or a pair the router gave
to an expert held on another chip is not counted, so a later kernel leaves
this yardstick alone.  Weights and cache entries are counted at the bytes
of the configuration's dtype.  ``n_routed_experts`` is the experts held
here, ``n_routed_experts_published`` the router's width.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _width(config: dict) -> int:
    return DTYPE_BYTES[config.get("torch_dtype", "bfloat16")]


def layers(config: dict) -> list[dict]:
    """Each layer's kind: KV heads, window (None: global), routed."""
    L = config["num_hidden_layers"]
    return [
        {
            "kv_heads": config["swa_num_key_value_heads" if window else "num_key_value_heads"],
            "window": config["sliding_window"] if window else None,
            "routed": bool(routed),
        }
        for window, routed in zip(config["hybrid_layer_pattern"][:L], config["moe_layer_freq"][:L])
    ]


def attention_params(config: dict, layer: dict) -> int:
    """The fused q, k, v projection and the output projection."""
    H, NH = config["hidden_size"], config["num_attention_heads"]
    D, Dv = config["head_dim"], config.get("v_head_dim", config["head_dim"])
    return H * (NH * D + layer["kv_heads"] * (D + Dv)) + NH * Dv * H


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def routed_layers(config: dict) -> int:
    return sum(1 for layer in layers(config) if layer["routed"])


def dense_params(config: dict, *, head: bool = True) -> int:
    """Parameters every token is multiplied by whatever its routing:
    attention, the routers at their published width, the dense layers'
    FFN and (``head``) the output head over the rows held."""
    H = config["hidden_size"]
    width = config.get("n_routed_experts_published", config["n_routed_experts"])
    total = 0
    for layer in layers(config):
        total += attention_params(config, layer)
        total += H * width if layer["routed"] else 3 * H * config["intermediate_size"]
    return total + (H * config["vocab_size"] if head else 0)


def held_share(config: dict) -> float:
    """The share of a token's pairs that falls on the experts held, on
    average."""
    return config["n_routed_experts"] / config.get(
        "n_routed_experts_published", config["n_routed_experts"]
    )


def kv_bytes(config: dict, context: float) -> float:
    """Live cache one sequence of ``context`` tokens reads: global layers
    every token, window layers the window's at most; keys ``head_dim``
    wide, values ``v_head_dim``."""
    D, Dv = config["head_dim"], config.get("v_head_dim", config["head_dim"])
    total = 0.0
    for layer in layers(config):
        kept = context if layer["window"] is None else min(context, layer["window"])
        total += kept * layer["kv_heads"] * (D + Dv)
    return total * _width(config)


def _attention_flops(config: dict, queries: float, context: float) -> float:
    """Scores and weighted values of ``queries`` tokens whose mean reach
    back is ``context`` (cut to the window where the layer has one)."""
    NH, D = config["num_attention_heads"], config["head_dim"]
    Dv = config.get("v_head_dim", D)
    total = 0.0
    for layer in layers(config):
        reach = context if layer["window"] is None else min(context, layer["window"])
        total += 2.0 * NH * (D + Dv) * reach * queries
    return total


def tokens(config: dict, *, tokens: float, pairs: float) -> dict:
    """Whole-step work of ``tokens`` tokens (prompt and generated alike)
    of which ``pairs`` token-expert pairs fell on the experts held: two
    operations per parameter a token or a pair is multiplied by."""
    return {
        "flops": 2.0 * dense_params(config) * tokens + 2.0 * expert_params(config) * pairs,
        "bytes": 0.0,
    }


def decode_step(config: dict, *, rows: float, context: float, experts_hit: float) -> dict:
    """One decode step of ``rows`` sequences, each attending to ``context``
    cached tokens, whose tokens met ``experts_hit`` held experts summed
    over the routed layers: every dense weight is read once, every expert
    that met a token once, every live cache entry once."""
    pairs = rows * config["num_experts_per_tok"] * routed_layers(config) * held_share(config)
    return {
        "flops": 2.0 * dense_params(config) * rows + 2.0 * expert_params(config) * pairs
        + _attention_flops(config, rows, context),
        "bytes": (dense_params(config) + experts_hit * expert_params(config)) * _width(config)
        + rows * kv_bytes(config, context),
    }


def prefill_chunk(
    config: dict, *, rows: float, chunk: float, context: float, experts_hit: float
) -> dict:
    """One prefill program of ``chunk`` prompt tokens for each of ``rows``
    sequences that already hold ``context`` tokens (the output head only
    where a prompt ends: left out)."""
    queries = rows * chunk
    pairs = queries * config["num_experts_per_tok"] * routed_layers(config) * held_share(config)
    return {
        "flops": 2.0 * dense_params(config, head=False) * queries
        + 2.0 * expert_params(config) * pairs
        + _attention_flops(config, queries, context + chunk / 2.0),
        "bytes": (dense_params(config, head=False) + experts_hit * expert_params(config))
        * _width(config) + rows * kv_bytes(config, context + chunk),
    }
