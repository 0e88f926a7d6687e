"""Operations and bytes the Laguna language model needs (``model_type:
laguna``), from its sizes and the program's counters.

What the algorithm needs, not what a program happens to do: a padded row,
a recomputed block, an expert read and not used, or a pair the router gave
to an expert held on another chip is not counted, so a later kernel leaves
this yardstick alone.  Weights and cache entries are counted at the bytes
of the configuration's dtype (bfloat16 where it states none).
``num_experts`` is the experts held here, ``num_experts_published`` the
router's width.  A full layer reads every earlier token's keys and values;
a window layer at most ``sliding_window`` of them.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _width(config: dict) -> int:
    return DTYPE_BYTES[config.get("torch_dtype", "bfloat16")]


def layers(config: dict) -> list[dict]:
    """Each layer's kind: query heads, window (None: a full layer), routed."""
    L = config["num_hidden_layers"]
    return [
        {
            "heads": heads,
            "window": config["sliding_window"] if layer_type == "sliding_attention" else None,
            "routed": mlp == "sparse",
        }
        for layer_type, mlp, heads in zip(
            config["layer_types"][:L], config["mlp_layer_types"][:L],
            config["num_attention_heads_per_layer"][:L],
        )
    ]


def attention_params(config: dict, layer: dict) -> int:
    """q, k, v, the output projection and the per-head gate."""
    H, D, NH = config["hidden_size"], config["head_dim"], layer["heads"]
    return H * (NH + 2 * config["num_key_value_heads"]) * D + NH * D * H + H * NH


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["shared_expert_intermediate_size"]


def router_params(config: dict) -> int:
    return config["hidden_size"] * config.get("num_experts_published", config["num_experts"])


def routed_layers(config: dict) -> int:
    return sum(1 for layer in layers(config) if layer["routed"])


def dense_params(config: dict, *, head: bool = True) -> int:
    """Parameters every token is multiplied by whatever its routing:
    attention, the dense layers' FFN, the routers at their published width
    and the shared experts, and (``head``) the output head over the rows
    held."""
    H = config["hidden_size"]
    total = 0
    for layer in layers(config):
        total += attention_params(config, layer)
        if layer["routed"]:
            total += router_params(config) + shared_params(config)
        else:
            total += 3 * H * config["intermediate_size"]
    return total + (H * config["vocab_size"] if head else 0)


def total_params(config: dict) -> int:
    """Every parameter held: what the chip's memory carries."""
    H = config["hidden_size"]
    return (
        dense_params(config) + H * config["vocab_size"] + H  # embedding, final norm
        + len(layers(config)) * 2 * H  # the two norms of a layer
        + routed_layers(config) * config["num_experts"] * expert_params(config)
    )


def held_share(config: dict) -> float:
    """The share of a token's pairs that falls on the experts held, on
    average."""
    return config["num_experts"] / config.get("num_experts_published", config["num_experts"])


def _reach(layer: dict, context: float) -> float:
    return context if layer["window"] is None else min(context, layer["window"])


def kv_bytes(config: dict, context: float) -> float:
    """Live cache one sequence of ``context`` tokens reads: full layers
    every token, window layers the window's at most."""
    per_token = 2 * config["num_key_value_heads"] * config["head_dim"] * _width(config)
    return sum(_reach(layer, context) for layer in layers(config)) * per_token


def _attention_flops(config: dict, queries: float, context: float) -> float:
    """Scores and weighted values of ``queries`` tokens whose mean reach
    back is ``context`` (cut to the window where the layer has one)."""
    D = config["head_dim"]
    return sum(
        4.0 * layer["heads"] * D * _reach(layer, context) * queries for layer in layers(config)
    )


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
    sequences that already hold ``context`` tokens (the earlier chunks of
    the prompt; the output head only where a prompt ends: left out): the
    weights read once a program, the earlier context's cache read and the
    chunk's own written."""
    queries = rows * chunk
    pairs = queries * config["num_experts_per_tok"] * routed_layers(config) * held_share(config)
    return {
        "flops": 2.0 * dense_params(config, head=False) * queries
        + 2.0 * expert_params(config) * pairs
        + _attention_flops(config, queries, context + chunk / 2.0),
        "bytes": (dense_params(config, head=False) + experts_hit * expert_params(config))
        * _width(config) + rows * (kv_bytes(config, context) + kv_bytes(config, chunk)),
    }
