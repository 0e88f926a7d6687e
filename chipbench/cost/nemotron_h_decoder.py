"""Operations and bytes the Nemotron-H language model needs (``model_type:
nemotron_h``), from its sizes and the routing's counters.

What the algorithm needs, not what a program happens to do: a padded row,
a recomputed block, an expert read and not used, a pair the router gave to
an expert held on another chip, or the recurrent state of a slot that
holds no sequence is not counted, so a later kernel leaves this yardstick
alone.  Weights and cache entries are counted at the bytes of the
configuration's dtype, the recurrent state at float32's.  The Mamba-2
scan is counted as the recurrence it computes (decay, outer product, read:
5 operations an entry of the state a token), whatever form implements it:
the chunked form's extra products are the program's, not the algorithm's.
``n_routed_experts`` is the experts held here,
``n_routed_experts_published`` the router's width.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
STATE_BYTES = 4  # the recurrent state is float32 whatever the weights are


def _width(config: dict) -> int:
    return DTYPE_BYTES[config.get("torch_dtype", "bfloat16")]


def layers(config: dict) -> str:
    """The letters of the layers held: M, * or E."""
    return config["hybrid_override_pattern"][: config["num_hidden_layers"]]


def count(config: dict, letter: str) -> int:
    return layers(config).count(letter)


def _ssm(config: dict) -> tuple[int, int, int]:
    """(inner width, convolved columns, state entries a layer)."""
    NH, P = config["mamba_num_heads"], config["mamba_head_dim"]
    GN = config["n_groups"] * config["ssm_state_size"]
    return NH * P, NH * P + 2 * GN, NH * P * config["ssm_state_size"]


def mamba_params(config: dict) -> int:
    """in_proj to [z | x B C | dt] and out_proj: the matrices of a mixer."""
    inner, columns, _state = _ssm(config)
    H = config["hidden_size"]
    return H * (inner + columns + config["mamba_num_heads"]) + inner * H


def mamba_small_params(config: dict) -> int:
    """The convolution's taps and bias, A_log, D, dt_bias, the gated
    norm's weight and the layer's norm."""
    inner, columns, _state = _ssm(config)
    return (config["conv_kernel"] + 1) * columns + 3 * config["mamba_num_heads"] + inner + config["hidden_size"]


def attention_params(config: dict) -> int:
    H, D = config["hidden_size"], config["head_dim"]
    NH, KH = config["num_attention_heads"], config["num_key_value_heads"]
    return H * (NH + 2 * KH) * D + NH * D * H


def expert_params(config: dict) -> int:
    return 2 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_params(config: dict) -> int:
    return 2 * config["hidden_size"] * config["moe_shared_expert_intermediate_size"]


def router_params(config: dict) -> int:
    return config["hidden_size"] * config.get(
        "n_routed_experts_published", config["n_routed_experts"]
    )


def dense_params(config: dict, *, head: bool = True) -> int:
    """Parameters every token is multiplied by whatever its routing: the
    mixers, the routers at their published width, the shared experts and
    (``head``) the output head over the rows held."""
    total = (
        count(config, "M") * mamba_params(config)
        + count(config, "*") * attention_params(config)
        + count(config, "E") * (router_params(config) + shared_params(config))
    )
    return total + (config["hidden_size"] * config["vocab_size"] if head else 0)


def total_params(config: dict) -> int:
    """Every parameter held: what the chip's memory carries."""
    H = config["hidden_size"]
    published = config.get("n_routed_experts_published", config["n_routed_experts"])
    return (
        dense_params(config) + H * config["vocab_size"] + H  # embedding, final norm
        + count(config, "M") * mamba_small_params(config)
        + count(config, "*") * H
        + count(config, "E") * (config["n_routed_experts"] * expert_params(config) + published + H)
    )


def held_share(config: dict) -> float:
    """The share of a token's pairs that falls on the experts held, on
    average."""
    return config["n_routed_experts"] / config.get(
        "n_routed_experts_published", config["n_routed_experts"]
    )


def state_bytes(config: dict) -> float:
    """One sequence's recurrent state across the Mamba-2 layers: the
    scan's float32 state and the convolution's tail."""
    _inner, columns, state = _ssm(config)
    tail = (config["conv_kernel"] - 1) * columns * _width(config)
    return count(config, "M") * (state * STATE_BYTES + tail)


def kv_bytes(config: dict, context: float) -> float:
    """Live cache one sequence of ``context`` tokens reads: keys and
    values of every token in the attention layers."""
    per_token = 2 * config["num_key_value_heads"] * config["head_dim"] * _width(config)
    return count(config, "*") * context * per_token


def _scan_flops(config: dict, tokens: float) -> float:
    """The recurrence (5 operations an entry of the state) and the
    convolution (a multiply and an add a tap), a token a Mamba-2 layer."""
    _inner, columns, state = _ssm(config)
    return count(config, "M") * tokens * (5.0 * state + 2.0 * config["conv_kernel"] * columns)


def _attention_flops(config: dict, queries: float, context: float) -> float:
    """Scores and weighted values of ``queries`` tokens whose mean reach
    back is ``context``."""
    return count(config, "*") * 4.0 * config["num_attention_heads"] * config["head_dim"] * context * queries


def tokens(config: dict, *, tokens: float, pairs: float) -> dict:
    """Whole-step work of ``tokens`` tokens (prompt and generated alike)
    of which ``pairs`` token-expert pairs fell on the experts held: two
    operations per parameter a token or a pair is multiplied by, and the
    scan's own."""
    return {
        "flops": 2.0 * dense_params(config) * tokens + 2.0 * expert_params(config) * pairs
        + _scan_flops(config, tokens),
        "bytes": 0.0,
    }


def decode_step(config: dict, *, rows: float, context: float, experts_hit: float) -> dict:
    """One decode step of ``rows`` sequences, each attending to ``context``
    cached tokens, whose tokens met ``experts_hit`` held experts summed
    over the routed layers: every dense weight is read once, every expert
    that met a token once, every live cache entry once, and a live row's
    recurrent state is read and written."""
    pairs = rows * config["num_experts_per_tok"] * count(config, "E") * held_share(config)
    return {
        "flops": 2.0 * dense_params(config) * rows + 2.0 * expert_params(config) * pairs
        + _scan_flops(config, rows) + _attention_flops(config, rows, context),
        "bytes": (dense_params(config) + experts_hit * expert_params(config)) * _width(config)
        + rows * (2.0 * state_bytes(config) + kv_bytes(config, context)),
    }


def prefill_chunk(
    config: dict, *, rows: float, chunk: float, context: float, experts_hit: float
) -> dict:
    """One prefill program of ``chunk`` prompt tokens for each of ``rows``
    sequences that already hold ``context`` tokens (the output head only
    where a prompt ends: left out)."""
    queries = rows * chunk
    pairs = queries * config["num_experts_per_tok"] * count(config, "E") * held_share(config)
    return {
        "flops": 2.0 * dense_params(config, head=False) * queries
        + 2.0 * expert_params(config) * pairs
        + _scan_flops(config, queries) + _attention_flops(config, queries, context + chunk / 2.0),
        "bytes": (dense_params(config, head=False) + experts_hit * expert_params(config))
        * _width(config)
        + rows * (2.0 * state_bytes(config) + kv_bytes(config, context + chunk)),
    }
