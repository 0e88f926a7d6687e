"""TPU-native decoder-only LLM (Mistral/LLaMA-class) for local serving.

The reference serves local chat models through a host-side torch pipeline
(``xpacks/llm/llms.py:314`` ``HFPipelineChat``); its Adaptive RAG template
runs Mistral-7B-Instruct that way.  Here the decoder is two jit-compiled
JAX programs over a paged KV cache, driven by the continuous-batching
scheduler (``serving/generation.py``):

  * **paged_prefill_chunk** — a chunk of each slot's prompt, its K/V
    scattered into the slot's pages; all the FLOPs land in large bf16
    matmuls on the MXU.
  * **paged_decode_step** — one token a slot against its pages, compiled
    once and re-used for every generated token (static slot count and
    table width, dynamic positions — no recompiles during generation).

and one full causal forward (``causal_lm_logits``) for training, which is
also what the tests hold the paged programs against.  The decoder layer
has these two bodies: ``decoder_layer`` and ``_paged_trunk``'s.

Layer parameters are stacked along a leading ``[layers, ...]`` axis and the
trunk runs under ``lax.scan``, so a 32-layer model traces one layer once
(fast compiles).  Weights follow the LLaMA family: RMSNorm, rotary position
embeddings, grouped-query attention, SwiGLU MLP.  ``tp_param_specs`` gives
the tensor-parallel layout (heads and FFN sharded over a ``model`` mesh
axis; XLA inserts the all-reduces after ``wo``/``wd`` contractions), used
by training and the multi-chip dry run.

Checkpoints: a locally cached HF llama/mistral-family checkpoint maps onto
the param tree via ``load_hf_decoder_weights``; without one (zero-egress
image) deterministic random init keeps shapes/FLOPs identical, which is
what the serving-throughput path measures.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from pathway_tpu.device.compile_cache import ensure_compile_cache
from pathway_tpu.models.tokenizer import load_tokenizer, may_have_local_checkpoint


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN rope scaling (``rope_type: yarn``): the rotated dims whose
    wavelength fits between ``beta_fast`` and ``beta_slow`` rotations over
    ``original_max_position_embeddings`` are ramped from their own
    frequency (extrapolated) to it divided by ``factor`` (interpolated),
    and cos and sin are multiplied by ``attention_factor``
    (:func:`rope_inv_frequencies`)."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What may differ between the layers of one model: a model is a
    sequence of runs of like layers (``DecoderConfig.layer_runs``), each
    run a stack scanned by one layer body that its kind parameterises."""

    kv_heads: int
    # which parts a layer is made of: "block" is attention followed by an
    # FFN, each behind its own norm; "attention", "mamba" (a Mamba-2
    # mixer, ``ops/ssm.py``) and "ffn" are layers of that one part alone,
    # behind the layer's one norm
    part: str = "block"
    # sliding-window attention over the last ``window`` positions (None =
    # every earlier position); a window layer's paged cache is a ring
    window: int | None = None
    rope_theta: float = 10000.0
    # rotary embedding on queries and keys (False: none; the model takes
    # its order from elsewhere, as ``nemotron_h`` does from its Mamba layers)
    rope: bool = True
    # a learned logit per query head that joins the softmax and carries no
    # value (the run's ``sink`` leaf)
    sink: bool = False
    # routed experts (width ``intermediate`` each) or one dense SwiGLU
    routed: bool = False
    intermediate: int = 14336
    # query heads of the kind's attention (None: the model's ``heads``);
    # rotary on the first ``rotary_dim`` dims of a query / key head (None:
    # the model's ``rotary_dim``), scaled by ``yarn`` where it is set
    heads: int | None = None
    rotary_dim: int | None = None
    yarn: YaRN | None = None
    # a learned sigmoid gate per query head multiplies that head's share
    # of the attention's output (the run's ``attn_gate`` leaf)
    gated: bool = False

    @property
    def attends(self) -> bool:
        return self.part in ("block", "attention")

    @property
    def has_ffn(self) -> bool:
        return self.part in ("block", "ffn")


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int = 32000
    hidden: int = 4096
    layers: int = 32
    heads: int = 32
    kv_heads: int = 8
    intermediate: int = 14336
    max_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # experts > 0 switches the MLP to sparse MoE: per-layer router +
    # stacked expert SwiGLU weights (parallel/moe.py: the capacity-based
    # GShard dispatch for training; for serving the sorted grouped product
    # where a program has many rows and a loop over the experts its rows
    # met where it has few; the expert axis is shardable over the mesh).  It counts the
    # experts HELD here: the router is ``experts_published`` wide where
    # that is set (one chip's share of an expert-parallel layer) and this
    # chip's experts start at ``experts_first``
    experts: int = 0
    experts_top_k: int = 2
    expert_capacity_factor: float = 2.0
    experts_published: int = 0
    experts_first: int = 0
    # "softmax" (Mixtral) or "sigmoid" with a per-expert correction bias
    # on the choice (``noaux_tc``)
    experts_scoring: str = "softmax"
    # an expert without a gate is ``down(relu(up x) ** 2)``; the chosen
    # experts' weights times ``experts_route_scale``; a shared expert of
    # width ``experts_shared`` (0: none) that every token takes
    experts_gated: bool = True
    experts_route_scale: float = 1.0
    experts_shared: int = 0
    # Mistral-v0.1-style sliding-window attention: each query attends to
    # at most the last `sliding_window` positions (None = full causal)
    sliding_window: int | None = None
    # rematerialize each layer in the backward pass (jax.checkpoint over
    # the scan body): activation memory drops from O(layers) to O(1)
    # layers at ~1/3 extra FLOPs — how long-sequence fine-tunes fit HBM
    remat: bool = False
    # head widths where they are not hidden // heads: query and key heads
    # ``qk_head_dim`` wide, value heads ``v_head_dim``, rotary on the first
    # ``rotary_dim`` of a query / key head, values scaled by ``value_scale``
    qk_head_dim: int | None = None
    v_head_dim: int | None = None
    rotary_dim: int | None = None
    value_scale: float = 1.0
    # layers of different kinds in one model: ``((kind, count), ...)`` in
    # layer order.  None: every layer is ``self.kind`` (the fields above)
    runs: tuple[tuple[LayerKind, int], ...] | None = None
    # a "mamba" layer's sizes: heads x head width is the mixer's inner
    # width, ``ssm_groups`` groups of heads share B and C of ``ssm_state``
    # entries, the convolution before the scan has ``ssm_conv`` taps, the
    # prefill program scans in chunks of ``ssm_chunk`` tokens; ``dt_bias``
    # is seeded so that the time step starts in ``ssm_dt_init`` (min, max,
    # floor)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 128
    ssm_dt_init: tuple[float, float, float] = (0.001, 0.1, 1e-4)

    @property
    def head_dim(self) -> int:
        return self.qk_head_dim or self.hidden // self.heads

    @property
    def v_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    @property
    def kind(self) -> LayerKind:
        """The one kind of a model whose layers are all alike."""
        return LayerKind(
            kv_heads=self.kv_heads, window=self.sliding_window,
            rope_theta=self.rope_theta, routed=self.experts > 0,
            intermediate=self.intermediate,
        )

    @property
    def layer_runs(self) -> tuple[tuple[LayerKind, int], ...]:
        return self.runs or ((self.kind, self.layers),)

    @property
    def routed_layers(self) -> int:
        return sum(n for kind, n in self.layer_runs if kind.routed)

    @property
    def ssm_layers(self) -> int:
        return sum(n for kind, n in self.layer_runs if kind.part == "mamba")

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """Columns the convolution runs over: x, then B and C of every group."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state


def run_stacks(cfg: DecoderConfig, layers, *pools):
    """``(kind, stacked layer params, *that run's pools)`` for each run.
    A model of one kind keeps its ``tree["layers"]`` dict and its pools as
    they are; a model with ``cfg.runs`` holds a tuple of each, one entry a
    run."""
    if cfg.runs is None:
        return [(cfg.kind, layers, *pools)]
    return [
        (kind, layers[r], *(p[r] for p in pools))
        for r, (kind, _n) in enumerate(cfg.runs)
    ]


PRESETS: dict[str, DecoderConfig] = {
    # v0.1 family: sliding-window attention over the last 4096 positions
    "mistral-7b-instruct": DecoderConfig(sliding_window=4096),
    "mistralai/Mistral-7B-Instruct-v0.2": DecoderConfig(rope_theta=1e6),
    "tinyllama-1.1b": DecoderConfig(
        hidden=2048, layers=22, heads=32, kv_heads=4, intermediate=5632,
        max_len=2048,
    ),
    # the MoE sibling of the Mistral family the reference's Adaptive RAG
    # template serves (block-sparse FFN, 8 experts, top-2 routing), served
    # through parallel/moe.py::moe_serve (softmax scores, all 8 experts
    # held): prefill sorts its pairs for one grouped product, a decode
    # step loops over the experts its rows met
    "mixtral-8x7b-instruct": DecoderConfig(
        rope_theta=1e6, experts=8, experts_top_k=2, max_len=8192,
    ),
    # tiny deterministic shape for tests: f32 so CPU numerics are exact
    "pw-tiny-decoder": DecoderConfig(
        vocab_size=512, hidden=64, layers=2, heads=4, kv_heads=2,
        intermediate=128, max_len=128, dtype=jnp.float32,
    ),
    "pw-tiny-moe-decoder": DecoderConfig(
        vocab_size=512, hidden=64, layers=2, heads=4, kv_heads=2,
        intermediate=128, max_len=128, dtype=jnp.float32,
        experts=4, experts_top_k=2,
    ),
    # every kind of layer MiMo-V2.5 has, tiny (the CPU tests' and the
    # benchmark's rehearsal preset): pattern G, W, W, W, W, G, W with the
    # first layer dense; a window shorter than the tests' sequences and
    # longer than a page; key heads wider than value heads; KV heads that
    # differ by kind; one of four shares of 16 sigmoid-routed experts
    # (``TINY_HYBRID_HF`` below, through the reader a ``config.json`` takes)
}


# ``model_type`` values of a ``config.json`` whose keys are the llama
# family's (one block, ``head_dim = hidden // heads``)
_LLAMA_TYPES = ("llama", "mistral", "mixtral")


def decoder_config_from_hf(hf: dict) -> DecoderConfig:
    """The shape a ``transformers`` ``config.json`` describes, by its
    ``model_type``; a type this reader does not know raises, for a
    llama-shaped model built from whatever keys are found would be another
    model under this one's name."""
    model_type = hf.get("model_type")
    if model_type == "mimo_v2":
        return _mimo_v2_config(hf)
    if model_type == "nemotron_h":
        return _nemotron_h_config(hf)
    if model_type == "laguna":
        return _laguna_config(hf)
    if model_type not in _LLAMA_TYPES:
        raise ValueError(
            f"config.json has model_type {model_type!r}; decoder_config_for "
            f"reads {', '.join(_LLAMA_TYPES)}, mimo_v2, nemotron_h and laguna"
        )
    return DecoderConfig(
        vocab_size=hf.get("vocab_size", 32000),
        hidden=hf.get("hidden_size", 4096),
        layers=hf.get("num_hidden_layers", 32),
        heads=hf.get("num_attention_heads", 32),
        kv_heads=hf.get("num_key_value_heads", hf.get("num_attention_heads", 32)),
        intermediate=hf.get("intermediate_size", 14336),
        max_len=min(hf.get("max_position_embeddings", 4096), 8192),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        experts=hf.get("num_local_experts", 0),
        experts_top_k=hf.get("num_experts_per_tok", 2),
        sliding_window=hf.get("sliding_window"),
    )


def _mimo_v2_config(hf: dict) -> DecoderConfig:
    """``model_type: mimo_v2`` (MiMo-V2-Flash / MiMo-V2.5, language model):
    window and global attention layers by ``hybrid_layer_pattern``, dense
    and routed FFNs by ``moe_layer_freq``, key heads wider than value
    heads, rotary on part of a head, a sink logit in window layers,
    sigmoid-scored experts chosen with a correction bias.

    ``n_routed_experts`` counts the experts held here; where the file is
    one chip's share of an expert-parallel layer it states the router's
    published width as ``n_routed_experts_published`` and which share this
    is as ``expert_shard_index`` (experts ``index * held`` onwards).  A
    setting this forward does not implement raises."""
    unread = {
        "scoring_func": ("sigmoid",), "topk_method": ("noaux_tc",),
        "n_group": (1, None), "topk_group": (1, None),
        "n_shared_experts": (None, 0), "routed_scaling_factor": (None, 1.0),
        "norm_topk_prob": (True,), "attention_bias": (False, None),
        "hidden_act": ("silu",), "tie_word_embeddings": (False, None),
    }
    for key, allowed in unread.items():
        if hf.get(key) not in allowed:
            raise NotImplementedError(
                f"mimo_v2 config: {key}={hf.get(key)!r} is not implemented "
                f"(this forward takes {allowed})"
            )
    scaling = hf.get("rope_scaling") or {}
    if scaling.get("rope_type", scaling.get("type", "default")) != "default":
        raise NotImplementedError(f"mimo_v2 config: rope_scaling {scaling!r}")
    L, heads, D = hf["num_hidden_layers"], hf["num_attention_heads"], hf["head_dim"]
    Dv = hf.get("v_head_dim", D)
    if (hf.get("swa_num_attention_heads", heads), hf.get("swa_head_dim", D),
            hf.get("swa_v_head_dim", Dv)) != (heads, D, Dv):
        raise NotImplementedError(
            "mimo_v2 config: window layers with other query heads or head "
            "widths than global layers"
        )
    pattern, routed = hf["hybrid_layer_pattern"][:L], hf["moe_layer_freq"][:L]
    if len(pattern) != L or len(routed) != L:
        raise ValueError(
            f"mimo_v2 config: {L} layers but hybrid_layer_pattern / "
            f"moe_layer_freq describe {len(pattern)} / {len(routed)}"
        )
    runs: list[tuple[LayerKind, int]] = []
    for window, moe in zip(pattern, routed):
        kind = LayerKind(
            kv_heads=hf["swa_num_key_value_heads" if window else "num_key_value_heads"],
            window=hf["sliding_window"] if window else None,
            rope_theta=float(hf["swa_rope_theta" if window else "rope_theta"]),
            sink=bool(hf.get(
                "add_swa_attention_sink_bias" if window
                else "add_full_attention_sink_bias", False
            )),
            routed=bool(moe),
            intermediate=hf["moe_intermediate_size" if moe else "intermediate_size"],
        )
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    held, published, first = _expert_share(hf, "mimo_v2")
    return DecoderConfig(
        vocab_size=hf["vocab_size"], hidden=hf["hidden_size"], layers=L,
        heads=heads, kv_heads=hf["num_key_value_heads"],
        intermediate=hf["intermediate_size"],
        max_len=min(hf.get("max_position_embeddings", 4096), 8192),
        rope_theta=float(hf["rope_theta"]),
        norm_eps=float(hf.get("layernorm_epsilon", 1e-5)),
        dtype=jnp.dtype(hf.get("torch_dtype", "bfloat16")),
        experts=held, experts_top_k=hf["num_experts_per_tok"],
        experts_published=published, experts_first=first,
        experts_scoring="sigmoid",
        qk_head_dim=D, v_head_dim=Dv,
        # 0.334 x 192 = 64.1: the even number of dims under it
        rotary_dim=int(hf.get("partial_rotary_factor", 1.0) * D) // 2 * 2,
        value_scale=float(hf.get("attention_value_scale") or 1.0),
        runs=tuple(runs),
    )


def _expert_share(hf: dict, model_type: str,
                  key: str = "n_routed_experts") -> tuple[int, int, int]:
    """``(held, published, first)``: ``key`` (``n_routed_experts``) counts
    the experts held here; a file that is one chip's share of an
    expert-parallel layer states the router's published width as
    ``<key>_published`` and which share this is as ``expert_shard_index``."""
    held = hf[key]
    published = hf.get(f"{key}_published", held)
    first = hf.get("expert_shard_index", 0) * held
    if first + held > published:
        raise ValueError(
            f"{model_type} config: experts [{first}, {first + held}) of {published}"
        )
    return held, published, first


# the one part each letter of ``hybrid_override_pattern`` makes a layer
_NEMOTRON_H_PARTS = {"M": "mamba", "*": "attention", "E": "ffn"}


def _nemotron_h_config(hf: dict) -> DecoderConfig:
    """``model_type: nemotron_h`` (Nemotron-H / Nemotron 3 Nano, language
    model): every layer is ONE part behind one norm, by its letter of
    ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``*`` grouped-query
    attention with no rotary embedding (the family takes its order from
    the Mamba layers), ``E`` sigmoid-routed experts without a gate
    (``down(relu(up x) ** 2)``) beside a shared expert, the chosen weights
    renormalised and scaled.  The share of an expert-parallel layer is
    stated as in ``mimo_v2``.  A setting this forward does not implement
    raises, as does an ``-`` (dense MLP) layer."""
    unread = {
        "n_group": (1, None), "topk_group": (1, None), "n_shared_experts": (1,),
        "norm_topk_prob": (True,), "attention_bias": (False, None),
        "mamba_proj_bias": (False, None), "mlp_bias": (False, None),
        "use_bias": (False, None), "use_conv_bias": (True,),
        "mamba_hidden_act": ("silu",), "mlp_hidden_act": ("relu2",),
        "tie_word_embeddings": (False, None), "sliding_window": (None,),
        "residual_in_fp32": (False, None), "moe_latent_size": (None, 0),
        "num_nextn_predict_layers": (None, 0), "time_step_limit": (None,),
        "norm_eps": (hf.get("layer_norm_epsilon", 1e-5), None),
    }
    for key, allowed in unread.items():
        if hf.get(key) not in allowed:
            raise NotImplementedError(
                f"nemotron_h config: {key}={hf.get(key)!r} is not implemented "
                f"(this forward takes {allowed})"
            )
    L = hf["num_hidden_layers"]
    pattern = hf["hybrid_override_pattern"][:L]
    if len(pattern) != L:
        raise ValueError(
            f"nemotron_h config: {L} layers but hybrid_override_pattern "
            f"describes {len(pattern)}"
        )
    other = sorted(set(pattern) - set(_NEMOTRON_H_PARTS))
    if other:
        raise NotImplementedError(
            f"nemotron_h config: layers {other} of hybrid_override_pattern are "
            f"not implemented (this forward takes {sorted(_NEMOTRON_H_PARTS)})"
        )
    runs: list[tuple[LayerKind, int]] = []
    for letter in pattern:
        part = _NEMOTRON_H_PARTS[letter]
        kind = LayerKind(
            kv_heads=hf["num_key_value_heads"] if part == "attention" else 0,
            part=part, rope=False, routed=part == "ffn",
            intermediate=hf["moe_intermediate_size"] if part == "ffn" else 0,
        )
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    held, published, first = _expert_share(hf, "nemotron_h")
    return DecoderConfig(
        vocab_size=hf["vocab_size"], hidden=hf["hidden_size"], layers=L,
        heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"],
        intermediate=hf["moe_intermediate_size"],
        max_len=min(hf.get("max_position_embeddings", 4096), 8192),
        norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
        dtype=jnp.dtype(hf.get("torch_dtype", "bfloat16")),
        experts=held, experts_top_k=hf["num_experts_per_tok"],
        experts_published=published, experts_first=first,
        experts_scoring="sigmoid", experts_gated=False,
        experts_route_scale=float(hf.get("routed_scaling_factor") or 1.0),
        experts_shared=hf["moe_shared_expert_intermediate_size"],
        qk_head_dim=hf["head_dim"], runs=tuple(runs),
        ssm_heads=hf["mamba_num_heads"], ssm_head_dim=hf["mamba_head_dim"],
        ssm_groups=hf["n_groups"], ssm_state=hf["ssm_state_size"],
        ssm_conv=hf["conv_kernel"], ssm_chunk=hf["chunk_size"],
        ssm_dt_init=(
            float(hf.get("time_step_min", 0.001)), float(hf.get("time_step_max", 0.1)),
            float(hf.get("time_step_floor", 1e-4)),
        ),
    )


# the attention of each ``layer_types`` entry a ``laguna`` file may name
_LAGUNA_WINDOWED = {"full_attention": False, "sliding_attention": True}


def _laguna_config(hf: dict) -> DecoderConfig:
    """``model_type: laguna`` (poolside's Laguna, language model): full and
    sliding-window attention layers by ``layer_types``, each with query
    heads of its own (``num_attention_heads_per_layer``), rotary of its
    own (``rope_parameters`` by layer type: on part or all of a head, with
    YaRN's scaling or without) and a sigmoid gate per query head on the
    attention's output; dense or routed FFNs by ``mlp_layer_types``: a
    dense SwiGLU, or softmax-routed SwiGLU experts beside a SwiGLU shared
    expert, the chosen weights renormalised and scaled.  ``num_experts``
    counts the experts held here; one chip's share of an expert-parallel
    layer is stated as ``num_experts_published`` and
    ``expert_shard_index``.  A setting this forward does not implement
    raises."""
    unread = {
        "gating": ("per-head",), "moe_router_logit_softcapping": (0, 0.0, None),
        "moe_apply_router_weight_on_input": (False, None),
        "attention_bias": (False, None), "tie_word_embeddings": (False, None),
        "norm_topk_prob": (True,), "decoder_sparse_step": (1, None),
        "hidden_act": ("silu", None),
    }
    for key, allowed in unread.items():
        if hf.get(key) not in allowed:
            raise NotImplementedError(
                f"laguna config: {key}={hf.get(key)!r} is not implemented "
                f"(this forward takes {allowed})"
            )
    L, D = hf["num_hidden_layers"], hf["head_dim"]
    per_layer = {
        name: hf[name][:L] for name in (
            "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
            "gating_types",
        )
    }
    short = {name: len(v) for name, v in per_layer.items() if len(v) != L}
    if short:
        raise ValueError(f"laguna config: {L} layers but {short} describe fewer")
    for key, values, allowed in (
        ("layer_types", per_layer["layer_types"], set(_LAGUNA_WINDOWED)),
        ("mlp_layer_types", per_layer["mlp_layer_types"], {"dense", "sparse"}),
        ("gating_types", per_layer["gating_types"], {"per_head"}),
    ):
        other = sorted(set(values) - allowed)
        if other:
            raise NotImplementedError(
                f"laguna config: {key} {other} are not implemented "
                f"(this forward takes {sorted(allowed)})"
            )
    dense = {l for l, m in enumerate(per_layer["mlp_layer_types"]) if m == "dense"}
    if {l for l in hf.get("mlp_only_layers", []) if l < L} != dense:
        raise ValueError(
            f"laguna config: mlp_only_layers {hf.get('mlp_only_layers')} disagree "
            f"with the dense layers of mlp_layer_types {sorted(dense)}"
        )
    kinds = {}
    for layer_type, windowed in _LAGUNA_WINDOWED.items():
        rope = hf["rope_parameters"].get(layer_type, {})
        rope_type = rope.get("rope_type", "default")
        if rope_type not in ("default", "yarn"):
            raise NotImplementedError(f"laguna config: {layer_type} rope_type {rope_type!r}")
        yarn = None
        if rope_type == "yarn":
            factor = float(rope["factor"])
            yarn = YaRN(
                factor=factor,
                original_max_position_embeddings=rope["original_max_position_embeddings"],
                beta_fast=float(rope.get("beta_fast", 32.0)),
                beta_slow=float(rope.get("beta_slow", 1.0)),
                # transformers' default where the file states none
                attention_factor=float(
                    rope.get("attention_factor") or 0.1 * np.log(factor) + 1.0
                ),
            )
        kinds[layer_type] = dict(
            window=hf["sliding_window"] if windowed else None,
            rope_theta=float(rope.get("rope_theta", 10000.0)),
            rotary_dim=int(rope.get("partial_rotary_factor", 1.0) * D) // 2 * 2,
            yarn=yarn,
        )
    runs: list[tuple[LayerKind, int]] = []
    for layer_type, mlp, heads in zip(
        per_layer["layer_types"], per_layer["mlp_layer_types"],
        per_layer["num_attention_heads_per_layer"],
    ):
        routed = mlp == "sparse"
        kind = LayerKind(
            kv_heads=hf["num_key_value_heads"], heads=heads, gated=True,
            routed=routed,
            intermediate=hf["moe_intermediate_size" if routed else "intermediate_size"],
            **kinds[layer_type],
        )
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    held, published, first = _expert_share(hf, "laguna", "num_experts")
    return DecoderConfig(
        vocab_size=hf["vocab_size"], hidden=hf["hidden_size"], layers=L,
        heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"],
        intermediate=hf["intermediate_size"],
        max_len=min(hf.get("max_position_embeddings", 4096), 8192),
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        dtype=jnp.dtype(hf.get("torch_dtype", "bfloat16")),
        experts=held, experts_top_k=hf["num_experts_per_tok"],
        experts_published=published, experts_first=first,
        experts_route_scale=float(hf.get("moe_routed_scaling_factor") or 1.0),
        experts_shared=hf["shared_expert_intermediate_size"],
        qk_head_dim=D, runs=tuple(runs),
    )


# ``chipbench/configs/mimo-v2.5-bge-rag.json``'s ``tiny.decoder`` block is
# this dictionary (a test holds them equal)
TINY_HYBRID_HF = {
    "model_type": "mimo_v2", "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 7, "num_attention_heads": 4,
    "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
    "head_dim": 24, "v_head_dim": 16, "partial_rotary_factor": 0.334,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "sliding_window": 24, "attention_value_scale": 0.707,
    "add_full_attention_sink_bias": False, "add_swa_attention_sink_bias": True,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1],
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "expert_shard_index": 0, "num_experts_per_tok": 4,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "hidden_act": "silu",
    "layernorm_epsilon": 1e-05, "max_position_embeddings": 128,
    "torch_dtype": "float32",
}
PRESETS["pw-tiny-hybrid-decoder"] = decoder_config_from_hf(TINY_HYBRID_HF)

# every kind of layer Nemotron 3 Nano has, tiny, in its order (one period
# and the two layers that follow, as the benchmark's cut ends): Mamba-2
# heads in groups, a chunk shorter than the tests' prompts, attention heads
# whose width times their number is not the hidden size, one of two shares
# of 8 ungated experts beside a shared expert.
# ``chipbench/configs/nemotron-3-nano-bge-rag.json``'s ``tiny.decoder`` block
TINY_MAMBA_HF = {
    "model_type": "nemotron_h", "vocab_size": 512, "hidden_size": 48,
    "num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 40, "moe_intermediate_size": 40,
    "moe_shared_expert_intermediate_size": 80,
    "n_routed_experts": 4, "n_routed_experts_published": 8,
    "expert_shard_index": 0, "num_experts_per_tok": 3, "n_shared_experts": 1,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "mamba_hidden_act": "silu",
    "mlp_hidden_act": "relu2", "use_conv_bias": True,
    "layer_norm_epsilon": 1e-05, "max_position_embeddings": 128,
    "torch_dtype": "float32",
}
PRESETS["pw-tiny-mamba-decoder"] = decoder_config_from_hf(TINY_MAMBA_HF)

# every kind of layer Laguna has, tiny, in the benchmark's cut's order
# (full and dense, three window layers, full and routed): 4 query heads on
# full layers and 6 on window layers over 2 KV heads, a per-head gate,
# YaRN on half of a full layer's head, a window of 24 (longer than the
# tests' 8-token pages, shorter than their prompts), one of two shares of
# 8 softmax-routed SwiGLU experts beside a SwiGLU shared expert; a context
# of 1,024, so that the benchmark's rehearsal sends prompts longer than
# one prefill program.
# ``chipbench/configs/laguna-s-2.1-bge-rag.json``'s ``tiny.decoder`` block
TINY_LAGUNA_HF = {
    "model_type": "laguna", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 1024, "attention_bias": False, "rms_norm_eps": 1e-06,
    "num_experts": 4, "num_experts_published": 8, "expert_shard_index": 0,
    "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [0], "tie_word_embeddings": False,
    "gating": "per-head", "sliding_window": 24,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 4,
            "original_max_position_embeddings": 32, "beta_slow": 1, "beta_fast": 32,
            "attention_factor": 1.1386294361119891, "partial_rotary_factor": 0.5,
        },
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1,
        },
    },
    "layer_types": [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention",
    ],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "gating_types": ["per_head"] * 5,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
    "moe_apply_router_weight_on_input": False, "moe_routed_scaling_factor": 2.5,
    "moe_router_logit_softcapping": 0, "torch_dtype": "float32",
}
PRESETS["pw-tiny-laguna-decoder"] = decoder_config_from_hf(TINY_LAGUNA_HF)


def decoder_config_for(model_name: str) -> DecoderConfig:
    """Preset lookup, or the shape read from a local ``config.json``
    (``transformers`` save directory) by its ``model_type``."""
    import json
    import os

    if model_name in PRESETS:
        return PRESETS[model_name]
    cfg_path = os.path.join(model_name, "config.json")
    if os.path.isfile(cfg_path):
        with open(cfg_path) as f:
            return decoder_config_from_hf(json.load(f))
    # an unknown name would otherwise build (and compile) a random 7B —
    # fail loudly instead, a typo should not cost 14 GB and minutes
    raise ValueError(
        f"unknown decoder model {model_name!r}: not a preset "
        f"({sorted(PRESETS)}) and not a local checkpoint directory"
    )


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _norm_init(key, divisor, shape, dtype):
    """Scaled-normal draw cast to ``dtype`` as ONE compiled program: the
    f32 draw fuses into the cast instead of materializing — eagerly, a
    16-layer 7B-wide ``wg`` is a 3.8 GB f32 transient (twice over, for the
    draw and the scaled copy) on top of the bf16 weights already resident.
    ``divisor`` is an operand, not a constant, so XLA keeps the division
    (a constant would become a reciprocal multiply, 1 ulp off the eager
    values the tests were tuned on)."""
    return (jax.random.normal(key, shape, jnp.float32) / divisor).astype(dtype)


def init_decoder_params(cfg: DecoderConfig, seed: int = 0):
    """Deterministic scaled-normal init of the stacked param tree.

    With ``cfg.experts > 0`` the MLP weights carry an extra expert axis
    (``[L, E, H, F]``) plus a per-layer f32 router ``[L, H, E]``.
    """
    H, L, F = cfg.hidden, cfg.layers, cfg.intermediate
    NH, KH, D = cfg.heads, cfg.kv_heads, cfg.head_dim
    keys = jax.random.split(jax.random.PRNGKey(seed), 11)

    def norm_init(key, shape, fan_in):
        return _norm_init(key, np.float32(np.sqrt(fan_in)), shape, cfg.dtype)

    if cfg.runs is not None:
        return {
            "embed": norm_init(keys[0], (cfg.vocab_size, H), H),
            "final_norm": jnp.ones((H,), cfg.dtype),
            "lm_head": norm_init(keys[1], (H, cfg.vocab_size), H),
            "layers": tuple(
                _init_run(cfg, kind, n, jax.random.fold_in(keys[2], r), norm_init)
                for r, (kind, n) in enumerate(cfg.runs)
            ),
        }
    layers = {
        "ln0": jnp.ones((L, H), cfg.dtype),
        "ln1": jnp.ones((L, H), cfg.dtype),
        "wq": norm_init(keys[2], (L, H, NH * D), H),
        "wk": norm_init(keys[3], (L, H, KH * D), H),
        "wv": norm_init(keys[4], (L, H, KH * D), H),
        "wo": norm_init(keys[5], (L, NH * D, H), NH * D),
    }
    if cfg.experts:
        E = cfg.experts
        layers.update(
            {
                # router stays f32 (routing decisions are f32 end-to-end)
                "moe_router": jax.random.normal(keys[9], (L, H, E), jnp.float32)
                / np.sqrt(H),
                "wg": norm_init(keys[6], (L, E, H, F), H),
                "wu": norm_init(keys[7], (L, E, H, F), H),
                "wd": norm_init(keys[8], (L, E, F, H), F),
            }
        )
    else:
        layers.update(
            {
                "wg": norm_init(keys[6], (L, H, F), H),
                "wu": norm_init(keys[7], (L, H, F), H),
                "wd": norm_init(keys[8], (L, F, H), F),
            }
        )
    return {
        "embed": norm_init(keys[0], (cfg.vocab_size, H), H),
        "final_norm": jnp.ones((H,), cfg.dtype),
        "lm_head": norm_init(keys[1], (H, cfg.vocab_size), H),
        "layers": layers,
    }


def _init_run(cfg: DecoderConfig, kind: LayerKind, n: int, key, norm_init):
    """One run's stacked leaves, by the parts its kind has.

    Attention: the fused ``wqkv`` (queries, then keys, then values, as
    ``attention_projection_layout: fused_qkv`` lays them), ``wo`` over the
    value heads, a sink logit per query head where the kind has one
    (normal x 0.5: not nought, or a test would not see it), the gate's
    ``attn_gate [H, heads]`` where the kind is gated (the sink's key
    folded with 1).  FFN: the
    dense SwiGLU, or the router (f32, at its published width, with the
    ``noaux_tc`` correction bias, normal x 0.02) and the experts HELD
    (their draw keyed by the first expert's index, so that another share
    of the layer draws other experts; no ``wg`` where experts have no
    gate) and the shared expert, which every share draws alike (of the
    experts' form: its gate, where they have one, from the router's key
    folded with 2).  A layer
    of one part has the one norm ``ln0``; ``_init_mamba`` draws a mixer."""
    H, D, Dv = cfg.hidden, cfg.head_dim, cfg.v_dim
    NH = kind.heads or cfg.heads
    keys = jax.random.split(key, 8)
    run = {"ln0": jnp.ones((n, H), cfg.dtype)}
    if kind.part == "block":
        run["ln1"] = jnp.ones((n, H), cfg.dtype)
    if kind.part == "mamba":
        run.update(_init_mamba(cfg, n, keys, norm_init))
    if kind.attends:
        qkv = NH * D + kind.kv_heads * (D + Dv)
        run["wqkv"] = norm_init(keys[0], (n, H, qkv), H)
        run["wo"] = norm_init(keys[1], (n, NH * Dv, H), NH * Dv)
        if kind.sink:
            run["sink"] = 0.5 * jax.random.normal(keys[2], (n, NH), jnp.float32)
        if kind.gated:
            run["attn_gate"] = norm_init(jax.random.fold_in(keys[2], 1), (n, H, NH), H)
    if not kind.has_ffn:
        return run
    F = kind.intermediate
    if not kind.routed:
        run.update({
            "wg": norm_init(keys[3], (n, H, F), H),
            "wu": norm_init(keys[4], (n, H, F), H),
            "wd": norm_init(keys[5], (n, F, H), F),
        })
        return run
    E, width = cfg.experts, cfg.experts_published or cfg.experts
    held = [jax.random.fold_in(k, cfg.experts_first) for k in keys[3:6]]
    run["moe_router"] = jax.random.normal(
        keys[6], (n, H, width), jnp.float32
    ) / np.sqrt(H)
    def stored(w, axis):
        # an expert's hidden width is stored as whole lanes (``_lanes``):
        # the added columns of ``wg`` / ``wu`` and rows of ``wd`` are
        # noughts and add nought, whichever form the expert has
        extra = [(0, _lanes(F) - F if a == axis else 0) for a in range(w.ndim)]
        return jnp.pad(w, extra) if _lanes(F) > F else w

    if cfg.experts_gated:
        run["wg"] = stored(norm_init(held[0], (n, E, H, F), H), 3)
    run["wu"] = stored(norm_init(held[1], (n, E, H, F), H), 3)
    run["wd"] = stored(norm_init(held[2], (n, E, F, H), F), 2)
    if cfg.experts_scoring == "sigmoid":
        run["moe_bias"] = 0.02 * jax.random.normal(keys[7], (n, width), jnp.float32)
    if cfg.experts_shared:
        Fs = cfg.experts_shared
        up, down = jax.random.split(jax.random.fold_in(keys[6], 1))
        if cfg.experts_gated:
            run["shared_gate"] = norm_init(jax.random.fold_in(keys[6], 2), (n, H, Fs), H)
        run["shared_up"] = norm_init(up, (n, H, Fs), H)
        run["shared_down"] = norm_init(down, (n, Fs, H), Fs)
    return run


def _lanes(width: int) -> int:
    """An expert's hidden width as it is stored: the next multiple of the
    chip's 128 lanes where it is wider than one and not a multiple.  The
    grouped product (``parallel/moe.py::moe_serve``) is a kernel that
    takes whole lanes: given 1,856 columns (14.5 x 128) the compiler pads
    a copy of ALL of a layer's experts for it, hit or not, every step (638
    MB a layer at Nemotron 3 Nano's widths).  In memory the columns are
    tiled by 128 anyway, so the stored padding costs ``wg`` / ``wu``
    nothing and ``wd`` its added rows."""
    return width if width <= 128 else -(-width // 128) * 128


def _init_mamba(cfg: DecoderConfig, n: int, keys, norm_init):
    """A Mamba-2 mixer's leaves, seeded away from nought so that a
    comparison sees each: ``in_proj`` to ``[z | x B C | dt]`` and
    ``out_proj`` (normal / sqrt(fan_in)); the convolution's taps ``[K,
    columns]`` (normal / sqrt(K)) and bias (normal x 0.1); ``A_log`` = log
    of uniform [1, 16) a head; ``dt_bias`` the inverse softplus of a time
    step drawn log-uniform in ``ssm_dt_init``'s range (the Mamba-2 recipe);
    ``D`` = 1 + normal x 0.5; the gated norm's weight ones.  The last four
    stay float32, as the scan reads them."""
    H, NH, K = cfg.hidden, cfg.ssm_heads, cfg.ssm_conv
    inner, columns = cfg.ssm_inner, cfg.ssm_conv_width
    lo, hi, floor = cfg.ssm_dt_init
    step = jnp.maximum(
        jnp.exp(
            jax.random.uniform(keys[4], (n, NH), jnp.float32)
            * (np.log(hi) - np.log(lo)) + np.log(lo)
        ),
        floor,
    )
    return {
        "in_proj": norm_init(keys[0], (n, H, inner + columns + NH), H),
        "out_proj": norm_init(keys[1], (n, inner, H), inner),
        "conv_w": norm_init(keys[2], (n, K, columns), K),
        "conv_b": (0.1 * jax.random.normal(keys[3], (n, columns), jnp.float32)).astype(cfg.dtype),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(keys[5], (n, NH), jnp.float32, 1.0, 16.0)),
        "D": 1.0 + 0.5 * jax.random.normal(keys[6], (n, NH), jnp.float32),
        "gate_norm": jnp.ones((n, inner), cfg.dtype),
    }


def tp_param_specs(cfg: DecoderConfig, axis: str = "model"):
    """Tensor-parallel PartitionSpecs: attention heads and FFN width sharded
    over ``axis``; contractions back to hidden leave XLA one all-reduce per
    block (the Megatron layout, expressed as shardings not collectives).

    MoE configs shard the EXPERT axis over ``axis`` instead of the FFN
    width — each chip owns ``E / |axis|`` whole experts and the GShard
    dispatch/combine einsums lower to ``all_to_all`` (expert parallelism
    in serving)."""
    if cfg.runs is not None:
        raise NotImplementedError(
            "tp_param_specs lays out a model whose layers are all alike; a "
            "model of runs (cfg.runs) has no tensor-parallel layout yet"
        )
    layer_specs = {
        "ln0": P(None, None),
        "ln1": P(None, None),
        "wq": P(None, None, axis),
        "wk": P(None, None, axis),
        "wv": P(None, None, axis),
        "wo": P(None, axis, None),
    }
    if cfg.experts:
        layer_specs.update(
            {
                "moe_router": P(None, None, None),
                "wg": P(None, axis, None, None),
                "wu": P(None, axis, None, None),
                "wd": P(None, axis, None, None),
            }
        )
    else:
        layer_specs.update(
            {
                "wg": P(None, None, axis),
                "wu": P(None, None, axis),
                "wd": P(None, axis, None),
            }
        )
    return {
        "embed": P(None, None),
        "final_norm": P(None),
        "lm_head": P(None, axis),
        "layers": layer_specs,
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * lax.rsqrt(var + eps)).astype(x.dtype) * scale


def _sw_mask(q_pos, k_pos, window: int):
    """True where key position ``k_pos`` lies inside the sliding window of
    query position ``q_pos`` (``q_pos - window < k_pos``); shapes
    broadcast.  The ONE definition of the window edge — shared by the
    trunk, the paged programs and the pipeline masks so they cannot drift."""
    return k_pos > q_pos - window


def _mm(x, w):
    """``x @ w`` for a float weight, an int8 weight-only quant pair, or a
    LoRA-adapted weight.

    Quantized weights are ``{"q": int8, "s": f32}`` with per-output-channel
    scales over the contraction axis (always ``-2`` in this tree's
    layouts), so the dequant commutes with the dot and is applied to the
    OUTPUT: the MXU reads int8 bytes from HBM (half of bf16 — decode is
    bandwidth-bound, so this is directly tokens/s) and XLA fuses the
    int8→bf16 convert into the dot's operand load.

    LoRA weights are ``{"w": frozen base, "a": [..., H, r], "b": [...,
    r, O]}``: the update routes through the rank-``r`` bottleneck
    (``(x@a)@b`` — never materializing the dense delta); the standard
    ``alpha/r`` scale is folded into ``a``'s init (``b`` starts zero).
    """
    if isinstance(w, dict) and "q" in w:
        return (x @ w["q"].astype(x.dtype)) * w["s"].astype(x.dtype)
    if isinstance(w, dict) and "a" in w:
        return x @ w["w"] + (x @ w["a"].astype(x.dtype)) @ w["b"].astype(x.dtype)
    return x @ w


def quantize_decoder_tree(tree):
    """Weight-only int8 quantization of a decoder param tree (serving).

    Every matmul weight (attention projections, dense or expert MLP,
    lm_head) becomes ``{"q": int8, "s": f32}`` with symmetric
    per-output-channel scales (``max|w| / 127`` over the contraction
    axis, which is ``-2`` in every layout here).  Embedding, norms and
    the MoE router stay full precision — they are lookup/elementwise/f32
    paths, not HBM-bound matmuls.  Inference-only: training keeps float
    trees.
    """
    if not isinstance(tree["layers"], dict):
        raise NotImplementedError(
            "quantize_decoder_tree takes a model whose layers are all alike; "
            "a tree of runs is served float by the scheduler's path"
        )
    quant_names = {"wq", "wk", "wv", "wo", "wg", "wu", "wd"}
    for name in quant_names:
        w = tree["layers"].get(name)
        if isinstance(w, dict) and "a" in w:
            raise ValueError(
                f"layer weight {name!r} carries LoRA adapters — call "
                "models.lora.merge_lora(tree) before quantizing"
            )

    def quant(w):
        w32 = jnp.asarray(w, jnp.float32)
        s = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 127.0
        s = jnp.maximum(s, 1e-12)
        q = jnp.clip(jnp.round(w32 / s), -127, 127).astype(jnp.int8)
        return {"q": q, "s": s}

    return {
        "embed": tree["embed"],
        "final_norm": tree["final_norm"],
        "lm_head": quant(tree["lm_head"]),
        "layers": {
            name: (quant(w) if name in quant_names else w)
            for name, w in tree["layers"].items()
        },
    }


def rope_inv_frequencies(d: int, theta: float, yarn: YaRN) -> np.ndarray:
    """YaRN's inverse frequencies ``[d / 2]`` of ``d`` rotated dims (float64;
    transformers' ``_compute_yarn_parameters``): ``f_i = theta^(-2i/d)``,
    the ramp ``r_i = clip((i - lo) / (hi - lo), 0, 1)`` between the dims
    ``lo = floor(d ln(M / (beta_fast 2 pi)) / (2 ln theta))`` and ``hi =
    ceil(d ln(M / (beta_slow 2 pi)) / (2 ln theta))`` (clipped to ``[0, d -
    1]``, ``M`` the original context), ``f_i / factor * r_i + f_i * (1 -
    r_i)``."""
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def dim(rotations: float) -> float:
        M = yarn.original_max_position_embeddings
        return d * np.log(M / (rotations * 2 * np.pi)) / (2 * np.log(theta))

    lo = max(np.floor(dim(yarn.beta_fast)), 0)
    hi = min(np.ceil(dim(yarn.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return f / yarn.factor * ramp + f * (1.0 - ramp)


def _rope(x, positions, theta, yarn: YaRN | None = None):
    """Rotary embedding; ``x`` is ``[..., S, H, D]``, positions ``[..., S]``.
    With ``yarn`` its frequencies (:func:`rope_inv_frequencies`), and cos
    and sin times its ``attention_factor``."""
    d = x.shape[-1]
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    else:
        inv = jnp.asarray(rope_inv_frequencies(d, theta, yarn), jnp.float32)
    freqs = positions[..., None].astype(jnp.float32) * inv  # [..., S, D/2]
    cos = jnp.cos(freqs)[..., None, :]  # [..., S, 1, D/2]
    sin = jnp.sin(freqs)[..., None, :]
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _rope_part(x, positions, theta, rot: int | None, yarn: YaRN | None = None):
    """Rotary on the first ``rot`` dims of each head (all of them where
    ``rot`` is None or the head's width), the rest untouched."""
    if rot is None or rot == x.shape[-1]:
        return _rope(x, positions, theta, yarn)
    return jnp.concatenate(
        [_rope(x[..., :rot], positions, theta, yarn), x[..., rot:]], axis=-1
    )


def _qkv(lp, x, positions, cfg: DecoderConfig, kind: LayerKind):
    """Input norm, projections, rotary, value scale of one layer: ``q
    [..., NH, D]``, ``k [..., KH, D]``, ``v [..., KH, Dv]`` from the
    residual stream ``x [..., H]`` (a run of kinds holds the fused
    ``wqkv``, a model of one kind ``wq`` / ``wk`` / ``wv``), NH the kind's
    query heads; and where the kind is gated the gate ``sigmoid(h .
    W_gate) [..., NH]`` (float32) of the normed ``h``, else None
    (:func:`_gate_heads` applies it).

    The barrier keeps each product a plain ``[..., H] @ [H, N]``.  Without
    it the TPU compiler folds the split into heads into the product (a
    convolution over the heads) and wants the weight head-major: in a layer
    scan that is the layer's slice of the stacked weight written out and
    copied into the other layout every step, 2 of a Mistral-7B decode
    step's 18.5 ms (PERF.md section 6, PR 32).  Behind it the slice fuses
    into the product, as for ``wo`` and the MLP, and the split is a view of
    the small output."""
    lead = x.shape[:-1]
    KH, D, Dv = kind.kv_heads, cfg.head_dim, cfg.v_dim
    NH = kind.heads or cfg.heads
    h = _rms(x, lp["ln0"], cfg.norm_eps)
    if "wqkv" in lp:
        nq, nk = NH * D, KH * D
        qkv = lax.optimization_barrier(_mm(h, lp["wqkv"]))
        q, k, v = qkv[..., :nq], qkv[..., nq:nq + nk], qkv[..., nq + nk:]
    else:
        q, k, v = lax.optimization_barrier(
            (_mm(h, lp["wq"]), _mm(h, lp["wk"]), _mm(h, lp["wv"]))
        )
    q = q.reshape(*lead, NH, D)
    k = k.reshape(*lead, KH, D)
    v = v.reshape(*lead, KH, Dv)
    if kind.rope:
        rot = kind.rotary_dim or cfg.rotary_dim

        def turn(t):
            return _rope_part(t, positions, kind.rope_theta, rot, kind.yarn)

        if kind.yarn is None:
            q, k = turn(q), turn(k)
        else:
            with jax.named_scope("rope.yarn"):
                q, k = turn(q), turn(k)
    if cfg.value_scale != 1.0:
        v = v * jnp.asarray(cfg.value_scale, v.dtype)
    gate = None
    if kind.gated:
        with jax.named_scope("attn.gate"):
            gate = jax.nn.sigmoid(_mm(h, lp["attn_gate"]).astype(jnp.float32))
    return q, k, v, gate


def _gate_heads(ctx, gate):
    """The attention's output ``ctx [..., NH * Dv]`` with each head's
    share times its gate ``[..., NH]`` (a kind without a gate: as it is)."""
    if gate is None:
        return ctx
    with jax.named_scope("attn.gate"):
        NH = gate.shape[-1]
        heads = ctx.reshape(*ctx.shape[:-1], NH, ctx.shape[-1] // NH)
        return (heads.astype(jnp.float32) * gate[..., None]).astype(ctx.dtype).reshape(ctx.shape)


def _attend(q, k, v, mask, cfg: DecoderConfig, sink=None):
    """GQA attention.  q ``[B, S, NH, D]``; k ``[B, C, KH, D]``, v ``[B,
    C, KH, Dv]``; mask ``[B, S, C]`` boolean (True = attend); ``sink``
    ``[NH]``, where the layer has one, joins each head's softmax as one
    more logit that carries no value."""
    from pathway_tpu.ops.attention import gqa_attention

    return gqa_attention(q, k, v, mask, sink)


def _ffn(lp, h, cfg: DecoderConfig, kind: LayerKind | None = None, *,
         serving: bool = False, valid=None):
    """SwiGLU MLP — dense, or sparse MoE where the layer's kind is routed
    (``parallel/moe.py``; the expert axis of ``wg/wu/wd`` is shardable over
    a mesh axis, see ``tp_param_specs``).  Training (``serving=False``)
    returns ``(out, aux)`` with the load-balance auxiliary loss of the
    capacity-based dispatch (0 for dense).  ``serving`` routes without
    capacity (a drop would silently degrade a generation) and returns
    ``(out, stats)``: the ``[pairs, experts_hit, tile_rows]`` of
    ``moe_serve`` over the ``valid`` tokens, or for a dense layer the
    same 0."""
    kind = kind or cfg.kind
    if kind.routed:
        from pathway_tpu.parallel.moe import MoEConfig, moe_ffn, moe_serve

        mcfg = MoEConfig(
            hidden=cfg.hidden,
            experts=cfg.experts,
            intermediate=kind.intermediate,
            top_k=cfg.experts_top_k,
            capacity_factor=cfg.expert_capacity_factor,
            dtype=cfg.dtype,
            scoring=cfg.experts_scoring,
            router_width=cfg.experts_published,
            first_expert=cfg.experts_first,
            gated=cfg.experts_gated,
            route_scale=cfg.experts_route_scale,
        )
        params = {"router": lp["moe_router"], "wu": lp["wu"], "wd": lp["wd"]}
        # what a layer has of: a gate, a correction bias, its index in the
        # run's expert stacks, a shared expert
        optional = {"wg": "wg", "bias": "moe_bias", "layer": "moe_layer",
                    "shared_gate": "shared_gate", "shared_up": "shared_up",
                    "shared_down": "shared_down"}
        params.update({name: lp[leaf] for name, leaf in optional.items() if leaf in lp})
        if serving:
            out, *counts = moe_serve(params, h, mcfg, valid)
            return out, jnp.stack(counts)
        if mcfg.scoring != "softmax" or mcfg.router_width not in (0, mcfg.experts):
            raise NotImplementedError(
                "training routes by softmax over experts that are all held "
                "(moe_ffn); a share of sigmoid-routed experts is served, "
                "not trained"
            )
        return moe_ffn(params, h, mcfg)
    with jax.named_scope("mlp.gate_up"):
        gated = jax.nn.silu(_mm(h, lp["wg"])) * _mm(h, lp["wu"])
    with jax.named_scope("mlp.down"):
        return _mm(gated, lp["wd"]), jnp.float32(0.0)


def decoder_layer(lp, x, positions, mask, cfg: DecoderConfig,
                  kind: LayerKind | None = None, *, serving=False):
    """One pre-norm transformer block (GQA attention + SwiGLU/MoE MLP) of
    ``kind`` (the model's one kind by default).

    ``lp`` holds a single layer's weights (no leading layer axis); ``mask``
    is the caller's, with the kind's window in it.  Returns ``(x, (k, v),
    aux)`` — the new residual stream, this layer's key/value projections
    ``[B, S, KH, D]`` / ``[B, S, KH, Dv]``, and the MoE load-balance aux
    loss (0 for dense; under ``serving`` the routing's ``[pairs,
    experts_hit]``).  Shared by the scanned trunk below and the pipeline-
    parallel stage runner (``parallel/pipeline.py``), so both paths compute
    identical math.  ``serving`` selects lossless MoE routing vs the
    capacity-drop policy (training).
    """
    kind = kind or cfg.kind
    if kind.part != "block":
        raise NotImplementedError(
            f"the full forward takes layers of attention followed by an FFN; "
            f"a {kind.part!r} layer is served by the scheduler's paged "
            "programs (_paged_trunk), not trained"
        )
    q, k, v, gate = _qkv(lp, x, positions, cfg, kind)
    ctx = _gate_heads(_attend(q, k, v, mask, cfg, lp.get("sink")), gate)
    x = x + _mm(ctx, lp["wo"])
    h = _rms(x, lp["ln1"], cfg.norm_eps)
    mlp, aux = _ffn(lp, h, cfg, kind, serving=serving)
    x = x + mlp
    return x, (k, v), aux


def _causal_trunk(tree, ids, lengths, cfg: DecoderConfig, *, serving=False):
    """Shared causal forward: ``(final-norm token reps [B, S, H], aux)``,
    aux the summed MoE load-balance loss (0 under ``serving``)."""
    B, S = ids.shape
    x = tree["embed"][ids]  # [B, S, H]
    positions = jnp.arange(S)[None, :].repeat(B, axis=0)
    valid = positions < lengths[:, None]  # [B, S]
    causal = jnp.tril(jnp.ones((S, S), bool))
    aux_sum = jnp.float32(0.0)
    for kind, layers in run_stacks(cfg, tree["layers"]):
        seen = causal
        if kind.window is not None:
            # each query sees at most the last `window` keys
            seen = seen & _sw_mask(
                jnp.arange(S)[:, None], jnp.arange(S)[None, :], kind.window
            )
        mask = seen[None, :, :] & valid[:, None, :]  # [B, S(q), S(kv)]

        def layer(x, lp, kind=kind, mask=mask):
            x, _kv, aux = decoder_layer(
                lp, x, positions, mask, cfg, kind, serving=serving
            )
            return x, aux

        if cfg.remat:
            # scan-over-remat: backward recomputes each layer's activations
            # from its residual-stream input instead of storing them.
            # prevent_cse=False: safe (and recommended) inside lax.scan, and
            # skips the optimization barriers that would block layer fusion
            layer = jax.checkpoint(layer, prevent_cse=False)
        x, aux = lax.scan(layer, x, layers)
        if not serving:
            aux_sum = aux_sum + aux.sum()
    return _rms(x, tree["final_norm"], cfg.norm_eps), aux_sum


def causal_lm_logits(tree, ids, lengths, cfg: DecoderConfig, *, serving=False):
    """All-position logits ``[B, S, vocab]`` (f32) for next-token training,
    or with ``serving`` the full forward as the serving path routes it (no
    expert capacity): what the paged programs are held against.
    """
    return causal_lm_logits_and_aux(tree, ids, lengths, cfg, serving=serving)[0]


def causal_lm_logits_and_aux(tree, ids, lengths, cfg: DecoderConfig, *, serving=False):
    """``(logits [B, S, vocab] f32, aux)`` — aux is the summed MoE
    load-balance loss over layers (0 for dense configs, and under
    ``serving``); MoE training adds it to the LM loss so routing stays
    spread over experts."""
    x, aux = _causal_trunk(tree, ids, lengths, cfg, serving=serving)
    return _mm(x, tree["lm_head"]).astype(jnp.float32), aux


def sample_logits(logits, key, temp, *, top_k=None, top_p=None, min_p=None):
    """On-device sampling: temperature, then optional min-p / top-k /
    nucleus (top-p) truncation, then categorical.  ``logits [B, V]`` f32.

    min-p keeps tokens whose probability is at least ``min_p x`` the top
    token's (the relative cutoff that adapts to how peaked the
    distribution is); top-k keeps the ``top_k`` largest logits (0, or a k
    past the vocabulary, keeps all); top-p keeps the smallest
    probability-sorted prefix of what top-k left whose mass reaches
    ``top_p`` (the first token always survives, so the distribution is
    never empty).  All filters set rejected logits to -inf BEFORE the
    categorical draw, inside the compiled program, and each is data: a
    scalar or one value a row (``[B, 1]``), so no value of it compiles
    anything.  top-k and top-p read one descending sort.
    """
    lg = logits / temp
    if min_p is not None:
        # log-space form of probs < min_p * max(probs): the softmax
        # normalizer cancels, so one max-reduce replaces a full-vocab
        # softmax in the per-token loop.  The clamp makes min_p > 1 (bad
        # client value) degrade to argmax-only, never an empty
        # distribution; min_p = 0 gives log 0 = -inf → a no-op.
        cut = jnp.max(lg, axis=-1, keepdims=True) + jnp.log(
            jnp.minimum(min_p, 1.0)
        )
        lg = jnp.where(lg < cut, -jnp.inf, lg)
    if top_k is not None or top_p is not None:
        sorted_lg = jnp.sort(lg, axis=-1)[..., ::-1]  # descending
    if top_k is not None:
        top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), lg.shape[:-1] + (1,))
        kth = jnp.take_along_axis(
            sorted_lg, jnp.clip(top_k - 1, 0, lg.shape[-1] - 1), axis=-1
        )
        kth = jnp.where(top_k > 0, kth, -jnp.inf)
        lg = jnp.where(lg < kth, -jnp.inf, lg)
        sorted_lg = jnp.where(sorted_lg < kth, -jnp.inf, sorted_lg)
    if top_p is not None:
        probs = jax.nn.softmax(sorted_lg, axis=-1)
        # exclusive prefix mass: token i survives while the mass BEFORE it
        # is still < top_p; the top token is forced alive so non-positive
        # top_p degrades to argmax instead of an empty distribution
        before = jnp.cumsum(probs, axis=-1) - probs
        keep = (before < top_p).at[..., 0].set(True)
        # threshold = smallest kept logit; everything below is cut
        kept_min = jnp.min(jnp.where(keep, sorted_lg, jnp.inf), axis=-1, keepdims=True)
        lg = jnp.where(lg < kept_min, -jnp.inf, lg)
    return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)


def apply_repetition_penalty(logits, seen, penalty):
    """HF-semantics repetition penalty: logits of already-generated tokens
    (``seen [B, V]`` bool) divide by ``penalty`` when positive, multiply
    when negative — pushing repeats down regardless of sign.  ``penalty``
    may be a traced scalar; 1.0 is a no-op."""
    scaled = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(seen, scaled, logits)


# ---------------------------------------------------------------------------
# Paged KV cache (continuous-batching serving path)
# ---------------------------------------------------------------------------
#
# A dense cache is one [L, B, max_cache, KH, D] block per K/V: every row
# pays for the worst case.  The paged layout stores KV in fixed-size PAGES
# of a preallocated pool ([L, P, page, KH, D]) with a per-slot block table
# mapping logical positions onto pages, so cache memory scales with LIVE
# tokens (the Ragged Paged Attention layout, PAPERS.md).  Page 0 is the
# reserved null page: unallocated block-table entries point at it, padding
# writes land in it, and no slot's attention mask ever reaches into it.
# The continuous-batching scheduler (pathway_tpu/serving/generation.py)
# owns the host-side PageAllocator and drives the two device programs
# below; all compiled shapes are static (slot count fixed, prefill shapes
# few, block-table width bucketed), so churning request mixes replay warm
# programs — `jax.cache.miss == 0` in steady state.


def ring_pages(window: int, page_size: int) -> int:
    """Pages of a window layer's ring, a slot: the window and one page
    more, so that a page can be overwritten while the window still reads
    every token it reaches."""
    return -(-window // page_size) + 1


def uses_ring(cfg: DecoderConfig, kind: LayerKind) -> bool:
    """A window layer of a model of runs keeps a bounded ring a slot; a
    model of one kind keeps every token in its block table, windowed or
    not, as it did."""
    return cfg.runs is not None and kind.window is not None


def init_kv_pool(cfg: DecoderConfig, num_pages: int, page_size: int, slots: int = 0):
    """Preallocate the paged KV pool: ``(k_pool, v_pool)``, ``[L,
    num_pages, page_size, KH, D]`` (values ``Dv`` wide).  Page 0 is the
    null page.  A model of runs gets a tuple of pools, one a run: a global
    run's like the above, a window run's ``[L, 1 + slots * ring, ...]``
    with slot ``i``'s ring at pages ``1 + i * ring`` onwards, fixed.

    A run keeps whatever state its layers carry from one program to the
    next in its pair of the two tuples, and the programs hand every pair
    on alike.  A Mamba-2 run's pair is the recurrent state, a slot: the
    convolution's tail ``[L, slots, K - 1, columns]`` (the last columns
    before the next token) and the scan's state ``[L, slots, heads, head
    width, state size]`` in float32, of fixed size whatever the sequence's
    length.  A run of FFN layers carries nothing: an empty pair."""
    def pool(n, kind):
        if kind.part == "mamba":
            return (
                jnp.zeros((n, slots, cfg.ssm_conv - 1, cfg.ssm_conv_width), cfg.dtype),
                jnp.zeros(
                    (n, slots, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                    jnp.float32,
                ),
            )
        if not kind.attends:
            return jnp.zeros((n, 0), cfg.dtype), jnp.zeros((n, 0), cfg.dtype)
        pages = num_pages
        if uses_ring(cfg, kind):
            pages = 1 + slots * ring_pages(kind.window, page_size)
        lead = (n, pages, page_size, kind.kv_heads)
        return (
            jnp.zeros(lead + (cfg.head_dim,), cfg.dtype),
            jnp.zeros(lead + (cfg.v_dim,), cfg.dtype),
        )

    if cfg.runs is None:
        return pool(cfg.layers, cfg.kind)
    return tuple(zip(*(pool(n, kind) for kind, n in cfg.runs)))


def _page_size(cfg: DecoderConfig, k_pool) -> int:
    """Tokens a page holds, read off the first run that keeps pages."""
    for kind, kp in run_stacks(cfg, k_pool):
        if kind.attends:
            return kp.shape[2]
    raise ValueError("no layer of this model keeps a paged cache")


def ssm_state_bytes_per_slot(cfg: DecoderConfig) -> int:
    """Bytes of recurrent state one slot holds across the Mamba-2 layers:
    the convolution's tail and the scan's float32 state."""
    tail = (cfg.ssm_conv - 1) * cfg.ssm_conv_width * jnp.dtype(cfg.dtype).itemsize
    state = cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
    return cfg.ssm_layers * (tail + state)


class PageExhaustedError(RuntimeError):
    """The pool has no free page — admission control must keep the sum of
    reserved pages within the pool, so hitting this mid-generation is a
    scheduler bug, not an overload condition."""


class PageAllocator:
    """Host-side free-list allocator over the page pool.

    Tracks which pool pages are free (page 0 is reserved as the null
    page), per-slot block tables, and live/peak KV byte accounting — the
    numbers behind ``generate.pages.*`` / ``generate.kv.bytes.*`` and the
    peak-below-dense acceptance pin."""

    def __init__(self, num_pages: int, page_size: int, bytes_per_token: int):
        if num_pages < 2:
            raise ValueError("pool needs >= 2 pages (page 0 is the null page)")
        self.num_pages = num_pages
        self.page_size = page_size
        self.bytes_per_token = bytes_per_token  # both K and V, all layers
        self._free: list[int] = list(range(num_pages - 1, 0, -1))
        self.reserved = 0  # admission-reserved pages (not yet allocated)
        self.peak_pages = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def pages_for(self, tokens: int) -> int:
        return -(-max(tokens, 1) // self.page_size)

    def can_reserve(self, pages: int) -> bool:
        return self.reserved + pages <= len(self._free)

    def reserve(self, pages: int) -> None:
        """Set aside capacity at admission time: the worst case of a
        request (prompt + max_new_tokens) is reserved up front so a
        mid-generation allocation can never fail (bounded queue instead
        of OOM — the admission contract)."""
        if not self.can_reserve(pages):
            raise PageExhaustedError(
                f"cannot reserve {pages} page(s): {len(self._free)} free, "
                f"{self.reserved} already reserved"
            )
        self.reserved += pages

    def alloc(self, *, reserved: bool = True) -> int:
        """Take one free page (consuming one unit of reservation when
        ``reserved``); pages are handed out lazily as tokens actually
        arrive, so live bytes track live tokens, not reservations."""
        if not self._free:
            raise PageExhaustedError("page pool exhausted")
        page = self._free.pop()
        if reserved:
            self.reserved -= 1
        self.peak_pages = max(self.peak_pages, self.used_pages)
        return page

    def release(self, pages: list[int], *, unreserve: int = 0) -> None:
        """Return a slot's pages (and any unused reservation) to the pool."""
        for p in pages:
            self._free.append(p)
        self.reserved -= unreserve

    @property
    def live_bytes(self) -> int:
        return self.used_pages * self.page_size * self.bytes_per_token

    @property
    def peak_bytes(self) -> int:
        return self.peak_pages * self.page_size * self.bytes_per_token


def kv_bytes_per_token(cfg: DecoderConfig, *, growing_only: bool = False) -> int:
    """K + V bytes one token occupies across all layers — the paged-vs-
    dense accounting unit.  ``growing_only`` leaves out the layers whose
    cache is a ring (:func:`uses_ring`): what a page of the allocator's
    pool holds, a token."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return sum(
        n * kind.kv_heads * (cfg.head_dim + cfg.v_dim) * itemsize
        for kind, n in cfg.layer_runs
        if not (growing_only and uses_ring(cfg, kind))
    )


def kv_ring_bytes_per_slot(cfg: DecoderConfig, page_size: int) -> int:
    """K + V bytes of one slot's rings across the window layers."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return sum(
        n * ring_pages(kind.window, page_size) * page_size
        * kind.kv_heads * (cfg.head_dim + cfg.v_dim) * itemsize
        for kind, n in cfg.layer_runs if uses_ring(cfg, kind)
    )


def _mamba_mixer(lp, h, tails, states, cfg: DecoderConfig, *, index, rows,
                 valid, fresh):
    """A Mamba-2 mixer over the normed rows ``h [S, T, H]``, continued
    from and handed back into the run's recurrent state: ``tails [n,
    slots, K-1, columns]`` and ``states [n, slots, heads, P, N]`` (float32)
    at layer ``index``, row ``r`` of the program being slot ``rows[r]``
    (``rows`` None: slot ``r``).  ``valid [S, T]`` marks the tokens (a
    row's first), ``fresh [S]`` the rows whose sequence starts with this
    program: they start from a state of noughts whatever the slot held.
    A padding token moves nothing (its time step is nought), a row
    without a token is written back as it was read.  One token a row (the
    decode step) takes the recurrence as written, more the chunked scan.  Returns ``(out [S, T, H], tails, states,
    tokens the scan advanced a state by)``."""
    from pathway_tpu.ops import ssm

    S, T, _H = h.shape
    NH, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    inner, columns = cfg.ssm_inner, cfg.ssm_conv_width
    at = (index,) if rows is None else (index, rows)
    lens = jnp.sum(valid, axis=1, dtype=jnp.int32)
    tail_was, state_was = tails[at], states[at]
    tail = jnp.where(fresh[:, None, None], 0, tail_was)
    state = jnp.where(fresh[:, None, None, None], 0, state_was)
    with jax.named_scope("ssm.in_proj"):
        # the barrier keeps the split into z, x B C and dt a view of the
        # product's output (``_qkv`` says why)
        proj = lax.optimization_barrier(_mm(h, lp["in_proj"]))
        z, xbc = proj[..., :inner], proj[..., inner:inner + columns]
        dt = proj[..., inner + columns:]
    with jax.named_scope("ssm.conv"):
        xbc, tail = ssm.causal_conv(xbc, tail, lp["conv_w"], lp["conv_b"], lens)
    x = xbc[..., :inner].reshape(S, T, NH, P)
    B = xbc[..., inner:inner + G * N].reshape(S, T, G, N)
    C = xbc[..., inner + G * N:].reshape(S, T, G, N)
    with jax.named_scope("ssm.scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
        dt = jnp.where(valid[..., None], dt, 0.0)
        A = -jnp.exp(lp["A_log"])
        if T == 1:
            y, state = ssm.ssm_step(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], state)
            y = y[:, None]
        else:
            y, state = ssm.ssd_chunked(x, dt, A, B, C, state, cfg.ssm_chunk)
        y = y + lp["D"][:, None] * x.astype(jnp.float32)
        advanced = jnp.sum(dt[..., 0] > 0, dtype=jnp.int32)
    with jax.named_scope("ssm.gate_norm"):
        # gate, then RMS norm over each group of heads with its weight
        y = y.reshape(S, T, G, inner // G) * jax.nn.silu(
            z.astype(jnp.float32)
        ).reshape(S, T, G, inner // G)
        y = y * lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg.norm_eps)
        y = y.reshape(S, T, inner).astype(h.dtype) * lp["gate_norm"]
    with jax.named_scope("ssm.out_proj"):
        out = _mm(y, lp["out_proj"])
    with jax.named_scope("ssm.state.write"):
        live = lens > 0
        tails = tails.at[at].set(jnp.where(live[:, None, None], tail, tail_was))
        states = states.at[at].set(
            jnp.where(live[:, None, None, None], state, state_was)
        )
    return out, tails, states, advanced


def _paged_trunk(tree, k_pool, v_pool, x, cfg: DecoderConfig, *, tables, rings,
                 positions, write_positions, mask, valid, starts, lens,
                 state_rows=None, fresh=None):
    """The layers of a paged program over the rows ``x [S, T, H]``: every
    run scanned by the one layer body, which its kind parameterises: a
    mixer (attention or Mamba-2) where the kind has one, then an FFN where
    it has one, each added to the residual stream behind its norm.

    A layer whose cache is the slot's block table (``tables [S, G]``)
    scatters its K/V at ``write_positions`` and attends through the gather
    under ``mask [S, T, G*page]``.  A layer whose cache is the slot's ring
    (``rings [S, R]``; :func:`uses_ring`) attends to what the ring held
    before this program (``starts [S]`` tokens) and to the program's own
    rows, by position, then writes the last of its ``lens [S]`` rows that
    the ring keeps.  A Mamba-2 layer continues the recurrent state of the
    slots ``state_rows [S]`` (:func:`_mamba_mixer`; ``fresh [S]`` marks the
    rows that start from noughts).  ``valid [S, T]`` marks the rows that
    hold a token.
    A run's pools are the scan's carry, not its ``xs`` and ``ys``: a layer
    scatters into and gathers from the stack at its index, so no layer's
    pool is sliced out of the stack or written back into one, and with the
    pools donated (``serving/generation.py``) the caller's buffers are
    updated in place.
    Returns ``(x, k_pool, v_pool, stats)``; ``stats`` is the routed
    layers' summed ``[pairs, experts_hit, tile_rows]`` (noughts without
    routed layers); for a model with Mamba-2 layers ``[pairs,
    experts_hit, tokens, tile_rows]``, ``tokens`` those the scan advanced
    a state by (of one layer: they all meet the same).
    """
    from pathway_tpu.ops import attention as attention_ops

    def body(kind, experts):
        ring = uses_ring(cfg, kind)
        scope = "attn.paged" if cfg.runs is None else (
            "attn.window" if ring else "attn.global"
        )
        if ring:
            # by position, and the same for every layer of the run
            cap = rings.shape[1] * _page_size(cfg, k_pool)
            ring_sees = attention_ops.ring_mask(
                starts, positions, valid, kind.window, cap
            )
            ring_at = attention_ops.ring_write_positions(positions, valid, lens, cap)

        def layer(carry, lp):
            x, kp, vp = carry
            lp, index = lp
            counts = None
            if experts:
                lp = {**lp, **experts, "moe_layer": index}
            if kind.part == "mamba":
                mixed, kp, vp, counts = _mamba_mixer(
                    lp, _rms(x, lp["ln0"], cfg.norm_eps), kp, vp, cfg, index=index,
                    rows=state_rows, valid=valid, fresh=fresh,
                )
                x = x + mixed
            if kind.attends:
                with jax.named_scope("attn.qkv"):
                    q, k, v, gate = _qkv(lp, x, positions, cfg, kind)
                if ring:
                    with jax.named_scope(scope):
                        ctx = attention_ops.ring_gqa_attention(
                            q, k, v, kp, vp, rings, ring_sees, lp.get("sink"), index
                        )
                    # read, then write: the rows that enter the ring replace
                    # entries the attention above still reads
                    with jax.named_scope("kv.write"):
                        kp = attention_ops.scatter_kv_pages(kp, rings, ring_at, k, index)
                        vp = attention_ops.scatter_kv_pages(vp, rings, ring_at, v, index)
                else:
                    with jax.named_scope("kv.write"):
                        kp = attention_ops.scatter_kv_pages(
                            kp, tables, write_positions, k, index
                        )
                        vp = attention_ops.scatter_kv_pages(
                            vp, tables, write_positions, v, index
                        )
                    with jax.named_scope(scope):
                        ctx = attention_ops.paged_gqa_attention(
                            q, kp, vp, tables, mask, lp.get("sink"), index
                        )
                ctx = _gate_heads(ctx, gate)
                with jax.named_scope("attn.out"):
                    x = x + _mm(ctx, lp["wo"])
            if kind.has_ffn:
                # a block's FFN has a norm of its own, a lone FFN the layer's
                h = _rms(x, lp["ln1" if kind.part == "block" else "ln0"], cfg.norm_eps)
                mlp, stats = _ffn(lp, h, cfg, kind, serving=True, valid=valid)
                x = x + mlp
                if kind.routed:
                    counts = stats
            return (x, kp, vp), counts

        return layer

    k_out, v_out = [], []
    stats, advanced = jnp.zeros((3,), jnp.int32), jnp.int32(0)
    for kind, layers, kp, vp in run_stacks(cfg, tree["layers"], k_pool, v_pool):
        experts = {}
        if kind.routed:
            # the run's expert stacks stay whole and the body is told its
            # layer's index in them (``moe_serve`` says why)
            experts = {
                name: layers[name] for name in ("wg", "wu", "wd") if name in layers
            }
            layers = {k: v for k, v in layers.items() if k not in experts}
        index = jnp.arange(kp.shape[0], dtype=jnp.int32)
        (x, kp, vp), counts = lax.scan(
            body(kind, experts), (x, kp, vp), (layers, index)
        )
        k_out.append(kp)
        v_out.append(vp)
        if kind.routed:
            stats = stats + counts.sum(0)
        elif kind.part == "mamba":
            advanced = advanced + counts.sum()
    if cfg.ssm_layers:
        # every Mamba-2 layer meets the same tokens: one layer's count,
        # before the tile rows, which stay last
        stats = jnp.concatenate(
            [stats[:2], (advanced // cfg.ssm_layers)[None], stats[2:]]
        )
    if cfg.runs is None:
        return x, k_out[0], v_out[0], stats
    return x, tuple(k_out), tuple(v_out), stats


def _split_tables(cfg: DecoderConfig, block_tables):
    """``(tables, rings, state_rows)``: a model of runs is handed its block
    tables and rings, and a prefill program of one with Mamba-2 layers the
    slot whose recurrent state each row continues as well (None: row ``r``
    is slot ``r``); a model of one kind its block tables alone."""
    if cfg.runs is None:
        return block_tables, None, None
    tables, rings, *state_rows = block_tables
    return tables, rings, (state_rows[0] if state_rows else None)


def paged_decode_step(tree, k_pool, v_pool, block_tables, seq_lens, token,
                      cfg: DecoderConfig, *, active=None, with_stats=False):
    """One generation step over paged KV: ``token`` ``[S]`` is written at
    each slot's next position (``seq_lens`` ``[S]``), attention gathers
    the slot's pages.  Returns ``(logits [S, V], k_pool, v_pool)``, and
    with ``with_stats`` the routed layers' ``[pairs, experts_hit,
    tile_rows]`` after them.

    The gathered context is a dense cache rearranged through the block
    table, and masked positions contribute exactly zero, so the step's
    logits are the full forward's at that position (pinned by tests
    against ``causal_lm_logits``).  Inactive slots (block table all null)
    write into and gather from the null page — finite garbage, masked
    everywhere, freeing the scheduler from shipping an active-mask into
    the program.  A model of runs (``cfg.runs``) takes ``block_tables`` as
    ``(tables, rings)`` and the mask ``active [S]`` of the slots that
    decode: a slot's ring is its own whether it decodes or not, so a slot
    that does not must not write, and the routed layers count and compute
    the active rows only.
    """
    tables, rings, _rows = _split_tables(cfg, block_tables)
    S = token.shape[0]
    C = tables.shape[1] * _page_size(cfg, k_pool)
    x = tree["embed"][token][:, None, :]  # [S, 1, H]
    positions = seq_lens[:, None]  # [S, 1]
    idx = jnp.arange(C)[None, None, :]
    mask = idx <= seq_lens[:, None, None]  # [S, 1, C]
    if cfg.sliding_window is not None:
        mask = mask & _sw_mask(seq_lens[:, None, None], idx, cfg.sliding_window)
    if active is None and rings is not None:
        active = jnp.ones((S,), bool)
    valid = None if active is None else active[:, None]
    write_positions = positions
    if active is not None:
        write_positions = jnp.where(valid, positions, jnp.int32(2**30))
    x, k_pool, v_pool, stats = _paged_trunk(
        tree, k_pool, v_pool, x, cfg, tables=tables, rings=rings,
        positions=positions, write_positions=write_positions, mask=mask,
        valid=valid, starts=seq_lens, lens=jnp.ones((S,), jnp.int32),
        # row r of a step is slot r, and no sequence starts with a step
        fresh=jnp.zeros((S,), bool),
    )
    with jax.named_scope("lm_head"):
        x = _rms(x, tree["final_norm"], cfg.norm_eps)
        logits = _mm(x[:, 0, :], tree["lm_head"]).astype(jnp.float32)
    if with_stats:
        return logits, k_pool, v_pool, stats
    return logits, k_pool, v_pool


def paged_prefill_chunk(tree, k_pool, v_pool, block_tables, chunk_ids,
                        chunk_lens, start, cfg: DecoderConfig, *, with_stats=False):
    """Prefill ONE chunk of each slot's prompt against paged KV.

    ``chunk_ids`` ``[S, T]`` holds the next ``chunk_lens[s]`` prompt
    tokens of each slot (ragged; 0-padded), starting at logical position
    ``start[s]``.  The chunk's K/V is scattered into the slot's pages,
    then each chunk query attends causally over the slot's whole context
    so far (earlier chunks + this one) — chunked prefill is exactly full
    prefill split along the query axis.  Returns ``(logits [S, V]`` at
    each slot's LAST chunk token``, k_pool, v_pool)``; rows with
    ``chunk_lens == 0`` produce garbage logits the scheduler ignores.
    ``with_stats`` adds the routed layers' ``[pairs, experts_hit,
    tile_rows]``.

    ``S`` and ``T`` are compile-time sizes and the scheduler chooses them
    from a few (``serving/generation.py::prefill_shape``): one row as
    wide as the prompt that waits, or every slot at a narrow width.  A
    prompt longer than the widest runs several chunks instead of one
    variable program, which is what lets the scheduler interleave prefill
    with decode without a long decode-tick stall (and without recompiles).
    A row may be wider than a window layer's ring: its queries attend
    inside the row and to what the ring held, and only the row's last
    tokens, those the ring keeps, are written (``_paged_trunk``).
    """
    tables, rings, state_rows = _split_tables(cfg, block_tables)
    T = chunk_ids.shape[1]
    C = tables.shape[1] * _page_size(cfg, k_pool)
    x = tree["embed"][chunk_ids]  # [S, T, H]
    positions = start[:, None] + jnp.arange(T)[None, :]  # [S, T]
    valid_q = jnp.arange(T)[None, :] < chunk_lens[:, None]  # [S, T]
    # padding queries (t >= chunk_lens, including whole rows with
    # chunk_lens == 0: slots that are DECODING while others prefill) must
    # scatter to the null page, never into a slot's live pages — at
    # start == 0 they would overwrite already-cached real tokens
    write_positions = jnp.where(valid_q, positions, jnp.int32(2**30))
    idx = jnp.arange(C)[None, None, :]
    mask = (idx <= positions[:, :, None]) & valid_q[:, :, None]
    if cfg.sliding_window is not None:
        mask = mask & _sw_mask(positions[:, :, None], idx, cfg.sliding_window)
    x, k_pool, v_pool, stats = _paged_trunk(
        tree, k_pool, v_pool, x, cfg, tables=tables, rings=rings,
        positions=positions, write_positions=write_positions, mask=mask,
        valid=valid_q, starts=start, lens=chunk_lens, state_rows=state_rows,
        # a prompt's first chunk: its recurrent state starts from noughts
        fresh=(start == 0) & (chunk_lens > 0),
    )
    with jax.named_scope("lm_head"):
        x = _rms(x, tree["final_norm"], cfg.norm_eps)
        last = jnp.take_along_axis(
            x,
            jnp.maximum(chunk_lens - 1, 0)[:, None, None].repeat(cfg.hidden, 2),
            axis=1,
        )[:, 0, :]
        logits = _mm(last, tree["lm_head"]).astype(jnp.float32)
    if with_stats:
        return logits, k_pool, v_pool, stats
    return logits, k_pool, v_pool


# ---------------------------------------------------------------------------
# Checkpoint mapping
# ---------------------------------------------------------------------------


def load_hf_decoder_weights(model_name: str, cfg: DecoderConfig):
    """Map a locally cached llama/mistral-family ``transformers`` checkpoint
    onto the stacked tree; returns ``None`` when absent (zero-egress)."""
    import os

    if not may_have_local_checkpoint(model_name):
        return None
    os.environ.setdefault("HF_HUB_OFFLINE", "1")
    try:
        from transformers import AutoModelForCausalLM

        hf = AutoModelForCausalLM.from_pretrained(model_name, local_files_only=True)
    except Exception:
        return None
    sd = {k: v.detach().cpu().numpy() for k, v in hf.state_dict().items()}
    if "model.layers.0.self_attn.q_proj.weight" not in sd:
        return None

    def stack(fmt, transpose=True):
        mats = [sd[fmt.format(i)] for i in range(cfg.layers)]
        arr = np.stack([m.T if transpose else m for m in mats])
        return jnp.asarray(arr, cfg.dtype)

    layers = {
        "ln0": stack("model.layers.{}.input_layernorm.weight", transpose=False),
        "ln1": stack(
            "model.layers.{}.post_attention_layernorm.weight", transpose=False
        ),
        "wq": stack("model.layers.{}.self_attn.q_proj.weight"),
        "wk": stack("model.layers.{}.self_attn.k_proj.weight"),
        "wv": stack("model.layers.{}.self_attn.v_proj.weight"),
        "wo": stack("model.layers.{}.self_attn.o_proj.weight"),
    }
    if cfg.experts and "model.layers.0.block_sparse_moe.gate.weight" in sd:
        # Mixtral block-sparse MoE: w1→wg (gate), w3→wu (up), w2→wd (down);
        # torch Linear weights are [out, in], transposed into matmul layout
        def stack_experts(wname, transpose=True):
            per_layer = []
            for i in range(cfg.layers):
                mats = [
                    sd[f"model.layers.{i}.block_sparse_moe.experts.{e}.{wname}.weight"]
                    for e in range(cfg.experts)
                ]
                per_layer.append(np.stack([m.T if transpose else m for m in mats]))
            return jnp.asarray(np.stack(per_layer), cfg.dtype)

        layers.update(
            {
                "moe_router": jnp.asarray(
                    np.stack(
                        [
                            sd[f"model.layers.{i}.block_sparse_moe.gate.weight"].T
                            for i in range(cfg.layers)
                        ]
                    ),
                    jnp.float32,
                ),
                "wg": stack_experts("w1"),
                "wu": stack_experts("w3"),
                "wd": stack_experts("w2"),
            }
        )
    elif cfg.experts:
        return None  # MoE config but a dense checkpoint on disk
    else:
        layers.update(
            {
                "wg": stack("model.layers.{}.mlp.gate_proj.weight"),
                "wu": stack("model.layers.{}.mlp.up_proj.weight"),
                "wd": stack("model.layers.{}.mlp.down_proj.weight"),
            }
        )
    lm_head = sd.get("lm_head.weight", sd["model.embed_tokens.weight"])
    return {
        "embed": jnp.asarray(sd["model.embed_tokens.weight"], cfg.dtype),
        "final_norm": jnp.asarray(sd["model.norm.weight"], cfg.dtype),
        "lm_head": jnp.asarray(lm_head.T, cfg.dtype),
        "layers": layers,
    }


# ---------------------------------------------------------------------------
# Serving wrapper
# ---------------------------------------------------------------------------


class DecoderLM:
    """A local decoder LLM as the generation scheduler takes it: config,
    weights (float, or weight-only int8), tokenizer and cache budget.

    It generates nothing itself: ``serving/generation.py``'s
    ``GenerationScheduler(lm)`` (or ``shared_scheduler``, which ``JaxChat``
    uses) drives the paged programs below over these weights.
    """

    def __init__(
        self,
        model_name: str = "mistral-7b-instruct",
        seed: int = 0,
        max_cache: int = 1024,
        eos_id: int | None = 2,
        quantize: str | None = None,
    ):
        if quantize not in (None, "int8"):
            raise ValueError(f"quantize must be None or 'int8', got {quantize!r}")
        ensure_compile_cache()
        self.config = decoder_config_for(model_name)
        self.model_name = model_name
        self.max_cache = min(max_cache, self.config.max_len)
        self.eos_id = eos_id
        self.tokenizer = load_tokenizer(
            model_name, self.config.vocab_size, self.config.max_len
        )
        tree = load_hf_decoder_weights(model_name, self.config)
        self.pretrained = tree is not None
        self.params = tree if tree is not None else init_decoder_params(
            self.config, seed
        )
        self.quantized = quantize == "int8"
        if self.quantized:
            # weight-only int8: halves the HBM bytes every decode step
            # sweeps (decode is bandwidth-bound, so ~2x tokens/s headroom)
            self.params = quantize_decoder_tree(self.params)

    def n_params(self) -> int:
        return sum(
            int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(self.params)
        )

    def _encode_prompt(self, prompt: str) -> list[int]:
        """Tokenize at the MODEL limit, not the cache limit: tokenizers
        truncate from the head, but chat serving must keep the prompt's
        TAIL — the scheduler does that tail-keeping against the cache
        budget itself (``GenerationScheduler.submit_request``)."""
        return self.tokenizer.encode(prompt, max_length=self.config.max_len)


@functools.lru_cache(maxsize=4)
def shared_decoder(
    model_name: str = "mistral-7b-instruct",
    max_cache: int = 1024,
    quantize: str | None = None,
) -> DecoderLM:
    return DecoderLM(model_name, max_cache=max_cache, quantize=quantize)
