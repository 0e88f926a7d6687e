"""The Laguna-S-2.1 configuration's own pieces on inputs with known
answers: its cost functions against hand counts from the published sizes,
its file against the catalog's rules and the program's tiny preset, its
reference's blocked attention against the plain one, its check on the tiny
block (sound and with the int8 control in the program's place), its
builder's prompt lengths, its metric files.
"""

from __future__ import annotations

import math
import pathlib
import random
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench import cost, manifest, text  # noqa: E402
from chipbench.cost import laguna_decoder as cost_laguna  # noqa: E402

NAME, CELL = "laguna-s-2.1-bge-rag", "rag-answer-laguna-long"
BENCH = manifest.benchmark()
CONFIG = manifest.config(BENCH, NAME)
# the published 48 layers: a full layer every fourth, the first dense
PUBLISHED = {
    **CONFIG, "num_hidden_layers": 48, "num_experts": 256, "vocab_size": 100352,
    "layer_types": ["full_attention" if l % 4 == 0 else "sliding_attention" for l in range(48)],
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "num_attention_heads_per_layer": [48 if l % 4 == 0 else 72 for l in range(48)],
    "gating_types": ["per_head"] * 48,
}
PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
M = 1e6


def test_cost_functions_count_the_published_sizes():
    full, window = cost_laguna.layers(CONFIG)[0], cost_laguna.layers(CONFIG)[1]
    assert [(l["heads"], l["window"], l["routed"]) for l in cost_laguna.layers(CONFIG)] == [
        (48, None, False), (72, 512, True), (72, 512, True), (72, 512, True), (48, None, True),
    ]
    # full: q 3,072 x 6,144 + k, v 2 x 3,072 x 1,024 + o 6,144 x 3,072 + gate 3,072 x 48
    assert cost_laguna.attention_params(CONFIG, full) == 3072 * (6144 + 2048) + 6144 * 3072 + 3072 * 48
    assert 44.18 < cost_laguna.attention_params(CONFIG, full) / M < 44.19
    # window: q and o 3,072 x 9,216 each, the same k and v, gate 3,072 x 72
    assert 63.13 < cost_laguna.attention_params(CONFIG, window) / M < 63.14
    assert cost_laguna.expert_params(CONFIG) == cost_laguna.shared_params(CONFIG) == 3 * 3072 * 1024
    assert round(cost_laguna.expert_params(CONFIG) / M, 3) == 9.437
    assert cost_laguna.router_params(CONFIG) == 3072 * 256 and cost_laguna.held_share(CONFIG) == 0.5
    # the chip's share: 5.57 G parameters = 11.14 GB; whole: 117.6 G ("118B")
    assert round(cost_laguna.total_params(CONFIG) / 1e9, 2) == 5.57
    assert round(cost_laguna.total_params(CONFIG) * 2 / 1e9, 2) == 11.14
    assert round(cost_laguna.total_params(PUBLISHED) / 1e9, 1) == 117.6
    # KV: full layers every token, window layers 512 at most; 8 heads x 128 x 2 bytes, K and V
    assert cost_laguna.kv_bytes(CONFIG, 2900) == (2 * 2900 + 3 * 512) * 2 * 8 * 128 * 2


def test_decode_step_and_prefill_chunk_at_the_cells_sizes():
    """A decode step at one row of ~2,900 tokens meeting ~5 held experts a
    routed layer reads ~1.55 GB: ~1.9 ms least, bandwidth-bound; a prefill
    chunk of 512 that meets all 512 held experts reads ~9.7 GB of them."""
    dense = cost_laguna.dense_params(CONFIG)
    attention = sum(cost_laguna.attention_params(CONFIG, l) for l in cost_laguna.layers(CONFIG))
    assert 555 < attention * 2 / 1e6 < 556  # whole here: 2 x its share
    step = cost_laguna.decode_step(CONFIG, rows=1, context=2900, experts_hit=20)
    assert 1.5e9 < step["bytes"] < 1.6e9
    least, bound = cost.least_seconds(step, PEAK)
    assert bound == "bandwidth" and 1.8 < least * 1e3 < 2.0
    assert step["bytes"] == (dense + 20 * cost_laguna.expert_params(CONFIG)) * 2 + cost_laguna.kv_bytes(CONFIG, 2900)
    chunk = cost_laguna.prefill_chunk(CONFIG, rows=1, chunk=512, context=1024, experts_hit=4 * 128)
    assert round(4 * 128 * cost_laguna.expert_params(CONFIG) * 2 / 1e9, 1) == 9.7
    least, bound = cost.least_seconds(chunk, PEAK)
    assert bound == "bandwidth" and 12 < least * 1e3 < 14
    work = cost_laguna.tokens(CONFIG, tokens=10, pairs=7)
    assert work["flops"] == 2.0 * dense * 10 + 2.0 * cost_laguna.expert_params(CONFIG) * 7


def test_file_is_the_catalog_row_but_for_what_it_lists():
    spec = CONFIG["chipbench"]
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["source"] in spec["source"][0] and len(entry["source"]) <= 200
    assert sorted(spec["reduced"]) == sorted(entry["reduced"]) == sorted([
        "num_hidden_layers", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
        "gating_types", "num_experts", "vocab_size",
    ])
    # no width is cut: every width of the row stands as published
    for key, value in {
        "hidden_size": 3072, "intermediate_size": 12288, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128, "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "num_experts_per_tok": 10,
        "moe_routed_scaling_factor": 2.5, "sliding_window": 512, "rms_norm_eps": 1e-06,
        "max_position_embeddings": 1048576, "gating": "per-head", "norm_topk_prob": True,
        "moe_router_logit_softcapping": 0, "moe_apply_router_weight_on_input": False,
    }.items():
        assert CONFIG[key] == value and key not in spec["reduced"], key
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer", "gating_types"):
        assert CONFIG[key] == PUBLISHED[key][:5], key
    full, sliding = CONFIG["rope_parameters"]["full_attention"], CONFIG["rope_parameters"]["sliding_attention"]
    assert (full["rope_type"], full["rope_theta"], full["factor"], full["partial_rotary_factor"]) == (
        "yarn", 500000, 128, 0.5)
    assert (full["original_max_position_embeddings"], full["beta_fast"], full["beta_slow"]) == (8192, 32, 1)
    assert (sliding["rope_type"], sliding["rope_theta"], sliding["partial_rotary_factor"]) == ("default", 10000, 1)
    assert CONFIG["num_experts_published"] == 256 and CONFIG["expert_shards"] == 2
    assert CONFIG["num_experts"] * 2 == 256 and CONFIG["vocab_size"] * 2 == 100352
    assert spec["deployment"]["chips_per_layer"] == 2 and spec["check"]["sample"] == 48
    assert spec["serving"]["max_cache"] == 4096 and spec["serving"]["search_topk"] == 6
    assert spec["corpus"] == {"documents": 2048, "words": [380, 460]}
    for key in ("gate", "shared_expert", "window", "rotary", "routing", "weights", "tokenizer"):
        assert key in spec["assumed"], key
    cell = manifest.cell_entry(BENCH, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "answer-laguna-long"
    mix = manifest.traffic_mix(cell["traffic"])
    assert mix["arrivals"]["draw_seed"] == 25 and mix["client"] == {"workers": 64, "timeout_s": 120.0}
    assert mix["trace"] == {"start_s": 2.0, "stop_before_close_s": 19.0}


def test_tiny_block_is_the_programs_preset():
    from pathway_tpu.models import decoder as dec

    tiny = manifest.config(BENCH, NAME, tiny=True)["chipbench"]
    assert tiny["decoder_model"] == "pw-tiny-laguna-decoder"
    assert tiny["decoder"] == dec.TINY_LAGUNA_HF
    assert dec.decoder_config_from_hf(tiny["decoder"]) == dec.PRESETS["pw-tiny-laguna-decoder"]
    # and the file as run is read by the program as the share it states
    cfg = dec.decoder_config_from_hf({k: v for k, v in CONFIG.items() if k != "chipbench"})
    assert (cfg.experts, cfg.experts_published, cfg.experts_first) == (128, 256, 0)
    assert [(k.heads, k.window, k.routed, n) for k, n in cfg.runs] == [
        (48, None, False, 1), (72, 512, True, 3), (48, None, True, 1),
    ]
    assert [k.rotary_dim for k, _n in cfg.runs] == [64, 128, 64]
    assert cfg.runs[0][0].yarn == dec.YaRN(128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    assert (cfg.experts_route_scale, cfg.experts_shared, cfg.vocab_size) == (2.5, 1024, 50176)


@pytest.mark.parametrize("window", [None, 24, 100])
def test_reference_attention_in_blocks_is_the_plain_one(window, monkeypatch):
    """The reference's attention a block of 16 queries at a time (a window
    layer's block against the keys its window reaches) equals the same
    softmax over the whole masked sequence."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import laguna_decoder as ref

    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    rng = np.random.default_rng(3)
    B, S, KH, G, D = 2, 64, 2, 3, 8
    q = rng.normal(size=(B, S, KH, G, D)).astype(np.float32)
    k, v = (rng.normal(size=(B, S, KH, D)).astype(np.float32) for _ in range(2))
    lengths = np.asarray([64, 37])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                       jnp.asarray(lengths), window))
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    real = np.arange(S)[None, :] < lengths[:, None]  # a padding query's row is not read
    seen = (j <= i) & ((j > i - window) if window else True)
    mask = seen[None] & (j[None] < lengths[:, None, None])
    scores = np.einsum("bskgd,bckd->bkgsc", q, k) / np.sqrt(D)
    scores = np.where(mask[:, None, None], scores, -1e9)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.einsum("bkgsc,bckd->bskgd", probs, v)
    np.testing.assert_allclose(got[real], want[real], rtol=1e-5, atol=1e-5)


def _tiny_check(bits):
    """The check's decoder half on the tiny block, with served tokens the
    reference's own greedy choice (what a sound program serves)."""
    from chipbench.checks import rag_answer_laguna as check
    from chipbench.reference import laguna_decoder as ref

    dec_config = manifest.config(BENCH, NAME, tiny=True)["chipbench"]["decoder"]
    weights = ref.init_weights(dec_config)
    rng = np.random.default_rng(4)
    lens, new, width = [30, 41, 36], 16, 64
    ids = np.zeros((3, width), np.int32)
    for i, n in enumerate(lens):
        ids[i, :n] = rng.integers(104, 512, size=n)
    for step in range(new):  # greedy, token by token, through the full forward (one shape)
        at = np.asarray([[n + step - 1] for n in lens])
        logits = ref.logits_at(weights, dec_config, ids, [n + step for n in lens], at)
        for i, n in enumerate(lens):
            ids[i, n + step] = int(logits[i, 0].argmax())
    sample = [
        {"prompt_ids": [int(t) for t in ids[i, :n]], "served": [int(t) for t in ids[i, n:n + new]]}
        for i, n in enumerate(lens)
    ]
    return check._logit_gaps(weights, dec_config, sample, 6, 16, bits)


@pytest.mark.parametrize("bits", [None, 8])
def test_check_reads_nought_for_the_references_own_tokens_and_more_for_int8(bits):
    gaps = _tiny_check(bits)
    assert gaps.shape == (48,) and float(gaps.min()) >= 0.0
    if bits is None:
        assert float(gaps.max()) == 0.0
    else:
        assert float(gaps.max()) > 0.0


@pytest.mark.parametrize("tiny", [False, True])
def test_limits_are_finite_and_name_every_number_the_check_compares(tiny):
    limits = manifest.config(BENCH, NAME, tiny=tiny)["chipbench"]["limits"]
    assert set(limits) == {"logit_gap", "logit_gap_mean", "score_gap", "rank_gap"}
    assert all(isinstance(v, float) and math.isfinite(v) and v > 0 for v in limits.values())
    assert limits["logit_gap_mean"] < limits["logit_gap"] or tiny


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_every_configurations_limits_are_finite_numbers(config):
    """``run.py`` decides ``correct`` by ``value <= limit``: a limit that is
    not a finite number fails every run, whatever the run reads."""
    for tiny in (False, True):
        limits = manifest.config(BENCH, config, tiny=tiny)["chipbench"]["limits"]
        assert limits and all(
            type(v) in (int, float) and math.isfinite(v) and v >= 0 for v in limits.values()
        ), limits


def test_warm_up_lengths_cover_every_prompt_the_mix_sends():
    """The builder's range, the mix's shortest to its longest prompt, holds
    the prompts of seeded questions, each with 6 of the seeded documents;
    they take 5-6 prefill programs of 512."""
    from chipbench.builders.rag_server_long import LongPromptRagServer

    mix = manifest.traffic_mix("answer-laguna-long")
    server = LongPromptRagServer.__new__(LongPromptRagServer)
    server.config, server.spec = CONFIG, CONFIG["chipbench"]
    server.serving = server.spec["serving"]
    server.documents = text.make_documents(2048, 11, tuple(server.spec["corpus"]["words"]))
    lo, hi = server.prompt_lengths(mix)
    assert 2300 < lo < hi < 2900 and hi + 64 <= server.serving["max_cache"]
    tokenizer = text.HashTokenizer(CONFIG["vocab_size"])
    rng = random.Random(5)
    questions = text.make_questions(40, 11, tuple(mix["payload"]["words"]), server.documents)
    for question in questions:
        prompt = text.rag_prompt(rng.sample(server.documents, 6), question)
        n = len(tokenizer.encode(prompt, 8192))
        assert lo <= n <= hi and 5 <= -(-n // 512) <= 6



def test_warm_up_runs_every_prefill_program_of_the_range_once():
    """At the cell's sizes (programs of 512, pages of 16, 8 slots, 4,096
    tokens a slot) the mix's lengths run six prefill programs: a row of
    512 at table widths 32 to 256, and a tail's row of 256 or the narrow
    rung's row of every slot at 256.  Three lengths reach all six."""
    from types import SimpleNamespace

    from chipbench.builders.rag_server_long import lengths_to_warm
    from pathway_tpu.serving.generation import GenerationScheduler, prefill_ladder

    sched = SimpleNamespace(
        _ladder=prefill_ladder(512), slots=8, pages_per_seq=256,
        allocator=SimpleNamespace(pages_for=lambda tokens: -(-tokens // 16)),
    )
    programs_of = lambda n: GenerationScheduler.prefill_programs(sched, n)  # noqa: E731
    lo, hi = 2352, 2844
    lengths = lengths_to_warm(programs_of, lo, hi)
    met = set().union(*(programs_of(n) for n in lengths))
    assert lengths == [2352, 2561, 2593]
    assert met == set().union(*(programs_of(n) for n in range(lo, hi + 1))) == {
        (1, 512, 32), (1, 512, 64), (1, 512, 128), (1, 512, 256), (8, 32, 256), (1, 256, 256),
    }

NEW_METRICS = [
    "laguna_decoder_step_mfu", "laguna_decode_roofline", "laguna_prefill_roofline",
    "laguna.experts_hit_per_decode_layer", "laguna.prefill_context_per_chunk",
]


@pytest.mark.parametrize("name", NEW_METRICS + ["laguna.window_pages_per_slot"])
def test_new_metric_reads_the_programs_counters(name):
    """Each new per-layer metric on a probe built by hand; on a program
    without the new counter (the parent) the two that read it read
    nothing, and none raises."""
    from chipbench import readers

    scalars = {
        "generate.tokens": 2432.0, "generate.decode.steps": 2400.0, "generate.prefill.chunks": 209.0,
        "generate.requests": 38.0, "generate.prefill.tokens": 98000.0,
        "generate.prefill.context_tokens": 230000.0,
        "generate.moe.decode.pairs": 48000.0, "generate.moe.prefill.pairs": 1960000.0,
        "generate.moe.decode.experts_hit": 48000.0, "generate.moe.prefill.experts_hit": 107000.0,
        "generate.kv.window.pages_released": 38.0 * 33, "generate.kv.window.slots_released": 38.0,
    }
    ctx = {
        "before": {"scalars": {}}, "after": {"scalars": scalars}, "span_s": 51.0,
        "work": {"decoder_tokens": 100432.0, "prompt_tokens": 98000.0, "context_tokens_mean": 2610.0},
        "sections": {"decoder": CONFIG}, "peak": PEAK,
        "trace": {"modules": {"jit__decode": {"seconds": 6.0, "runs": 2400},
                              "jit__prefill": {"seconds": 5.2, "runs": 209}}},
    }
    value = readers.evaluate(manifest.metric_file("per_layer", name), ctx)
    assert value is not None and value > 0
    if name.endswith("_roofline") or name.endswith("_mfu"):
        assert value < 100
    if name == "laguna.experts_hit_per_decode_layer":
        assert value == pytest.approx(48000 / 2400 / 4)
    if name == "laguna.prefill_context_per_chunk":
        assert value == pytest.approx(230000 / 209)
    if name == "laguna.window_pages_per_slot":  # the ring: ceil(512 / 16) + 1 pages
        assert value == 33.0
    parent = {**ctx, "after": {"scalars": {
        k: v for k, v in scalars.items() if k != "generate.prefill.context_tokens"
    }}}
    reads = readers.evaluate(manifest.metric_file("per_layer", name), parent)
    assert (reads is None) == (name in ("laguna_prefill_roofline", "laguna.prefill_context_per_chunk"))
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]


@pytest.mark.parametrize("name,shared", [
    ("laguna.tick_host_ms.decode", "sched.tick_host_ms.decode"),
    ("laguna.host_device_idle_pct.answer", "host.device_idle_pct.answer"),
])
def test_the_cells_own_timeline_metrics_are_the_shared_ones_from_second_two(name, shared):
    mine, theirs = manifest.metric_file("per_layer", name), manifest.metric_file("per_layer", shared)
    assert mine["from_s"] == manifest.traffic_mix("answer-laguna-long")["trace"]["start_s"] == 2.0
    same = lambda body: {k: v for k, v in body.items() if k not in ("from_s", "what")}  # noqa: E731
    assert same(mine) == same(theirs) and theirs["from_s"] == 10.0
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["source"] == "program_span"
