"""The MiMo-V2.5 configuration's own pieces on inputs with known answers:
its cost functions against hand counts from the published sizes, its file
against the catalog's rules and the program's tiny preset, its check on
the tiny block (sound, and with the int8 control in the program's place).
"""

from __future__ import annotations

import json
import pathlib
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench import cost, manifest  # noqa: E402
from chipbench.cost import mimo_decoder as cost_mimo  # noqa: E402

BENCH = manifest.benchmark()
CONFIG = manifest.config(BENCH, "mimo-v2.5-bge-rag")
PUBLISHED = {
    **CONFIG, "n_routed_experts": 256, "vocab_size": 152576, "num_hidden_layers": 48,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0],
    "moe_layer_freq": [0] + [1] * 47,
}
M = 1e6


def test_cost_functions_count_the_published_sizes():
    kinds = cost_mimo.layers(CONFIG)
    assert [k["window"] for k in kinds] == [None, 128, 128, 128, 128, None, 128]
    assert [k["routed"] for k in kinds] == [False] + [True] * 6
    glob, window = kinds[0], kinds[1]
    # query 4096 x 12,288 = 50.3 M, output 8,192 x 4096 = 33.6 M, K+V 5.2 / 10.5 M
    assert cost_mimo.attention_params(CONFIG, glob) == 4096 * (12288 + 4 * 320) + 8192 * 4096
    assert round(cost_mimo.attention_params(CONFIG, glob) / M, 1) == 89.1
    assert round(cost_mimo.attention_params(CONFIG, window) / M, 1) == 94.4
    assert cost_mimo.expert_params(CONFIG) == 3 * 4096 * 2048  # 25.2 M, 50.3 MB
    assert cost_mimo.routed_layers(CONFIG) == 6 and cost_mimo.held_share(CONFIG) == 1 / 8
    # what a token is multiplied by whatever its routing: 2 global + 5 window
    # attentions, 6 routers of 256, the dense layer's 201.3 M, the head's 78.1 M
    dense = 2 * 89128960 + 5 * 94371840 + 6 * 4096 * 256 + 3 * 4096 * 16384 + 4096 * 19072
    assert cost_mimo.dense_params(CONFIG) == dense and round(dense / M, 1) == 935.9
    # the chip's share: 5.85 G parameters with the embedding and 32 experts a routed layer
    held = dense + 4096 * 19072 + 6 * 32 * cost_mimo.expert_params(CONFIG)
    assert round(held / 1e9, 2) == 5.85


def test_decode_step_and_prefill_program_of_the_issue():
    # one row, one expert a routed layer: 2.17 GB of which 0.30 GB experts, 2.65 ms least
    step = cost_mimo.decode_step(CONFIG, rows=1, context=420, experts_hit=6)
    experts = 6 * cost_mimo.expert_params(CONFIG) * 2
    assert round(step["bytes"] / 1e9, 2) == 2.18 and round(experts / 1e9, 2) == 0.30
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    least, bound = cost.least_seconds(step, peak)
    assert bound == "bandwidth" and round(least * 1e3, 2) == 2.66
    # live KV: global layers the context, window layers 128 tokens
    assert cost_mimo.kv_bytes(CONFIG, 420) == 2 * (2 * 420 * 4 * 320 + 5 * 128 * 8 * 320)
    assert cost_mimo.kv_bytes(CONFIG, 50) == 2 * (2 * 50 * 4 * 320 + 5 * 50 * 8 * 320)
    # eight rows meet about 7 experts a layer: experts are over half the bytes
    eight = cost_mimo.decode_step(CONFIG, rows=8, context=420, experts_hit=6 * 7)
    assert 0.5 < 6 * 7 * cost_mimo.expert_params(CONFIG) * 2 / eight["bytes"] < 0.58
    # a 390-token prompt reads all 32 experts of each routed layer: 85 % of 11.4 GB
    chunk = cost_mimo.prefill_chunk(CONFIG, rows=1, chunk=390, context=0, experts_hit=6 * 32)
    all_experts = 6 * 32 * cost_mimo.expert_params(CONFIG) * 2
    assert round(all_experts / 1e9, 1) == 9.7 and round(chunk["bytes"] / 1e9, 1) == 11.4
    least, bound = cost.least_seconds(chunk, peak)
    assert bound == "bandwidth" and round(least * 1e3, 1) == 13.9
    # tokens: 2 x dense parameters a token + 2 x 25.2 M a pair
    work = cost_mimo.tokens(CONFIG, tokens=10, pairs=7)
    assert work["flops"] == 2.0 * cost_mimo.dense_params(CONFIG) * 10 + 2.0 * 25165824 * 7


def test_published_model_adds_up_to_its_name():
    whole = (
        cost_mimo.dense_params(PUBLISHED) + 4096 * 152576
        + cost_mimo.routed_layers(PUBLISHED) * 256 * cost_mimo.expert_params(PUBLISHED)
    )
    assert cost_mimo.routed_layers(PUBLISHED) == 47 and round(whole / 1e9) == 309


def test_file_is_the_catalog_row_but_for_what_it_lists():
    spec = CONFIG["chipbench"]
    entry = next(c for c in BENCH["configs"] if c["name"] == "mimo-v2.5-bge-rag")
    assert entry["source"] in spec["source"][0] and len(entry["source"]) <= 200
    assert sorted(spec["reduced"]) == sorted(entry["reduced"])
    # no width is cut: every width of the row stands as published
    for key, value in {
        "hidden_size": 4096, "head_dim": 192, "v_head_dim": 128, "swa_head_dim": 192,
        "swa_v_head_dim": 128, "intermediate_size": 16384, "moe_intermediate_size": 2048,
        "num_attention_heads": 64, "num_key_value_heads": 4, "swa_num_key_value_heads": 8,
        "num_experts_per_tok": 8, "sliding_window": 128, "partial_rotary_factor": 0.334,
        "attention_value_scale": 0.707, "rope_theta": 10000000, "swa_rope_theta": 10000,
    }.items():
        assert CONFIG[key] == value and key not in spec["reduced"], key
    assert CONFIG["n_routed_experts_published"] == 256 and CONFIG["expert_shards"] == 8
    assert CONFIG["n_routed_experts"] * CONFIG["expert_shards"] == 256
    assert CONFIG["vocab_size"] * 8 == 152576
    assert CONFIG["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert spec["deployment"]["chips_per_layer"] == 8
    for key in ("towers", "multi_token_prediction", "rotary", "attention_chunk_size", "sink_and_bias"):
        assert key in spec["assumed"], key
    assert any("no routed pair dropped" in g for g in spec["guarantees"])


def test_tiny_block_is_the_programs_preset():
    from pathway_tpu.models import decoder as dec

    tiny = manifest.config(BENCH, "mimo-v2.5-bge-rag", tiny=True)["chipbench"]
    assert tiny["decoder_model"] == "pw-tiny-hybrid-decoder"
    assert tiny["decoder"] == dec.TINY_HYBRID_HF
    assert dec.decoder_config_from_hf(tiny["decoder"]) == dec.PRESETS["pw-tiny-hybrid-decoder"]
    # and the file as run is read by the program as the share it states
    cfg = dec.decoder_config_from_hf({k: v for k, v in CONFIG.items() if k != "chipbench"})
    assert (cfg.experts, cfg.experts_published, cfg.experts_first) == (32, 256, 0)
    assert [n for _k, n in cfg.runs] == [1, 4, 1, 1] and cfg.vocab_size == 19072
    assert (cfg.head_dim, cfg.v_dim, cfg.rotary_dim, cfg.value_scale) == (192, 128, 64, 0.707)


def _tiny_check(control: bool):
    """The check's decoder half on the tiny block, with served tokens the
    reference's own greedy choice (what a sound program serves)."""
    from chipbench.checks import rag_answer_mimo as check
    from chipbench.reference import mimo_decoder as ref

    tiny = manifest.config(BENCH, "mimo-v2.5-bge-rag", tiny=True)["chipbench"]
    dec_config = tiny["decoder"]
    weights = ref.init_weights(dec_config)
    rng = np.random.default_rng(4)
    lens = [30, 41, 36]
    new, width = 16, 64
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
    return check._logit_gaps(weights, dec_config, sample, 6, 16, 8 if control else None)


def test_check_reads_nought_for_the_references_own_tokens_and_more_for_int8():
    gaps = _tiny_check(control=False)
    assert gaps.shape == (48,) and float(np.abs(gaps).max()) == 0.0
    # int8 puts another token first somewhere among the 48 positions (the
    # rehearsal's 288 positions are what the tiny limit was read from)
    low = _tiny_check(control=True)
    assert float(low.max()) > 0.0 and float(low.mean()) > 0.0


@pytest.mark.parametrize("tiny", [False, True])
def test_limits_name_every_number_the_check_compares(tiny):
    """``logit_gap`` and ``logit_gap_mean`` are two readings of the same
    gaps, each with a limit in the configuration and in its tiny block."""
    limits = manifest.config(BENCH, "mimo-v2.5-bge-rag", tiny=tiny)["chipbench"]["limits"]
    assert set(limits) == {"logit_gap", "logit_gap_mean", "score_gap", "rank_gap"}
    assert 0 < limits["logit_gap_mean"] < limits["logit_gap"] or tiny


@pytest.mark.parametrize("name", [
    "moe_decoder_step_mfu", "moe_decode_roofline", "moe_prefill_roofline",
    "moe.experts_hit_per_decode_layer", "kv.window_pages_per_slot",
])
def test_new_metric_reads_the_programs_counters(name):
    """Each new per-layer metric on a probe built by hand; on a program
    without the counters (the parent) it reads nothing and does not raise."""
    from chipbench import readers

    scalars = {
        "generate.tokens": 640.0, "generate.decode.steps": 600.0, "generate.prefill.chunks": 10.0,
        "generate.moe.decode.pairs": 3800.0, "generate.moe.prefill.pairs": 23000.0,
        "generate.moe.decode.experts_hit": 3700.0, "generate.moe.prefill.experts_hit": 1900.0,
        "generate.kv.window.pages_released": 90.0, "generate.kv.window.slots_released": 10.0,
    }
    ctx = {
        "before": {"scalars": {}}, "after": {"scalars": scalars}, "span_s": 51.0,
        "work": {"decoder_tokens": 4540.0, "prompt_tokens": 3900.0, "context_tokens_mean": 422.0},
        "sections": {"decoder": CONFIG}, "peak": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
        "trace": {"modules": {"jit__decode": {"seconds": 2.4, "runs": 600}, "jit__prefill": {"seconds": 0.3, "runs": 10}}},
    }
    value = readers.evaluate(manifest.metric_file("per_layer", name), ctx)
    assert value is not None and value > 0
    if name.endswith("_roofline") or name.endswith("_mfu"):
        assert value < 100
    if name == "moe.experts_hit_per_decode_layer":
        assert value == pytest.approx(3700 / 600 / 6)
    if name == "kv.window_pages_per_slot":
        assert value == 9.0
    parent = {**ctx, "after": {"scalars": {"generate.tokens": 640.0, "generate.decode.steps": 600.0, "generate.prefill.chunks": 10.0}}}
    assert readers.evaluate(manifest.metric_file("per_layer", name), parent) is None


@pytest.mark.parametrize("lead", [0, 1, 4, 5])
def test_settling_uses_up_the_lead_that_lets_waiting_answers_share_an_epoch(lead, monkeypatch):
    """An engine of the runner's rule, in a thread: one epoch at a time;
    an epoch takes every waiting answer the lead covers and uses it up."""
    import threading
    import time

    from chipbench.builders import common, rag_server_kinds

    state = {"lead": lead, "epochs": []}
    waiting: list[threading.Event] = []
    lock, stop = threading.Lock(), threading.Event()

    def engine() -> None:
        while not stop.is_set():
            with lock:
                take = waiting[: 1 + state["lead"]]
                del waiting[: len(take)]
                state["lead"] -= max(0, len(take) - 1)
            if not take:
                time.sleep(0.001)
                continue
            time.sleep(0.04)
            state["epochs"].append(len(take))
            for answered in take:
                answered.set()

    def post(_port, route, _payload):
        assert route == "/v2/answer"
        answered = threading.Event()
        with lock:
            waiting.append(answered)
        assert answered.wait(10.0)
        return {"response": "so"}

    monkeypatch.setattr(common, "post", post)
    server = rag_server_kinds.SettledRagServer.__new__(rag_server_kinds.SettledRagServer)
    server.port = 0
    thread = threading.Thread(target=engine, daemon=True)
    thread.start()
    try:
        probes = server.settle("a question")
    finally:
        stop.set()
        thread.join()
    assert state["lead"] == 0 and state["epochs"][-3:] == [1, 1, 1]
    assert probes is not None and sum(state["epochs"]) == 3 * probes
