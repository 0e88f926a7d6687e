"""The Nemotron 3 Nano configuration's own pieces on inputs with known
answers: its cost functions against hand counts from the published sizes,
its file against the catalog's rules and the program's tiny preset, its
reference's Mamba-2 layer against the recurrence written out in numpy, its
check on the tiny block (sound, with the int8 control and with the
bfloat16-state control in the program's place), its metric files.
"""

from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench import cost, manifest  # noqa: E402
from chipbench.cost import nemotron_h_decoder as cost_nemo  # noqa: E402

NAME, CELL = "nemotron-3-nano-bge-rag", "rag-answer-mamba-steady"
BENCH = manifest.benchmark()
CONFIG = manifest.config(BENCH, NAME)
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
PUBLISHED = {
    **CONFIG, "n_routed_experts": 128, "vocab_size": 131072, "num_hidden_layers": 52,
    "hybrid_override_pattern": PATTERN,
}
PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
M = 1e6


def test_cost_functions_count_the_published_sizes():
    assert cost_nemo.layers(CONFIG) == "MEMEM*EMEMEM*EME"
    assert [cost_nemo.count(CONFIG, letter) for letter in "M*E"] == [7, 2, 7]
    # a mixer: in_proj 2,688 x 10,304 (z 4,096 + xBC 6,144 + dt 64) + out_proj 4,096 x 2,688
    assert cost_nemo.mamba_params(CONFIG) == 2688 * (4096 + 6144 + 64) + 4096 * 2688
    # its taps and bias, A_log, D, dt_bias, the gated norm, the layer's norm: 38.74 M in all
    assert cost_nemo.mamba_small_params(CONFIG) == 5 * 6144 + 3 * 64 + 4096 + 2688
    assert cost_nemo.mamba_params(CONFIG) + cost_nemo.mamba_small_params(CONFIG) == 38_744_896
    # q 2,688 x 4,096, k and v 2,688 x 256 each, o 4,096 x 2,688: 23.40 M
    assert cost_nemo.attention_params(CONFIG) == 2688 * (4096 + 512) + 4096 * 2688 == 23_396_352
    assert cost_nemo.expert_params(CONFIG) == 2 * 2688 * 1856  # 9.98 M, 20.0 MB: no gate, no padding
    assert round(cost_nemo.expert_params(CONFIG) * 2 / M, 1) == 20.0
    assert cost_nemo.shared_params(CONFIG) == 2 * 2688 * 3712 and cost_nemo.held_share(CONFIG) == 0.5
    assert cost_nemo.router_params(CONFIG) == 2688 * 128
    # the chip's share: 5.28 G parameters = 10.56 GB; whole: 31.58 G
    assert cost_nemo.total_params(CONFIG) == 5_282_534_208
    assert round(cost_nemo.total_params(CONFIG) * 2 / 1e9, 2) == 10.57
    assert cost_nemo.total_params(PUBLISHED) == 31_577_940_288
    # recurrent state: 7 x (64 x 64 x 128 float32 + 3 x 6,144 bfloat16) = 14.9 MB a slot
    assert cost_nemo.state_bytes(CONFIG) == 7 * (64 * 64 * 128 * 4 + 3 * 6144 * 2) == 14_938_112
    assert cost_nemo.kv_bytes(CONFIG, 420) == 2 * 420 * 2 * 2 * 128 * 2


def test_decode_step_and_prefill_program_of_the_issue():
    # one row, about 3 held experts a routed layer: 1.69 GB of weights + 0.03 of state and cache
    dense = cost_nemo.dense_params(CONFIG)
    by_hand = 7 * 38_707_200 + 2 * 23_396_352 + 7 * (19_955_712 + 344_064) + 65536 * 2688
    assert dense == by_hand and round(dense * 2 / 1e9, 2) == 1.27
    step = cost_nemo.decode_step(CONFIG, rows=1, context=420, experts_hit=21)
    weights = (dense + 21 * cost_nemo.expert_params(CONFIG)) * 2
    assert round(weights / 1e9, 2) == 1.69
    assert step["bytes"] == weights + 2 * cost_nemo.state_bytes(CONFIG) + cost_nemo.kv_bytes(CONFIG, 420)
    least, bound = cost.least_seconds(step, PEAK)
    assert bound == "bandwidth" and round(least * 1e3, 2) == 2.10
    # by bytes: Mamba-2 layers a third, expert layers two fifths, the head a fifth
    mamba = (7 * cost_nemo.mamba_params(CONFIG) * 2 + 2 * cost_nemo.state_bytes(CONFIG)) / step["bytes"]
    experts = (7 * (19_955_712 + 344_064) + 21 * 9_977_856) * 2 / step["bytes"]
    assert 0.30 < mamba < 0.35 and 0.38 < experts < 0.44
    # the scan is counted as the recurrence: 5 operations an entry of the state a token
    assert cost_nemo._scan_flops(CONFIG, 1) == 7 * (5 * 64 * 64 * 128 + 2 * 4 * 6144)
    # a 390-token prompt meets all 64 held experts of each routed layer: bandwidth-bound
    chunk = cost_nemo.prefill_chunk(CONFIG, rows=1, chunk=390, context=0, experts_hit=7 * 64)
    assert round(7 * 64 * cost_nemo.expert_params(CONFIG) * 2 / 1e9, 1) == 8.9
    least, bound = cost.least_seconds(chunk, PEAK)
    assert bound == "bandwidth" and 11.5 < least * 1e3 < 12.5
    work = cost_nemo.tokens(CONFIG, tokens=10, pairs=7)
    assert work["flops"] == 2.0 * dense * 10 + 2.0 * 9_977_856 * 7 + cost_nemo._scan_flops(CONFIG, 10)


def test_file_is_the_catalog_row_but_for_what_it_lists():
    spec = CONFIG["chipbench"]
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["source"] in spec["source"][0] and len(entry["source"]) <= 200
    assert sorted(spec["reduced"]) == sorted(entry["reduced"]) == sorted(
        ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"]
    )
    # no width is cut: every width of the row stands as published
    for key, value in {
        "hidden_size": 2688, "head_dim": 128, "num_attention_heads": 32, "num_key_value_heads": 2,
        "mamba_num_heads": 64, "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
        "conv_kernel": 4, "chunk_size": 128, "expand": 2, "intermediate_size": 1856,
        "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
        "num_experts_per_tok": 6, "routed_scaling_factor": 2.5, "n_shared_experts": 1,
        "rope_theta": 10000, "partial_rotary_factor": 1, "max_position_embeddings": 262144,
    }.items():
        assert CONFIG[key] == value and key not in spec["reduced"], key
    assert CONFIG["hybrid_override_pattern"] == PATTERN[:16] and CONFIG["num_hidden_layers"] == 16
    assert CONFIG["n_routed_experts_published"] == 128 and CONFIG["expert_shards"] == 2
    assert CONFIG["n_routed_experts"] * 2 == 128 and CONFIG["vocab_size"] * 2 == 131072
    assert spec["deployment"]["chips_per_layer"] == 2 and spec["check"]["sample"] == 48
    for key in ("rotary", "seeded_state_parameters", "weights", "tokenizer", "expand", "time_step"):
        assert key in spec["assumed"], key
    assert any("recurrent state" in g and "float32" in g for g in spec["guarantees"])
    assert any("no routed pair dropped" in g for g in spec["guarantees"])
    cell = manifest.cell_entry(BENCH, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "answer-mamba-steady"
    mix = manifest.traffic_mix(cell["traffic"])
    assert mix["arrivals"]["draw_seed"] == 25 and mix["client"] == {"workers": 64, "timeout_s": 120.0}
    assert mix["trace"] == {"start_s": 2.0, "stop_before_close_s": 19.0}
    # the rate is half the sustained rate the mix's own text gives, to 0.05
    assert round(mix["arrivals"]["rate_per_s"] / 0.05) * 0.05 == pytest.approx(mix["arrivals"]["rate_per_s"])


def test_tiny_block_is_the_programs_preset():
    from pathway_tpu.models import decoder as dec

    tiny = manifest.config(BENCH, NAME, tiny=True)["chipbench"]
    assert tiny["decoder_model"] == "pw-tiny-mamba-decoder"
    assert tiny["decoder"] == dec.TINY_MAMBA_HF
    assert dec.decoder_config_from_hf(tiny["decoder"]) == dec.PRESETS["pw-tiny-mamba-decoder"]
    # and the file as run is read by the program as the share it states
    cfg = dec.decoder_config_from_hf({k: v for k, v in CONFIG.items() if k != "chipbench"})
    assert (cfg.experts, cfg.experts_published, cfg.experts_first) == (64, 128, 0)
    assert [k.part[0] for k, n in cfg.runs for _ in range(n)] == list("mfmfmafmfmfmafmf")
    assert cfg.vocab_size == 65536 and cfg.runs is not None


def test_reference_mixer_is_the_recurrence_written_out():
    """``reference/nemotron_h_decoder.py::mamba_mixer`` against the
    equations in numpy, one token and one head at a time."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import nemotron_h_decoder as ref

    rng = np.random.default_rng(0)
    B, S, H, NH, P, G, N, K = 1, 9, 12, 4, 3, 2, 5, 4
    inner, GN = NH * P, G * N
    draw = lambda *shape: rng.normal(size=shape).astype(np.float32)
    w = {
        "in_proj": draw(H, 2 * inner + 2 * GN + NH) / 3, "out_proj": draw(inner, H) / 3,
        "conv_w": draw(K, inner + 2 * GN) / 2, "conv_b": draw(inner + 2 * GN) / 10,
        "dt_bias": draw(NH), "A_log": np.log(rng.uniform(1, 16, NH)).astype(np.float32),
        "D": 1 + draw(NH) / 2, "gate_norm": 1 + draw(inner) / 10,
    }
    h = draw(B, S, H)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.mamba_mixer(
            jnp.asarray(h), {k: jnp.asarray(v) for k, v in w.items()},
            heads=NH, head=P, groups=G, state=N, eps=1e-5,
        ))
    proj = h[0] @ w["in_proj"]
    z, xbc, dt = proj[:, :inner], proj[:, inner:2 * inner + 2 * GN], proj[:, 2 * inner + 2 * GN:]
    padded = np.concatenate([np.zeros((K - 1, xbc.shape[1]), np.float32), xbc])
    conv = np.stack([w["conv_b"] + sum(w["conv_w"][k] * padded[t + k] for k in range(K)) for t in range(S)])
    xbc = conv / (1 + np.exp(-conv))
    dt = np.log1p(np.exp(dt + w["dt_bias"]))
    y = np.zeros((S, NH, P), np.float32)
    for head in range(NH):
        g, state = head // (NH // G), np.zeros((P, N), np.float32)
        for t in range(S):
            x_t = xbc[t, head * P:(head + 1) * P]
            B_t = xbc[t, inner + g * N: inner + (g + 1) * N]
            C_t = xbc[t, inner + GN + g * N: inner + GN + (g + 1) * N]
            state = np.exp(-dt[t, head] * np.exp(w["A_log"][head])) * state + dt[t, head] * np.outer(x_t, B_t)
            y[t, head] = state @ C_t + w["D"][head] * x_t
    y = y.reshape(S, inner) * (z / (1 + np.exp(-z)))
    y = y.reshape(S, G, inner // G)
    y = (y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5)).reshape(S, inner) * w["gate_norm"]
    np.testing.assert_allclose(got[0], y @ w["out_proj"], rtol=2e-4, atol=2e-5)


def _tiny_check(control):
    """The check's decoder half on the tiny block, with served tokens the
    reference's own greedy choice (what a sound program serves)."""
    from chipbench.checks import rag_answer_nemotron_h as check
    from chipbench.reference import nemotron_h_decoder as ref

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
    lowered = {"int8": {"weight_bits": 8}, "state_bfloat16": {"state_dtype": "bfloat16"}}.get(control)
    return check._logit_gaps(weights, dec_config, sample, 6, 16, lowered)


def test_check_reads_nought_for_the_references_own_tokens():
    gaps = _tiny_check(None)
    assert gaps.shape == (48,) and float(np.abs(gaps).max()) == 0.0


@pytest.mark.parametrize("control", ["int8", "state_bfloat16"])
def test_controls_run_at_tiny_and_put_another_token_first_somewhere(control):
    """Both controls run through the check's own comparison.  int8 weights
    move a choice among the 48 positions; a bfloat16 state moves the
    logits (by how much at the published widths is read on the chip:
    PERF.md section 6)."""
    from chipbench.reference import nemotron_h_decoder as ref

    gaps = _tiny_check(control)
    assert gaps.shape == (48,) and float(gaps.min()) >= 0.0
    if control == "int8":
        assert float(gaps.max()) > 0.0
    dec_config = manifest.config(BENCH, NAME, tiny=True)["chipbench"]["decoder"]
    weights = ref.init_weights(dec_config)
    ids = np.random.default_rng(1).integers(104, 512, size=(2, 48)).astype(np.int32)
    at = np.tile(np.arange(40, 48), (2, 1))
    sound = ref.logits_at(weights, dec_config, ids, [48, 48], at)
    lowered = {"weight_bits": 8} if control == "int8" else {"state_dtype": "bfloat16"}
    moved = np.abs(ref.logits_at(weights, dec_config, ids, [48, 48], at, **lowered) - sound).max()
    # at hidden size 48 an int8 channel is coarse: it moves a logit of ~5 by ~1
    assert 1e-4 < moved < (5.0 if control == "int8" else 0.5)


def test_check_refuses_a_control_it_does_not_know():
    from chipbench.checks import rag_answer_nemotron_h as check

    class Deployment:
        documents = ["document 0 : a"]

    ctx = {"config": manifest.config(BENCH, NAME, tiny=True), "deployment": Deployment(),
           "results": [], "control": "float16", "seed": 1}
    with pytest.raises(ValueError, match="unknown control"):
        check.check(ctx)


@pytest.mark.parametrize("tiny", [False, True])
def test_limits_name_every_number_the_check_compares(tiny):
    limits = manifest.config(BENCH, NAME, tiny=tiny)["chipbench"]["limits"]
    assert set(limits) == {"logit_gap", "logit_gap_mean", "score_gap", "rank_gap"}
    assert 0 < limits["logit_gap_mean"] < limits["logit_gap"] or tiny


NEW_METRICS = [
    "mamba_decoder_step_mfu", "mamba_decode_roofline", "mamba_prefill_roofline",
    "mamba.experts_hit_per_decode_layer", "mamba.state_resets_per_answer",
]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_reads_the_programs_counters(name):
    """Each new per-layer metric on a probe built by hand; on a program
    without the counters (the parent) it reads nothing and does not raise."""
    from chipbench import readers

    scalars = {
        "generate.tokens": 3840.0, "generate.decode.steps": 3800.0, "generate.prefill.chunks": 60.0,
        "generate.requests": 60.0, "generate.ssm.state.resets": 60.0,
        "generate.moe.decode.pairs": 80000.0, "generate.moe.prefill.pairs": 490000.0,
        "generate.moe.decode.experts_hit": 79000.0, "generate.moe.prefill.experts_hit": 26800.0,
    }
    ctx = {
        "before": {"scalars": {}}, "after": {"scalars": scalars}, "span_s": 51.0,
        "work": {"decoder_tokens": 27240.0, "prompt_tokens": 23400.0, "context_tokens_mean": 422.0},
        "sections": {"decoder": CONFIG}, "peak": PEAK,
        "trace": {"modules": {"jit__decode": {"seconds": 12.0, "runs": 3800}, "jit__prefill": {"seconds": 1.8, "runs": 60}}},
    }
    value = readers.evaluate(manifest.metric_file("per_layer", name), ctx)
    assert value is not None and value > 0
    if name.endswith("_roofline") or name.endswith("_mfu"):
        assert value < 100
    if name == "mamba.experts_hit_per_decode_layer":
        assert value == pytest.approx(79000 / 3800 / 7)
    if name == "mamba.state_resets_per_answer":
        assert value == 1.0
    parent = {**ctx, "after": {"scalars": {
        "generate.tokens": 3840.0, "generate.decode.steps": 3800.0,
        "generate.prefill.chunks": 60.0, "generate.requests": 60.0,
    }}}
    assert readers.evaluate(manifest.metric_file("per_layer", name), parent) is None


@pytest.mark.parametrize("name,shared", [
    ("mamba.tick_host_ms.decode", "sched.tick_host_ms.decode"),
    ("mamba.host_device_idle_pct.answer", "host.device_idle_pct.answer"),
])
def test_the_cells_own_timeline_metrics_are_the_shared_ones_from_second_two(name, shared):
    mine, theirs = manifest.metric_file("per_layer", name), manifest.metric_file("per_layer", shared)
    assert mine["from_s"] == manifest.traffic_mix("answer-mamba-steady")["trace"]["start_s"] == 2.0
    same = lambda body: {k: v for k, v in body.items() if k not in ("from_s", "what")}
    assert same(mine) == same(theirs) and theirs["from_s"] == 10.0
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["source"] == "program_span"
