"""Query heads, rotary and a gate that differ by kind of layer (``model_type:
laguna``), at the tiny preset ``pw-tiny-laguna-decoder``: every kind of
layer Laguna has, in the benchmark's cut's order (full and dense, three
window layers, full and routed); 4 query heads on full layers and 6 on
window layers over 2 KV heads; YaRN on half of a full layer's head, the
default rope on all of a window layer's; a sigmoid gate per query head; a
window of 24 tokens, longer than the 8-token pages here and shorter than
the prompts; one of two shares of 8 softmax-routed SwiGLU experts (top-3,
route scale 2.5) beside a SwiGLU shared expert; float32.

The scheduler's programs (a prompt prefilled in several chunks, each
attending to a ring that earlier chunks wrapped, then paged decode) are held
to ``chipbench/reference/laguna_decoder.py``: the plain full forward, which
imports nothing of the program and draws its own weights from the seed.

Tolerance ``TOL``: the program and the reference compute the same float32
sums in another order (a ring and a table against one sequence, a loop or
a grouped product against experts gathered by index), so logits of
magnitude ~4 agree to a few 1e-6; 3e-4 leaves two orders of room.  The same
model computed in bfloat16 lies ~1 away (``test_full_forward_...``), so a
program that rounded where it should not fails it.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import laguna_decoder as ref
from pathway_tpu.models import decoder as dec
from pathway_tpu.parallel.moe import MoEConfig, moe_serve
from pathway_tpu.serving.generation import GenerationScheduler

CFG = dec.decoder_config_for("pw-tiny-laguna-decoder")
HF = dec.TINY_LAGUNA_HF
PAGE, SLOTS = 8, 3
TOL = dict(rtol=3e-4, atol=3e-4)


@pytest.fixture(scope="module")
def lm():
    return dec.DecoderLM("pw-tiny-laguna-decoder", max_cache=128)


@pytest.fixture(scope="module")
def ref_weights():
    return ref.init_weights(HF)


def _prompt(rng, n):
    return [int(t) for t in rng.integers(104, CFG.vocab_size, size=n)]


def _scalars():
    from pathway_tpu.engine.metrics import get_registry

    return dict(get_registry().scalar_metrics())


def test_the_preset_has_every_kind_of_layer_in_order():
    kinds = [kind for kind, _n in CFG.runs]
    assert [n for _k, n in CFG.runs] == [1, 3, 1] and CFG.layers == 5
    assert [k.window for k in kinds] == [None, 24, None]
    assert [k.heads for k in kinds] == [4, 6, 4] and CFG.heads == 4
    assert [k.kv_heads for k in kinds] == [2, 2, 2]
    assert [k.rotary_dim for k in kinds] == [8, 16, 8] and CFG.head_dim == 16
    assert [k.yarn is not None for k in kinds] == [True, False, True]
    assert [k.rope_theta for k in kinds] == [500000.0, 10000.0, 500000.0]
    assert all(k.gated for k in kinds)
    assert [k.routed for k in kinds] == [False, True, True]
    assert [k.intermediate for k in kinds] == [128, 32, 32]
    assert (CFG.experts, CFG.experts_published, CFG.experts_top_k) == (4, 8, 3)
    assert (CFG.experts_scoring, CFG.experts_gated) == ("softmax", True)
    assert (CFG.experts_route_scale, CFG.experts_shared, CFG.norm_eps) == (2.5, 32, 1e-6)


def test_reference_draws_the_weights_the_program_draws(lm, ref_weights):
    tree = lm.params
    for name in ("embed", "lm_head", "final_norm"):
        np.testing.assert_array_equal(np.asarray(tree[name]), np.asarray(ref_weights[name]))
    names = {"moe_router": "router"}
    for run, ref_run in zip(tree["layers"], ref_weights["runs"]):
        assert {names.get(k, k) for k in run} == set(ref_run)
        for name, leaf in run.items():
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(ref_run[names.get(name, name)])
            )
    # the window run's query and gate widths are its own kind's
    window = tree["layers"][1]
    assert window["wqkv"].shape == (3, 64, (6 + 2 * 2) * 16)
    assert window["attn_gate"].shape == (3, 64, 6) and window["wo"].shape == (3, 96, 64)
    assert tree["layers"][2]["shared_gate"].shape == (1, 64, 32)


def test_full_forward_agrees_with_the_reference_and_bfloat16_does_not(lm, ref_weights):
    """``decoder_layer`` by kind, scanned run by run, against the
    reference at every position of two rows longer than the window; the
    same model in bfloat16 reads far outside ``TOL``."""
    rng = np.random.default_rng(0)
    ids = rng.integers(104, CFG.vocab_size, size=(2, 60)).astype(np.int32)
    lengths = np.asarray([60, 41], np.int32)
    positions = np.tile(np.arange(41), (2, 1))
    want = ref.logits_at(ref_weights, HF, ids, lengths, positions)

    def forward(tree, cfg):
        out = dec.causal_lm_logits(tree, jnp.asarray(ids), jnp.asarray(lengths), cfg, serving=True)
        return np.asarray(out)[:, :41]

    np.testing.assert_allclose(forward(lm.params, CFG), want, **TOL)
    matrices = jax.tree_util.tree_map(
        lambda t: t.astype(jnp.bfloat16) if t.ndim > 1 and t.dtype == jnp.float32 else t,
        {**lm.params, "layers": tuple(
            {k: v for k, v in run.items() if k != "moe_router"} for run in lm.params["layers"]
        )},
    )
    for run, full in zip(matrices["layers"], lm.params["layers"]):
        if "moe_router" in full:
            run["moe_router"] = full["moe_router"]
    rounded = forward(matrices, dataclasses.replace(CFG, dtype=jnp.bfloat16))
    assert np.abs(rounded - want).max() > 100 * TOL["atol"]


class _Recorder:
    """Wraps the scheduler's two programs and keeps the logits each decode
    step gave, with the positions they belong to, and each prefill
    program's shape and starts."""

    def __init__(self, sched):
        self.decode = []  # (seq_lens [S], active [S], logits [S, V])
        self.prefill = []  # (shape, starts)
        decode_fn, prefill_fn = sched._decode_fn, sched._prefill_fn

        def decode(*args):
            out = decode_fn(*args)
            self.decode.append(
                (np.asarray(args[4]), np.asarray(args[10]), np.asarray(out[1]))
            )
            return out

        def prefill(*args):
            self.prefill.append((tuple(args[4].shape), np.asarray(args[6])))
            return prefill_fn(*args)

        sched._decode_fn, sched._prefill_fn = decode, prefill


@pytest.mark.parametrize("chunk", [64, 32, 16])
def test_chunked_prefill_across_a_wrapped_ring_then_decode_agree_with_the_reference(
        lm, ref_weights, chunk):
    """A prompt of 100 tokens in programs of 64 (a row wider than the
    ring's 32 entries: 2 chunks), 32 (4) and 16 (7, each narrower than the
    window: a chunk's queries reach into what the ring kept of the chunks
    before), beside one of 13 tokens, 24 new tokens each: every logit the
    decode steps gave, through the rings, the tables and the gates,
    against the reference's full forward."""
    sched = GenerationScheduler(lm, slots=SLOTS, page_size=PAGE, prefill_chunk=chunk)
    assert sched.ring_pages == 4 and sched.ring_pages * PAGE < 100
    rec = _Recorder(sched)
    rng = np.random.default_rng(1)
    prompts, new = [_prompt(rng, 100), _prompt(rng, 13)], 24
    before = _scalars()
    try:
        with sched._lock:  # both are admitted by the first tick
            futures = [sched.submit_ids(p, max_new_tokens=new) for p in prompts]
        outs = [f.result(timeout=300) for f in futures]
    finally:
        sched.shutdown()
    after = _scalars()
    # the long prompt's chunks after its first start where the one before ended
    chunks = -(-100 // chunk)
    assert sorted(int(s.max()) for _shape, s in rec.prefill)[-(chunks - 1):] == [
        chunk * k for k in range(1, chunks)
    ]
    # and the counter sums each program's rows' earlier context
    grew = after["generate.prefill.context_tokens"] - before.get("generate.prefill.context_tokens", 0.0)
    assert grew == chunk * chunks * (chunks - 1) // 2 == sum(int(s.sum()) for _sh, s in rec.prefill)
    width = max(len(p) for p in prompts) + new
    ids = np.zeros((2, width), np.int32)
    lengths = np.zeros(2, np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        ids[i, : len(p) + new] = p + o
        lengths[i] = len(p) + new
    positions = np.stack([np.arange(len(p) - 1, len(p) - 1 + new) for p in prompts])
    want = ref.logits_at(ref_weights, HF, ids, lengths, positions)
    # the served token is the reference's own choice at every step
    assert [list(w.argmax(-1)) for w in want] == outs
    checked = 0
    for seq_lens, active, logits in rec.decode:
        for slot, prompt in enumerate(prompts):
            step = seq_lens[slot] - len(prompt) + 1  # logits after this step's token
            if active[slot] and 0 < step < new:
                np.testing.assert_allclose(logits[slot], want[slot, step], **TOL)
                checked += 1
    assert checked == 2 * (new - 1)


@pytest.mark.parametrize("length", [20, 64, 90, 100])
def test_prefill_programs_names_the_shapes_a_lone_prompt_runs(lm, length):
    """``prefill_programs`` (what a warm-up compiles for a prompt length)
    is what the scheduler runs for such a prompt alone: at programs of 64,
    a tail of 26 tokens takes the narrow rung's row of every slot, one of
    36 a row of 64, each at the table width its pages bucket to."""
    sched = GenerationScheduler(lm, slots=SLOTS, page_size=PAGE, prefill_chunk=64)
    ran = []
    prefill_fn = sched._prefill_fn

    def prefill(*args):
        ran.append((*args[4].shape, args[3][0].shape[1]))
        return prefill_fn(*args)

    sched._prefill_fn = prefill
    try:
        sched.submit_ids(_prompt(np.random.default_rng(length), length), max_new_tokens=1).result(timeout=300)
    finally:
        sched.shutdown()
    assert ran == sched.prefill_programs(length)
    assert ran == {
        20: [(SLOTS, 32, 4)], 64: [(1, 64, 8)], 90: [(1, 64, 8), (SLOTS, 32, 16)],
        100: [(1, 64, 8), (1, 64, 16)],
    }[length]


@pytest.mark.parametrize("tokens", [40, 300])
def test_shares_add_up_to_the_uncut_routed_layer(tokens):
    """The share test, on both of ``moe_serve``'s paths (40 rows: the loop
    over the experts met; 300: the grouped product): the two shares of 8
    gated experts, each routed by softmax over all 8 and scaled by 2.5,
    with the shared expert counted once, add up to what the reference
    gives the uncut layer; and each share is the reference's share."""
    H, F, E, K = 16, 8, 8, 3
    keys = jax.random.split(jax.random.PRNGKey(5), 8)
    router = jax.random.normal(keys[0], (H, E), jnp.float32)
    wg, wu = (jax.random.normal(k, (1, E, H, F), jnp.float32) / 4 for k in keys[1:3])
    wd = jax.random.normal(keys[3], (1, E, F, H), jnp.float32) / 3
    shared = {
        "shared_gate": jax.random.normal(keys[4], (H, F), jnp.float32) / 4,
        "shared_up": jax.random.normal(keys[5], (H, F), jnp.float32) / 4,
        "shared_down": jax.random.normal(keys[6], (F, H), jnp.float32) / 3,
    }
    x = jax.random.normal(keys[7], (tokens, H), jnp.float32)
    routed = dict(top_k=K, route_scale=2.5)
    with jax.default_matmul_precision("highest"):
        s = {k: np.asarray(v) for k, v in shared.items()}
        h = np.asarray(x)
        silu = lambda t: t / (1 + np.exp(-t))
        shared_out = (silu(h @ s["shared_gate"]) * (h @ s["shared_up"])) @ s["shared_down"]
        whole = np.asarray(ref.routed_ffn(x, router, wg, wu, wd, first=0, **routed)) + shared_out
        total, pairs = 0.0, 0
        for share in range(2):
            held = slice(4 * share, 4 * share + 4)
            cfg = MoEConfig(
                hidden=H, experts=4, intermediate=F, top_k=K, router_width=E,
                first_expert=4 * share, route_scale=2.5,
            )
            params = {"router": router, "wg": wg[0, held], "wu": wu[0, held], "wd": wd[0, held]}
            if share == 0:
                params.update(shared)
            y, n, _hit, _tiles = moe_serve(params, x, cfg)
            want = ref.routed_ffn(
                x, router, wg[:, held], wu[:, held], wd[:, held], first=4 * share, **routed
            )
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(want) + (shared_out if share == 0 else 0.0),
                rtol=1e-5, atol=1e-5,
            )
            total, pairs = total + np.asarray(y), pairs + int(n)
    assert pairs == tokens * K  # every pair computed on exactly one share: none dropped
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)


def test_yarn_frequencies_and_scale_follow_the_formula():
    """At the published full layer's sizes (64 rotated dims of 128, base
    500,000, factor 128 over 8,192 positions, betas 32 and 1): the ramp
    runs from dim lo = 9 to hi = 18, so the end dims keep their frequency
    (i <= 9) or take it over 128 (i >= 18), and a mid dim mixes the two;
    cos and sin are scaled by the attention factor."""
    yarn = dec.YaRN(128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    inv = dec.rope_inv_frequencies(64, 500000.0, yarn)
    f = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    lo = np.floor(64 * np.log(8192 / (32 * 2 * np.pi)) / (2 * np.log(500000.0)))
    hi = np.ceil(64 * np.log(8192 / (1 * 2 * np.pi)) / (2 * np.log(500000.0)))
    assert (lo, hi) == (9.0, 18.0)
    np.testing.assert_allclose(inv[:10], f[:10], rtol=1e-12)
    np.testing.assert_allclose(inv[18:], f[18:] / 128, rtol=1e-12)
    ramp = (13 - 9) / (18 - 9)
    np.testing.assert_allclose(inv[13], f[13] / 128 * ramp + f[13] * (1 - ramp), rtol=1e-12)
    published = (128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    np.testing.assert_allclose(ref.yarn_inv_frequencies(64, 500000.0, published), inv, rtol=1e-12)
    # the rotation of a head that is a unit vector along dim i (i < 32) at
    # position p: cos(p inv_i) on dim i and sin(p inv_i) on dim i + 32, each x 1.4852
    p, i = 37, 13
    x = np.zeros((1, 1, 1, 128), np.float32)
    x[..., i] = 1.0
    out = np.asarray(dec._rope_part(jnp.asarray(x), jnp.asarray([[p]]), 500000.0, 64, yarn))
    np.testing.assert_allclose(out[0, 0, 0, i], 1.4852030263919618 * np.cos(p * inv[i]), rtol=1e-5)
    np.testing.assert_allclose(out[0, 0, 0, i + 32], 1.4852030263919618 * np.sin(p * inv[i]), rtol=1e-5)
    assert float(np.abs(out[0, 0, 0, 64:]).max()) == 0.0


@pytest.mark.parametrize("change, error", [
    ({"gating": "per-tensor"}, "gating"),
    ({"moe_router_logit_softcapping": 30.0}, "moe_router_logit_softcapping"),
    ({"moe_apply_router_weight_on_input": True}, "moe_apply_router_weight_on_input"),
    ({"attention_bias": True}, "attention_bias"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"gating_types": ["per_head"] * 4 + ["per_tensor"]}, "gating_types"),
    ({"rope_parameters": {**HF["rope_parameters"], "full_attention": {
        "rope_type": "linear", "rope_theta": 500000, "factor": 4}}}, "rope_type 'linear'"),
    ({"layer_types": HF["layer_types"][:4]}, "describe fewer"),
    ({"mlp_layer_types": HF["mlp_layer_types"][:4]}, "describe fewer"),
    ({"num_attention_heads_per_layer": HF["num_attention_heads_per_layer"][:4]}, "describe fewer"),
    ({"mlp_layer_types": ["sparse"] * 5}, "mlp_only_layers"),
])
def test_the_reader_raises_on_what_it_does_not_implement(tmp_path, change, error):
    (tmp_path / "config.json").write_text(json.dumps({**HF, **change}))
    with pytest.raises((NotImplementedError, ValueError), match=error):
        dec.decoder_config_for(str(tmp_path))
    (tmp_path / "config.json").write_text(json.dumps(HF))
    assert dec.decoder_config_for(str(tmp_path)) == CFG
