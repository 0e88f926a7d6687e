"""A model whose layers are ONE part each (``model_type: nemotron_h``), at
the tiny preset ``pw-tiny-mamba-decoder``: every kind of layer Nemotron 3
Nano has, in its order (``MEMEM*EME``: Mamba-2 mixers of 8 heads in 2
groups with a chunk of 8 tokens, shorter than the prompts here; attention
without rotary whose 4 heads x 16 are not the hidden size 48; one of two
shares of 8 ungated relu^2 experts, top-3, route scale 2.5, beside a shared
expert), float32.

The scheduler's programs (chunked prefill through the chunked scan, then
the one-token recurrence of paged decode, the recurrent state carried in
the slot) are held to ``chipbench/reference/nemotron_h_decoder.py``: the
plain full forward with the Mamba-2 layer token by token, which imports
nothing of the program and draws its own weights from the seed.

Tolerances: the program and the reference compute the same float32 sums in
another order (a chunk's masked product against a token-by-token scan, a
grouped product against a loop over experts), so logits of magnitude ~5
agree to a few 1e-6; 3e-4 leaves two orders of room and is what the hybrid
model's tests use.  State and tail comparisons that say "bit-equal" are
``assert_array_equal``.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import nemotron_h_decoder as ref
from pathway_tpu.models import decoder as dec
from pathway_tpu.ops import ssm
from pathway_tpu.parallel.moe import MoEConfig, moe_serve
from pathway_tpu.serving.generation import GenerationScheduler

REPO = pathlib.Path(__file__).resolve().parents[1]
CFG = dec.decoder_config_for("pw-tiny-mamba-decoder")
HF = dec.TINY_MAMBA_HF
PAGE, SLOTS = 8, 3
TOL = dict(rtol=3e-4, atol=3e-4)


@pytest.fixture(scope="module")
def lm():
    return dec.DecoderLM("pw-tiny-mamba-decoder", max_cache=128)


@pytest.fixture(scope="module")
def ref_weights():
    return ref.init_weights(HF)


def _scheduler(lm, **kw):
    kw = {"slots": SLOTS, "page_size": PAGE, "prefill_chunk": 64, **kw}
    return GenerationScheduler(lm, **kw)


def _prompt(rng, n):
    return [int(t) for t in rng.integers(104, CFG.vocab_size, size=n)]


def _scalars():
    from pathway_tpu.engine.metrics import get_registry

    return dict(get_registry().scalar_metrics())


def test_the_preset_has_every_kind_of_layer_in_order():
    assert [(k.part, n) for k, n in CFG.runs] == [
        (part, 1) for part in
        ("mamba", "ffn", "mamba", "ffn", "mamba", "attention", "ffn", "mamba", "ffn")
    ]
    assert (CFG.ssm_layers, CFG.routed_layers, CFG.layers) == (4, 4, 9)
    attention = next(k for k, _n in CFG.runs if k.part == "attention")
    assert not attention.rope and attention.kv_heads == 2
    assert CFG.heads * CFG.head_dim != CFG.hidden
    assert (CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_groups, CFG.ssm_state) == (8, 8, 2, 16)
    assert CFG.ssm_chunk == 8 and CFG.ssm_conv_width == 64 + 2 * 2 * 16
    assert (CFG.experts, CFG.experts_published, CFG.experts_top_k) == (4, 8, 3)
    assert (CFG.experts_gated, CFG.experts_route_scale, CFG.experts_shared) == (False, 2.5, 80)


def test_reference_draws_the_weights_the_program_draws(lm, ref_weights):
    tree = lm.params
    for name in ("embed", "lm_head", "final_norm"):
        np.testing.assert_array_equal(np.asarray(tree[name]), np.asarray(ref_weights[name]))
    names = {"moe_router": "router", "moe_bias": "bias"}
    for run, ref_run in zip(tree["layers"], ref_weights["runs"]):
        assert {names.get(k, k) for k in run} == set(ref_run)
        for name, leaf in run.items():
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(ref_run[names.get(name, name)])
            )
    # seeded away from nought, or the comparisons below would not see them
    mamba, experts = tree["layers"][0], tree["layers"][1]
    for name in ("conv_b", "dt_bias", "A_log", "D"):
        assert float(jnp.abs(mamba[name]).mean()) > 0.05
    assert float(jnp.abs(experts["moe_bias"]).mean()) > 0.005


@pytest.mark.parametrize("T", [8, 16, 13, 37, 3])
def test_chunked_scan_is_the_token_by_token_recurrence(T):
    """Lengths that are and are not multiples of the chunk (8), one shorter
    than a chunk; a ragged batch (the second row's tail is padding: its
    time step is nought) continued from a state that is not nought."""
    rng = np.random.default_rng(T)
    S, NH, P, G, N = 2, 8, 4, 2, 16
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    x, B, C = draw(S, T, NH, P), draw(S, T, G, N), draw(S, T, G, N)
    lens = np.array([T, max(1, T - 5)])
    dt = jnp.abs(draw(S, T, NH)) * 0.1 * (np.arange(T)[None, :] < lens[:, None])[..., None]
    A = -jnp.asarray(rng.uniform(1, 16, size=NH), jnp.float32)
    state = draw(S, NH, P, N)
    with jax.default_matmul_precision("highest"):
        y, last = ssm.ssd_chunked(x, dt, A, B, C, state, 8)
    held, ys = state, []
    for t in range(T):
        y_t, held = ssm.ssm_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], held)
        ys.append(y_t)
    np.testing.assert_allclose(np.asarray(y), np.stack(ys, 1), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(last), np.asarray(held), rtol=1e-5, atol=1e-5)


class _Recorder:
    """Wraps the scheduler's two programs and keeps the logits each decode
    step gave, with the positions they belong to."""

    def __init__(self, sched):
        self.decode = []  # (seq_lens [S], active [S], logits [S, V])
        self.prefill_shapes = []
        decode_fn, prefill_fn = sched._decode_fn, sched._prefill_fn

        def decode(*args):
            out = decode_fn(*args)
            self.decode.append(
                (np.asarray(args[4]), np.asarray(args[10]), np.asarray(out[1]))
            )
            return out

        def prefill(*args):
            self.prefill_shapes.append(tuple(args[4].shape))
            return prefill_fn(*args)

        sched._decode_fn, sched._prefill_fn = decode, prefill


def _reference_logits(ref_weights, prompts, outs, new):
    width = max(len(p) for p in prompts) + new
    ids = np.zeros((len(prompts), width), np.int32)
    lengths = np.zeros(len(prompts), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        ids[i, : len(p) + len(o)] = p + o
        lengths[i] = len(p) + len(o)
    positions = np.stack([np.arange(len(p) - 1, len(p) - 1 + new) for p in prompts])
    return ref.logits_at(ref_weights, HF, ids, lengths, positions)


def test_scheduler_prefill_and_decode_agree_with_the_reference(lm, ref_weights):
    """Two rows at different positions in every step: a prompt of 70 tokens
    (two programs: a row of 64 = 8 chunks of the scan, then a narrow one)
    beside one of 13, 24 tokens each.  Every logit the decode steps gave,
    through the slot's recurrent state and the attention layer's pages,
    against the reference's full forward."""
    sched = _scheduler(lm)
    assert sched._ladder == (32, 64) and sched._ssm and sched.ring_pages == 0
    rec = _Recorder(sched)
    rng = np.random.default_rng(1)
    prompts, new = [_prompt(rng, 70), _prompt(rng, 13)], 24
    before = _scalars()
    try:
        futures = [sched.submit_ids(p, max_new_tokens=new) for p in prompts]
        outs = [f.result(timeout=300) for f in futures]
    finally:
        sched.shutdown()
    after = _scalars()
    assert (1, 64) in rec.prefill_shapes and (SLOTS, 32) in rec.prefill_shapes
    want = _reference_logits(ref_weights, prompts, outs, new)
    assert [list(w.argmax(-1)) for w in want] == outs
    checked = 0
    for seq_lens, active, logits in rec.decode:
        for slot, prompt in enumerate(prompts):
            step = seq_lens[slot] - len(prompt) + 1  # logits after this step's token
            if active[slot] and 0 < step < new:
                np.testing.assert_allclose(logits[slot], want[slot, step], **TOL)
                checked += 1
    assert checked == 2 * (new - 1)
    grew = lambda name: after[name] - before.get(name, 0.0)
    # no padding token or padding row leaked into a state; one reset a request
    assert grew("generate.ssm.prefill.tokens") == grew("generate.prefill.tokens") == 83
    assert grew("generate.ssm.decode.tokens") == 2 * new  # a step a token a row
    assert grew("generate.ssm.state.resets") == grew("generate.requests") == 2
    assert 0 < grew("generate.moe.decode.pairs") <= 2 * new * 4 * 3


@pytest.mark.parametrize("chunk", [64, 32, 16])
def test_a_prompt_prefilled_in_one_two_and_three_chunks_gives_the_same_logits(
        lm, ref_weights, chunk):
    """40 tokens through programs of 64, 32 + 8 and 16 + 16 + 8: the scan's
    state and the convolution's tail pass from one program to the next."""
    sched = _scheduler(lm, prefill_chunk=chunk)
    rec = _Recorder(sched)
    prompt, new = _prompt(np.random.default_rng(2), 40), 6
    try:
        out = sched.submit_ids(prompt, max_new_tokens=new).result(timeout=300)
    finally:
        sched.shutdown()
    assert len(rec.prefill_shapes) == {64: 1, 32: 2, 16: 3}[chunk]
    want = _reference_logits(ref_weights, [prompt], [out], new)
    assert list(want[0].argmax(-1)) == out
    for seq_lens, _active, logits in rec.decode:
        step = seq_lens[0] - len(prompt) + 1
        if 0 < step < new:
            np.testing.assert_allclose(logits[0], want[0, step], **TOL)


def _programs():
    prefill = jax.jit(
        lambda tree, kp, vp, bt, ids, lens, start: dec.paged_prefill_chunk(
            tree, kp, vp, bt, ids, lens, start, CFG, with_stats=True
        )
    )
    decode = jax.jit(
        lambda tree, kp, vp, bt, lens, tok, active: dec.paged_decode_step(
            tree, kp, vp, bt, lens, tok, CFG, active=active, with_stats=True
        )
    )
    return prefill, decode


def _state(kp, vp):
    """The recurrent state of every Mamba-2 run: (tails, scan states)."""
    runs = [r for r, (kind, _n) in enumerate(CFG.runs) if kind.part == "mamba"]
    return [np.asarray(kp[r]) for r in runs], [np.asarray(vp[r]) for r in runs]


def test_padding_rows_and_padding_tokens_leave_state_and_tail_bit_equal(lm):
    """A row's padding tokens move nothing (its state after 11 real tokens
    in a row 16 wide is its state after the same 11 in a row 12 wide), a
    row without a token is written back as it was read, in prefill and in
    decode, and a slot the program does not name is not touched."""
    prefill, decode = _programs()
    S = SLOTS
    tables = jnp.asarray(1 + np.arange(S * 8, dtype=np.int32).reshape(S, 8))
    no_rings = jnp.zeros((S, 0), jnp.int32)
    rng = np.random.default_rng(3)
    ids = rng.integers(104, CFG.vocab_size, size=(S, 16)).astype(np.int32)
    zeros = jnp.zeros((S,), jnp.int32)

    def fresh_pools():
        kp, vp = dec.init_kv_pool(CFG, 1 + S * 8, PAGE, S)
        # what a slot's last request left: noise, not noughts
        mess = lambda t, i: jnp.asarray(
            np.random.default_rng(i).normal(size=t.shape), t.dtype
        )
        return (
            tuple(mess(t, i) if k.part == "mamba" else t for i, (t, (k, _n)) in enumerate(zip(kp, CFG.runs))),
            tuple(mess(t, 50 + i) if k.part == "mamba" else t for i, (t, (k, _n)) in enumerate(zip(vp, CFG.runs))),
        )

    kp0, vp0 = fresh_pools()
    tails0, states0 = _state(kp0, vp0)
    lens = jnp.asarray([11, 0, 5], jnp.int32)
    rows = jnp.arange(S, dtype=jnp.int32)
    _lg, kp, vp, stats = prefill(
        lm.params, kp0, vp0, (tables, no_rings, rows), jnp.asarray(ids), lens, zeros
    )
    tails, states = _state(kp, vp)
    assert int(stats[2]) == 16  # the tokens the scan advanced a state by
    for t0, t1, s0, s1 in zip(tails0, tails, states0, states):
        np.testing.assert_array_equal(t1[:, 1], t0[:, 1])  # the row without a token
        np.testing.assert_array_equal(s1[:, 1], s0[:, 1])
        assert np.abs(s1[:, 0] - s0[:, 0]).max() > 0
    # the same 11 tokens in a narrower row, from other noise: the same state
    kp1, vp1 = fresh_pools()
    tails_1, states_1 = _state(kp1, vp1)
    narrow = prefill(
        lm.params, kp1, vp1, (tables[:1], no_rings[:1], rows[:1]),
        jnp.asarray(ids[:1, :12]), lens[:1], zeros[:1],
    )
    tails_n, states_n = _state(narrow[1], narrow[2])
    for t1, tn, s1, sn in zip(tails, tails_n, states, states_n):
        # another program shape sums in another order: equal to rounding
        np.testing.assert_allclose(tn[:, 0], t1[:, 0], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(sn[:, 0], s1[:, 0], rtol=1e-5, atol=1e-6)
    for was, now in zip(tails_1 + states_1, tails_n + states_n):
        np.testing.assert_array_equal(now[:, 1:], was[:, 1:])  # slots it does not name
    # a decode step in which slot 1 does not decode
    active = jnp.asarray([True, False, True])
    _lg, kp2, vp2, dstats = decode(
        lm.params, kp, vp, (tables, no_rings), lens, jnp.asarray(ids[:, 15]), active
    )
    tails2, states2 = _state(kp2, vp2)
    assert int(dstats[2]) == 2
    for t1, t2, s1, s2 in zip(tails, tails2, states, states2):
        np.testing.assert_array_equal(t2[:, 1], t1[:, 1])
        np.testing.assert_array_equal(s2[:, 1], s1[:, 1])
        assert np.abs(s2[:, 0] - s1[:, 0]).max() > 0
        np.testing.assert_array_equal(t2[:, 0, :-1], t1[:, 0, 1:])  # the tail moved by one column


def test_a_reused_slot_starts_from_zero_and_state_is_released_with_it(lm):
    """The second request of a slot reads nothing of the first's state: it
    answers as it does in a scheduler of its own.  The gauges count the
    taken slots' state and go back to nought."""
    rng = np.random.default_rng(4)
    first, second = _prompt(rng, 50), _prompt(rng, 9)
    sched = _scheduler(lm, slots=1)
    seen = []
    tick = sched._tick

    def watched():
        tick()
        seen.append(_scalars().get("generate.ssm.state.bytes", 0.0))

    sched._tick = watched
    try:
        out1 = sched.submit_ids(first, max_new_tokens=20).result(timeout=300)
        out2 = sched.submit_ids(second, max_new_tokens=8).result(timeout=300)
        snap = sched.snapshot()
    finally:
        sched.shutdown()
    per_slot = dec.ssm_state_bytes_per_slot(CFG)
    assert per_slot == 4 * (3 * 128 * 4 + 8 * 8 * 16 * 4) == snap["ssm_state_bytes_per_slot"]
    assert max(seen) == per_slot and seen[-1] == 0.0 and snap["ssm_state_bytes_live"] == 0
    fresh = _scheduler(lm, slots=1)
    try:
        assert fresh.submit_ids(second, max_new_tokens=8).result(timeout=300) == out2
        assert fresh.submit_ids(first, max_new_tokens=20).result(timeout=300) == out1
    finally:
        fresh.shutdown()


def test_run_ahead_with_a_row_that_ends_mid_flight(lm, ref_weights):
    """A row whose token turns out to be EOS has been given one step too
    many: that step advanced the slot's state once more and its token is
    dropped.  The row beside it is not disturbed, and the next request of
    the slot starts from noughts."""
    rng = np.random.default_rng(5)
    long_, short = _prompt(rng, 21), _prompt(rng, 9)
    new = 12
    plain = _scheduler(lm)
    try:
        futures = [plain.submit_ids(p, max_new_tokens=new) for p in (long_, short)]
        free_run = [f.result(timeout=300) for f in futures]
    finally:
        plain.shutdown()
    eos = free_run[1][4]  # the short row's fifth token ends it
    assert eos not in free_run[1][:4] and eos not in free_run[0]
    stopping = dec.DecoderLM("pw-tiny-mamba-decoder", max_cache=128, eos_id=eos)
    sched = _scheduler(stopping)
    before = _scalars()
    try:
        futures = [sched.submit_ids(p, max_new_tokens=new) for p in (long_, short)]
        outs = [f.result(timeout=300) for f in futures]
        again = sched.submit_ids(short, max_new_tokens=new).result(timeout=300)
    finally:
        sched.shutdown()
    after = _scalars()
    assert outs == [free_run[0], free_run[1][:4]] and again == free_run[1][:4]
    assert after["generate.decode.wasted"] - before.get("generate.decode.wasted", 0.0) >= 1
    # every decode step's routed layers loop over the experts they met
    assert after["generate.decode.steps"] - before.get("generate.decode.steps", 0.0) > 0
    assert after["generate.moe.decode.in_place"] == 1.0
    want = _reference_logits(ref_weights, [long_], [outs[0]], new)
    assert list(want[0].argmax(-1)) == outs[0]


def _published() -> dict:
    """The catalog row's ``config``: the benchmark's file with its four cut
    keys set back to what the file says was published."""
    body = json.loads((REPO / "chipbench/configs/nemotron-3-nano-bge-rag.json").read_text())
    reduced = body["chipbench"]["reduced"]
    hf = {k: v for k, v in body.items() if k != "chipbench"}
    pattern = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    assert hf["hybrid_override_pattern"] == pattern[:16]
    whole = {
        **hf, "hybrid_override_pattern": pattern,
        **{k: reduced[k][0] for k in ("num_hidden_layers", "n_routed_experts", "vocab_size")},
    }
    return {"cut": hf, "whole": {k: v for k, v in whole.items() if k not in (
        "n_routed_experts_published", "expert_shard_index", "expert_shards")}}


@pytest.mark.parametrize("which,params,held", [
    ("whole", 31_577_940_288, (23, 23, 6)), ("cut", 5_282_534_208, (7, 7, 2)),
])
def test_reader_gives_the_published_counts(which, params, held):
    cfg = dec.decoder_config_from_hf(_published()[which])
    parts = [k.part for k, n in cfg.runs for _ in range(n)]
    assert (parts.count("mamba"), parts.count("ffn"), parts.count("attention")) == held
    assert (cfg.hidden, cfg.heads, cfg.head_dim, cfg.kv_heads) == (2688, 32, 128, 2)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state) == (64, 64, 8, 128)
    assert (cfg.ssm_inner, cfg.ssm_conv_width, cfg.ssm_conv, cfg.ssm_chunk) == (4096, 6144, 4, 128)
    assert (cfg.experts_top_k, cfg.experts_route_scale, cfg.experts_shared) == (6, 2.5, 3712)
    assert (cfg.experts_published or cfg.experts) == 128 and cfg.dtype == jnp.bfloat16
    shapes = jax.eval_shape(lambda: dec.init_decoder_params(cfg))
    stored = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes))
    # an expert's 1,856 columns are stored as 1,920 (15 x 128 lanes): noughts
    padding = cfg.routed_layers * cfg.experts * 2 * 2688 * (1920 - 1856)
    assert stored - padding == params
    if which == "cut":
        assert dec.ssm_state_bytes_per_slot(cfg) == 7 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)


@pytest.mark.parametrize("change,match", [
    ({"hybrid_override_pattern": "MEMEM-EMEMEM*EME"}, "layers \\['-'\\]"),
    ({"moe_latent_size": 1024}, "moe_latent_size"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
    ({"time_step_limit": [0.0, 1.0]}, "time_step_limit"),
])
def test_reader_raises_on_what_it_does_not_implement(change, match):
    with pytest.raises(NotImplementedError, match=match):
        dec.decoder_config_from_hf({**_published()["cut"], **change})


def test_both_halves_and_the_shared_expert_once_are_the_uncut_layer():
    """The share tied to the model: the routed sums of the two shares of a
    tiny 2-share split (experts 0-3 and 4-7) plus the shared expert counted
    ONCE equal the uncut reference's whole layer; each share's own output
    carries the shared expert whole."""
    H, F, Fs, E, K, T = 16, 8, 12, 8, 3, 40
    keys = jax.random.split(jax.random.PRNGKey(7), 7)
    router = jax.random.normal(keys[0], (H, E), jnp.float32)
    bias = 0.3 * jax.random.normal(keys[1], (E,), jnp.float32)
    wu = jax.random.normal(keys[2], (E, H, F), jnp.float32) / 4
    wd = jax.random.normal(keys[3], (E, F, H), jnp.float32) / 3
    su = jax.random.normal(keys[4], (H, Fs), jnp.float32) / 4
    sd = jax.random.normal(keys[5], (Fs, H), jnp.float32) / 3
    x = jax.random.normal(keys[6], (T, H), jnp.float32)
    with jax.default_matmul_precision("highest"):
        shared = jnp.square(jnp.maximum(x @ su, 0.0)) @ sd
        whole = ref.routed_ffn(
            x, router, bias, wu, wd, top_k=K, first=0, route_scale=2.5
        ) + shared
        routed, pairs = 0.0, 0
        for share in range(2):
            held = slice(4 * share, 4 * share + 4)
            cfg = MoEConfig(
                hidden=H, experts=4, intermediate=F, top_k=K, scoring="sigmoid",
                router_width=E, first_expert=4 * share, gated=False, route_scale=2.5,
            )
            params = {"router": router, "bias": bias, "wu": wu[held], "wd": wd[held],
                      "shared_up": su, "shared_down": sd}
            y, n, _hit, _tiles = moe_serve(params, x, cfg)
            want = ref.routed_ffn(
                x, router, bias, wu[held], wd[held], top_k=K, first=4 * share,
                route_scale=2.5,
            ) + shared
            np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-5)
            routed, pairs = routed + (y - shared), pairs + int(n)
    assert pairs == T * K  # every pair computed on exactly one share: none dropped
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(whole), rtol=1e-5, atol=1e-5)


def test_an_expert_width_stored_as_whole_lanes_adds_nought():
    """1,856 columns are stored as 1,920: the added columns of ``wu`` and
    rows of ``wd`` are noughts, and relu(0)^2 through a row of noughts adds
    nothing.  Here 200 stored as 256."""
    assert [dec._lanes(w) for w in (40, 128, 200, 1856, 2048)] == [40, 128, 256, 1920, 2048]
    H, F, E, K, T = 16, 200, 4, 2, 24
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    cfg = MoEConfig(hidden=H, experts=E, intermediate=F, top_k=K, scoring="sigmoid", gated=False)
    params = {
        "router": jax.random.normal(keys[0], (H, E), jnp.float32),
        "wu": jax.random.normal(keys[1], (E, H, F), jnp.float32) / 4,
        "wd": jax.random.normal(keys[2], (E, F, H), jnp.float32) / 14,
    }
    x = jax.random.normal(keys[3], (T, H), jnp.float32)
    stored = {
        **params,
        "wu": jnp.pad(params["wu"], ((0, 0), (0, 0), (0, 56))),
        "wd": jnp.pad(params["wd"], ((0, 0), (0, 56), (0, 0))),
    }
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            np.asarray(moe_serve(stored, x, cfg)[0]), np.asarray(moe_serve(params, x, cfg)[0]),
            rtol=1e-6, atol=1e-6,
        )


def test_the_full_forward_says_it_does_not_take_a_layer_of_one_part(lm):
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="'mamba' layer is served"):
        dec.causal_lm_logits(lm.params, ids, jnp.asarray([8]), CFG, serving=True)


def test_a_config_json_of_the_model_type_is_read(tmp_path):
    (tmp_path / "config.json").write_text(json.dumps(HF))
    assert dec.decoder_config_for(str(tmp_path)) == CFG
    with pytest.raises(ValueError, match="nemotron_h"):
        dec.decoder_config_from_hf({"model_type": "gpt_neox"})
