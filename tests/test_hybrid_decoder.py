"""Layers of different kinds in one model (``DecoderConfig.runs``), at the
tiny preset ``pw-tiny-hybrid-decoder``: every kind MiMo-V2.5 has (pattern
G, W, W, W, W, G, W; the first layer dense, the rest routed; window 24,
longer than the 8-token pages and shorter than the sequences here; key
heads 24 wide beside value heads of 16; 1 KV head in global layers, 2 in
window layers; rotary on 8 of 24 dims; a sink logit in window layers; 16
sigmoid-routed experts of which this share holds 4, top-4).

The scheduler's programs (prefill through table and ring, then paged
decode) are held to ``chipbench/reference/mimo_decoder.py``: the plain
full forward, which imports nothing of the program and draws its own
weights from the seed.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import mimo_decoder as ref
from pathway_tpu.models import decoder as dec
from pathway_tpu.parallel.moe import MoEConfig, moe_serve
from pathway_tpu.serving.generation import GenerationScheduler

CFG = dec.decoder_config_for("pw-tiny-hybrid-decoder")
HF = dec.TINY_HYBRID_HF
PAGE, SLOTS = 8, 3


@pytest.fixture(scope="module")
def lm():
    return dec.DecoderLM("pw-tiny-hybrid-decoder", max_cache=128)


@pytest.fixture(scope="module")
def ref_weights():
    return ref.init_weights(HF)


def _scheduler(lm, **kw):
    return GenerationScheduler(
        lm, slots=SLOTS, page_size=PAGE, prefill_chunk=64, **kw
    )


def _prompt(rng, n):
    return [int(t) for t in rng.integers(104, CFG.vocab_size, size=n)]


def test_the_preset_has_every_kind_of_layer():
    kinds = [kind for kind, _n in CFG.runs]
    assert [n for _k, n in CFG.runs] == [1, 4, 1, 1] and CFG.layers == 7
    assert [k.window for k in kinds] == [None, 24, None, 24]
    assert [k.routed for k in kinds] == [False, True, True, True]
    assert [k.kv_heads for k in kinds] == [1, 2, 1, 2]
    assert [k.sink for k in kinds] == [False, True, False, True]
    assert (CFG.head_dim, CFG.v_dim, CFG.rotary_dim) == (24, 16, 8)
    assert CFG.heads * CFG.head_dim != CFG.hidden
    assert (CFG.experts, CFG.experts_published, CFG.experts_top_k) == (4, 16, 4)


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


def test_full_forward_agrees_with_the_reference(lm, ref_weights):
    """(a), full-forward half: ``decoder_layer`` by kind, scanned run by
    run, against the reference at every position."""
    rng = np.random.default_rng(0)
    ids = rng.integers(104, CFG.vocab_size, size=(2, 60)).astype(np.int32)
    lengths = np.asarray([60, 41], np.int32)
    got = dec.causal_lm_logits(
        lm.params, jnp.asarray(ids), jnp.asarray(lengths), CFG, serving=True
    )
    positions = np.tile(np.arange(41), (2, 1))
    want = ref.logits_at(ref_weights, HF, ids, lengths, positions)
    np.testing.assert_allclose(np.asarray(got)[:, :41], want, rtol=2e-4, atol=2e-4)


class _Recorder:
    """Wraps the scheduler's two programs and keeps the logits each gave,
    with the positions they belong to."""

    def __init__(self, sched):
        self.sched = sched
        self.decode = []  # (seq_lens [S], active [S], logits [S, V])
        decode_fn, prefill_fn = sched._decode_fn, sched._prefill_fn

        def decode(*args):
            out = decode_fn(*args)
            self.decode.append(
                (np.asarray(args[4]), np.asarray(args[10]), np.asarray(out[1]))
            )
            return out

        self.prefill_shapes = []

        def prefill(*args):
            self.prefill_shapes.append(tuple(args[4].shape))
            return prefill_fn(*args)

        sched._decode_fn, sched._prefill_fn = decode, prefill


def test_scheduler_prefill_and_decode_agree_with_the_reference(lm, ref_weights):
    """(a): a prompt longer than the ring (70 > 4 pages x 8) whose first
    program's row (64) is wider than the ring, beside one that ends inside
    a page (13), in two slots of different lengths: every logit the decode
    steps produced, through ring and table, against the full forward."""
    sched = _scheduler(lm)
    assert sched.ring_pages == 4 and sched._ladder == (32, 64)
    rec = _Recorder(sched)
    rng = np.random.default_rng(1)
    prompts = [_prompt(rng, 70), _prompt(rng, 13)]
    new = 24
    try:
        futures = [sched.submit_ids(p, max_new_tokens=new) for p in prompts]
        outs = [f.result(timeout=300) for f in futures]
    finally:
        sched.shutdown()
    assert (1, 64) in rec.prefill_shapes and (SLOTS, 32) in rec.prefill_shapes
    assert all(len(o) == new for o in outs)
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
                np.testing.assert_allclose(
                    logits[slot], want[slot, step], rtol=3e-4, atol=3e-4
                )
                checked += 1
    assert checked == 2 * (new - 1)


def test_shares_add_up_to_the_uncut_routed_layer():
    """(b), the share test: the partial sums of the 4 shares that hold 4
    experts each add up to what the uncut reference gives for the layer,
    and each share is what the reference gives for that share."""
    H, F, E, K, T = 16, 8, 16, 4, 40
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    router = jax.random.normal(keys[0], (H, E), jnp.float32)
    bias = 0.3 * jax.random.normal(keys[1], (E,), jnp.float32)
    wg = jax.random.normal(keys[2], (E, H, F), jnp.float32) / 4
    wu = jax.random.normal(keys[3], (E, H, F), jnp.float32) / 4
    wd = jax.random.normal(keys[4], (E, F, H), jnp.float32) / 3
    x = jax.random.normal(keys[5], (T, H), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.routed_ffn(x, router, bias, wg, wu, wd, top_k=K, first=0)
        total, pairs = 0.0, 0
        for share in range(4):
            held = slice(4 * share, 4 * share + 4)
            cfg = MoEConfig(
                hidden=H, experts=4, intermediate=F, top_k=K, scoring="sigmoid",
                router_width=E, first_expert=4 * share,
            )
            params = {"router": router, "bias": bias, "wg": wg[held], "wu": wu[held], "wd": wd[held]}
            y, n, _hit, _tiles = moe_serve(params, x, cfg)
            want = ref.routed_ffn(
                x, router, bias, wg[held], wu[held], wd[held], top_k=K, first=4 * share
            )
            np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-5)
            total, pairs = total + y, pairs + int(n)
    assert pairs == T * K  # every pair computed on exactly one share: none dropped
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=1e-5, atol=1e-5)


def test_ring_stays_its_size_and_release_returns_the_pages(lm):
    """(c): whatever the context, a slot's window cache is its ring; the
    global layers' pages come from the allocator and go back."""
    from pathway_tpu.engine.metrics import get_registry

    sched = _scheduler(lm)
    ring = sched.ring_pages
    k_pools = sched._k_pool
    for (kind, n), pool in zip(CFG.runs, k_pools):
        pages = 1 + SLOTS * ring if kind.window else sched.num_pages
        assert pool.shape == (n, pages, PAGE, kind.kv_heads, CFG.head_dim)
    assert sched._v_pool[1].shape[-1] == CFG.v_dim
    seen = []
    tick = sched._tick

    def watched():
        tick()
        with sched._lock:
            seen.append((sched._ring_pages_in_use(), sched.allocator.used_pages))

    sched._tick = watched
    rng = np.random.default_rng(2)
    first = _prompt(rng, 50)
    try:
        out1 = sched.submit_ids(first, max_new_tokens=60).result(timeout=300)
        assert max(r for r, _g in seen) == ring  # 110 tokens, 14 pages in the table
        assert max(g for _r, g in seen) == -(-(50 + 60) // PAGE)
        gauges = get_registry().scalar_metrics()
        assert gauges["generate.kv.pages.window"] == 0.0
        assert gauges["generate.kv.pages.global"] == 0.0
        assert sched.allocator.used_pages == 0 and sched.allocator.reserved == 0
        # the slot's ring still holds the first answer's tokens: a second
        # request in the same slot reads none of them
        short = _prompt(rng, 5)
        again = sched.submit_ids(short, max_new_tokens=8).result(timeout=300)
        assert max(r for r, _g in seen) == ring
    finally:
        sched.shutdown()
    fresh = _scheduler(lm)
    try:
        assert fresh.submit_ids(short, max_new_tokens=8).result(timeout=300) == again
        assert fresh.submit_ids(first, max_new_tokens=60).result(timeout=300) == out1
    finally:
        fresh.shutdown()
    snap = sched.snapshot()
    assert snap["ring_pages_per_slot"] == ring and snap["pages_used"] == 0
    assert snap["kv_bytes_peak"] == (
        14 * PAGE * dec.kv_bytes_per_token(CFG, growing_only=True)
        + dec.kv_ring_bytes_per_slot(CFG, PAGE)
    )


def test_routing_counters_count_every_pair_once(lm):
    """The two ``pairs`` counters add up to what the routing gives this
    share, read from the reference's own choice; ``experts_hit`` stays
    inside the experts held."""
    from pathway_tpu.engine.metrics import get_registry

    def scalars():
        return dict(get_registry().scalar_metrics())

    sched = _scheduler(lm)
    rng = np.random.default_rng(3)
    prompt, new = _prompt(rng, 37), 6
    before = scalars()
    try:
        out = sched.submit_ids(prompt, max_new_tokens=new).result(timeout=300)
    finally:
        sched.shutdown()
    after = scalars()

    def grew(name):
        return after[name] - before.get(name, 0.0)

    tokens = len(prompt) + new  # each through every routed layer once
    pairs = grew("generate.moe.prefill.pairs") + grew("generate.moe.decode.pairs")
    # of the 4 experts a token chooses among 16, this share holds 4: a
    # quarter of the pairs on average, all of them at most
    assert 0 < pairs <= tokens * CFG.routed_layers * CFG.experts_top_k
    assert abs(pairs / (tokens * CFG.routed_layers) - 1.0) < 0.5
    steps = grew("generate.decode.steps")
    assert steps == new
    # every step's routed layers loop over the experts they met (8 rows)
    assert after["generate.moe.decode.in_place"] == 1.0
    assert grew("generate.moe.decode.pairs") <= steps * CFG.routed_layers * CFG.experts_top_k
    assert 0 < grew("generate.moe.decode.experts_hit") <= steps * CFG.routed_layers * CFG.experts
    assert 0 < grew("generate.moe.prefill.experts_hit") <= (
        grew("generate.prefill.chunks") * CFG.routed_layers * CFG.experts
    )
    assert grew("generate.kv.window.slots_released") == 1
    assert 0 < grew("generate.kv.window.pages_released") <= sched.ring_pages
    assert len(out) == new


# sha256 of ``jit(...).lower(...).as_text()`` of the scheduler's two
# programs for ``pw-tiny-decoder``: a Mistral model is one run and lowers
# to the program it did.  Pinned at the commit before layer kinds (dba011d)
# and moved once since, by PR 32, which changed the text by design: the
# pools ride in the layer scan's carry and are donated, and a barrier
# stands between each of ``wq`` / ``wk`` / ``wv``'s products and its split
# into heads.  That the programs still compute the same is held token for
# token against ``causal_lm_logits`` (``tests/decoder_oracle.py``)
MISTRAL_TINY_LOWERED = {
    "decode": "c38a3dd9a9a88fb1fb26da4c3bb25f26eed519c7cdd2ac91ee79e4ea3a85ad1e",
    "prefill": "e3ef90174d0b21f91de3c266f8ba769df0b50d951b5cf0b4c7be142388452ca4",
}


def test_mistral_programs_lower_as_before():
    """(d)"""
    lm = dec.DecoderLM("pw-tiny-decoder", max_cache=64)
    sched = GenerationScheduler(lm, slots=4, page_size=8, pages=32, prefill_chunk=8)
    S, G = 4, 2
    tables = jnp.zeros((S, G), jnp.int32)
    lens = jnp.zeros((S,), jnp.int32)
    f = jnp.zeros((S,), jnp.float32)
    decode = sched._decode_fn.lower(
        lm.params, sched._k_pool, sched._v_pool, tables, lens, sched._logits,
        jax.random.PRNGKey(0), f, f, f,
    ).as_text()
    prefill = sched._prefill_fn.lower(
        lm.params, sched._k_pool, sched._v_pool, tables, jnp.zeros((S, 8), jnp.int32),
        lens, lens, sched._logits, jnp.arange(S, dtype=jnp.int32), jnp.zeros((S,), bool),
    ).as_text()
    sched.shutdown()
    assert hashlib.sha256(decode.encode()).hexdigest() == MISTRAL_TINY_LOWERED["decode"]
    assert hashlib.sha256(prefill.encode()).hexdigest() == MISTRAL_TINY_LOWERED["prefill"]
    # and its caches are as they were: one pool, one table
    assert sched._k_pool.shape == (2, 32, 8, 2, 16) and not sched.ring_pages


def test_quantize_raises_for_layer_kinds(lm):
    with pytest.raises(NotImplementedError, match="scheduler's path"):
        dec.quantize_decoder_tree(lm.params)


def test_penalised_and_top_k_rows_beside_a_plain_row(lm):
    """The history-carrying step of a model that counts its routing (the
    ``active`` mask and the carried counts ride behind ``seen``): the plain
    row is the full forward's argmax chain, the others what they get
    alone, and the routing is counted as by the plain step."""
    from pathway_tpu.engine.metrics import get_registry
    from tests.decoder_oracle import generate_ids, reference_greedy

    rng = np.random.default_rng(6)
    rows = [
        (_prompt(rng, 30), dict(repetition_penalty=1.6)),
        (_prompt(rng, 9), {}),
        (_prompt(rng, 12), dict(temperature=0.8, top_k=1)),
    ]
    new = 12
    sched = _scheduler(lm)
    pairs = "generate.moe.decode.pairs"
    before = get_registry().scalar_metrics().get(pairs, 0.0)
    try:
        with sched._lock:
            futures = [sched.submit_ids(p, max_new_tokens=new, **kw) for p, kw in rows]
        outs = [f.result(timeout=300) for f in futures]
        assert sched._decode_fn._cache_size() == 0
        assert sched._decode_history_fn._cache_size() >= 1
    finally:
        sched.shutdown()
    assert get_registry().scalar_metrics()[pairs] > before
    assert outs[1] == reference_greedy(lm, rows[1][0], new)
    assert outs[2] == reference_greedy(lm, rows[2][0], new)  # k = 1
    small = dict(slots=SLOTS, page_size=PAGE, prefill_chunk=64)
    assert [outs[0]] == generate_ids(
        lm, [rows[0][0]], max_new_tokens=new, scheduler=small, **rows[0][1]
    )
    assert outs[0] != reference_greedy(lm, rows[0][0], new)


def test_unknown_model_type_raises(tmp_path):
    import json

    for model_type in ("gpt_neox", None):
        config = {"hidden_size": 64, "num_attention_heads": 4}
        if model_type:
            config["model_type"] = model_type
        (tmp_path / "config.json").write_text(json.dumps(config))
        with pytest.raises(ValueError, match=f"model_type {model_type!r}"):
            dec.decoder_config_for(str(tmp_path))
    for model_type in ("mistral", "llama", "mixtral"):
        (tmp_path / "config.json").write_text(json.dumps({"model_type": model_type, "num_hidden_layers": 3}))
        assert dec.decoder_config_for(str(tmp_path)).layers == 3
    (tmp_path / "config.json").write_text(json.dumps(HF))
    assert dec.decoder_config_for(str(tmp_path)) == CFG
    (tmp_path / "config.json").write_text(json.dumps({**HF, "n_shared_experts": 1}))
    with pytest.raises(NotImplementedError, match="n_shared_experts"):
        dec.decoder_config_for(str(tmp_path))


def test_top_shows_pairs_a_token_and_experts_hit_a_step():
    from pathway_tpu.internals.top import render_top

    generation = {
        "generate.slots.total": 8.0, "generate.tokens": 640.0,
        "generate.prefill.tokens": 3900.0, "generate.decode.steps": 600.0,
        "generate.moe.decode.pairs": 3840.0, "generate.moe.prefill.pairs": 23400.0,
        "generate.moe.decode.experts_hit": 3720.0,
        "generate.moe.decode.in_place": 1.0,
    }
    assert (
        "experts: 6.0 pair(s) a token · 6.2 hit a decode step · steps in place"
        in render_top({"generation": generation})
    )
    generation["generate.moe.decode.in_place"] = 0.0  # more than 128 slots
    assert "hit a decode step · steps grouped" in render_top({"generation": generation})
    dense = {k: v for k, v in generation.items() if ".moe." not in k}
    assert "experts:" not in render_top({"generation": dense})


def test_top_shows_the_real_share_of_the_grouped_kernels_rows():
    from pathway_tpu.internals.top import render_top

    generation = {
        "generate.slots.total": 8.0, "generate.tokens": 640.0,
        "generate.prefill.tokens": 3900.0, "generate.decode.steps": 600.0,
        "generate.moe.decode.pairs": 3840.0, "generate.moe.prefill.pairs": 23400.0,
        "generate.moe.decode.experts_hit": 3720.0,
        "generate.moe.decode.in_place": 1.0,
    }
    assert "tile rows" not in render_top({"generation": generation})
    generation["generate.moe.prefill.tile_rows"] = 83200.0
    assert (
        "steps in place · 28% of prefill tile rows real"
        in render_top({"generation": generation})
    )


def test_prefill_span_carries_the_routed_pairs(lm):
    from pathway_tpu.engine import tracing

    sched = _scheduler(lm)
    trace = tracing.RequestTrace("/v2/answer")
    try:
        with tracing.trace_scope(trace):
            future = sched.submit_ids(_prompt(np.random.default_rng(6), 40), max_new_tokens=2)
        future.result(timeout=300)
    finally:
        sched.shutdown()
    (span,) = [s for s in trace.spans if s["name"] == "generate.prefill"]
    assert 0 < span["attributes"]["pairs"] <= 40 * CFG.routed_layers * CFG.experts_top_k


def test_prefill_counts_the_grouped_kernels_tile_rows(lm, monkeypatch):
    """Prompts that share one prefill program of 8 rows x 32 (256 rows:
    the grouped path) count ``generate.moe.prefill.tile_rows``: at least
    their pairs, and exactly the tiles of 128 that each routed layer's
    groups touch, read from the groups the program routed; a dense
    model's scheduler counts none."""
    from pathway_tpu.engine.metrics import get_registry
    from pathway_tpu.parallel import moe

    groups = []
    count = moe.row_tiles

    def recorded(group_sizes):
        jax.debug.callback(lambda g: groups.append(np.asarray(g)), group_sizes)
        return count(group_sizes)

    monkeypatch.setattr(moe, "row_tiles", recorded)
    names = ("generate.moe.prefill.tile_rows", "generate.moe.prefill.pairs")

    def counted(model_lm, prompts):
        before = {n: get_registry().scalar_metrics().get(n, 0.0) for n in names}
        sched = GenerationScheduler(model_lm, slots=8, page_size=PAGE, prefill_chunk=64)
        try:
            with sched._lock:
                futures = [sched.submit_ids(p, max_new_tokens=3) for p in prompts]
            for f in futures:
                f.result(timeout=300)
        finally:
            sched.shutdown()
        after = get_registry().scalar_metrics()
        return [after.get(n, 0.0) - before[n] for n in names]

    rng = np.random.default_rng(9)
    tile_rows, pairs = counted(lm, [_prompt(rng, n) for n in (20, 27, 31)])
    assert len(groups) >= CFG.routed_layers
    formula = 0
    for sizes in groups:
        ends = np.cumsum(sizes)
        formula += 128 * sum(
            -(-e // 128) - (e - s) // 128 for s, e in zip(sizes, ends) if s
        )
    assert tile_rows == formula >= pairs > 0
    dense = dec.DecoderLM("pw-tiny-decoder", max_cache=128)
    assert counted(dense, [_prompt(rng, 20)]) == [0.0, 0.0]
