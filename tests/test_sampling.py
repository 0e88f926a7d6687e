"""On-device sampling knobs (sample_logits: temperature, top-k, top-p).

Pinned: top_k=1 is argmax, tiny top_p is argmax, samples always fall in
the allowed truncated set, the first token always survives top-p, and
the serving surface is deterministic per seed.  Every knob is data a slot
of the scheduler's step: no value of it compiles anything.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models.decoder import DecoderLM, sample_logits
from pathway_tpu.serving.generation import GenerationScheduler
from tests.decoder_oracle import generate_ids, reference_greedy


def _logits(rng, b=64, v=32):
    return jnp.asarray(rng.normal(size=(b, v)) * 3.0, jnp.float32)


def test_top_k_1_and_tiny_top_p_are_argmax():
    lg = _logits(np.random.default_rng(0))
    want = np.argmax(np.asarray(lg), -1)
    key = jax.random.PRNGKey(0)
    np.testing.assert_array_equal(
        np.asarray(sample_logits(lg, key, jnp.float32(1.0), top_k=1)), want
    )
    np.testing.assert_array_equal(
        np.asarray(sample_logits(lg, key, jnp.float32(1.0), top_p=1e-9)), want
    )


def test_top_k_samples_stay_in_top_k_set():
    rng = np.random.default_rng(1)
    lg = _logits(rng)
    k = 5
    allowed = np.argsort(np.asarray(lg), -1)[:, -k:]
    for seed in range(8):
        toks = np.asarray(
            sample_logits(lg, jax.random.PRNGKey(seed), jnp.float32(1.0), top_k=k)
        )
        for b in range(lg.shape[0]):
            assert toks[b] in allowed[b]


def test_top_p_samples_stay_in_nucleus():
    rng = np.random.default_rng(2)
    lg = _logits(rng)
    p = 0.6
    probs = np.asarray(jax.nn.softmax(lg, -1))
    order = np.argsort(-probs, -1)
    for seed in range(8):
        toks = np.asarray(
            sample_logits(lg, jax.random.PRNGKey(seed), jnp.float32(1.0), top_p=p)
        )
        for b in range(lg.shape[0]):
            sorted_probs = probs[b][order[b]]
            before = np.cumsum(sorted_probs) - sorted_probs
            nucleus = set(order[b][before < p].tolist())
            assert int(toks[b]) in nucleus


def test_peaked_distribution_survives_top_p():
    # one token with ~all the mass: nucleus is that single token
    lg = jnp.full((2, 16), -10.0).at[:, 3].set(10.0)
    toks = sample_logits(lg, jax.random.PRNGKey(0), jnp.float32(1.0), top_p=0.5)
    assert toks.tolist() == [3, 3]


def test_boundary_top_p_zero_and_oversized_top_k():
    lg = _logits(np.random.default_rng(3), b=8, v=16)
    want = np.argmax(np.asarray(lg), -1)
    key = jax.random.PRNGKey(0)
    # top_p=0.0 degrades to argmax (top token forced alive), not an
    # empty distribution
    np.testing.assert_array_equal(
        np.asarray(sample_logits(lg, key, jnp.float32(1.0), top_p=0.0)), want
    )
    # oversized top_k clamps to the vocab (no truncation) instead of
    # crashing the trace
    toks = sample_logits(lg, key, jnp.float32(1.0), top_k=10_000)
    assert np.asarray(toks).shape == (8,)


def _programs(sched) -> int:
    return sched._decode_fn._cache_size() + sched._decode_history_fn._cache_size()


def _serve(sched, prompt, **sampling):
    return sched.submit_ids(prompt, max_new_tokens=4, **sampling).result(timeout=120)


def test_traced_top_p_shares_one_compile():
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    sched = GenerationScheduler(lm)
    try:
        _serve(sched, [5, 9], temperature=0.9, top_p=0.9)
        n = _programs(sched)
        _serve(sched, [5, 9], temperature=0.9, top_p=0.73)
        _serve(sched, [5, 9], temperature=0.9, top_p=0.42)
        assert _programs(sched) == n  # top_p is traced, not baked in
    finally:
        sched.shutdown()


def test_min_p_relative_cutoff():
    # peaked distribution: min_p keeps only tokens near the max
    lg = jnp.asarray([[10.0, 9.9, 5.0, 0.0]], jnp.float32)
    key = jax.random.PRNGKey(0)
    for seed in range(16):
        tok = int(
            sample_logits(lg, jax.random.PRNGKey(seed), jnp.float32(1.0), min_p=0.5)[0]
        )
        assert tok in (0, 1)  # token 2 is e^-5 of the max — cut
    # min_p > 1 degrades to argmax, never an empty distribution
    np.testing.assert_array_equal(
        np.asarray(sample_logits(lg, key, jnp.float32(1.0), min_p=5.0)), [0]
    )
    # min_p=0 is a no-op (full distribution reachable)
    seen = {
        int(sample_logits(lg * 0, jax.random.PRNGKey(s), jnp.float32(1.0), min_p=0.0)[0])
        for s in range(64)
    }
    assert len(seen) == 4


def test_min_p_generation_traced_and_deterministic():
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    a = generate_ids(lm, [[5, 9, 3]], max_new_tokens=6, temperature=0.9,
                     seed=3, min_p=0.1)
    b = generate_ids(lm, [[5, 9, 3]], max_new_tokens=6, temperature=0.9,
                     seed=3, min_p=0.1)
    assert a == b and len(a[0]) == 6
    sched = GenerationScheduler(lm)
    try:
        _serve(sched, [5, 9, 3], temperature=0.9, min_p=0.1)
        n = _programs(sched)
        _serve(sched, [5, 9, 3], temperature=0.9, min_p=0.4)
        assert _programs(sched) == n  # min_p traced, no recompile
    finally:
        sched.shutdown()


def test_repetition_penalty_discourages_repeats():
    from pathway_tpu.models.decoder import apply_repetition_penalty

    lg = jnp.asarray([[2.0, 1.9, -1.0, 0.5]], jnp.float32)
    seen = jnp.asarray([[True, False, True, False]])
    out = np.asarray(apply_repetition_penalty(lg, seen, jnp.float32(2.0)))
    np.testing.assert_allclose(out, [[1.0, 1.9, -2.0, 0.5]])
    # penalty 1.0 is a no-op
    np.testing.assert_allclose(
        np.asarray(apply_repetition_penalty(lg, seen, jnp.float32(1.0))),
        np.asarray(lg),
    )


def test_repetition_penalty_generation():
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    base = generate_ids(lm, [[5, 9, 3]], max_new_tokens=20)
    pen = generate_ids(
        lm, [[5, 9, 3]], max_new_tokens=20, repetition_penalty=1.8
    )
    # deterministic per config, and a strong penalty changes the greedy
    # chain while producing more distinct tokens than the base chain
    pen2 = generate_ids(
        lm, [[5, 9, 3]], max_new_tokens=20, repetition_penalty=1.8
    )
    assert pen == pen2
    assert pen != base
    assert len(set(pen[0])) >= len(set(base[0]))
    # data, a slot: other penalties and other k reuse the same program
    sched = GenerationScheduler(lm)
    try:
        _serve(sched, [5, 9, 3], repetition_penalty=1.8)
        n = _programs(sched)
        _serve(sched, [5, 9, 3], repetition_penalty=1.3)
        _serve(sched, [5, 9, 3], temperature=0.9, top_k=7)
        _serve(sched, [5, 9, 3], temperature=0.9, top_k=3, repetition_penalty=1.1)
        assert _programs(sched) == n
        # out-of-range values rejected at the edge (HF semantics)
        with pytest.raises(ValueError, match="repetition_penalty"):
            sched.submit_ids([5], max_new_tokens=2, repetition_penalty=0.0)
        with pytest.raises(ValueError, match="top_k"):
            sched.submit_ids([5], max_new_tokens=2, top_k=0)
    finally:
        sched.shutdown()


def test_generation_with_knobs_is_deterministic():
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    a = generate_ids(lm, [[5, 9, 3]], max_new_tokens=8, temperature=0.9,
                     seed=7, top_k=10, top_p=0.9)
    b = generate_ids(lm, [[5, 9, 3]], max_new_tokens=8, temperature=0.9,
                     seed=7, top_k=10, top_p=0.9)
    assert a == b
    c = generate_ids(lm, [[5, 9, 3]], max_new_tokens=8, temperature=0.9, seed=8,
                     top_k=10, top_p=0.9)
    assert len(c[0]) == 8


def test_top_k_is_data_a_row():
    """One program, a k a row: 0 and a k past the vocabulary cut nothing,
    every other row stays inside its own k largest."""
    lg = _logits(np.random.default_rng(4), b=6, v=32)
    ks = np.asarray([[1], [3], [0], [10_000], [32], [5]], np.int32)
    order = np.argsort(-np.asarray(lg), -1)
    draw = jax.jit(lambda key, k: sample_logits(lg, key, jnp.float32(1.0), top_k=k))
    hits = [set() for _ in range(6)]
    for seed in range(200):
        toks = np.asarray(draw(jax.random.PRNGKey(seed), jnp.asarray(ks)))
        for b, t in enumerate(toks):
            hits[b].add(int(t))
    assert draw._cache_size() == 1
    assert hits[0] == {int(order[0, 0])}
    assert hits[1] <= set(order[1, :3].tolist()) and len(hits[1]) > 1
    assert hits[5] <= set(order[5, :5].tolist())
    for b in (2, 3, 4):  # no cut: tokens below any small k are drawn too
        assert hits[b] - set(order[b, :5].tolist())


# ---------------------------------------------------------------------------
# top_k and the repetition penalty in the scheduler's batch: the step that
# carries what a slot has seen, beside the plain one
# ---------------------------------------------------------------------------

SMALL = dict(slots=4, page_size=8, pages=40, prefill_chunk=8)


def _watch(sched):
    """Counts the calls of the scheduler's two decode programs, with the
    rows each decoded for."""
    calls = {"plain": [], "history": []}
    plain, history = sched._decode_fn, sched._decode_history_fn

    def plain_step(*args):
        calls["plain"].append(None)
        return plain(*args)

    def history_step(*args):
        calls["history"].append(int(np.asarray(args[13]).sum()))  # active rows
        return history(*args)

    sched._decode_fn, sched._decode_history_fn = plain_step, history_step
    return calls


def test_top_k_penalised_and_greedy_rows_share_one_batch():
    """A ``top_k`` row, a penalised row and a plain greedy row decode side
    by side, each as it would alone; the greedy one is the full forward's
    argmax chain."""
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    rows = [
        ([5, 9, 3], dict(temperature=0.9, top_k=3)),
        ([7, 11, 2, 8], dict(repetition_penalty=1.8)),
        ([4, 19, 6], {}),
    ]
    new = 20
    sched = GenerationScheduler(lm, seed=5, **SMALL)
    calls = _watch(sched)
    try:
        with sched._lock:
            futures = [
                sched.submit_ids(p, max_new_tokens=new, **kw) for p, kw in rows
            ]
        together = [f.result(timeout=120) for f in futures]
    finally:
        sched.shutdown()
    assert not calls["plain"] and max(calls["history"]) == 3
    for slot, (prompt, kw) in enumerate(rows):
        # alone in the same slot of a scheduler with the same key stream
        before = [([1], dict(max_new_tokens=1))] * slot
        alone = GenerationScheduler(lm, seed=5, **SMALL)
        try:
            with alone._lock:
                for filler, fkw in before:
                    alone.submit_ids(filler, **fkw)
                future = alone.submit_ids(prompt, max_new_tokens=new, **kw)
            assert future.result(timeout=120) == together[slot], slot
        finally:
            alone.shutdown()
    assert together[2] == reference_greedy(lm, rows[2][0], new)
    assert together[1] != reference_greedy(lm, rows[1][0], new)  # the penalty bit
    assert len(set(together[0])) > 1


def test_penalty_counts_the_prompt_from_the_first_step():
    """``seen`` starts from the prompt: a prompt token that would be the
    first answer token is discouraged at once."""
    from pathway_tpu.models.decoder import apply_repetition_penalty, causal_lm_logits

    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, lm.config.vocab_size, size=(256, 24)).astype(np.int32)
    last = np.asarray(causal_lm_logits(
        lm.params, jnp.asarray(prompts), jnp.full((256,), 24), lm.config, serving=True
    ))[:, -1]
    repeats = [i for i in range(256) if last[i].argmax() in prompts[i]]
    assert repeats  # some prompt's greedy answer starts with one of its tokens
    prompt, logits = prompts[repeats[0]].tolist(), last[repeats[0]]
    seen = np.zeros(lm.config.vocab_size, bool)
    seen[prompt] = True
    want = int(np.asarray(apply_repetition_penalty(
        jnp.asarray(logits), jnp.asarray(seen), jnp.float32(50.0)
    )).argmax())
    assert want != int(logits.argmax())
    plain = generate_ids(lm, [prompt], max_new_tokens=1)[0]
    penalised = generate_ids(lm, [prompt], max_new_tokens=1, repetition_penalty=50.0)[0]
    assert plain == [int(logits.argmax())] and penalised == [want]


@pytest.mark.parametrize("k,like", [
    (1, dict(temperature=0.0)), (10_000, dict(temperature=0.9)),
], ids=["k_1_is_greedy", "oversized_k_is_no_cut"])
def test_top_k_boundaries_in_the_scheduler(k, like):
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    got = generate_ids(lm, [[5, 9, 3]], seed=2, max_new_tokens=12, temperature=0.9, top_k=k)
    assert got == generate_ids(lm, [[5, 9, 3]], seed=2, max_new_tokens=12, **like)


def test_readmitted_slot_does_not_inherit_seen():
    """One slot, two penalised requests in turn: the second's ``seen`` is
    its own prompt and tokens, and its answer what it gets alone."""
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    first, second, new = [5, 9, 3, 40, 41, 42], [7, 11], 16
    sched = GenerationScheduler(lm, **{**SMALL, "slots": 1})
    try:
        out1 = sched.submit_ids(first, max_new_tokens=new, repetition_penalty=1.8).result(120)
        out2 = sched.submit_ids(second, max_new_tokens=new, repetition_penalty=1.8).result(120)
        seen = np.flatnonzero(np.asarray(sched._seen[0]))
    finally:
        sched.shutdown()
    assert set(seen) == set(second) | set(out2)
    assert not set(first) <= set(seen)
    assert [out2] == generate_ids(
        lm, [second], max_new_tokens=new, repetition_penalty=1.8,
        scheduler={**SMALL, "slots": 1},
    )


def test_plain_batches_never_build_the_history_program():
    """The step every other batch runs is the plain one: the history
    program is built by the first request that asks, runs while that
    request lives, and the plain one takes over again after it."""
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    sched = GenerationScheduler(lm, **SMALL)
    history = sched._decode_history_fn
    calls = _watch(sched)
    try:
        _serve(sched, [5, 9, 3])
        _serve(sched, [5, 9, 3], temperature=0.9, top_p=0.8, min_p=0.05)
        _serve(sched, [5, 9, 3], repetition_penalty=1.0)  # no penalty at all
        assert history._cache_size() == 0 and sched._seen is None
        assert len(calls["plain"]) == 12 and not calls["history"]
        _serve(sched, [5, 9, 3], temperature=0.9, top_k=4)
        assert history._cache_size() == 1 and len(calls["history"]) == 4
        assert sched._history_slots == 0
        _serve(sched, [5, 9, 3])
        assert len(calls["plain"]) == 16 and len(calls["history"]) == 4
    finally:
        sched.shutdown()


def test_a_prefilling_slot_sees_nothing_of_the_steps_that_pass_it():
    """A penalised prompt that prefills over several ticks while another
    row decodes: the steps it sits out sample a token for its row too
    (from stale logits), and none of them joins its ``seen``."""
    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    prompt, new = list(range(100, 130)), 6  # four programs of 8
    sched = GenerationScheduler(lm, **{**SMALL, "slots": 2})
    calls = _watch(sched)
    try:
        with sched._lock:
            running = sched.submit_ids([5, 9, 3], max_new_tokens=30)
            late = sched.submit_ids(prompt, max_new_tokens=new, repetition_penalty=1.7)
        out = late.result(timeout=120)
        seen = set(np.flatnonzero(np.asarray(sched._seen[1])))
        running.result(timeout=120)
    finally:
        sched.shutdown()
    assert calls["history"][:3] == [1, 1, 1]  # the other row decoded alone meanwhile
    assert seen == set(prompt) | set(out)
    assert [out] == generate_ids(
        lm, [prompt], max_new_tokens=new, repetition_penalty=1.7,
        scheduler={**SMALL, "slots": 2},
    )
