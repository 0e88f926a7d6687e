"""Prefill shaped by the prompt that waits (ISSUE 28).

The scheduler gives its prefill program rows and width from what is left
of the waiting prompts: one row at the smallest rung of a short ladder
that covers a long remainder, every slot at the narrowest rung for short
ones.  These tests hold the rule as a pure function, that the ladder
computes what the fixed ``[slots, 32]`` program computed, that two
waiting prompts replay the one program a single prompt compiled, the
counters that say it engages, and that a one-row prefill touches no page
of a slot that is decoding.  All drive ``_tick()`` / ``_run_prefill()``
by hand: no worker thread, no sleeps.
"""

from __future__ import annotations

import pytest

jax = pytest.importorskip("jax")

import numpy as np  # noqa: E402

from pathway_tpu.engine import metrics as em  # noqa: E402
from pathway_tpu.engine import tracing  # noqa: E402
from pathway_tpu.engine.profiler import install_jax_accounting  # noqa: E402
from pathway_tpu.models import decoder as dec  # noqa: E402
from pathway_tpu.serving import generation  # noqa: E402
from pathway_tpu.serving.generation import prefill_ladder, prefill_shape  # noqa: E402
from tests.decoder_oracle import reference_greedy  # noqa: E402

MODEL = "pw-tiny-decoder-long"
MAX_CACHE = 256
WIDEST = 128  # ladder (32, 128): small enough for the CPU, two rungs


@pytest.fixture(scope="module")
def lm():
    """The tiny float32 preset with room for prompts past the widest rung."""
    import dataclasses

    dec.PRESETS[MODEL] = dataclasses.replace(dec.PRESETS["pw-tiny-decoder"], max_len=512)
    try:
        yield dec.DecoderLM(MODEL, max_cache=MAX_CACHE)
    finally:
        del dec.PRESETS[MODEL]


def _scheduler(lm, prefill_chunk: int | None, slots: int = 2):
    return generation.GenerationScheduler(
        lm, slots=slots, page_size=16, pages=64, prefill_chunk=prefill_chunk,
        queue_limit=16,
    )


@pytest.fixture(scope="module")
def ladder_sched(lm):
    sched = _scheduler(lm, WIDEST)
    yield sched
    sched.shutdown()


@pytest.fixture(scope="module")
def fixed_sched(lm):
    """One rung of 32: the fixed ``[slots, 32]`` program of before."""
    sched = _scheduler(lm, 32)
    yield sched
    sched.shutdown()


def _prompt(seed: int, n: int) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(1, 500, n)]


def _idle(sched) -> bool:
    with sched._lock:
        return not sched._queue and all(s is None for s in sched._slots)


def _drive(sched, max_ticks: int = 600) -> None:
    for _ in range(max_ticks):
        if _idle(sched):
            return
        sched._tick()
    raise AssertionError("scheduler did not drain")


def _admit(sched, req) -> int:
    """Queue ``req`` and admit it; the slot it was given."""
    with sched._lock:
        sched._queue.append(req)
        sched._admit(0.0)
        return next(i for i, s in enumerate(sched._slots) if s is not None and s.req is req)


def _prefill_by_hand(sched, row: int) -> np.ndarray:
    """Run the slot's prefill programs alone and return its logits."""
    for _ in range(64):
        if sched._run_prefill([row]):
            return np.asarray(sched._logits[row])
    raise AssertionError("prefill did not end")


def _scalars() -> dict[str, float]:
    return em.get_registry().scalar_metrics()


# ---------------------------------------------------------------------------
# (ii) the shape rule as a pure function
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "widest, ladder",
    [
        (512, (32, 256, 512)),
        (2048, (32, 512, 1024, 2048)),
        (1024, (32, 256, 512, 1024)),
        (256, (32, 256)),
        (128, (32, 128)),
        (64, (32, 64)),
        (32, (32,)),
        (16, (16,)),
        (8, (8,)),
        (4, (4,)),
    ],
)
def test_prefill_ladder_derives_from_the_widest_rung(widest, ladder):
    assert prefill_ladder(widest) == ladder
    assert len(ladder) <= 4 and ladder[-1] == widest


@pytest.mark.parametrize(
    "remaining, ladder, slots, shape",
    [
        (1, (32, 256, 512), 8, (8, 32)),
        (32, (32, 256, 512), 8, (8, 32)),
        (33, (32, 256, 512), 8, (1, 256)),
        (256, (32, 256, 512), 8, (1, 256)),
        (257, (32, 256, 512), 8, (1, 512)),
        (350, (32, 256, 512), 8, (1, 512)),
        (430, (32, 256, 512), 8, (1, 512)),
        (512, (32, 256, 512), 8, (1, 512)),
        (513, (32, 256, 512), 8, (1, 512)),
        (5000, (32, 256, 512), 8, (1, 512)),
        (1, (8,), 2, (2, 8)),
        (20, (8,), 2, (2, 8)),
        (20, (4,), 1, (1, 4)),
        (100, (32, 128), 2, (1, 128)),
        (12, (32, 128), 2, (2, 32)),
    ],
)
def test_prefill_shape_rows_and_width_from_remaining_tokens(remaining, ladder, slots, shape):
    assert prefill_shape(remaining, ladder, slots) == shape


def test_ladder_is_capped_by_the_cache_and_explicit_chunk_is_the_only_rung(lm):
    default = _scheduler(lm, None)
    small = _scheduler(dec.shared_decoder("pw-tiny-decoder", max_cache=64), None)
    explicit = _scheduler(lm, 8)
    try:
        assert default.prefill_chunk == 512 and default._ladder == (32, 256)
        # the default widest rung is cut to what a slot can hold
        assert small.prefill_chunk == 512 and small._ladder == (32, 64)
        assert explicit._ladder == (8,)
    finally:
        for sched in (default, small, explicit):
            sched.shutdown()


# ---------------------------------------------------------------------------
# (i) the ladder computes what the fixed 32-wide program computed
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("beside_decoding", [False, True], ids=["alone", "beside-decoding"])
@pytest.mark.parametrize(
    "prompt_len",
    [100, 128, 140, 200],
    ids=["shorter", "widest", "widest-and-narrow-tail", "longer"],
)
def test_ladder_prefill_equals_fixed_width_prefill(
    ladder_sched, fixed_sched, prompt_len, beside_decoding
):
    """Same logits at the end of the prompt (the float32 tolerance of
    ``tests/test_paged_decoder.py``) and the same greedy tokens, for a
    prompt under, at and over the widest rung, with the other slot empty
    or decoding."""
    logits, tokens, programs = [], [], []
    for sched in (ladder_sched, fixed_sched):
        other = None
        if beside_decoding:
            other = generation.GenRequest(_prompt(1, 9), 40)
            _admit(sched, other)
            for _ in range(3):
                sched._tick()
            assert other.first_token_at is not None and not other.future.done()
        chunks = _scalars().get("generate.prefill.chunks", 0.0)
        req = generation.GenRequest(_prompt(prompt_len, prompt_len), 8)
        logits.append(_prefill_by_hand(sched, _admit(sched, req)))
        programs.append(_scalars()["generate.prefill.chunks"] - chunks)
        _drive(sched)
        tokens.append(
            (req.future.result(timeout=5), other and other.future.result(timeout=5))
        )
    np.testing.assert_allclose(logits[0], logits[1], rtol=2e-4, atol=2e-4)
    assert tokens[0] == tokens[1]
    assert programs == [-(-prompt_len // WIDEST), -(-prompt_len // 32)]


# ---------------------------------------------------------------------------
# (iii) two prompts waiting together replay one prompt's programs
# ---------------------------------------------------------------------------


def test_two_waiting_prompts_compile_nothing_a_single_prompt_did_not(lm):
    """The benchmark warms with ONE answer; two answers that wait
    together in the window must find every program compiled."""
    assert install_jax_accounting(force=True)
    sched = _scheduler(lm, WIDEST, slots=4)
    try:
        warm = generation.GenRequest(_prompt(3, 100), 8)
        _admit(sched, warm)
        _drive(sched)
        before = _scalars()
        pair = [generation.GenRequest(_prompt(4 + n, 90 + 20 * n), 8) for n in range(2)]
        with sched._lock:
            sched._queue.extend(pair)
        sched._tick()
        sched._tick()
        # both prefilled in the first tick, each alone at the 128 rung, and
        # the second read the decode step that the first enqueued behind them
        assert all(r.first_token_at is not None for r in pair)
        _drive(sched)
        after = _scalars()
        assert after["generate.prefill.chunks"] - before["generate.prefill.chunks"] == 2.0
        assert after["jax.cache.miss"] - before.get("jax.cache.miss", 0.0) == 0.0
        assert after["jax.compile.count"] - before.get("jax.compile.count", 0.0) == 0.0
        assert all(len(r.future.result(timeout=5)) == 8 for r in pair)
    finally:
        sched.shutdown()


# ---------------------------------------------------------------------------
# (iv) the counters and attributes that say it engages
# ---------------------------------------------------------------------------


def test_prefill_counters_and_width_attributes(ladder_sched):
    sched = ladder_sched
    before = _scalars()
    since = tracing.timeline()[-1]["end"] if tracing.timeline() else 0.0
    traces = [tracing.RequestTrace("/v1/generate") for _ in range(3)]
    wide = generation.GenRequest(_prompt(7, 100), 3, trace=traces[0])
    short = generation.GenRequest(_prompt(8, 5), 3, trace=traces[1])
    with sched._lock:
        sched._queue.extend([wide, short])
    _drive(sched)
    tailed = generation.GenRequest(_prompt(9, 140), 3, trace=traces[2])
    with sched._lock:
        sched._queue.append(tailed)
    _drive(sched)
    after = _scalars()
    delta = {
        k: after[f"generate.prefill.{k}"] - before.get(f"generate.prefill.{k}", 0.0)
        for k in ("chunks", "tokens", "padded")
    }
    # [1,128] for 100; [2,32] for 5; [1,128] then [2,32] for 128 + 12
    assert delta == {
        "chunks": 4.0,
        "tokens": 100.0 + 5.0 + 140.0,
        "padded": 28.0 + 59.0 + 0.0 + 52.0,
    }
    enqueues = [
        (r["attributes"]["rows"], r["attributes"]["width"])
        for r in tracing.timeline(since=since)
        if r["name"] == "tick.prefill.enqueue" and r["start"] >= since
    ]
    assert enqueues == [(1, 128), (2, 32), (1, 128), (2, 32)]
    for trace, (chunks, width, prompt_len) in zip(
        traces, [(1, 128, 100), (1, 32, 5), (2, 128, 140)]
    ):
        (span,) = [s for s in trace.spans if s["name"] == "generate.prefill"]
        attributes = span["attributes"]
        assert (attributes["chunks"], attributes["width"], attributes["prompt_len"]) == (
            chunks, width, prompt_len,
        )


def test_top_shows_the_prefill_line():
    from pathway_tpu.internals.top import render_top

    text = render_top(
        {
            "generation": {
                "generate.slots.total": 8.0,
                "generate.prefill.chunks": 20.0,
                "generate.prefill.tokens": 7800.0,
                "generate.prefill.padded": 2440.0,
            }
        }
    )
    assert "prefill: 20 program(s) · 7800 prompt token(s) · 24% of rows padding" in text


# ---------------------------------------------------------------------------
# (v) the null-page rule at one row
# ---------------------------------------------------------------------------


def test_one_row_prefill_leaves_a_decoding_slots_pages_alone(lm, ladder_sched):
    """The one-row program holds the prefilling slot's block table only,
    and its padding queries (100 real tokens of 128) write to the null
    page: the decoding slot's pages, logits and length are bit-for-bit
    what they were."""
    sched = ladder_sched
    decoding = generation.GenRequest(_prompt(11, 20), 40)
    row = _admit(sched, decoding)
    for _ in range(4):
        sched._tick()
    with sched._lock:
        pages = list(sched._slots[row].pages)
        seq_len = sched._slots[row].seq_len
    assert pages and not decoding.future.done()
    pools_before = [np.asarray(sched._k_pool), np.asarray(sched._v_pool)]
    logits_before = np.asarray(sched._logits[row])
    newcomer = generation.GenRequest(_prompt(12, 100), 4)
    new_row = _admit(sched, newcomer)
    assert new_row != row
    _prefill_by_hand(sched, new_row)
    with sched._lock:
        new_pages = list(sched._slots[new_row].pages)
    assert not set(new_pages) & set(pages) and 0 not in new_pages
    # what may change: the newcomer's first 100 positions and the null page
    allowed = np.zeros(pools_before[0].shape[1:3], bool)
    allowed[0] = True
    for position in range(100):
        allowed[new_pages[position // 16], position % 16] = True
    for before, pool in zip(pools_before, (sched._k_pool, sched._v_pool)):
        changed = (before != np.asarray(pool)).any(axis=(0, 3, 4))
        assert not (changed & ~allowed).any()
        assert changed[new_pages[0]].all() and not changed[pages].any()
    np.testing.assert_array_equal(np.asarray(sched._logits[row]), logits_before)
    assert sched._slots[row].seq_len == seq_len
    _drive(sched)
    assert decoding.future.result(timeout=5) == reference_greedy(
        lm, decoding.prompt_ids, 40
    )
    assert newcomer.future.result(timeout=5) == reference_greedy(
        lm, newcomer.prompt_ids, 4
    )
