"""Paged KV cache vs the full causal forward (ISSUE 18 tentpole pins).

The paged path must be the full forward's attention rearranged through a
block table: same math, same mask semantics, memory that scales with live
tokens.  These tests pin (a) the page allocator's reservation/accounting
contract, (b) scatter/gather correctness including null-page routing for
out-of-table positions, and (c) logits equivalence of paged prefill +
decode against ``causal_lm_logits``, which keeps no cache, on ragged
batches.
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from pathway_tpu.models import decoder as dec  # noqa: E402
from pathway_tpu.ops import attention as attention_ops  # noqa: E402

CFG = dec.decoder_config_for("pw-tiny-decoder")


# ---------------------------------------------------------------------------
# PageAllocator
# ---------------------------------------------------------------------------


def test_allocator_basic_accounting():
    a = dec.PageAllocator(9, page_size=4, bytes_per_token=10)
    assert a.free_pages == 8  # page 0 reserved as the null page
    assert a.used_pages == 0 and a.live_bytes == 0 and a.peak_bytes == 0
    assert a.pages_for(1) == 1
    assert a.pages_for(4) == 1
    assert a.pages_for(5) == 2
    assert a.pages_for(0) == 1  # empty prompt still holds one token

    a.reserve(3)
    assert a.reserved == 3
    pages = [a.alloc() for _ in range(3)]
    assert a.reserved == 0
    assert 0 not in pages  # the null page is never handed out
    assert a.used_pages == 3
    assert a.live_bytes == 3 * 4 * 10
    a.release(pages)
    assert a.used_pages == 0 and a.live_bytes == 0
    assert a.peak_bytes == 3 * 4 * 10  # high-water mark survives release


def test_allocator_reservation_bounds_admission():
    a = dec.PageAllocator(5, page_size=2, bytes_per_token=1)
    assert a.can_reserve(4)
    a.reserve(4)
    assert not a.can_reserve(1)
    with pytest.raises(dec.PageExhaustedError):
        a.reserve(1)
    # a slot that finishes early returns its unused reservation too
    p = a.alloc()
    a.release([p], unreserve=3)
    assert a.reserved == 0 and a.free_pages == 4


def test_allocator_exhaustion_raises():
    a = dec.PageAllocator(3, page_size=2, bytes_per_token=1)
    a.reserve(2)
    a.alloc()
    a.alloc()
    with pytest.raises(dec.PageExhaustedError):
        a.alloc(reserved=False)


def test_allocator_rejects_degenerate_pool():
    with pytest.raises(ValueError):
        dec.PageAllocator(1, page_size=2, bytes_per_token=1)


def test_kv_bytes_per_token():
    expected = (
        2 * CFG.layers * CFG.kv_heads * CFG.head_dim
        * jnp.dtype(CFG.dtype).itemsize
    )
    assert dec.kv_bytes_per_token(CFG) == expected


# ---------------------------------------------------------------------------
# scatter / gather
# ---------------------------------------------------------------------------


def _tiny_pool(num_pages=6, page=4, kh=2, d=3):
    shape = (num_pages, page, kh, d)
    return jnp.zeros(shape, jnp.float32)


def test_scatter_gather_roundtrip():
    rng = np.random.default_rng(0)
    pool = _tiny_pool()
    page = 4
    # slot 0 uses pages [1, 2]; slot 1 uses pages [3]
    bt = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    # write 3 tokens at slot 0 positions [0,1,5] and 2 at slot 1 [0,1]
    positions = jnp.asarray([[0, 1, 5], [0, 1, 1]], jnp.int32)
    values = jnp.asarray(rng.normal(size=(2, 3, 2, 3)), jnp.float32)
    pool = attention_ops.scatter_kv_pages(pool, bt, positions, values)
    got = attention_ops.gather_kv_pages(pool, bt)  # [S, 8, KH, D]
    np.testing.assert_allclose(got[0, 0], values[0, 0])
    np.testing.assert_allclose(got[0, 1], values[0, 1])
    np.testing.assert_allclose(got[0, 5], values[0, 2])
    # same-position scatter takes the last write (set semantics)
    np.testing.assert_allclose(got[1, 0], values[1, 0])
    np.testing.assert_allclose(got[1, 1], values[1, 2])
    # untouched positions stay zero
    assert float(jnp.abs(got[0, 2:5]).sum()) == 0.0


def test_scatter_out_of_table_routes_to_null_page():
    """Positions beyond the block-table width must land in page 0 (the
    null page), NEVER wrap into a slot's live pages — ragged prefill
    padding would otherwise corrupt real cached tokens."""
    pool = _tiny_pool()
    page = 4
    bt = jnp.asarray([[1, 2]], jnp.int32)  # covers positions [0, 8)
    live = jnp.ones((1, 1, 2, 3), jnp.float32) * 7.0
    pool = attention_ops.scatter_kv_pages(
        pool, bt, jnp.asarray([[3]], jnp.int32), live
    )
    # position 9 is past the table: slot_of = 2 >= G
    garbage = jnp.ones((1, 1, 2, 3), jnp.float32) * 99.0
    pool = attention_ops.scatter_kv_pages(
        pool, bt, jnp.asarray([[9]], jnp.int32), garbage
    )
    got = attention_ops.gather_kv_pages(pool, bt)
    np.testing.assert_allclose(np.asarray(got[0, 3]), 7.0)
    # live pages untouched by the OOB write...
    assert float(jnp.abs(got[0, 4:]).sum()) == 0.0
    # ...which landed in the null page instead
    assert float(jnp.abs(pool[0, 1]).sum()) == float(2 * 3 * 99.0)


def test_null_block_table_entries_gather_null_page():
    pool = _tiny_pool()
    pool = pool.at[2].set(5.0)  # a "stale" page some other slot owns
    bt = jnp.asarray([[1, 0]], jnp.int32)  # entry 1 is null
    got = attention_ops.gather_kv_pages(pool, bt)
    # positions [4, 8) come from the null page: zeros, not page 2's 5.0
    assert float(jnp.abs(got[0, 4:]).sum()) == 0.0


# ---------------------------------------------------------------------------
# paged vs full-forward equivalence
# ---------------------------------------------------------------------------


def _last_logits(tree, ids, lens):
    """The full forward's logits at each row's last real token."""
    logits = dec.causal_lm_logits(
        tree, jnp.asarray(ids), jnp.asarray(lens, jnp.int32), CFG, serving=True
    )
    return np.asarray(logits)[np.arange(len(lens)), np.asarray(lens) - 1]


def _alloc_tables(lens, max_tokens, page, num_pages):
    """Contiguous host-side page assignment, the scheduler's shape."""
    G = -(-max_tokens // page)
    bt = np.zeros((len(lens), G), np.int32)
    nxt = 1
    for s, n in enumerate(lens):
        for g in range(-(-n // page)):
            bt[s, g] = nxt
            nxt += 1
    assert nxt <= num_pages
    return jnp.asarray(bt)


@pytest.mark.parametrize("chunk", [64, 5])
def test_paged_prefill_matches_dense(chunk):
    """Full-prompt and chunked paged prefill must match the full
    forward's logits at each row's last token on a ragged batch (chunked
    prefill is full prefill split along the query axis)."""
    tree = dec.init_decoder_params(CFG, seed=3)
    lens = [7, 12, 1]
    S = len(lens)
    rng = np.random.default_rng(1)
    ids = np.zeros((S, max(lens)), np.int32)
    for s, n in enumerate(lens):
        ids[s, :n] = rng.integers(1, CFG.vocab_size, n)

    dense_logits = _last_logits(tree, ids, lens)

    page = 4
    num_pages = 16
    k_pool, v_pool = dec.init_kv_pool(CFG, num_pages, page)
    bt = _alloc_tables(lens, 32, page, num_pages)
    done = [0] * S
    logits = None
    while any(done[s] < lens[s] for s in range(S)):
        cids = np.zeros((S, chunk), np.int32)
        clens = np.zeros(S, np.int32)
        starts = np.zeros(S, np.int32)
        take = np.zeros(S, bool)
        for s in range(S):
            n = min(chunk, lens[s] - done[s])
            if n <= 0:
                continue
            cids[s, :n] = ids[s, done[s]:done[s] + n]
            clens[s] = n
            starts[s] = done[s]
            take[s] = done[s] + n >= lens[s]
        new_logits, k_pool, v_pool = dec.paged_prefill_chunk(
            tree, k_pool, v_pool, bt, jnp.asarray(cids),
            jnp.asarray(clens), jnp.asarray(starts), CFG,
        )
        logits = (
            new_logits if logits is None
            else jnp.where(jnp.asarray(take)[:, None], new_logits, logits)
        )
        for s in range(S):
            done[s] += int(clens[s])

    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(dense_logits), rtol=2e-4, atol=2e-4
    )


def test_paged_decode_matches_dense_greedy():
    """Greedy continuation after prefill on a ragged batch: at every one
    of many steps the paged decode step's logits are the full forward's
    over the sequence so far, and it picks the same token."""
    tree = dec.init_decoder_params(CFG, seed=5)
    lens = [5, 9]
    S = len(lens)
    rng = np.random.default_rng(2)
    cache_len = 32
    seqs = np.zeros((S, cache_len), np.int32)
    for s, n in enumerate(lens):
        seqs[s, :n] = rng.integers(1, CFG.vocab_size, n)

    page = 4
    k_pool, v_pool = dec.init_kv_pool(CFG, 24, page)
    bt = _alloc_tables([cache_len] * S, cache_len, page, 24)
    p_logits, k_pool, v_pool = dec.paged_prefill_chunk(
        tree, k_pool, v_pool, bt, jnp.asarray(seqs[:, :max(lens)]),
        jnp.asarray(lens), jnp.zeros(S, jnp.int32), CFG,
    )

    pos = np.asarray(lens, np.int64)
    for step in range(10):
        d_logits = _last_logits(tree, seqs, pos)
        np.testing.assert_allclose(
            np.asarray(p_logits), d_logits, rtol=2e-4, atol=2e-4, err_msg=f"step {step}"
        )
        p_tok = np.asarray(jnp.argmax(p_logits, axis=-1))
        np.testing.assert_array_equal(p_tok, d_logits.argmax(-1), err_msg=f"step {step}")
        seqs[np.arange(S), pos] = p_tok
        p_logits, k_pool, v_pool = dec.paged_decode_step(
            tree, k_pool, v_pool, bt, jnp.asarray(pos, jnp.int32),
            jnp.asarray(p_tok, jnp.int32), CFG,
        )
        pos += 1


def test_paged_pool_scales_with_live_tokens():
    """The acceptance pin's accounting basis: a churny trace's peak pages
    stay far below the dense slots x max_cache worst case."""
    bpt = dec.kv_bytes_per_token(CFG)
    slots, max_cache, page = 8, 128, 16
    a = dec.PageAllocator(40, page, bpt)
    # 8 concurrent short requests (prompt+output ~24 tokens each)
    held = []
    for _ in range(slots):
        need = a.pages_for(24)
        a.reserve(need)
        held.append([a.alloc() for _ in range(need)])
    dense = slots * max_cache * bpt
    assert a.peak_bytes <= dense // 4
    for pages in held:
        a.release(pages)


# ---------------------------------------------------------------------------
# Scope names in the serving programs (ISSUE 26): metadata only
# ---------------------------------------------------------------------------

SCOPES = (
    "attn.qkv", "kv.write", "attn.paged", "attn.out", "mlp.gate_up", "mlp.down", "lm_head",
)


def _serving_program(which: str):
    """The scheduler's jitted body, on the tiny preset's shapes."""
    S, T, pages, page = 2, 4, 9, 8
    tree = dec.init_decoder_params(CFG, seed=0)
    kp, vp = dec.init_kv_pool(CFG, pages, page)
    bt = jnp.zeros((S, 2), jnp.int32)
    lens = jnp.zeros((S,), jnp.int32)
    if which == "decode":
        fn = lambda t, k, v: dec.paged_decode_step(t, k, v, bt, lens, lens, CFG)  # noqa: E731
    else:
        ids = jnp.zeros((S, T), jnp.int32)
        fn = lambda t, k, v: dec.paged_prefill_chunk(t, k, v, bt, ids, lens, lens, CFG)  # noqa: E731
    return jax.jit(fn).lower(tree, kp, vp)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_scope_names_are_metadata_only(which, monkeypatch):
    import contextlib
    import re

    def instructions(lowered) -> int:
        text = lowered.compile().as_text()
        return len(re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = ", text, flags=re.M))

    scoped = _serving_program(which)
    names = scoped.as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in names, scope
    with_scopes = instructions(scoped)
    monkeypatch.setattr(jax, "named_scope", lambda _name: contextlib.nullcontext())
    bare = _serving_program(which)
    assert "attn.qkv" not in bare.as_text(debug_info=True)
    assert with_scopes == instructions(bare) and with_scopes > 50


# ---------------------------------------------------------------------------
# the compiled programs touch of the pools what a slot holds
# ---------------------------------------------------------------------------


def _compiled_program(model: str, which: str, pages: int):
    """The scheduler's compiled decode or prefill program for ``model`` over
    pools of ``pages`` pages, with the pools' shapes and the position of
    the first of them among the program's parameters."""
    from pathway_tpu.serving.generation import GenerationScheduler
    from tests.decoder_oracle import lower_program

    lm = dec.shared_decoder(model, max_cache=64)
    sched = GenerationScheduler(lm, slots=3, page_size=8, pages=pages, prefill_chunk=16)
    try:
        lowered = lower_program(
            sched, which, lm.params, sched._k_pool, sched._v_pool, width=16
        )
        pools = jax.tree_util.tree_leaves((sched._k_pool, sched._v_pool))
        first = len(jax.tree_util.tree_leaves(lm.params))
        return lowered.compile().as_text(), [p.shape for p in pools], first
    finally:
        sched.shutdown()


@pytest.mark.parametrize("which", ["decode", "prefill"])
@pytest.mark.parametrize("model", ["pw-tiny-decoder", "pw-tiny-hybrid-decoder"])
def test_a_program_neither_copies_nor_slices_a_pool(model, which):
    """A step's cost must not follow the pool: at two pool sizes, no
    instruction of the compiled program that moves data whole (a slice, an
    update of a slice, a copy, a broadcast) yields a layer's pool or a
    whole pool, and every pool goes in and comes out in one buffer.

    One thing is let through: a ``copy`` of a window layer's ring pool.
    The ring is read before it is written, and the CPU backend, which
    drops the order between a gather and a scatter that share no value,
    keeps it by copying; the TPU's compiler orders the two and copies
    nothing (``tests/test_tpu_compiled_step.py`` holds the step compiled
    for the chip).  A ring's pool is as large as the slots' windows, not
    as the pages the scheduler was given."""
    from tests.decoder_oracle import HLO_INSTRUCTION, aliased_parameters, elements

    movers = ("dynamic-slice", "dynamic-update-slice", "copy", "broadcast")

    def sizes(shapes):
        whole = {int(np.prod(shape)) for shape in shapes}
        return whole | {int(np.prod(shape[1:])) for shape in shapes}

    for pages in (37, 61):  # primes: no other array of the program has such a size
        text, pools, first = _compiled_program(model, which, pages)
        rings = sizes([shape for shape in pools if shape[1] != pages])
        moved = [
            (op, dims) for _name, dims, op in HLO_INSTRUCTION.findall(text)
            if op in movers and (n := elements(dims)) in sizes(pools)
            and not (op == "copy" and n in rings)
        ]
        assert not moved, moved
        assert set(range(first, first + len(pools))) <= aliased_parameters(text)
