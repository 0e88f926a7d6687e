"""What the decoder's tests hold the serving path against: the full causal
forward (``causal_lm_logits``), which keeps no cache.  The serving path
itself is reached the way a caller reaches it, through a
``GenerationScheduler``."""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

from pathway_tpu.models import decoder as dec
from pathway_tpu.serving.generation import GenerationScheduler


@functools.lru_cache(maxsize=None)
def _forward(cfg):
    return jax.jit(
        lambda tree, ids, lengths: dec.causal_lm_logits(
            tree, ids, lengths, cfg, serving=True
        )
    )


def reference_greedy(lm, prompt_ids, n: int) -> list[int]:
    """``n`` greedy tokens after ``prompt_ids``: a plain loop that runs the
    full forward again on the growing sequence and takes the argmax.  The
    sequence is padded to its final length so one program serves every
    step (what lies behind a position cannot reach it).  Stops at the
    model's EOS, which is not part of the answer."""
    seq = list(prompt_ids)
    total = len(seq) + n
    forward = _forward(lm.config)
    out: list[int] = []
    for _ in range(n):
        ids = np.zeros((1, total), np.int32)
        ids[0, : len(seq)] = seq
        logits = forward(lm.params, jnp.asarray(ids), jnp.asarray([len(seq)], jnp.int32))
        tok = int(np.argmax(np.asarray(logits)[0, len(seq) - 1]))
        if lm.eos_id is not None and tok == lm.eos_id:
            break
        out.append(tok)
        seq.append(tok)
    return out


def generate_ids(lm, prompts, *, seed: int = 0, scheduler=None, timeout: float = 120.0,
                 **sampling) -> list[list[int]]:
    """The generated ids of ``prompts`` served side by side by one
    scheduler (a fresh ``GenerationScheduler(lm, seed=seed, **scheduler)``,
    shut down afterwards).  All are queued before the first tick, so a run
    is the same every time: prompt ``i`` takes slot ``i``."""
    sched = GenerationScheduler(lm, seed=seed, **(scheduler or {}))
    try:
        with sched._lock:  # the worker's first tick waits for all of them
            futures = [sched.submit_ids(list(p), **sampling) for p in prompts]
        return [f.result(timeout=timeout) for f in futures]
    finally:
        sched.shutdown()


@functools.lru_cache(maxsize=None)
def _paged_programs(cfg):
    prefill = jax.jit(
        lambda tree, kp, vp, bt, ids, lens, start: dec.paged_prefill_chunk(
            tree, kp, vp, bt, ids, lens, start, cfg
        )
    )
    decode = jax.jit(
        lambda tree, kp, vp, bt, lens, tok: dec.paged_decode_step(
            tree, kp, vp, bt, lens, tok, cfg
        )
    )
    return prefill, decode


def paged_logits(tree, cfg, ids, cut: int, *, page: int = 4) -> np.ndarray:
    """The serving programs' logits over the rows ``ids [B, T]`` of a model
    of one kind: the first ``cut`` tokens of every row through one
    ``paged_prefill_chunk``, the rest fed one a ``paged_decode_step``.
    Returns ``[B, T - cut + 1, V]``: the next-token logits after ``cut``,
    ``cut + 1``, ... ``T`` tokens."""
    ids = np.asarray(ids, np.int32)
    B, T = ids.shape
    per_row = -(-T // page)
    k_pool, v_pool = dec.init_kv_pool(cfg, 1 + B * per_row, page)
    tables = jnp.asarray(1 + np.arange(B * per_row, dtype=np.int32).reshape(B, per_row))
    prefill, decode = _paged_programs(cfg)
    logits, k_pool, v_pool = prefill(
        tree, k_pool, v_pool, tables, jnp.asarray(ids[:, :cut]),
        jnp.full((B,), cut, jnp.int32), jnp.zeros((B,), jnp.int32),
    )
    outs = [np.asarray(logits)]
    for t in range(cut, T):
        logits, k_pool, v_pool = decode(
            tree, k_pool, v_pool, tables, jnp.full((B,), t, jnp.int32),
            jnp.asarray(ids[:, t]),
        )
        outs.append(np.asarray(logits))
    return np.stack(outs, axis=1)


def lower_program(sched, which: str, tree, k_pool, v_pool, *, width: int, rows=None,
                  table_width: int = 2, array=None):
    """The scheduler's ``"decode"`` step, or its ``"prefill"`` program over
    ``rows`` rows (every slot by default) of ``width`` tokens, lowered over
    ``tree`` and the pools: the arguments a tick passes, noughts, each made
    by ``array(shape, dtype)`` (``jnp.zeros`` by default; a shape with a
    sharding where nothing is to be allocated)."""
    array = array or jnp.zeros
    S = sched.slots

    def tables(n, prefill=False):
        bt = array((n, table_width), jnp.int32)
        if not sched._hybrid:
            return bt
        slot_of_row = (array((n,), jnp.int32),) if prefill and sched._ssm else ()
        return (bt, array((n, sched.ring_pages), jnp.int32), *slot_of_row)

    logits = array((S, sched.cfg.vocab_size), jnp.float32)
    carried = (array(sched._no_stats.shape, jnp.int32),) if sched._counted else ()
    if which == "decode":
        f32 = array((S,), jnp.float32)
        active = (array((S,), jnp.bool_),) if sched._counted else ()
        return sched._decode_fn.lower(
            tree, k_pool, v_pool, tables(S), array((S,), jnp.int32), logits,
            array((2,), jnp.uint32), f32, f32, f32, *active, *carried,
        )
    R = S if rows is None else rows
    lens = array((R,), jnp.int32)
    return sched._prefill_fn.lower(
        tree, k_pool, v_pool, tables(R, prefill=True), array((R, width), jnp.int32), lens, lens,
        logits, lens, array((R,), jnp.bool_), *carried,
    )


# an instruction of a compiled module's text: name, result dims, opcode
HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(", flags=re.M
)


def elements(dims: str) -> int:
    return int(np.prod([int(d) for d in dims.split(",")])) if dims else 1


def aliased_parameters(compiled_text: str) -> set[int]:
    """The parameters a compiled module's header says come out again in
    the buffer they went in (``input_output_alias``)."""
    header = compiled_text.splitlines()[0]
    assert "input_output_alias" in header, header[:300]
    return {int(p) for p in re.findall(r"\{\d+\}: \((\d+), \{\}", header)}
