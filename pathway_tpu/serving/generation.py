"""Continuous-batching generation scheduler over the paged KV cache.

The one generation path: every request, whatever its sampling, decodes
here.  A static batch would make every request wait for the slowest row
of its batch and every arrival for the batch to drain, over a dense KV
cache that pays ``B × max_cache`` regardless of live tokens; this is the
vLLM/Ragged-Paged-Attention serving shape instead (PAPERS.md):

* **Slots** — a fixed device batch of ``S`` generation slots.  At every
  decode step, finished/lapsed rows are evicted immediately and queued
  requests are admitted into the freed slots — continuous batching.
* **Paged KV** — each slot's cache lives in fixed-size pages of the
  preallocated pool (``models/decoder.py::init_kv_pool``), allocated
  lazily as tokens arrive and freed at eviction, so KV memory scales
  with live tokens.  Admission reserves a request's worst case up front:
  the pool can never OOM mid-generation; requests queue (bounded) at
  the edge instead.
* **Recurrent state** — a Mamba-2 layer keeps no cache that grows: a slot
  holds, a layer, the convolution's last columns and the scan's float32
  state, of fixed size (``models/decoder.py::init_kv_pool``).  It is the
  third kind of state in the one manager, beside the pages and the rings:
  it lives in the same pools the programs carry and donate, belongs to the
  slot from admission to release, and starts from noughts with the
  prompt's first chunk, inside the program, whatever the slot's last
  request or a step that ran ahead left there.  Padding rows and padding
  tokens do not move it.
* **Chunked prefill** — a prompt prefills in programs shaped by what is
  left of it (:func:`prefill_shape`): alone, as one row of the smallest
  rung of a short ladder of widths that covers it, so the weights are
  read once for a prompt that fits the widest rung and no padded slot is
  multiplied by them; slots with little left (one-token requests, tails)
  share one ``[slots, narrowest]`` program.  Prefill interleaves with
  decode ticks, so a long prompt cannot stall every other request's
  token cadence for longer than one widest-rung program per waiting
  prompt (no head-of-line blocking; pinned by the ``request_churn``
  chaos test).
* **Deadlines** — requests carry the PR 17 :class:`engine.serving
  .Deadline`; a row that lapses mid-generation is shed at the next tick
  and counted under ``serve.deadline.exceeded{where=decode}``.
* **The decode step runs one ahead of the host's read** — a step samples
  its token on the device from logits that never leave it, so the host
  needs step N's tokens only to hand them out and to see an EOS.  A tick
  therefore enqueues step N+1 first and reads step N while N+1 runs: the
  device works through the host's part of a tick instead of waiting for
  it.  The depth is one, always.  A row that ends by count is not given
  a step it cannot use; a row whose token turns out to be EOS, or that
  its deadline evicted, has been given one step too many, and that
  step's token is dropped (``generate.decode.wasted``).  A step carries
  the requests it decoded for, so a slot released and taken again
  meanwhile never receives the stale token, and the newcomer's prefill,
  later in the device's order, overwrites what the stale step wrote.
* **Sampling is data, a slot** — temperature, ``top_p``, ``min_p``,
  ``top_k`` and the repetition penalty ride into the step as one value a
  slot, so requests of any mix share a batch and no value compiles
  anything.  The last two read more than the step's logits (a cut at the
  k-th largest; the tokens a slot has seen, kept on the device), so they
  have a step program of their own, and a tick takes it only while some
  live slot asked for either: the step every other batch runs is not
  touched by them.

Every device program has a static shape: slot count fixed, prefill
rows and width one of the few :func:`prefill_ladder` allows, block-table
width bucketed to powers of two — a churning request mix replays warm
compiled programs (``jax.cache.miss == 0`` steady-state, pinned in
``tests/test_jax_accounting.py``).
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from concurrent.futures import Future
from typing import Any, NamedTuple

import numpy as np

from pathway_tpu.engine import tracing
from pathway_tpu.internals.config import env_int

__all__ = [
    "GenRequest",
    "GenerationScheduler",
    "prefill_ladder",
    "prefill_shape",
    "reset_shared_schedulers",
    "shared_scheduler",
]


def _pow2_bucket(n: int, cap: int) -> int:
    b = 1
    while b < n and b < cap:
        b <<= 1
    return min(b, cap)


# Timed on a v5e at Mistral-7B widths, 24 layers (PERF.md, PR 28): one row
# of 32, 64, 128 or 256 tokens costs 19.0 / 19.2 / 19.9 / 22.6 ms, about
# one read of the weights whatever the width, and from there the width is
# paid for: 512 cost 38.6 ms, 1024 82.2.  So rungs halve from the widest
# down to 256 and no further, and under them lies the one narrow rung
# whose program every slot shares (8 slots x 32 = 256 rows, 24.5 ms).
_NARROW_RUNG = 32
_PAID_FROM = 256
_MAX_RUNGS = 4


def prefill_ladder(widest: int) -> tuple[int, ...]:
    """The widths a prefill program may take, ascending, derived from the
    most prompt tokens one program holds: ``512 -> (32, 256, 512)``.  A
    small ``widest`` (the tests' 4 and 8) is the only rung."""
    rungs = [widest]
    while len(rungs) < _MAX_RUNGS - 1 and rungs[-1] // 2 >= _PAID_FROM:
        rungs.append(rungs[-1] // 2)
    if widest > _NARROW_RUNG:
        rungs.append(_NARROW_RUNG)
    return tuple(reversed(rungs))


def prefill_shape(remaining: int, ladder: tuple[int, ...], slots: int) -> tuple[int, int]:
    """``(rows, width)`` of the program that prefills a slot with
    ``remaining`` prompt tokens left: the smallest rung that covers them
    (the widest for a longer prompt, which takes several programs), as
    one row of its own; at the narrowest rung, as one of ``slots`` rows
    shared with every other slot that has as little left."""
    width = next((r for r in ladder if r >= remaining), ladder[-1])
    return (slots if width == ladder[0] else 1), width


class GenRequest:
    """One queued/running generation request."""

    __slots__ = (
        "prompt_ids", "max_new_tokens", "temperature", "top_p", "min_p",
        "top_k", "repetition_penalty", "deadline", "future", "loop_future",
        "synthetic", "submitted_at", "first_token_at", "finished_at", "out",
        "pages_reserved",
        "trace", "submitted_wall", "first_token_wall",
    )

    def __init__(
        self,
        prompt_ids: list[int],
        max_new_tokens: int,
        *,
        temperature: float = 0.0,
        top_p: float | None = None,
        min_p: float | None = None,
        top_k: int | None = None,
        repetition_penalty: float | None = None,
        deadline=None,
        synthetic: bool = False,
        trace=None,
    ):
        self.prompt_ids = prompt_ids
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_p = top_p
        self.min_p = min_p
        self.top_k = top_k
        self.repetition_penalty = repetition_penalty
        self.deadline = deadline
        self.future: Future = Future()
        self.synthetic = synthetic
        self.submitted_at = time.monotonic()
        # request trace (engine/tracing.py): captured at submit time in the
        # caller's context, spans recorded from the scheduler thread — wall
        # timestamps ride along because spans use wall-clock starts while
        # the scheduler's own telemetry stays monotonic
        self.trace = trace
        self.submitted_wall = time.time()
        self.first_token_wall: float | None = None
        self.first_token_at: float | None = None
        self.finished_at: float | None = None
        self.out: list[int] = []
        self.pages_reserved = 0

    @property
    def wants_history(self) -> bool:
        """Whether the request's sampling reads more than the step's
        logits: a cut at the k-th largest, or the tokens seen so far."""
        return self.top_k is not None or self.repetition_penalty is not None

    @property
    def ttft_s(self) -> float | None:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at


class _Slot:
    """Device-slot state: which request occupies row ``i`` of the batch."""

    __slots__ = (
        "req", "pages", "seq_len", "prefill_done", "prompt_len",
        "prefill_started", "prefill_chunks", "prefill_enqueue_s",
        "prefill_width",
    )

    def __init__(self, req: GenRequest):
        self.req = req
        self.pages: list[int] = []
        # tokens written into the paged cache, or enqueued to be written
        self.seq_len = 0
        self.prompt_len = len(req.prompt_ids)
        self.prefill_done = False
        # the request's ``generate.prefill`` span, closed at the first
        # sync after its last chunk: wall time of the first chunk's
        # enqueue (None again once the span is written), chunks so far,
        # their summed dispatch time and the widest of them
        self.prefill_started: float | None = None
        self.prefill_chunks = 0
        self.prefill_enqueue_s = 0.0
        self.prefill_width = 0


class _Step(NamedTuple):
    """A decode step that is enqueued and not read yet."""

    tok: Any  # its tokens, on the device
    rows: list[tuple[int, GenRequest]]  # (slot index, request) it decoded for
    # which of the scheduler's programs it was: its read drains the device
    # if none was enqueued after it
    program: int


class GenerationScheduler:
    """Continuous-batching scheduler for one :class:`DecoderLM`.

    A dedicated worker thread runs the tick loop: evict → admit →
    chunked prefill → enqueue the next decode step → read and deliver
    the one before it.  ``submit_ids`` /
    ``submit`` are thread-safe and return ``concurrent.futures.Future``;
    the async serving edge (``JaxChat``) awaits them via
    ``asyncio.wrap_future``.
    """

    def __init__(
        self,
        lm,
        *,
        slots: int | None = None,
        page_size: int | None = None,
        pages: int | None = None,
        prefill_chunk: int | None = None,
        queue_limit: int | None = None,
        seed: int = 0,
    ):
        from pathway_tpu.models import decoder as dec

        self.lm = lm
        self.cfg = lm.config
        self.max_cache = lm.max_cache
        self.slots = slots if slots is not None else env_int("PATHWAY_GENERATE_SLOTS")
        self.page_size = (
            page_size if page_size is not None
            else env_int("PATHWAY_GENERATE_PAGE_SIZE")
        )
        self.prefill_chunk = (
            prefill_chunk if prefill_chunk is not None
            else env_int("PATHWAY_GENERATE_PREFILL_CHUNK")
        )
        self.queue_limit = (
            queue_limit if queue_limit is not None
            else env_int("PATHWAY_GENERATE_QUEUE")
        )
        self.pages_per_seq = -(-self.max_cache // self.page_size)
        # no program is wider than the cache a slot can hold
        self._ladder = prefill_ladder(
            min(self.prefill_chunk, self.pages_per_seq * self.page_size)
        )
        n_pages = pages if pages is not None else env_int("PATHWAY_GENERATE_PAGES")
        if n_pages <= 0:
            # auto: half the dense worst case (the whole point of paging),
            # floored so at least one full-cache request always fits
            n_pages = max(
                self.slots * self.pages_per_seq // 2, self.pages_per_seq
            ) + 1
        self.num_pages = n_pages
        # the kinds of cache in one manager: the layers whose cache grows
        # with the sequence take pages from the allocator and are counted
        # by the token; a window layer of a model of runs keeps a ring a
        # slot (``dec.uses_ring``), fixed here and counted by the slot
        self.dense_kv_bytes = (
            self.slots * self.max_cache * dec.kv_bytes_per_token(self.cfg)
        )
        self.allocator = dec.PageAllocator(
            self.num_pages, self.page_size,
            dec.kv_bytes_per_token(self.cfg, growing_only=True),
        )
        self._init_pools()
        self._hybrid = self.cfg.runs is not None
        windows = {
            k.window for k, _n in self.cfg.layer_runs if dec.uses_ring(self.cfg, k)
        }
        if len(windows) > 1:
            raise NotImplementedError(
                f"window layers of several sizes ({sorted(windows)}): one ring "
                "table a slot serves one window"
            )
        self.ring_pages = (
            dec.ring_pages(windows.pop(), self.page_size) if windows else 0
        )
        self.ring_bytes_per_slot = dec.kv_ring_bytes_per_slot(self.cfg, self.page_size)
        # the third kind: a Mamba-2 layer's recurrent state, a slot, fixed
        self._ssm = self.cfg.ssm_layers > 0
        self.ssm_bytes_per_slot = dec.ssm_state_bytes_per_slot(self.cfg)
        # slot i's ring: pages 1 + i * ring onwards of every window run's
        # pool, for as long as the scheduler lives
        self._ring_tables = 1 + np.arange(
            self.slots * self.ring_pages, dtype=np.int32
        ).reshape(self.slots, self.ring_pages)

        import jax
        import jax.numpy as jnp

        self._jax, self._jnp = jax, jnp
        self._logits = jnp.zeros((self.slots, self.cfg.vocab_size), jnp.float32)
        self._key = jax.random.PRNGKey(seed)
        self._block_tables = np.zeros(
            (self.slots, self.pages_per_seq), np.int32
        )
        self._seq_lens = np.zeros(self.slots, np.int32)
        self._temps = np.zeros(self.slots, np.float32)
        self._top_ps = np.ones(self.slots, np.float32)
        self._min_ps = np.zeros(self.slots, np.float32)
        # what the history-carrying step reads besides: a slot's k (0: no
        # cut), its repetition penalty (1: none) and, on the device, the
        # tokens it has seen (allocated with the first request that asks)
        self._top_ks = np.zeros(self.slots, np.int32)
        self._penalties = np.ones(self.slots, np.float32)
        self._seen = None
        # live slots whose request asked for either: while there is one, a
        # tick takes the history-carrying step
        self._history_slots = 0

        cfg = self.cfg
        # a model of runs, or with routed experts, is told which slots
        # decode (``active``) and hands back its routing's counts: the
        # decode step's ``[pairs, experts_hit]`` and those the prefill
        # programs since the last step carried forward ride behind the
        # tokens, in the one array the tick syncs anyway
        self._counted = counted = self._hybrid or self.cfg.routed_layers > 0
        # a prefill program's counts: the routing's [pairs, experts_hit],
        # with Mamba-2 layers the tokens the scan advanced a state by, and
        # the rows the grouped kernel multiplied
        self._no_stats = jnp.zeros((3 + self._ssm,), jnp.int32)
        self._prefill_stats = self._no_stats

        def _sample(lg, key, temp, top_p, min_p, top_k=None):
            with jax.named_scope("sample"):
                greedy_tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                sampled = dec.sample_logits(
                    lg, key, jnp.maximum(temp, 1e-6)[:, None], top_k=top_k,
                    top_p=top_p[:, None], min_p=min_p[:, None],
                )
                return jnp.where(temp > 0.0, sampled, greedy_tok)

        def _advance(tree, kp, vp, bt, sl, tok, *counts):
            if not counted:
                lg2, kp, vp = dec.paged_decode_step(tree, kp, vp, bt, sl, tok, cfg)
                return tok, lg2, kp, vp
            active, carried = counts
            lg2, kp, vp, stats = dec.paged_decode_step(
                tree, kp, vp, bt, sl, tok, cfg, active=active, with_stats=True
            )
            # the grouped kernel's tile rows are counted for prefill
            # programs alone (a step of the slots' rows loops in place)
            return jnp.concatenate([tok, stats[:-1], carried]), lg2, kp, vp

        def _decode(tree, kp, vp, bt, sl, lg, key, temp, top_p, min_p, *counts):
            tok = _sample(lg, key, temp, top_p, min_p)
            return _advance(tree, kp, vp, bt, sl, tok, *counts)

        def _decode_history(tree, kp, vp, bt, sl, lg, key, temp, top_p, min_p,
                            top_k, penalty, seen, active, *carried):
            """The step for a batch in which some slot asked for ``top_k``
            or a repetition penalty: the penalty over what the slot has
            ``seen [slots, vocab]`` (its prompt and its tokens so far), the
            cut at its k, and the sampled token joins ``seen`` where the
            slot decodes.  A slot that asked for neither carries 0 and 1.0
            and samples what the plain step would."""
            lg = dec.apply_repetition_penalty(lg, seen, penalty[:, None])
            tok = _sample(lg, key, temp, top_p, min_p, top_k[:, None])
            seen = seen | (
                active[:, None] & jax.nn.one_hot(tok, lg.shape[-1], dtype=bool)
            )
            counts = (active, *carried) if counted else ()
            return *_advance(tree, kp, vp, bt, sl, tok, *counts), seen

        def _prefill(tree, kp, vp, bt, ids, cl, st, old_lg, lanes, take, *carried):
            lg, kp, vp, *stats = dec.paged_prefill_chunk(
                tree, kp, vp, bt, ids, cl, st, cfg, with_stats=counted
            )
            # row r of the program is slot ``lanes[r]``: where its prompt
            # ended, its logits replace that slot's (others are dropped)
            dest = jnp.where(take, lanes, old_lg.shape[0])
            out = old_lg.at[dest].set(lg, mode="drop"), kp, vp
            if counted:
                out += (carried[0] + stats[0],)
            return out

        # a program consumes the pools it is given and hands back their
        # buffers, updated in place: the scheduler holds the one copy, and
        # chains it from each program to the next
        pools = (1, 2)
        self._decode_fn = jax.jit(_decode, donate_argnums=pools)
        self._decode_history_fn = jax.jit(_decode_history, donate_argnums=pools)
        self._seed_seen_fn = jax.jit(lambda seen, i, row: seen.at[i].set(row))
        self._prefill_fn = jax.jit(_prefill, donate_argnums=pools)

        self._lock = threading.Condition()
        self._queue: list[GenRequest] = []
        self._slots: list[_Slot | None] = [None] * self.slots
        self._running = False
        self._thread: threading.Thread | None = None
        self._peak_active = 0  # most slots taken at once: each holds its rings
        self._churn_ttfts: list[float] = []
        self._tokens_total = 0
        self._tick_failures = 0
        self._last_tick_error: str | None = None
        self._tok_window: list[tuple[float, int]] = []  # (t, tokens) per tick
        self._ticks = 0
        # the timeline's ``device.inflight`` interval: open from an enqueue
        # that found the device drained to the return of the sync that
        # drains it again, with the programs enqueued meanwhile
        self._inflight = None
        self._programs = 0  # enqueued since the scheduler was built
        self._inflight_from = 0  # and when the open interval began
        self._phase = None  # the running tick's open phase on the timeline
        # the decode step that runs ahead of the host's read, one at most
        self._step: _Step | None = None
        # when the last decode read returned, while the device has not
        # drained since: the start of an inter-token interval
        self._last_read_at: float | None = None

        from pathway_tpu.engine import metrics as em

        reg = em.get_registry()
        self._m_requests = reg.counter(
            "generate.requests", "generation requests accepted"
        )
        self._m_tokens = reg.counter(
            "generate.tokens", "tokens generated across all requests"
        )
        self._m_prefill_chunks = reg.counter(
            "generate.prefill.chunks", "chunked-prefill programs dispatched"
        )
        self._m_prefill_tokens = reg.counter(
            "generate.prefill.tokens", "prompt tokens dispatched to prefill"
        )
        self._m_prefill_padded = reg.counter(
            "generate.prefill.padded",
            "token rows of prefill programs that held no prompt token",
        )
        self._m_prefill_context = reg.counter(
            "generate.prefill.context_tokens",
            "tokens a prefill program's rows held in the cache before it ran",
        )
        self._m_decode_steps = reg.counter(
            "generate.decode.steps", "continuous decode ticks dispatched"
        )
        self._m_decode_overlapped = reg.counter(
            "generate.decode.overlapped",
            "decode steps enqueued while the one before was still unread",
        )
        self._m_decode_wasted = reg.counter(
            "generate.decode.wasted",
            "row-steps computed for a row that had already ended",
        )
        self._m_decode_tick = reg.histogram(
            "generate.decode.tick.ms",
            "return of one decode read -> return of the next (ms)",
            buckets=em.MS_BUCKETS,
        )
        pairs_help = "token-expert pairs computed on the experts held here"
        hit_help = (
            "held experts that met a token, summed over routed layers and programs"
        )
        # in the order they ride behind a decode step's tokens: the step's
        # counts, then the carried prefill programs'
        decode_counts = [
            reg.counter("generate.moe.decode.pairs", pairs_help),
            reg.counter("generate.moe.decode.experts_hit", hit_help),
        ]
        prefill_counts = [
            reg.counter("generate.moe.prefill.pairs", pairs_help),
            reg.counter("generate.moe.prefill.experts_hit", hit_help),
        ]
        # a static fact of the step program (its rows are the slots), on
        # /status as the gauge generate.moe.decode.in_place
        self._moe_in_place = False
        if self.cfg.routed_layers > 0:
            from pathway_tpu.parallel.moe import serves_in_place

            self._moe_in_place = serves_in_place(self.slots)
        if self._ssm:
            ssm_help = (
                "real tokens a Mamba-2 scan advanced a slot's state by, summed over rows"
            )
            decode_counts.append(reg.counter("generate.ssm.decode.tokens", ssm_help))
            prefill_counts.append(reg.counter("generate.ssm.prefill.tokens", ssm_help))
            self._m_ssm_resets = reg.counter(
                "generate.ssm.state.resets",
                "slots whose recurrent state a prompt's first chunk started from noughts",
            )
        # last behind a prefill program's counts: the rows its grouped
        # kernel multiplied (the pairs over it: the share of them that is real)
        prefill_counts.append(reg.counter(
            "generate.moe.prefill.tile_rows",
            "rows the grouped expert kernel multiplied: its row tiles of 128",
        ))
        self._m_counts = decode_counts + prefill_counts
        self._decode_counted = len(decode_counts)
        self._m_window_pages_released = reg.counter(
            "generate.kv.window.pages_released",
            "ring pages that held a token, a window layer, when their slot was released",
        )
        self._m_window_slots_released = reg.counter(
            "generate.kv.window.slots_released",
            "slots released that held a sequence in their ring",
        )
        self._m_ttft = reg.histogram(
            "generate.ttft.ms", "request submit -> first token (ms)",
            buckets=em.MS_BUCKETS,
        )
        self._m_tick_failures = reg.counter(
            "generate.tick.failures",
            "scheduler ticks that raised (every queued/active request of "
            "the tick was failed)",
        )
        self._m_churn = reg.counter(
            "generate.churn.synthetic",
            "synthetic burst requests injected by the request_churn fault",
        )
        # the generation panel's gauges, evaluated when scraped
        reg.register_collector("generate.state", self._gauge_state)

        from pathway_tpu.engine import flight_recorder as _blackbox

        _blackbox.get_recorder().set_generation_supplier(self.snapshot)

    # -- submission --------------------------------------------------------

    def submit_request(
        self,
        prompt_ids: list[int],
        *,
        max_new_tokens: int = 64,
        temperature: float = 0.0,
        top_p: float | None = None,
        min_p: float | None = None,
        top_k: int | None = None,
        repetition_penalty: float | None = None,
        deadline=None,
        synthetic: bool = False,
    ) -> GenRequest:
        """Enqueue one request and return it — the request object carries
        the per-request telemetry (``ttft_s``, ``finished_at``) the
        serving benchmark reads; its ``.future`` resolves to the
        generated id list.

        ``top_p`` / ``min_p`` / ``top_k`` truncate the sampling
        distribution (only meaningful with ``temperature > 0``);
        ``repetition_penalty`` (HF semantics, > 1 discourages repeats)
        penalizes every token of the prompt or generated so far, greedy
        rows included.

        Raises :class:`OverloadedError` when the bounded queue is full
        (the page pool's backpressure — never an OOM) and
        :class:`DeadlineExceededError` when the request arrives already
        lapsed."""
        from pathway_tpu.engine import serving as edge

        if max_new_tokens >= self.max_cache:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} must be < "
                f"max_cache={self.max_cache}"
            )
        if top_k is not None and top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        if repetition_penalty is not None and repetition_penalty <= 0:
            # HF semantics: penalty 0 would divide logits by zero (turning
            # repeats into the unconditional winner) and negatives flip
            # the sign branches — reject like RepetitionPenaltyLogitsProcessor
            raise ValueError(
                f"repetition_penalty must be > 0, got {repetition_penalty}"
            )
        if repetition_penalty == 1.0:
            repetition_penalty = None  # no penalty: the plain step serves it
        if deadline is None:
            deadline = edge.current_deadline()
        if deadline is not None and deadline.expired():
            edge.note_deadline_shed("generate-queue")
            raise edge.DeadlineExceededError(
                "request deadline lapsed before generation was queued"
            )
        limit = self.max_cache - max_new_tokens
        prompt_ids = list(prompt_ids[-limit:]) if len(prompt_ids) > limit else list(prompt_ids)
        if not prompt_ids:
            prompt_ids = [0]
        req = GenRequest(
            prompt_ids, max_new_tokens, temperature=temperature,
            top_p=top_p, min_p=min_p, top_k=top_k,
            repetition_penalty=repetition_penalty, deadline=deadline,
            synthetic=synthetic, trace=tracing.current_trace(),
        )
        with self._lock:
            if len(self._queue) >= self.queue_limit:
                raise edge.OverloadedError(
                    "generation queue full", retry_after_s=1.0
                )
            self._queue.append(req)
            self._ensure_thread()
            self._lock.notify_all()
        self._m_requests.inc()
        return req

    def submit_ids(self, prompt_ids: list[int], **kwargs) -> Future:
        """Enqueue one request; resolves to the generated id list."""
        return self.submit_request(prompt_ids, **kwargs).future

    def submit(self, prompt: str, **kwargs) -> Future:
        """Text-in/text-out: resolves to the decoded completion."""
        ids = self.lm._encode_prompt(prompt)
        inner = self.submit_ids(ids, **kwargs)
        outer: Future = Future()

        def _done(f: Future) -> None:
            exc = f.exception()
            if exc is not None:
                outer.set_exception(exc)
            else:
                outer.set_result(self.lm.tokenizer.decode(f.result()))

        inner.add_done_callback(_done)
        return outer

    def generate(self, prompt: str, timeout: float | None = 120.0, **kwargs) -> str:
        return self.submit(prompt, **kwargs).result(timeout=timeout)

    async def agenerate(self, prompt: str, **kwargs) -> str:
        return await asyncio.wrap_future(self.submit(prompt, **kwargs))

    # -- worker loop -------------------------------------------------------

    def _ensure_thread(self) -> None:
        if self._running:
            return
        self._running = True
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="pathway:generate"
        )
        self._thread.start()

    def _loop(self) -> None:
        while True:
            with self._lock:
                idle = None  # one interval per wait for work, not per time-out
                while (
                    self._running
                    and not self._queue
                    and all(s is None for s in self._slots)
                    and self._step is None
                ):
                    if idle is None:
                        idle = tracing.begin("sched", "sched.idle")
                    self._lock.wait(timeout=0.5)
                tracing.end(idle)
                if not self._running:
                    return
            try:
                self._tick()
            except Exception as exc:  # noqa: BLE001 - fail requests, not the thread
                # the thread and the server live on, so the failure must
                # be visible somewhere other than each client's 500
                self._tick_failures += 1
                self._last_tick_error = f"{type(exc).__name__}: {exc}"[:300]
                self._m_tick_failures.inc()
                logging.getLogger(__name__).exception(
                    "generation tick failed; failing %d queued/active request(s)",
                    len(self._queue) + sum(s is not None for s in self._slots),
                )
                self._fail_all(exc)

    def shutdown(self) -> None:
        """Stop the worker; queued/active requests fail rather than hang."""
        from pathway_tpu.engine import flight_recorder as _blackbox
        from pathway_tpu.engine.serving import RequestFailedError

        with self._lock:
            self._running = False
            self._lock.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
        self._fail_all(RequestFailedError("generation scheduler shut down"))
        _blackbox.get_recorder().set_generation_supplier(None)

    def _init_pools(self) -> None:
        from pathway_tpu.models import decoder as dec

        self._k_pool, self._v_pool = dec.init_kv_pool(
            self.cfg, self.num_pages, self.page_size, self.slots
        )

    def _fail_all(self, exc: BaseException) -> None:
        """Fail every queued and active request.  A step in flight has no
        one left to deliver to: it is let go unread, and nothing is waited
        for any more.  A program that failed after it consumed its pools
        left none behind: they are made anew, since no request that held
        a page of them lives on."""
        self._step = None
        self._drained(failed=True)
        pools = self._jax.tree_util.tree_leaves((self._k_pool, self._v_pool))
        if any(leaf.is_deleted() for leaf in pools):
            self._init_pools()
        with self._lock:
            victims = [r for r in self._queue]
            self._queue.clear()
            for i, slot in enumerate(self._slots):
                if slot is not None:
                    victims.append(slot.req)
                    self._release_slot(i)
            for r in victims:
                if not r.future.done():
                    r.future.set_exception(exc)

    # -- the tick ----------------------------------------------------------

    def _tick(self) -> None:
        """One tick: admit, enqueue the prefill programs of what waits,
        enqueue the next decode step, and only then read and deliver the
        step the tick before enqueued, while the new one runs.  A tick with
        no row left to decode reads the step in flight at once, so an
        answer's last token is never held back.  Its phases
        (``tick.admit``, ``tick.prefill.prepare``, ``tick.prefill.enqueue``,
        ``tick.decode.prepare``, ``tick.decode.upload``,
        ``tick.decode.enqueue``, ``tick.decode.sync``, ``tick.deliver``)
        tile it on the timeline's ``sched`` track: each ends where the next
        starts."""
        t0 = time.monotonic()
        self._ticks += 1
        self._phase = tracing.begin("sched", "tick.admit", tick=self._ticks)
        try:
            with self._lock:
                self._evict_lapsed(t0)
                self._admit(t0)
                prefill_rows = [
                    i for i, s in enumerate(self._slots)
                    if s is not None and not s.prefill_done
                ]
            if prefill_rows:
                self._run_prefill(prefill_rows)
            unread = self._step
            pending = () if unread is None else {req for _i, req in unread.rows}
            with self._lock:
                # a row that ends by count with the step in flight is
                # given no further one
                decode_rows = [
                    i for i, s in enumerate(self._slots)
                    if s is not None and s.prefill_done
                    and len(s.req.out) + (s.req in pending)
                    < s.req.max_new_tokens
                ]
            if decode_rows and unread is not None:
                self._m_decode_overlapped.inc()
            self._step = self._enqueue_decode(decode_rows) if decode_rows else None
            if unread is not None:
                self._deliver(unread)
            self._tok_window.append((t0, len(decode_rows)))
            if len(self._tok_window) > 256:
                del self._tok_window[:128]
        finally:
            tracing.end(self._phase)
            self._phase = None

    def _next_phase(self, name: str, **attributes: Any) -> None:
        self._phase = tracing.switch(self._phase, name, **attributes)

    def _enqueued(self) -> None:
        """A program is about to be enqueued: the device is in flight from
        here until :meth:`_drained`."""
        if self._inflight is None:
            self._inflight = tracing.begin("sched", "device.inflight")
            self._inflight_from = self._programs
        self._programs += 1

    def _drained(self, failed: bool = False) -> None:
        """The sync that drains the device returned (or the tick failed and
        nothing is waited for any more)."""
        self._last_read_at = None
        if self._inflight is not None:
            attributes: dict[str, Any] = {
                "programs": self._programs - self._inflight_from
            }
            if failed:
                attributes["failed"] = True
            tracing.end(self._inflight, **attributes)
            self._inflight = None

    def _evict_lapsed(self, now: float) -> None:
        """Shed active rows whose deadline lapsed mid-generation, and
        queued requests that lapsed while waiting.  Runs under the lock."""
        from pathway_tpu.engine import serving as edge

        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            d = slot.req.deadline
            if d is not None and d.expired(now):
                edge.note_deadline_shed("decode")
                req = slot.req
                self._release_slot(i)
                if not req.future.done():
                    req.future.set_exception(
                        edge.DeadlineExceededError(
                            "deadline lapsed mid-generation "
                            f"({len(req.out)} token(s) produced)"
                        )
                    )
        kept = []
        for req in self._queue:
            d = req.deadline
            if d is not None and d.expired(now):
                edge.note_deadline_shed("generate-queue")
                if not req.future.done():
                    req.future.set_exception(
                        edge.DeadlineExceededError(
                            "deadline lapsed while queued for generation"
                        )
                    )
            else:
                kept.append(req)
        self._queue[:] = kept

    def _admit(self, now: float) -> None:
        """Fill free slots from the queue.  The whole queue is scanned
        (not just the head): a huge request that cannot reserve pages yet
        must not head-of-line-block small ones that can.  Runs under the
        lock."""
        self._maybe_inject_churn()
        free = [i for i, s in enumerate(self._slots) if s is None]
        if not free:
            return
        remaining: list[GenRequest] = []
        for req in self._queue:
            if not free:
                remaining.append(req)
                continue
            need = self.allocator.pages_for(
                len(req.prompt_ids) + req.max_new_tokens
            )
            if not self.allocator.can_reserve(need):
                remaining.append(req)
                continue
            self.allocator.reserve(need)
            req.pages_reserved = need
            i = free.pop(0)
            if req.trace is not None:
                # queue-wait span: submit → slot grant, attributed to the
                # request's own trace (the scheduler thread has no ambient)
                req.trace.add_span(
                    "generate.queue",
                    req.submitted_wall,
                    max(0.0, time.time() - req.submitted_wall),
                    slot=i,
                    pages=need,
                )
            slot = _Slot(req)
            self._slots[i] = slot
            self._peak_active = max(self._peak_active, self.slots - len(free))
            self._block_tables[i, :] = 0
            self._seq_lens[i] = 0
            self._temps[i] = req.temperature
            self._top_ps[i] = 1.0 if req.top_p is None else req.top_p
            self._min_ps[i] = 0.0 if req.min_p is None else req.min_p
            if req.wants_history:
                self._admit_history(i, req)
        self._queue[:] = remaining

    def _admit_history(self, i: int, req: GenRequest) -> None:
        """Slot ``i`` takes a request that asked for ``top_k`` or a
        repetition penalty: its ``seen`` row starts from the prompt's
        tokens (HF counts the prompt too), whatever the slot held before."""
        jnp = self._jnp
        self._history_slots += 1
        self._top_ks[i] = req.top_k or 0
        self._penalties[i] = req.repetition_penalty or 1.0
        if self._seen is None:
            self._seen = jnp.zeros((self.slots, self.cfg.vocab_size), bool)
        row = np.zeros(self.cfg.vocab_size, bool)
        row[req.prompt_ids] = True
        self._seen = self._seed_seen_fn(self._seen, jnp.int32(i), jnp.asarray(row))

    def _maybe_inject_churn(self) -> None:
        """The ``request_churn`` fault: a burst of short synthetic
        requests lands mid-long-generation — the chaos lever behind the
        no-head-of-line-blocking pin."""
        from pathway_tpu.engine import faults

        spec = faults.check("request_churn", source=self.lm.model_name)
        if spec is None:
            return
        count = int(spec.count or 4)
        for n in range(count):
            req = GenRequest(
                [1 + (n % 7)], 4, temperature=0.0, synthetic=True,
            )
            if len(self._queue) < self.queue_limit:
                self._queue.append(req)
                self._m_churn.inc()

    def _ensure_pages(self, i: int, tokens_needed: int) -> None:
        """Grow slot ``i``'s block table to cover ``tokens_needed`` tokens
        (lazy allocation against the admission-time reservation)."""
        slot = self._slots[i]
        while len(slot.pages) * self.page_size < tokens_needed:
            page = self.allocator.alloc()
            slot.pages.append(page)
            self._block_tables[i, len(slot.pages) - 1] = page

    def _release_slot(self, i: int) -> None:
        slot = self._slots[i]
        if slot is None:
            return
        unreserve = max(slot.req.pages_reserved - len(slot.pages), 0)
        self.allocator.release(slot.pages, unreserve=unreserve)
        if self.ring_pages and slot.seq_len:
            self._m_window_pages_released.inc(
                min(self.ring_pages, self.allocator.pages_for(slot.seq_len))
            )
            self._m_window_slots_released.inc()
        self._slots[i] = None
        self._block_tables[i, :] = 0
        self._seq_lens[i] = 0
        self._temps[i] = 0.0
        self._top_ps[i] = 1.0
        self._min_ps[i] = 0.0
        if slot.req.wants_history:
            self._history_slots -= 1
            self._top_ks[i] = 0
            self._penalties[i] = 1.0

    def _tables(self, block_tables: np.ndarray, lanes=None):
        """The tables a paged program takes for the slots ``lanes`` (a
        decode step: every slot, row ``r`` slot ``r``): their block tables,
        for a model of runs their rings beside them, and for a prefill
        program of a model with recurrent state the slot each row is."""
        jnp = self._jnp
        if not self._hybrid:
            return jnp.asarray(block_tables)
        rings = self._ring_tables if lanes is None else self._ring_tables[lanes]
        tables = jnp.asarray(block_tables), jnp.asarray(rings)
        if self._ssm and lanes is not None:
            tables += (jnp.asarray(lanes, jnp.int32),)
        return tables

    def _ring_pages_in_use(self) -> int:
        """Ring pages that hold a token, a window layer: a slot's ring
        fills as its sequence grows and then stays at its size.  Runs
        under the lock."""
        return sum(
            min(self.ring_pages, self.allocator.pages_for(s.seq_len))
            for s in self._slots if s is not None and s.seq_len
        )

    def _table_width(self, lanes: list[int] | None = None) -> int:
        """Power-of-two block-table width covering the slots ``lanes`` (all
        of them by default) — the bucketed static gather width of the
        compiled step."""
        most = 1
        for i, s in enumerate(self._slots):
            if s is not None and len(s.pages) > most and (
                lanes is None or i in lanes
            ):
                most = len(s.pages)
        return _pow2_bucket(most, self.pages_per_seq)

    def prefill_programs(self, prompt_len: int) -> list[tuple[int, int, int]]:
        """``(rows, width, table width)`` of each prefill program a prompt
        of ``prompt_len`` tokens runs through alone, in order: the shapes
        a warm-up has to compile for such prompts."""
        programs, done = [], 0
        while done < prompt_len:
            rows, width = prefill_shape(prompt_len - done, self._ladder, self.slots)
            done = min(prompt_len, done + width)
            pages = _pow2_bucket(self.allocator.pages_for(done), self.pages_per_seq)
            programs.append((rows, width, pages))
        return programs

    def _run_prefill(self, rows: list[int]) -> list[int]:
        """The tick's prefill programs, shaped by what waits
        (:func:`prefill_shape`): a one-row program for every slot with
        more left than the narrowest rung holds, oldest request first,
        then one program shared by the slots with less; returns the rows
        whose prompt completed (now decode-ready)."""
        with self._lock:
            left = {
                i: s.prompt_len - s.seq_len
                for i in rows if (s := self._slots[i]) is not None
            }
            oldest_first = sorted(
                left, key=lambda i: self._slots[i].req.submitted_at
            )
        finishing: list[int] = []
        shared: list[int] = []
        for i in oldest_first:
            n_rows, width = prefill_shape(left[i], self._ladder, self.slots)
            if n_rows == 1:
                finishing += self._prefill_program([i], [i], width)
            else:
                shared.append(i)
        if shared:
            finishing += self._prefill_program(
                shared, list(range(self.slots)), self._ladder[0]
            )
        return finishing

    def _prefill_program(
        self, rows: list[int], lanes: list[int], T: int
    ) -> list[int]:
        """One prefill program ``[len(lanes), T]``: row ``r`` is slot
        ``lanes[r]`` and holds that slot's next chunk where the slot is
        in ``rows``, nothing otherwise.  Returns the slots whose prompt
        ended."""
        jnp = self._jnp
        R = len(lanes)
        self._next_phase("tick.prefill.prepare", rows=len(rows))
        ids = np.zeros((R, T), np.int32)
        chunk_lens = np.zeros(R, np.int32)
        starts = np.zeros(R, np.int32)
        take = np.zeros(R, bool)
        finishing: list[int] = []
        chunked: list[_Slot] = []
        with self._lock:
            for r, i in enumerate(lanes):
                slot = self._slots[i]
                if slot is None or i not in rows:
                    continue
                done = slot.seq_len
                n = min(T, slot.prompt_len - done)
                if n <= 0:
                    continue
                self._ensure_pages(i, done + n)
                ids[r, :n] = slot.req.prompt_ids[done:done + n]
                chunk_lens[r] = n
                starts[r] = done
                if self._ssm and done == 0:
                    self._m_ssm_resets.inc()  # the program starts it from noughts
                chunked.append(slot)
                slot.seq_len = done + n
                self._seq_lens[i] = slot.seq_len
                if slot.seq_len >= slot.prompt_len:
                    take[r] = True
                    slot.prefill_done = True
                    finishing.append(i)
            G = self._table_width(lanes)
            bt = self._tables(self._block_tables[lanes, :G], lanes)
        self._next_phase("tick.prefill.enqueue", rows=R, width=T)
        self._enqueued()
        enqueue_started = time.time()
        # asynchronous: the call returns once the chunk is enqueued, its
        # work ends with the read of the decode step behind it (``_deliver``)
        out = self._prefill_fn(
            self.lm.params, self._k_pool, self._v_pool, bt,
            jnp.asarray(ids), jnp.asarray(chunk_lens), jnp.asarray(starts),
            self._logits, jnp.asarray(lanes, jnp.int32), jnp.asarray(take),
            *((self._prefill_stats,) if self._counted else ()),
        )
        self._logits, self._k_pool, self._v_pool = out[:3]
        if self._counted:
            self._prefill_stats = out[3]
        self._m_prefill_chunks.inc()
        real = int(chunk_lens.sum())
        self._m_prefill_tokens.inc(real)
        self._m_prefill_padded.inc(R * T - real)
        # the earlier context the program's attention reads: a prompt's
        # chunks after its first
        self._m_prefill_context.inc(int(starts.sum()))
        enqueue_s = max(0.0, time.time() - enqueue_started)
        for slot in chunked:
            if slot.prefill_chunks == 0:
                slot.prefill_started = enqueue_started
            slot.prefill_chunks += 1
            slot.prefill_enqueue_s += enqueue_s
            slot.prefill_width = max(slot.prefill_width, T)
        return finishing

    def _enqueue_decode(self, rows: list[int]) -> _Step:
        """Enqueue one continuous decode step for the slots ``rows``: each
        samples its next token on the device and writes its paged KV.  The
        slots' lengths, pages and the sampling key advance here, at the
        enqueue, so the next step can be prepared before this one is read."""
        jax, jnp = self._jax, self._jnp
        self._next_phase("tick.decode.prepare", rows=len(rows))
        with self._lock:
            taken = [(i, s) for i in rows if (s := self._slots[i]) is not None]
            for i, slot in taken:
                self._ensure_pages(i, slot.seq_len + 1)
            G = self._table_width()
            bt = self._block_tables[:, :G].copy()
            sl = self._seq_lens.copy()
            temps = self._temps.copy()
            top_ps = self._top_ps.copy()
            min_ps = self._min_ps.copy()
            history = self._history_slots > 0
            if history:
                top_ks = self._top_ks.copy()
                penalties = self._penalties.copy()
            for i, slot in taken:
                slot.seq_len += 1
                self._seq_lens[i] = slot.seq_len
        # every array the step takes from the host is made here, before
        # the call: its own phase, so the upload and the dispatch are
        # timed apart
        self._next_phase("tick.decode.upload")
        self._enqueued()
        counts = ()
        if self._counted or history:
            active = np.zeros(self.slots, bool)
            active[rows] = True
            counts = (jnp.asarray(active),)
        if self._counted:
            counts += (self._prefill_stats,)
            self._prefill_stats = self._no_stats
        self._key, sub = jax.random.split(self._key)
        args = (
            self.lm.params, self._k_pool, self._v_pool, self._tables(bt),
            jnp.asarray(sl), self._logits, sub, jnp.asarray(temps),
            jnp.asarray(top_ps), jnp.asarray(min_ps),
        )
        if history:
            sampling = (jnp.asarray(top_ks), jnp.asarray(penalties))
        self._next_phase("tick.decode.enqueue")
        if history:
            tok, self._logits, self._k_pool, self._v_pool, self._seen = (
                self._decode_history_fn(*args, *sampling, self._seen, *counts)
            )
        else:
            tok, self._logits, self._k_pool, self._v_pool = self._decode_fn(
                *args, *counts
            )
        tok.copy_to_host_async()  # on its way before the host asks for it
        self._m_decode_steps.inc()
        return _Step(tok, [(i, slot.req) for i, slot in taken], self._programs)

    def _deliver(self, step: _Step) -> None:
        """Read a decode step's tokens (the one host sync a tick) and hand
        them to the requests it decoded for; finished rows are released.
        A request that ended meanwhile (an EOS read a step late, a lapsed
        deadline) is no longer in its slot, and its token is dropped."""
        self._next_phase("tick.decode.sync")
        htok = np.asarray(step.tok)
        t_now = time.monotonic()
        synced = time.time()
        if self._last_read_at is not None:
            self._m_decode_tick.observe((t_now - self._last_read_at) * 1e3)
        if step.program == self._programs:
            self._drained()  # nothing was enqueued behind it
        else:
            self._last_read_at = t_now
        self._next_phase("tick.deliver")
        prefill_pairs = 0
        if self._counted:
            # behind the slots' tokens: this step's counts and the carried
            # prefill programs', in the order of self._m_counts
            counts = [int(n) for n in htok[self.slots:]]
            for counter, n in zip(self._m_counts, counts):
                counter.inc(n)
            prefill_pairs = counts[self._decode_counted]
        eos = self.lm.eos_id
        produced = 0
        wasted = 0
        with self._lock:
            for i, req in step.rows:
                slot = self._slots[i]
                if slot is None or slot.req is not req:
                    wasted += 1
                    continue
                t = int(htok[i])
                if slot.prefill_started is not None:
                    # the first sync after the request's last chunk: its
                    # prefill, from the first chunk's enqueue, ends here
                    if req.trace is not None:
                        req.trace.add_span(
                            "generate.prefill", slot.prefill_started,
                            max(0.0, synced - slot.prefill_started),
                            chunks=slot.prefill_chunks,
                            width=slot.prefill_width,
                            prompt_len=slot.prompt_len,
                            enqueue_s=slot.prefill_enqueue_s,
                            # routed pairs of the prefill programs that
                            # ended with this sync (this prompt's, where
                            # one prompt prefilled at a time)
                            pairs=prefill_pairs,
                        )
                    slot.prefill_started = None
                if req.first_token_at is None:
                    req.first_token_at = t_now
                    req.first_token_wall = time.time()
                    ttft_s = t_now - req.submitted_at
                    self._m_ttft.observe(
                        ttft_s * 1e3,
                        trace_id=(
                            req.trace.trace_id
                            if req.trace is not None else None
                        ),
                    )
                    if req.trace is not None:
                        # TTFT span: submit → first sampled token, the
                        # duration matches the histogram observation
                        req.trace.add_span(
                            "generate.ttft", req.submitted_wall, ttft_s,
                            prompt_len=slot.prompt_len,
                        )
                    if req.synthetic:
                        self._churn_ttfts.append(t_now - req.submitted_at)
                stop = eos is not None and t == eos
                if not stop:
                    req.out.append(t)
                    produced += 1
                if stop or len(req.out) >= req.max_new_tokens:
                    req.finished_at = t_now
                    if req.trace is not None:
                        start = req.first_token_wall or req.submitted_wall
                        req.trace.add_span(
                            "generate.decode", start,
                            max(0.0, time.time() - start),
                            tokens=len(req.out),
                            eos=bool(stop),
                        )
                    self._release_slot(i)
                    if not req.future.done():
                        req.future.set_result(req.out)
        if produced:
            self._tokens_total += produced
            self._m_tokens.inc(produced)
        if wasted:
            self._m_decode_wasted.inc(wasted)

    # -- observability -----------------------------------------------------

    def _gauge_state(self) -> dict[str, float]:
        """The ``generate.state`` collector: the panel's gauges, computed
        when someone scrapes them and not on every tick."""
        now = time.monotonic()
        window = [(t, n) for (t, n) in list(self._tok_window) if now - t <= 5.0]
        span = (now - window[0][0]) if len(window) > 1 else 0.0
        a = self.allocator
        with self._lock:
            active = sum(1 for s in self._slots if s is not None)
            return {
                "generate.slots.active": float(active),
                # pages by kind of cache: the allocator's (layers that keep
                # every token) and the rings' (a window layer's, a slot)
                "generate.kv.pages.global": float(a.used_pages),
                "generate.kv.pages.window": float(self._ring_pages_in_use()),
                "generate.slots.total": float(self.slots),
                "generate.queue.depth": float(len(self._queue)),
                "generate.pages.used": float(a.used_pages),
                "generate.pages.total": float(self.num_pages - 1),
                # a taken slot's rings are whole: fixed memory
                "generate.kv.bytes.live": float(
                    a.live_bytes + active * self.ring_bytes_per_slot
                ),
                "generate.kv.bytes.peak": float(
                    a.peak_bytes + self._peak_active * self.ring_bytes_per_slot
                ),
                # what the dense slots x max_cache layout would hold resident
                "generate.kv.bytes.dense": float(self.dense_kv_bytes),
                # recurrent state: a taken slot's is whole, whatever its length
                "generate.ssm.state.slots": float(active if self._ssm else 0),
                "generate.ssm.state.bytes": float(active * self.ssm_bytes_per_slot),
                # 1 where the decode step's routed layers loop over the
                # experts they met (parallel/moe.py::serves_in_place)
                "generate.moe.decode.in_place": float(self._moe_in_place),
                # sustained decode throughput over the last 5 s
                "generate.tokens_per_s": (
                    sum(n for _, n in window) / span if span > 0 else 0.0
                ),
            }

    def snapshot(self) -> dict[str, Any]:
        """Generation panel for ``/status`` dumps and the flight recorder."""
        with self._lock:
            active = sum(1 for s in self._slots if s is not None)
            prefilling = sum(
                1 for s in self._slots if s is not None and not s.prefill_done
            )
            return {
                "slots": self.slots,
                "active": active,
                "prefilling": prefilling,
                "queued": len(self._queue),
                "pages_total": self.num_pages - 1,
                "pages_used": self.allocator.used_pages,
                "pages_reserved": self.allocator.reserved,
                "ring_pages_per_slot": self.ring_pages,
                "kv_bytes_live": self.allocator.live_bytes
                + active * self.ring_bytes_per_slot,
                "kv_bytes_peak": self.allocator.peak_bytes
                + self._peak_active * self.ring_bytes_per_slot,
                "kv_bytes_dense": self.dense_kv_bytes,
                "ssm_state_bytes_per_slot": self.ssm_bytes_per_slot,
                "ssm_state_bytes_live": active * self.ssm_bytes_per_slot,
                "tokens_total": self._tokens_total,
                "tick_failures": self._tick_failures,
                "last_tick_error": self._last_tick_error,
            }


# ---------------------------------------------------------------------------
# Shared schedulers (the JaxChat wiring point)
# ---------------------------------------------------------------------------

_shared: dict[tuple, GenerationScheduler] = {}
_shared_lock = threading.Lock()


def shared_scheduler(
    model_name: str, max_cache: int = 1024, quantize: str | None = None
) -> GenerationScheduler:
    """Process-wide scheduler per (model, cache, quant) — all serving
    surfaces (every JaxChat UDF, every route) feed ONE continuous batch
    per model, which is the entire point."""
    from pathway_tpu.models.decoder import shared_decoder

    key = (model_name, max_cache, quantize)
    with _shared_lock:
        sched = _shared.get(key)
        if sched is None:
            sched = GenerationScheduler(
                shared_decoder(model_name, max_cache=max_cache, quantize=quantize)
            )
            _shared[key] = sched
        return sched


def reset_shared_schedulers() -> None:
    """Test hook: shut down and drop every shared scheduler."""
    with _shared_lock:
        scheds = list(_shared.values())
        _shared.clear()
    for s in scheds:
        s.shutdown()
