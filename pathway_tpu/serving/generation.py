"""Continuous-batching generation scheduler over the paged KV cache.

The static serving path batches requests per sampling config and runs
each batch to completion (``utils/batching.py`` → ``DecoderLM
.generate_many``): every request waits for the slowest row in its batch,
new arrivals wait for the whole batch to drain, and the dense KV cache
pays ``B × max_cache`` regardless of live tokens.  This module replaces
that loop with the vLLM/Ragged-Paged-Attention serving shape (PAPERS.md):

* **Slots** — a fixed device batch of ``S`` generation slots.  At every
  decode step, finished/lapsed rows are evicted immediately and queued
  requests are admitted into the freed slots — continuous batching.
* **Paged KV** — each slot's cache lives in fixed-size pages of the
  preallocated pool (``models/decoder.py::init_kv_pool``), allocated
  lazily as tokens arrive and freed at eviction, so KV memory scales
  with live tokens.  Admission reserves a request's worst case up front:
  the pool can never OOM mid-generation; requests queue (bounded) at
  the edge instead.
* **Chunked prefill** — prompts prefill in fixed-width chunks interleaved
  with decode ticks, so a long prompt cannot stall every other request's
  token cadence (no head-of-line blocking; pinned by the
  ``request_churn`` chaos test).
* **Deadlines** — requests carry the PR 17 :class:`engine.serving
  .Deadline`; a row that lapses mid-generation is shed at the next tick
  and counted under ``serve.deadline.exceeded{where=decode}``.

Every device program has a static shape: slot count fixed, prefill chunk
width fixed, block-table width bucketed to powers of two — a churning
request mix replays warm compiled programs (``jax.cache.miss == 0``
steady-state, pinned in ``tests/test_jax_accounting.py``).
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from concurrent.futures import Future
from typing import Any

import numpy as np

from pathway_tpu.internals.config import env_bool, env_int

__all__ = [
    "GenRequest",
    "GenerationScheduler",
    "reset_shared_schedulers",
    "shared_scheduler",
]


def _pow2_bucket(n: int, cap: int) -> int:
    b = 1
    while b < n and b < cap:
        b <<= 1
    return min(b, cap)


class GenRequest:
    """One queued/running generation request."""

    __slots__ = (
        "prompt_ids", "max_new_tokens", "temperature", "top_p", "min_p",
        "deadline", "future", "loop_future", "synthetic", "submitted_at",
        "first_token_at", "finished_at", "out", "pages_reserved",
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
        deadline=None,
        synthetic: bool = False,
        trace=None,
    ):
        self.prompt_ids = prompt_ids
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_p = top_p
        self.min_p = min_p
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
    def ttft_s(self) -> float | None:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at


class _Slot:
    """Device-slot state: which request occupies row ``i`` of the batch."""

    __slots__ = ("req", "pages", "seq_len", "prefill_done", "prompt_len")

    def __init__(self, req: GenRequest):
        self.req = req
        self.pages: list[int] = []
        self.seq_len = 0  # tokens written into the paged cache
        self.prompt_len = len(req.prompt_ids)
        self.prefill_done = False


class GenerationScheduler:
    """Continuous-batching scheduler for one :class:`DecoderLM`.

    A dedicated worker thread runs the tick loop: evict → admit →
    chunked prefill → one decode step → deliver.  ``submit_ids`` /
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
        n_pages = pages if pages is not None else env_int("PATHWAY_GENERATE_PAGES")
        if n_pages <= 0:
            # auto: half the dense worst case (the whole point of paging),
            # floored so at least one full-cache request always fits
            n_pages = max(
                self.slots * self.pages_per_seq // 2, self.pages_per_seq
            ) + 1
        self.num_pages = n_pages
        bytes_per_token = dec.kv_bytes_per_token(self.cfg)
        self.dense_kv_bytes = self.slots * self.max_cache * bytes_per_token
        self.allocator = dec.PageAllocator(
            self.num_pages, self.page_size, bytes_per_token
        )
        self._k_pool, self._v_pool = dec.init_kv_pool(
            self.cfg, self.num_pages, self.page_size
        )

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

        cfg = self.cfg

        def _decode(tree, kp, vp, bt, sl, lg, key, temp, top_p, min_p):
            greedy_tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            sampled = dec.sample_logits(
                lg, key, jnp.maximum(temp, 1e-6)[:, None],
                top_p=top_p[:, None], min_p=min_p[:, None],
            )
            tok = jnp.where(temp > 0.0, sampled, greedy_tok)
            lg2, kp, vp = dec.paged_decode_step(tree, kp, vp, bt, sl, tok, cfg)
            return tok, lg2, kp, vp

        def _prefill(tree, kp, vp, bt, ids, cl, st, old_lg, take):
            lg, kp, vp = dec.paged_prefill_chunk(
                tree, kp, vp, bt, ids, cl, st, cfg
            )
            lg = jnp.where(take[:, None], lg, old_lg)
            return lg, kp, vp

        self._decode_fn = jax.jit(_decode)
        self._prefill_fn = jax.jit(_prefill)

        self._lock = threading.Condition()
        self._queue: list[GenRequest] = []
        self._slots: list[_Slot | None] = [None] * self.slots
        self._running = False
        self._thread: threading.Thread | None = None
        self._churn_ttfts: list[float] = []
        self._tokens_total = 0
        self._tick_failures = 0
        self._last_tick_error: str | None = None
        self._tok_window: list[tuple[float, int]] = []  # (t, tokens) per tick

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
        self._m_decode_steps = reg.counter(
            "generate.decode.steps", "continuous decode ticks dispatched"
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
        self._gauges = reg  # gauges updated per tick in _update_gauges

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
        deadline=None,
        synthetic: bool = False,
    ) -> GenRequest:
        """Enqueue one request and return it — the request object carries
        the per-request telemetry (``ttft_s``, ``finished_at``) the
        serving benchmark reads; its ``.future`` resolves to the
        generated id list.

        Raises :class:`OverloadedError` when the bounded queue is full
        (the page pool's backpressure — never an OOM) and
        :class:`DeadlineExceededError` when the request arrives already
        lapsed."""
        from pathway_tpu.engine import serving as edge
        from pathway_tpu.engine import tracing

        if max_new_tokens >= self.max_cache:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} must be < "
                f"max_cache={self.max_cache}"
            )
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
            top_p=top_p, min_p=min_p, deadline=deadline, synthetic=synthetic,
            trace=tracing.current_trace(),
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
                while (
                    self._running
                    and not self._queue
                    and all(s is None for s in self._slots)
                ):
                    self._update_gauges()
                    self._lock.wait(timeout=0.5)
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

    def _fail_all(self, exc: BaseException) -> None:
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
        t0 = time.monotonic()
        with self._lock:
            self._evict_lapsed(t0)
            self._admit(t0)
            prefill_rows = [
                i for i, s in enumerate(self._slots)
                if s is not None and not s.prefill_done
            ]
            decode_rows = [
                i for i, s in enumerate(self._slots)
                if s is not None and s.prefill_done
            ]
        if prefill_rows:
            newly_ready = self._run_prefill(prefill_rows)
            decode_rows.extend(newly_ready)
        if decode_rows:
            self._run_decode(decode_rows, t0)
        with self._lock:
            self._update_gauges()
        dt = time.monotonic() - t0
        self._tok_window.append((t0, len(decode_rows)))
        if len(self._tok_window) > 256:
            del self._tok_window[:128]
        del dt

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
            self._block_tables[i, :] = 0
            self._seq_lens[i] = 0
            self._temps[i] = req.temperature
            self._top_ps[i] = 1.0 if req.top_p is None else req.top_p
            self._min_ps[i] = 0.0 if req.min_p is None else req.min_p
        self._queue[:] = remaining

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
        self._slots[i] = None
        self._block_tables[i, :] = 0
        self._seq_lens[i] = 0
        self._temps[i] = 0.0
        self._top_ps[i] = 1.0
        self._min_ps[i] = 0.0

    def _table_width(self) -> int:
        """Power-of-two block-table width covering every active slot —
        the bucketed static gather width of the compiled step."""
        most = 1
        for s in self._slots:
            if s is not None and len(s.pages) > most:
                most = len(s.pages)
        return _pow2_bucket(most, self.pages_per_seq)

    def _run_prefill(self, rows: list[int]) -> list[int]:
        """One fixed-width prefill chunk for every prefilling slot;
        returns the rows whose prompt completed (now decode-ready)."""
        jnp = self._jnp
        T = self.prefill_chunk
        ids = np.zeros((self.slots, T), np.int32)
        chunk_lens = np.zeros(self.slots, np.int32)
        starts = np.zeros(self.slots, np.int32)
        take = np.zeros(self.slots, bool)
        finishing: list[int] = []
        traced_chunks: list[tuple] = []
        with self._lock:
            for i in rows:
                slot = self._slots[i]
                if slot is None:
                    continue
                done = slot.seq_len
                n = min(T, slot.prompt_len - done)
                if n <= 0:
                    continue
                self._ensure_pages(i, done + n)
                chunk = slot.req.prompt_ids[done:done + n]
                ids[i, :n] = chunk
                chunk_lens[i] = n
                starts[i] = done
                if slot.req.trace is not None:
                    traced_chunks.append((slot.req.trace, n, done))
                if done + n >= slot.prompt_len:
                    take[i] = True
                    finishing.append(i)
            G = self._table_width()
            bt = self._block_tables[:, :G].copy()
        chunk_started = time.time()
        self._logits, self._k_pool, self._v_pool = self._prefill_fn(
            self.lm.params, self._k_pool, self._v_pool, jnp.asarray(bt),
            jnp.asarray(ids), jnp.asarray(chunk_lens), jnp.asarray(starts),
            self._logits, jnp.asarray(take),
        )
        self._m_prefill_chunks.inc()
        if traced_chunks:
            # one shared prefill program, one span per traced request —
            # the wall duration is the whole chunk's (work is fused), the
            # attributes are the request's own chunk geometry
            chunk_s = max(0.0, time.time() - chunk_started)
            for trace, n, done in traced_chunks:
                trace.add_span(
                    "generate.prefill.chunk", chunk_started, chunk_s,
                    chunk_len=int(n), prompt_start=int(done),
                )
        with self._lock:
            for i in rows:
                slot = self._slots[i]
                if slot is None:
                    continue
                n = int(chunk_lens[i])
                slot.seq_len += n
                self._seq_lens[i] = slot.seq_len
                if take[i]:
                    slot.prefill_done = True
        return finishing

    def _run_decode(self, rows: list[int], now: float) -> None:
        """One continuous decode step: sample every decode-ready row's
        next token, write paged KV, deliver/evict finished rows."""
        jax, jnp = self._jax, self._jnp
        with self._lock:
            for i in rows:
                slot = self._slots[i]
                if slot is not None:
                    self._ensure_pages(i, slot.seq_len + 1)
            G = self._table_width()
            bt = self._block_tables[:, :G].copy()
            sl = self._seq_lens.copy()
            temps = self._temps.copy()
            top_ps = self._top_ps.copy()
            min_ps = self._min_ps.copy()
        self._key, sub = jax.random.split(self._key)
        tok, self._logits, self._k_pool, self._v_pool = self._decode_fn(
            self.lm.params, self._k_pool, self._v_pool, jnp.asarray(bt),
            jnp.asarray(sl), self._logits, sub, jnp.asarray(temps),
            jnp.asarray(top_ps), jnp.asarray(min_ps),
        )
        self._m_decode_steps.inc()
        htok = np.asarray(tok)  # the one host sync per tick
        t_now = time.monotonic()
        eos = self.lm.eos_id
        produced = 0
        with self._lock:
            for i in rows:
                slot = self._slots[i]
                if slot is None or not slot.prefill_done:
                    continue
                req = slot.req
                t = int(htok[i])
                slot.seq_len += 1
                self._seq_lens[i] = slot.seq_len
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

    # -- observability -----------------------------------------------------

    def _update_gauges(self) -> None:
        reg = self._gauges
        active = sum(1 for s in self._slots if s is not None)
        a = self.allocator
        reg.gauge("generate.slots.active", "occupied generation slots").set(active)
        reg.gauge("generate.slots.total", "configured generation slots").set(
            self.slots
        )
        reg.gauge("generate.queue.depth", "requests queued for a slot").set(
            len(self._queue)
        )
        reg.gauge("generate.pages.used", "KV pool pages holding live tokens").set(
            a.used_pages
        )
        reg.gauge("generate.pages.total", "KV pool pages (page 0 reserved)").set(
            self.num_pages - 1
        )
        reg.gauge(
            "generate.kv.bytes.live", "KV bytes backing live tokens"
        ).set(a.live_bytes)
        reg.gauge(
            "generate.kv.bytes.peak", "high-water mark of live KV bytes"
        ).set(a.peak_bytes)
        reg.gauge(
            "generate.kv.bytes.dense",
            "what the dense slots x max_cache layout would hold resident",
        ).set(self.dense_kv_bytes)
        now = time.monotonic()
        window = [(t, n) for (t, n) in self._tok_window if now - t <= 5.0]
        span = (now - window[0][0]) if len(window) > 1 else 0.0
        rate = sum(n for _, n in window) / span if span > 0 else 0.0
        reg.gauge(
            "generate.tokens_per_s", "sustained decode throughput (5 s window)"
        ).set(rate)

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
                "kv_bytes_live": self.allocator.live_bytes,
                "kv_bytes_peak": self.allocator.peak_bytes,
                "kv_bytes_dense": self.dense_kv_bytes,
                "tokens_total": self._tokens_total,
                "tick_failures": self._tick_failures,
                "last_tick_error": self._last_tick_error,
            }


# ---------------------------------------------------------------------------
# Shared schedulers (the JaxChat wiring point)
# ---------------------------------------------------------------------------

_shared: dict[tuple, GenerationScheduler] = {}
_shared_lock = threading.Lock()


def continuous_enabled() -> bool:
    return env_bool("PATHWAY_GENERATE_CONTINUOUS")


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
