"""JAX device accounting: the DYNAMIC half of recompile-count == 0.

`pathway_tpu lint`'s jit rules (PR 6, `analysis/jit.py`) statically
reject call-site shapes that guarantee recompiles; these tests close the
loop at runtime: `engine/profiler.py` registers `jax.monitoring`
listeners so `jax.cache.miss` / `jax.compile.*` count real traces and
XLA compilations.  The pin (ROADMAP, DeviceExecutor arc): a steady-state
stream of repeat batches through a jitted model path must record ZERO
cache misses; a forced shape change must move the counter.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pathway_tpu.engine import metrics as em
from pathway_tpu.engine.profiler import (
    install_jax_accounting,
    install_transfer_accounting,
    uninstall_transfer_accounting,
)
from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoderModule

# tiny trunk: the real module tree (models/encoder.py), CPU-jittable in
# well under a second
_CFG = EncoderConfig(
    vocab_size=64, hidden=16, layers=1, heads=2, intermediate=32,
    max_len=32, dtype=jnp.float32,
)


def _counters() -> dict[str, float]:
    s = em.get_registry().scalar_metrics()
    return {
        "miss": s.get("jax.cache.miss", 0.0),
        "compiles": s.get("jax.compile.count", 0.0),
        "compile_s": s.get("jax.compile.seconds", 0.0),
    }


@pytest.fixture(scope="module")
def jitted_encoder():
    assert install_jax_accounting(force=True)
    module = SentenceEncoderModule(_CFG)
    params = module.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32),
        jnp.ones((1, 8), jnp.int32),
    )
    apply = jax.jit(module.apply)
    return apply, params


def _batch(batch: int, seq: int):
    ids = jnp.asarray(np.ones((batch, seq), np.int32))
    mask = jnp.asarray(np.ones((batch, seq), np.int32))
    return ids, mask


def test_first_encode_counts_cache_miss_and_compile(jitted_encoder):
    apply, params = jitted_encoder
    before = _counters()
    apply(params, *_batch(2, 8)).block_until_ready()
    after = _counters()
    assert after["miss"] > before["miss"]
    assert after["compiles"] > before["compiles"]
    assert after["compile_s"] > before["compile_s"]


def test_steady_state_repeat_batches_record_zero_misses(jitted_encoder):
    """THE pin: N repeat batches of the warm (bucketed) shape through the
    jitted encode path — `jax.cache.miss` must not move at all."""
    apply, params = jitted_encoder
    apply(params, *_batch(2, 8)).block_until_ready()  # warm the cache
    before = _counters()
    for _ in range(5):
        # fresh host arrays each iteration, same shapes — the streaming
        # steady state the DeviceExecutor bucketing is meant to produce
        apply(params, *_batch(2, 8)).block_until_ready()
    after = _counters()
    assert after["miss"] - before["miss"] == 0.0
    assert after["compiles"] - before["compiles"] == 0.0


def test_forced_shape_change_moves_the_miss_counter(jitted_encoder):
    apply, params = jitted_encoder
    apply(params, *_batch(2, 8)).block_until_ready()  # warm shape A
    before = _counters()
    apply(params, *_batch(4, 16)).block_until_ready()  # unbucketed shape
    after = _counters()
    assert after["miss"] > before["miss"]
    assert after["compiles"] > before["compiles"]


def test_executor_churning_ragged_batches_record_zero_misses(jitted_encoder):
    """THE DeviceExecutor pin (ISSUE 11): a churning stream of RAGGED
    batch sizes through the executor's bucketed path — after warmup,
    `jax.cache.miss` must not move at all.  This is the half the static
    jit rules cannot see (shape-value variance), closed dynamically."""
    del jitted_encoder  # only need the module-scoped accounting install
    from pathway_tpu.device import BucketPolicy, DeviceExecutor
    from pathway_tpu.models.encoder import SentenceEncoderModule

    module = SentenceEncoderModule(_CFG)
    params = module.init(
        jax.random.PRNGKey(1),
        jnp.zeros((1, 8), jnp.int32),
        jnp.ones((1, 8), jnp.int32),
    )
    ex = DeviceExecutor(collector_name=None)
    ex.register(
        "accounting:encoder",
        lambda p, ids, mask: module.apply(p, ids, mask),
        policy=BucketPolicy(max_bucket=16),
    )
    ex.warmup(
        "accounting:encoder",
        row_shapes=((8,), (8,)),
        dtypes=(np.int32, np.int32),
        operands=(params,),
    )
    before = _counters()
    rng = np.random.default_rng(11)
    for _ in range(12):
        n = int(rng.integers(1, 23))  # ragged, and sometimes > max bucket
        ids = np.ones((n, 8), np.int32)
        mask = np.ones((n, 8), np.int32)
        out = ex.run_batch("accounting:encoder", (ids, mask), operands=(params,))
        assert out.shape == (n, _CFG.hidden)
    after = _counters()
    assert after["miss"] - before["miss"] == 0.0
    assert after["compiles"] - before["compiles"] == 0.0
    assert ex.stats("accounting:encoder")["cold"] == 0


def test_paged_decode_churn_records_zero_misses(jitted_encoder):
    """THE continuous-batching pin (ISSUE 18): a churning request mix —
    mixed prompt lengths, admissions into freed slots, chunked prefill
    interleaved with decode — replays WARM compiled programs.  Slot count
    is fixed, prefill shapes come from a ladder of at most four widths
    (one here: an explicit ``prefill_chunk``) and the block-table gather
    width is bucketed to powers of two, so after one warm pass over the
    trace the same trace (fresh host arrays every tick) must record ZERO
    cache misses."""
    del jitted_encoder  # only need the module-scoped accounting install
    from pathway_tpu.models.decoder import shared_decoder
    from pathway_tpu.serving.generation import GenRequest, GenerationScheduler

    lm = shared_decoder("pw-tiny-decoder", max_cache=64)
    sched = GenerationScheduler(
        lm, slots=2, page_size=16, prefill_chunk=8, queue_limit=32
    )
    rng = np.random.default_rng(18)
    # (arrival tick, prompt length, max_new): long prompts force several
    # prefill chunks while short ones decode; staggered arrivals force
    # admission into freed slots mid-stream
    trace = [(0, 3, 6), (0, 20, 4), (2, 1, 8), (5, 11, 5), (9, 2, 4)]
    prompts = [
        [int(t) for t in rng.integers(1, 500, n)] for _, n, _ in trace
    ]

    def run_trace():
        reqs = []
        tick = 0
        while True:
            for (at, _, mn), ids in zip(trace, prompts):
                if at == tick:
                    # fresh host list each pass: greedy + same ids means
                    # an identical schedule, so pass 2 replays the exact
                    # shape sequence pass 1 compiled
                    reqs.append(GenRequest(list(ids), mn))
                    with sched._lock:
                        sched._queue.append(reqs[-1])
            with sched._lock:
                idle = not sched._queue and all(
                    s is None for s in sched._slots
                )
            if idle and tick > max(at for at, _, _ in trace):
                return reqs
            sched._tick()
            tick += 1
            assert tick < 500

    try:
        first = run_trace()  # warm pass: compiles every bucketed variant
        before = _counters()
        second = run_trace()
        after = _counters()
        assert after["miss"] - before["miss"] == 0.0
        assert after["compiles"] - before["compiles"] == 0.0
        # and the replay really generated: identical greedy outputs
        for a, b in zip(first, second):
            assert a.future.result(timeout=1) == b.future.result(timeout=1)
    finally:
        sched.shutdown()


def test_transfer_accounting_counts_explicit_bytes():
    assert install_transfer_accounting(force=True)
    try:
        reg = em.get_registry()
        before = reg.scalar_metrics()
        x = np.ones((16, 16), np.float32)  # 1024 bytes
        on_device = jax.device_put(x)
        jax.device_get(on_device)
        after = reg.scalar_metrics()
        assert (
            after["jax.transfer.h2d.bytes"]
            - before.get("jax.transfer.h2d.bytes", 0.0)
        ) >= x.nbytes
        assert (
            after["jax.transfer.d2h.bytes"]
            - before.get("jax.transfer.d2h.bytes", 0.0)
        ) >= x.nbytes
    finally:
        uninstall_transfer_accounting()
    # uninstall restores the real entry points
    assert jax.device_put.__module__.startswith("jax")
