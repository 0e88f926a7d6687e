"""Mixture-of-Experts layer + expert parallelism (parallel/moe.py).

Semantics pinned here:
  * identical experts + ample capacity ⇒ MoE output equals the dense
    SwiGLU FFN exactly (renormalised top-k gates sum to 1),
  * expert-parallel sharded execution matches the unsharded layer,
  * capacity overflow drops tokens (zero contribution) instead of
    corrupting others,
  * gradients flow through routing: the EP train step reduces the loss,
  * load-balance aux loss is minimal iff routing is uniform,
  * serving's grouped product, the TPU's Pallas kernel (interpreted here)
    or ``jax.lax.ragged_dot``, equals the loop over the hit experts.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from pathway_tpu.ops import grouped_matmul as gm
from pathway_tpu.parallel.moe import (
    MoEConfig,
    ep_param_specs,
    init_moe_params,
    make_ep_mesh,
    make_moe_train_step,
    moe_ffn,
    moe_serve,
    route,
)


def _dense_swiglu(x, wg, wu, wd):
    h = jax.nn.silu(x @ wg) * (x @ wu)
    return h @ wd


def test_identical_experts_match_dense_ffn():
    cfg = MoEConfig(hidden=16, experts=4, intermediate=32, top_k=2,
                    capacity_factor=8.0)
    params = init_moe_params(cfg, seed=0)
    # make every expert identical to expert 0
    for name in ("wg", "wu", "wd"):
        params[name] = jnp.broadcast_to(
            params[name][:1], params[name].shape
        )
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 5, 16), jnp.float32)
    y, aux = moe_ffn(params, x, cfg)
    want = _dense_swiglu(
        x.reshape(-1, 16), params["wg"][0], params["wu"][0], params["wd"][0]
    ).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert np.isfinite(float(aux))


def test_expert_parallel_matches_unsharded():
    cfg = MoEConfig(hidden=8, experts=8, intermediate=16, top_k=2)
    params = init_moe_params(cfg, seed=2)
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 8), jnp.float32)
    y_ref, aux_ref = moe_ffn(params, x, cfg)

    mesh = make_ep_mesh(8)  # ("data", "expert") = (1, 8)
    specs = ep_param_specs()
    sharded = jax.tree_util.tree_map(
        lambda t, s: jax.device_put(t, NamedSharding(mesh, s)), params, specs
    )
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    y_ep, aux_ep = jax.jit(lambda p, v: moe_ffn(p, v, cfg, mesh))(sharded, xs)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux_ep), float(aux_ref), rtol=1e-5)


def test_capacity_overflow_drops_not_corrupts():
    # capacity 2 tokens/expert; all-positive tokens × a column-0-biased
    # router puts every token's top choice on expert 0
    cfg = MoEConfig(hidden=8, experts=4, intermediate=16, top_k=1,
                    capacity_factor=0.5)
    params = init_moe_params(cfg, seed=4)
    params["router"] = jnp.zeros_like(params["router"]).at[:, 0].set(100.0)
    x = 0.1 + jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (16, 8), jnp.float32))
    y, _ = moe_ffn(params, x, cfg)
    C = cfg.capacity(16)
    assert C < 16
    got = np.asarray(y)
    # first C tokens processed by expert 0, the rest dropped to exactly zero
    want_head = _dense_swiglu(
        x[:C], params["wg"][0], params["wu"][0], params["wd"][0]
    )
    np.testing.assert_allclose(got[:C], np.asarray(want_head), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[C:], 0.0, atol=1e-6)


def test_ep_train_step_reduces_loss():
    cfg = MoEConfig(hidden=8, experts=4, intermediate=16, top_k=2)
    mesh = make_ep_mesh(8, expert_parallel=4)  # ("data","expert") = (2, 4)
    init_fn, step_fn = make_moe_train_step(cfg, optax.adam(1e-2), mesh)
    params, opt_state = init_fn(seed=0)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    target = np.tanh(x @ rng.normal(size=(8, 8)).astype(np.float32))
    losses = []
    for _ in range(10):
        params, opt_state, loss = step_fn(params, opt_state, x, target)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_grouped_dispatch_matches_single_group():
    # GShard group axis: chunking tokens into groups (with a padded ragged
    # tail) must not change the output when capacity is ample
    import dataclasses

    base = MoEConfig(hidden=8, experts=4, intermediate=16, top_k=2,
                     capacity_factor=8.0, group_size=0)
    grouped = dataclasses.replace(base, group_size=7)  # 5 groups, tail pad 3
    params = init_moe_params(base, seed=8)
    x = jax.random.normal(jax.random.PRNGKey(9), (32, 8), jnp.float32)
    y_single, aux_single = moe_ffn(params, x, base)
    y_grouped, aux_grouped = moe_ffn(params, x, grouped)
    np.testing.assert_allclose(
        np.asarray(y_grouped), np.asarray(y_single), rtol=1e-5, atol=1e-5
    )
    # aux is a per-group weighted mean of the same statistic — close but
    # not identical (group-local token fractions)
    assert np.isfinite(float(aux_grouped))


def test_serving_never_drops():
    # capacity_factor tiny: the training form drops most tokens, the
    # serving form (sorted pairs, one grouped product) has no capacity —
    # identical experts must still reproduce the dense FFN
    cfg = MoEConfig(hidden=8, experts=4, intermediate=16, top_k=2,
                    capacity_factor=0.1)
    params = init_moe_params(cfg, seed=10)
    for name in ("wg", "wu", "wd"):
        params[name] = jnp.broadcast_to(params[name][:1], params[name].shape)
    x = jax.random.normal(jax.random.PRNGKey(11), (24, 8), jnp.float32)
    y, pairs, hit, _tiles = moe_serve(params, x, cfg)
    want = _dense_swiglu(x, params["wg"][0], params["wu"][0], params["wd"][0])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert int(pairs) == 24 * 2 and 1 <= int(hit) <= 4
    y_drop, _ = moe_ffn(params, x, cfg)
    assert not np.allclose(np.asarray(y_drop), np.asarray(want), atol=1e-3)


def test_aux_loss_prefers_uniform_routing():
    # drive _routing with crafted logits: uniform probabilities score the
    # minimum (1.0); collapsed routing scores ≈ E
    from pathway_tpu.parallel.moe import _routing

    cfg = MoEConfig(hidden=4, experts=4, intermediate=8, top_k=1)
    uniform = jnp.zeros((32, 4), jnp.float32)
    _, _, aux_uniform = _routing(uniform, cfg, capacity=32)
    collapsed = uniform.at[:, 0].set(50.0)
    _, _, aux_collapsed = _routing(collapsed, cfg, capacity=32)
    assert float(aux_uniform) == pytest.approx(1.0, abs=1e-4)
    assert float(aux_collapsed) == pytest.approx(4.0, abs=1e-2)


def test_serving_matches_training_form_with_room():
    # with capacity to spare nothing is dropped, and the two forms are the
    # same mathematics: softmax top-2, renormalised, every expert held
    cfg = MoEConfig(hidden=8, experts=4, intermediate=16, top_k=2,
                    capacity_factor=8.0)
    params = init_moe_params(cfg, seed=12)
    x = jax.random.normal(jax.random.PRNGKey(13), (32, 8), jnp.float32)
    y_train, _ = moe_ffn(params, x, cfg)
    y_serve, pairs, _hit, _tiles = moe_serve(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(y_serve), np.asarray(y_train), rtol=1e-5, atol=1e-5
    )
    assert int(pairs) == 64


def test_serving_padding_takes_no_expert():
    # rows that hold no token are routed nowhere: not counted, not computed
    cfg = MoEConfig(hidden=8, experts=4, intermediate=16, top_k=2)
    params = init_moe_params(cfg, seed=14)
    x = jax.random.normal(jax.random.PRNGKey(15), (2, 6, 8), jnp.float32)
    valid = jnp.arange(6)[None, :] < jnp.asarray([6, 2])[:, None]
    y, pairs, _hit, _tiles = moe_serve(params, x, cfg, valid)
    assert int(pairs) == (6 + 2) * 2
    assert np.all(np.asarray(y)[1, 2:] == 0.0)
    y_all, *_ = moe_serve(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y)[0], np.asarray(y_all)[0], atol=1e-6)


# --- the two paths of ``moe_serve``: a loop over the hit experts where the
# program has few rows, the grouped product where it has many ---------------

def _serve_params(cfg, *, seed, dtype=jnp.float32, shared=0, bias=False, layers=0,
                  int8=False):
    """``moe_serve``'s params for ``cfg``: a router over the whole width,
    the held experts' matrices (stacked ``[layers, E, ...]`` where
    ``layers``; int8 pairs where ``int8``), a correction bias, a shared
    expert."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    E, H, F = cfg.experts, cfg.hidden, cfg.intermediate
    lead = (layers, E) if layers else (E,)

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)).astype(dtype)

    def stored(w):
        if not int8:
            return w
        s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        return {"q": jnp.round(w / s).astype(jnp.int8), "s": s.astype(jnp.float32)}

    params = {
        "router": jax.random.normal(keys[0], (H, cfg.router_width or E), jnp.float32),
        "wu": stored(normal(keys[2], lead + (H, F), H)),
        "wd": stored(normal(keys[3], lead + (F, H), F)),
    }
    if cfg.gated:
        params["wg"] = stored(normal(keys[1], lead + (H, F), H))
    if bias:
        params["bias"] = 0.3 * jax.random.normal(keys[4], (cfg.router_width or E,))
    if shared:
        params["shared_up"] = normal(keys[5], (H, shared), H)
        params["shared_down"] = normal(keys[6], (shared, H), shared)
    if layers:
        params["layer"] = jnp.int32(layers - 2)
    return params


def _served(monkeypatch, rows_in_place, params, x, cfg, valid=None, kernel=False):
    """``moe_serve`` jitted with the rule's constant set for the test (and
    with ``kernel`` the TPU's grouped kernel, interpreted, as the grouped
    product), and the path its program took, read from the program:
    ``(y, pairs, experts_hit, in place, tile_rows, the program's text)``."""
    from pathway_tpu.parallel import moe

    monkeypatch.setattr(moe, "IN_PLACE_ROWS", rows_in_place)
    if kernel:
        monkeypatch.setattr(
            moe, "_grouped_product", functools.partial(gm.grouped_matmul, interpret=True)
        )
    fn = jax.jit(lambda p, x: moe_serve(p, x, cfg, valid))
    text = str(jax.make_jaxpr(fn)(params, x))
    grouped = "ragged_dot" in text or "pallas_call" in text
    assert grouped != ("while[" in text)
    y, pairs, hit, tile_rows = fn(params, x)
    return np.asarray(y, np.float32), int(pairs), int(hit), not grouped, int(tile_rows), text


_WIDE = dict(hidden=16, experts=4, intermediate=24, router_width=16, first_expert=8)


def _elsewhere_only(params, cfg):
    # every token chooses among experts 0-3, none of those held here (8-11)
    bias = jnp.where(jnp.arange(cfg.router_width) < 4, 100.0, 0.0)
    return {**params, "bias": bias}


def _one_expert(params, cfg):
    # both rows choose held expert 2 (the router's column 10), whatever they hold
    return {**params, "bias": jnp.where(jnp.arange(cfg.router_width) == 10, 100.0, 0.0)}


SERVE_CASES = {
    # name: (cfg, params' keywords, rows, valid, what is done to params, (pairs, hit) where known)
    "gated": (MoEConfig(hidden=16, experts=4, intermediate=24, top_k=2), {}, 24, None, None, (48, None)),
    "ungated-shared-sigmoid-bias-scaled": (
        MoEConfig(**_WIDE, top_k=6, scoring="sigmoid", gated=False, route_scale=2.5),
        dict(shared=32, bias=True), 8, None, None, None,
    ),
    "held-elsewhere-only-shared": (
        MoEConfig(**_WIDE, top_k=3, scoring="sigmoid", gated=False),
        dict(shared=32), 8, None, _elsewhere_only, (0, 0),
    ),
    "held-elsewhere-only-noughts": (
        MoEConfig(**_WIDE, top_k=3, scoring="sigmoid"), {}, 8, None, _elsewhere_only, (0, 0),
    ),
    "every-row-invalid": (
        MoEConfig(hidden=16, experts=4, intermediate=24, top_k=2), {}, 8,
        np.zeros(8, bool), None, (0, 0),
    ),
    "some-rows-invalid": (
        MoEConfig(**_WIDE, top_k=6, gated=False), dict(shared=32), 8,
        np.arange(8) % 3 == 0, None, None,
    ),
    "two-rows-one-expert": (
        MoEConfig(**_WIDE, top_k=1, scoring="sigmoid"), {}, 2, None, _one_expert, (2, 1),
    ),
    "stacked-with-layer": (
        MoEConfig(**_WIDE, top_k=6, gated=False), dict(layers=3, shared=32), 8, None, None, None,
    ),
    "stacked-gated": (
        MoEConfig(hidden=16, experts=4, intermediate=24, top_k=2), dict(layers=3), 8, None, None, (16, None),
    ),
    "int8-pair": (
        MoEConfig(hidden=16, experts=4, intermediate=24, top_k=2), dict(int8=True), 8, None, None, (16, None),
    ),
    "int8-pair-stacked-ungated": (
        MoEConfig(**_WIDE, top_k=6, gated=False), dict(int8=True, layers=3), 8, None, None, None,
    ),
}


def _tile_rows(params, x, cfg, valid=None) -> int:
    """The rows the grouped kernel multiplies for ``x``'s routing, by the
    formula: each held expert's run of sorted pairs touches the tiles of
    128 from the one its first pair falls in to the one its last does."""
    logits = jnp.matmul(
        x.reshape(-1, cfg.hidden).astype(jnp.float32), params["router"],
        precision=jax.lax.Precision.HIGHEST,
    )
    local = np.asarray(route(logits, cfg, params.get("bias"))[0]) - cfg.first_expert
    here = (local >= 0) & (local < cfg.experts)
    if valid is not None:
        here &= np.asarray(valid).reshape(-1)[:, None]
    ends = np.cumsum(np.bincount(local[here], minlength=cfg.experts))
    starts = np.concatenate([[0], ends[:-1]])
    return 128 * sum(-(-e // 128) - s // 128 for s, e in zip(starts, ends) if e > s)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(SERVE_CASES))
def test_serving_loop_over_hit_experts_equals_grouped_product(monkeypatch, case, dtype):
    """The same inputs through both paths, the grouped one with
    ``jax.lax.ragged_dot`` and with the TPU's kernel (interpreted) as its
    product: equal results (float32 to 1e-5; bfloat16 within the rounding
    of the hidden activation and the output), ``pairs`` and
    ``experts_hit`` equal exactly, ``tile_rows`` the formula's (nought on
    the loop's path).  An int8 weight-only pair keeps ``ragged_dot``."""
    cfg, keywords, rows, valid, change, counts = SERVE_CASES[case]
    dtype = jnp.dtype(dtype)
    params = _serve_params(cfg, seed=len(case), dtype=dtype, **keywords)
    if change is not None:
        params = change(params, cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), (rows, cfg.hidden), jnp.float32).astype(dtype)
    valid = None if valid is None else jnp.asarray(valid)
    loop = _served(monkeypatch, 10**9, params, x, cfg, valid)
    grouped = _served(monkeypatch, 0, params, x, cfg, valid)
    kernel = _served(monkeypatch, 0, params, x, cfg, valid, kernel=True)
    assert loop[3] and not grouped[3] and not kernel[3]  # each took its path
    int8 = "int8" in case
    assert ("pallas_call" in kernel[5]) != int8 and ("ragged_dot" in kernel[5]) == int8
    assert loop[1:3] == grouped[1:3] == kernel[1:3]
    assert loop[4] == 0 and grouped[4] == kernel[4] == _tile_rows(params, x, cfg, valid)
    assert kernel[4] >= kernel[1]
    tol = 1e-5 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(loop[0], grouped[0], rtol=tol, atol=tol)
    np.testing.assert_allclose(kernel[0], grouped[0], rtol=tol, atol=tol)
    np.testing.assert_allclose(kernel[0], loop[0], rtol=tol, atol=tol)
    if counts is not None:
        pairs, hit = counts
        assert loop[1] == pairs and (hit is None or loop[2] == hit)
    if counts == (0, 0):
        # a trip count of nought: the shared expert's alone, or noughts
        if "shared_up" in params:
            h = jnp.square(jax.nn.relu(x @ params["shared_up"]))
            want = np.asarray((h @ params["shared_down"]).astype(jnp.float32))
            np.testing.assert_allclose(loop[0], want, rtol=tol, atol=tol)
            assert np.abs(want).max() > 0.1
        else:
            assert np.all(loop[0] == 0.0)
    elif valid is not None:
        assert np.abs(loop[0][np.asarray(valid)]).max() > 0.01


# the grouped kernel alone against ``jax.lax.ragged_dot``: rows ``M``, ``K``
# and ``N`` wide, each group's rows in order (the last rows past them held
# elsewhere), a budget for the weight block that makes ``tk`` < ``K``
GROUPED_CASES = {
    "empty-groups-ragged-sizes-rows-past": (300, 64, 96, [70, 0, 130, 0, 45, 0], None),
    "one-group-of-every-row": (256, 64, 128, [256], None),
    "no-rows-in-any-group": (128, 64, 128, [0, 0, 0], None),
    # a stack of three layers' four experts, layer 2's at 2 * 4 (the
    # scan's layout: a layer reads the stack where it lies)
    "stacked-layer-groups-at-layer-times-experts": (
        256, 64, 128, [0] * 8 + [40, 90, 0, 100], None,
    ),
    "fifteen-lanes": (384, 128, 1920, [100, 3, 0, 150, 60, 20], None),
    "twenty-one-lanes": (256, 128, 2688, [20, 200, 0, 30], None),
    "twenty-one-lanes-deep": (256, 2688, 128, [129, 0, 100], None),
    "deep-in-tiles-of-k": (256, 384, 256, [60, 60, 0, 130], 2 * 256 * 2 * 150),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(GROUPED_CASES))
def test_grouped_kernel_equals_ragged_dot(monkeypatch, case, dtype):
    """Every row of a group is the product ``jax.lax.ragged_dot`` gives
    (float32 to 1e-5; bfloat16, accumulated in float32 by both, within
    one rounding of the output), in the rows' dtype, and the steps the
    kernel takes are the row tiles its groups touch."""
    M, K, N, sizes, budget = GROUPED_CASES[case]
    dtype = jnp.dtype(dtype)
    if budget is not None:
        monkeypatch.setattr(gm, "_WEIGHT_VMEM", budget)
        gm.grouped_matmul.clear_cache()
    tm, tk, tn = gm.tiling(K, N, dtype.itemsize)
    assert tm == 128 and K % tk == 0 and N % tn == 0
    assert (tk < K) == (budget is not None)
    kx, kw = jax.random.split(jax.random.PRNGKey(len(case)))
    x = jax.random.normal(kx, (M, K), jnp.float32).astype(dtype)
    w = (jax.random.normal(kw, (len(sizes), K, N), jnp.float32) / np.sqrt(K)).astype(dtype)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = gm.grouped_matmul(x, w, group_sizes, interpret=True)
    if budget is not None:
        gm.grouped_matmul.clear_cache()
    want = jax.lax.ragged_dot(x, w, group_sizes)
    assert got.shape == (M, N) and got.dtype == dtype
    n = sum(sizes)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got[:n], np.float32), np.asarray(want[:n], np.float32), rtol=tol, atol=tol
    )
    ends = np.cumsum(sizes)
    touched = sum(-(-e // tm) - (e - s) // tm for s, e in zip(sizes, ends) if s)
    assert int(gm.row_tiles(group_sizes)) == touched
    tiles = -(-M // tm)
    offsets, group_of, tile_of, steps = gm.tile_plan(group_sizes, tiles)
    assert int(steps) == touched
    walk = list(zip(np.asarray(group_of)[:touched], np.asarray(tile_of)[:touched]))
    # a tile is visited by its groups one after another, in row order
    assert walk == sorted(walk) and all(0 <= t < tiles for _g, t in walk)
    assert list(np.asarray(offsets)) == [0, *ends]


def test_grouped_kernel_tiling_is_a_rule_of_the_widths():
    """``tm`` 128; ``tn`` the widest lane multiple dividing ``N`` up to
    1,024; ``tk`` all of ``K`` where its double-buffered weight block fits
    16 MiB: the widths of the benchmark's three routed models' products,
    a deeper one, and widths that are no lane multiple."""
    bf16 = 2
    assert gm.tiling(2688, 1920, bf16) == (128, 2688, 640)  # Nemotron's up
    assert gm.tiling(1920, 2688, bf16) == (128, 1920, 896)  # ... and down
    assert gm.tiling(3072, 1024, bf16) == (128, 3072, 1024)  # Laguna's up
    assert gm.tiling(1024, 3072, bf16) == (128, 1024, 1024)
    assert gm.tiling(4096, 2048, bf16) == (128, 4096, 1024)  # MiMo's up
    assert gm.tiling(2048, 4096, bf16) == (128, 2048, 1024)
    assert gm.tiling(16384, 1024, bf16) == (128, 4096, 1024)
    assert gm.tiling(48, 40, 4) == (128, 48, 40)


def test_serving_path_is_chosen_by_the_rows_alone(monkeypatch):
    """128 rows loop, 129 sort: the rule's two sides give equal results on
    the rows they share, and a decode step's 8 rows are far inside it."""
    from pathway_tpu.parallel import moe

    assert moe.IN_PLACE_ROWS == 128
    assert moe.serves_in_place(8) and moe.serves_in_place(128)
    assert not moe.serves_in_place(129) and not moe.serves_in_place(256)
    cfg = MoEConfig(**_WIDE, top_k=6, gated=False, route_scale=2.5)
    params = _serve_params(cfg, seed=3, shared=32)
    x = jax.random.normal(jax.random.PRNGKey(5), (129, 16), jnp.float32)
    few = _served(monkeypatch, moe.IN_PLACE_ROWS, params, x[:128], cfg)
    many = _served(monkeypatch, moe.IN_PLACE_ROWS, params, x, cfg)
    assert few[3] and not many[3]
    np.testing.assert_allclose(few[0], many[0][:128], rtol=1e-5, atol=1e-5)
    assert 0 < few[1] <= many[1] <= few[1] + 6 and few[2] == many[2] == 4
    # leading axes multiply: [8, 16] rows take the loop, [8, 32] the sort
    for shape, in_place in (((8, 16, 16), True), ((8, 32, 16), False), ((8, 1, 16), True)):
        assert _served(monkeypatch, moe.IN_PLACE_ROWS, params, jnp.ones(shape), cfg)[3] is in_place
