"""Mixture-of-Experts layer + expert parallelism (parallel/moe.py).

Semantics pinned here:
  * identical experts + ample capacity ⇒ MoE output equals the dense
    SwiGLU FFN exactly (renormalised top-k gates sum to 1),
  * expert-parallel sharded execution matches the unsharded layer,
  * capacity overflow drops tokens (zero contribution) instead of
    corrupting others,
  * gradients flow through routing: the EP train step reduces the loss,
  * load-balance aux loss is minimal iff routing is uniform.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from pathway_tpu.parallel.moe import (
    MoEConfig,
    ep_param_specs,
    init_moe_params,
    make_ep_mesh,
    make_moe_train_step,
    moe_ffn,
    moe_serve,
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
    y, pairs, hit = moe_serve(params, x, cfg)
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
    y_serve, pairs, _hit = moe_serve(params, x, cfg)
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
    y, pairs, _hit = moe_serve(params, x, cfg, valid)
    assert int(pairs) == (6 + 2) * 2
    assert np.all(np.asarray(y)[1, 2:] == 0.0)
    y_all, _, _ = moe_serve(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y)[0], np.asarray(y_all)[0], atol=1e-6)
