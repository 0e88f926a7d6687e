"""Pipeline parallelism for the decoder family (GPipe schedule over a
``stage`` mesh axis).

The reference's only parallel axis is data sharding over SPMD workers
(SURVEY.md §2b); models that do not fit one device's memory are out of its
reach.  Here the decoder trunk splits into ``n_stages`` contiguous layer
groups, one per chip along a ``stage`` mesh axis, and microbatches stream
through the classic GPipe schedule: ``n_micro + n_stages - 1`` ticks, each
stage processing one microbatch per tick while activations rotate to the
next stage via ``ppermute`` (one hop over ICI per tick — the collective
pattern from the scaling-book pipelining chapter).

TPU-first design notes:

* **One compiled program.**  The whole schedule is a ``lax.scan`` over
  ticks inside a single ``shard_map`` — every stage runs the same SPMD
  code, XLA overlaps the ``ppermute`` with the next tick's matmuls.
* **Static schedule.**  Bubble ticks compute on zero activations with an
  all-False attention mask (finite by construction — uniform softmax over
  a constant row) and their results are discarded; no data-dependent
  control flow, no recompiles.
* **Backward = autodiff.**  The pipelined forward is a pure jittable
  function; ``jax.grad`` differentiates through ``ppermute`` (its
  transpose is the reverse rotation), giving pipeline-parallel training
  without a hand-written backward schedule.
* Embedding and the LM head are computed outside the pipeline on the
  full batch (replicated params — they are a few percent of weights);
  the stage axis carries only the transformer trunk, which is where the
  per-layer weight memory lives.

Microbatch inputs are replicated to every stage (the GPipe "all inputs
visible" simplification): memory cost ``n_micro × mb × S × H`` per chip,
negligible next to stage weights at serving shapes.  A production
refinement would stream microbatches into stage 0 only.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pathway_tpu.models.decoder import DecoderConfig, decoder_layer, _rms, _sw_mask


def make_pp_mesh(n_stages: int) -> Mesh:
    """A 1-D ``("stage",)`` mesh over the first ``n_stages`` devices."""
    devices = jax.devices()[:n_stages]
    return Mesh(np.asarray(devices).reshape(n_stages), ("stage",))


def stack_stages(tree, n_stages: int):
    """Reshape the decoder's stacked layer tree ``[L, ...]`` into
    ``[n_stages, L/n_stages, ...]`` so stage ``s`` owns rows ``[s]``."""
    L = jax.tree_util.tree_leaves(tree["layers"])[0].shape[0]
    if L % n_stages:
        raise ValueError(f"{L} layers do not split into {n_stages} stages")
    lps = L // n_stages
    return {
        **tree,
        "layers": jax.tree_util.tree_map(
            lambda p: p.reshape(n_stages, lps, *p.shape[1:]), tree["layers"]
        ),
    }


def pp_param_specs(tree, axis: str = "stage"):
    """PartitionSpecs for the stage-stacked ``tree``: every layer leaf
    (whatever its name — dense or MoE) shards its leading stage axis;
    embed/norm/head replicated (computed off-pipeline)."""
    return {
        "embed": P(None, None),
        "final_norm": P(None),
        "lm_head": P(None, None),
        "layers": jax.tree_util.tree_map(lambda _: P(axis), tree["layers"]),
    }


def place_pp_params(tree, mesh: Mesh):
    """Stack ``tree`` by the mesh's stage count and shard it."""
    n_stages = mesh.shape["stage"]
    stacked = stack_stages(tree, n_stages)
    specs = pp_param_specs(stacked)
    return jax.tree_util.tree_map(
        lambda t, s: jax.device_put(t, NamedSharding(mesh, s)), stacked, specs
    )


def _stage_forward(stage_layers, x, valid, cfg: DecoderConfig):
    """Run one stage's layer rows over activations ``x [mb, S, H]``."""
    S = x.shape[1]
    positions = jnp.arange(S)[None, :].repeat(x.shape[0], axis=0)
    causal = jnp.tril(jnp.ones((S, S), bool))
    if cfg.sliding_window is not None:
        causal = causal & _sw_mask(
            jnp.arange(S)[:, None], jnp.arange(S)[None, :], cfg.sliding_window
        )
    mask = causal[None, :, :] & (valid > 0)[:, None, :]

    def body(x, lp):
        # the pipelined trunk is a serving path (MoE training under pp is
        # rejected), so MoE dispatch runs lossless
        x, _, _ = decoder_layer(lp, x, positions, mask, cfg, serving=True)
        return x, None

    if cfg.remat:
        # honor the memory knob under pp training too: each stage's
        # backward recomputes its layers instead of storing activations
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = lax.scan(body, x, stage_layers)
    return x


def make_pipelined_causal_lm(
    cfg: DecoderConfig, mesh: Mesh, n_micro: int
) -> Callable:
    """Pipelined all-position logits: ``fn(tree, ids, lengths) -> [B, S, V]``.

    ``tree`` is a stage-stacked param tree (``place_pp_params``); the
    batch ``B = n_micro × mb`` splits into microbatches along its leading
    axis.  Matches ``causal_lm_logits`` within tight f32 tolerance (pinned
    by tests at 2e-4) — the schedule changes the execution order, not the
    math.

    MoE configs pipeline too, with LOSSLESS expert dispatch (the pipelined
    trunk is a serving path — MoE training under pp is rejected), so it
    matches ``causal_lm_logits`` — whose training-policy dispatch can drop
    tokens — only when the trunk drops nothing (ample capacity factor; the
    MoE pinning test uses 16.0).  The aux loss is not collected — see
    ``make_pp_train_step``.
    """
    n_stages = mesh.shape["stage"]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    n_ticks = n_micro + n_stages - 1

    def trunk(stage_layers, xs, valids):
        # stage_layers: this stage's rows [1, Lps, ...]; xs [n_micro, mb, S, H]
        stage_layers = jax.tree_util.tree_map(lambda p: p[0], stage_layers)
        stage = lax.axis_index("stage")
        state_x = jnp.zeros_like(xs[0])
        state_valid = jnp.zeros_like(valids[0])
        outputs = jnp.zeros_like(xs)

        def tick(carry, t):
            state_x, state_valid, outputs = carry
            inj = jnp.clip(t, 0, n_micro - 1)
            in_x = lax.dynamic_index_in_dim(xs, inj, 0, keepdims=False)
            in_v = lax.dynamic_index_in_dim(valids, inj, 0, keepdims=False)
            first = stage == 0
            x = jnp.where(first, in_x, state_x)
            valid = jnp.where(first, in_v, state_valid)
            y = _stage_forward(stage_layers, x, valid, cfg)
            out_idx = t - (n_stages - 1)
            outputs = jnp.where(
                (stage == n_stages - 1) & (out_idx >= 0),
                lax.dynamic_update_index_in_dim(
                    outputs, y, jnp.clip(out_idx, 0, n_micro - 1), 0
                ),
                outputs,
            )
            state_x = lax.ppermute(y, "stage", perm)
            state_valid = lax.ppermute(valid, "stage", perm)
            return (state_x, state_valid, outputs), None

        (_, _, outputs), _ = lax.scan(
            tick, (state_x, state_valid, outputs), jnp.arange(n_ticks)
        )
        # only the last stage holds real outputs; psum broadcasts them
        outputs = jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs))
        return lax.psum(outputs, "stage")

    trunk_sm = jax.shard_map(
        trunk,
        mesh=mesh,
        # P("stage") is a tree prefix: every layer leaf (dense or MoE)
        # shards its leading stage axis
        in_specs=(P("stage"), P(None), P(None)),
        out_specs=P(None),
        check_vma=False,
    )

    def fn(tree, ids, lengths):
        B, S = ids.shape
        if B % n_micro:
            raise ValueError(f"batch {B} not divisible by n_micro={n_micro}")
        mb = B // n_micro
        x = tree["embed"][ids]  # [B, S, H]
        positions = jnp.arange(S)[None, :]
        valid = (positions < lengths[:, None]).astype(jnp.int32)
        xs = x.reshape(n_micro, mb, S, cfg.hidden)
        valids = valid.reshape(n_micro, mb, S)
        out = trunk_sm(tree["layers"], xs, valids)
        x = out.reshape(B, S, cfg.hidden)
        x = _rms(x, tree["final_norm"], cfg.norm_eps)
        return (x @ tree["lm_head"]).astype(jnp.float32)

    return fn


def make_pp_train_step(
    cfg: DecoderConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    n_micro: int,
) -> tuple[Callable, Callable]:
    """Pipeline-parallel next-token training.

    Returns ``(init_state, run)``; same loss as
    ``make_causal_lm_train_step`` but the decoder trunk executes under the
    GPipe schedule with stage-sharded weights — backward runs through the
    transposed ``ppermute`` rotation automatically.
    """
    from pathway_tpu.models.decoder import init_decoder_params
    from pathway_tpu.parallel.train import TrainState, masked_next_token_loss

    if cfg.experts:
        raise NotImplementedError(
            "pipeline-parallel MoE training is not supported: the MoE "
            "load-balance aux loss is not threaded through the GPipe "
            "schedule (it would be silently dropped) — train MoE decoders "
            "with make_causal_lm_train_step (dp×tp×ep) instead; the "
            "pipelined FORWARD supports MoE configs"
        )

    fwd = make_pipelined_causal_lm(cfg, mesh, n_micro)

    def init_state(seed: int = 0) -> TrainState:
        tree = place_pp_params(init_decoder_params(cfg, seed), mesh)
        return TrainState(params=tree, opt_state=optimizer.init(tree))

    def loss_fn(tree, ids, lengths):
        return masked_next_token_loss(fwd(tree, ids, lengths), ids, lengths)

    @jax.jit
    def step(params, opt_state, ids, lengths):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids, lengths)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def run(state: TrainState, ids, lengths):
        ids = jnp.asarray(np.asarray(ids, np.int32))
        lengths = jnp.asarray(np.asarray(lengths, np.int32))
        params, opt_state, loss = step(state.params, state.opt_state, ids, lengths)
        return (
            TrainState(params=params, opt_state=opt_state, step=state.step + 1),
            loss,
        )

    return init_state, run
