"""LoRA adapters for the decoder family (low-rank fine-tuning).

The reference consumes frozen checkpoints only; this framework trains,
and the standard way users adapt an LLM is LoRA: freeze the base
weights, learn a rank-``r`` update ``ΔW = a @ b`` per targeted matmul.
TPU-shaped by construction — the forward routes activations through the
bottleneck (``(x@a)@b``, two skinny matmuls) instead of materializing
dense deltas, the frozen base stays in whatever layout serving uses, and
adapter state (megabytes, not gigabytes) is what the optimizer carries
and the checkpointer saves.

``_mm`` in ``models/decoder.py`` recognises the ``{"w", "a", "b"}``
leaves, so LoRA trees run through the full forward, the scheduler's
paged programs and the pipelined trunk unchanged.  Quantization needs a
plain tree — ``merge_lora`` the adapters back into plain weights first;
``quantize_decoder_tree`` rejects adapted trees with that instruction.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pathway_tpu.models.decoder import DecoderConfig

# attention projections (+ optionally the dense MLP) — the usual targets;
# MoE expert weights go through the GShard einsums, not _mm, so they are
# rejected rather than silently left unadapted
DEFAULT_TARGETS = ("wq", "wv")
_ADAPTABLE = {"wq", "wk", "wv", "wo", "wg", "wu", "wd"}


def lora_decoder_tree(
    tree,
    cfg: DecoderConfig,
    *,
    rank: int = 8,
    alpha: float = 16.0,
    targets: tuple[str, ...] = DEFAULT_TARGETS,
    seed: int = 0,
):
    """Wrap ``targets`` layer weights as ``{"w", "a", "b"}`` LoRA leaves.

    ``a`` is scaled-normal, ``b`` zeros — the adapted model starts
    EXACTLY equal to the base (pinned by tests); ``alpha/rank`` is folded
    into ``a``'s init scale so the merged update is
    ``(alpha/rank) * a_raw @ b``.
    """
    unknown = set(targets) - _ADAPTABLE
    if unknown:
        raise ValueError(f"unknown LoRA targets {sorted(unknown)}")
    if cfg.experts and any(t in ("wg", "wu", "wd") for t in targets):
        raise ValueError(
            "LoRA on MoE expert MLP weights is not supported (they run "
            "through the GShard dispatch einsums); target the attention "
            "projections instead"
        )
    keys = jax.random.split(jax.random.PRNGKey(seed), len(targets))
    layers = dict(tree["layers"])
    for key, name in zip(keys, targets):
        w = layers[name]
        if isinstance(w, dict):
            raise ValueError(
                f"layer weight {name!r} is already wrapped ({sorted(w)}); "
                "LoRA applies to plain float trees"
            )
        H, O = w.shape[-2], w.shape[-1]
        a_shape = (*w.shape[:-1], rank)
        b_shape = (*w.shape[:-2], rank, O)
        scale = (alpha / rank) / np.sqrt(H)
        layers[name] = {
            "w": w,
            "a": (jax.random.normal(key, a_shape, jnp.float32) * scale).astype(
                w.dtype
            ),
            "b": jnp.zeros(b_shape, w.dtype),
        }
    return {**tree, "layers": layers}


def merge_lora(tree):
    """Fold every ``{"w", "a", "b"}`` leaf into a plain weight."""
    layers = {
        name: (
            (w["w"] + w["a"].astype(jnp.float32) @ w["b"].astype(jnp.float32)).astype(
                w["w"].dtype
            )
            if isinstance(w, dict) and "a" in w
            else w
        )
        for name, w in tree["layers"].items()
    }
    return {**tree, "layers": layers}


def lora_mask(tree):
    """Pytree of bools marking the trainable (adapter) leaves."""

    def mark(path, _leaf):
        return any(getattr(p, "key", None) in ("a", "b") for p in path)

    return jax.tree_util.tree_map_with_path(mark, tree)


def make_lora_train_step(
    cfg: DecoderConfig,
    base_tree,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    rank: int = 8,
    alpha: float = 16.0,
    targets: tuple[str, ...] = DEFAULT_TARGETS,
    moe_aux_weight: float = 0.01,
    seed: int = 0,
) -> tuple[Callable, Callable]:
    """Data-parallel LoRA fine-tuning of a frozen ``base_tree``.

    Weights replicate over the mesh (adapters are megabytes — dp is the
    right axis for LoRA) and the batch shards over ``data``; the
    optimizer is masked to the adapter leaves, so the base never moves
    and optimizer state is adapter-sized.  Returns ``(init_state, run)``
    compatible with ``TrainCheckpointer``.
    """
    from pathway_tpu.parallel.train import TrainState, make_lm_step_runner

    tree0 = lora_decoder_tree(
        base_tree, cfg, rank=rank, alpha=alpha, targets=targets, seed=seed
    )
    # multi_transform, NOT optax.masked: masked passes the complement's
    # updates through as raw gradients (ascent on the frozen base);
    # set_to_zero pins every non-adapter leaf
    labels = jax.tree_util.tree_map(
        lambda m: "train" if m else "freeze", lora_mask(tree0)
    )
    opt = optax.multi_transform(
        {"train": optimizer, "freeze": optax.set_to_zero()}, labels
    )

    def init_state() -> TrainState:
        replicated = NamedSharding(mesh, P())
        tree = jax.tree_util.tree_map(
            lambda t: jax.device_put(t, replicated), tree0
        )
        return TrainState(params=tree, opt_state=opt.init(tree))

    run = make_lm_step_runner(cfg, opt, mesh, moe_aux_weight=moe_aux_weight)
    return init_state, run
