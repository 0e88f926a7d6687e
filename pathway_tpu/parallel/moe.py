"""Mixture-of-Experts layer with expert parallelism (the ``expert`` mesh axis).

The reference serves dense Mistral-class chat models through a host torch
pipeline (``xpacks/llm/llms.py:314``); the MoE siblings of that family
(Mixtral-class) are out of its reach on one GPU.  On TPU they are the
natural scale-out: expert FFN weights shard over an ``expert`` mesh axis,
tokens route to experts through the GShard einsum formulation — dispatch
and combine are dense one-hot contractions, so XLA lowers the token
exchange to ``all_to_all`` over ICI from the sharding annotations alone
(no hand-written collectives, per the scaling-book recipe).

Design points, all MXU/XLA-motivated:

* **Static capacity (training, ``moe_ffn``).**  Each expert processes a
  fixed ``capacity`` of token slots per batch; overflow tokens are dropped
  from that expert (their residual stream passes through unchanged).
  Static shapes keep the whole layer one compiled program.
* **No capacity (serving, ``moe_serve``).**  No (token, expert) pair that
  falls on the experts this chip holds is dropped.  A program of many
  rows (prefill) sorts them by expert for one grouped product (a Pallas
  kernel on the TPU, ``ops/grouped_matmul.py``; ``jax.lax.ragged_dot``
  elsewhere): no expert is multiplied by a token it was not given.  A
  program of few rows (a decode step) loops over the experts its rows
  met and reads each once, where it lies.
* **Top-k routing with renormalised gates** (k=2 default, the
  Mixtral/GShard setting): the combine weights of the selected experts
  are renormalised to sum to 1, so with identical experts the layer
  degenerates exactly to the dense FFN (pinned by tests).
* **Load-balance auxiliary loss** (Switch-Transformer form):
  ``E * Σ_e f_e · P_e`` where ``f_e`` is the fraction of tokens whose
  top-1 choice is ``e`` and ``P_e`` the mean router probability — keeps
  routing from collapsing onto one chip's experts.
* **Router in f32.**  Routing decisions are taken in f32 regardless of
  the activation dtype (bf16 softmax ties break non-deterministically
  across backends).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pathway_tpu.ops.grouped_matmul import TILE_ROWS, grouped_matmul, row_tiles


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    hidden: int
    experts: int
    intermediate: int
    top_k: int = 2
    capacity_factor: float = 1.25
    # GShard group axis: tokens are chunked into groups of at most this
    # many and dispatched group-locally, so the [G, Tg, E, C] dispatch
    # tensor stays LINEAR in the total token count (C scales with Tg, not
    # T).  0 disables grouping (one global group).
    group_size: int = 4096
    dtype: Any = jnp.float32
    # how a router logit becomes a score: "softmax" over all experts
    # (Mixtral, GShard) or "sigmoid" of each (DeepSeek-V3-style
    # ``noaux_tc``, where a learned per-expert correction bias joins the
    # score for the CHOICE only, never the weight)
    scoring: str = "softmax"
    # serving (``moe_serve``) on one chip's share of an expert-parallel
    # layer: the router is ``router_width`` wide (0 = ``experts``) and this
    # chip holds experts ``[first_expert, first_expert + experts)`` of them
    router_width: int = 0
    first_expert: int = 0
    # serving: a gated expert is ``down(silu(gate x) * up x)`` (three
    # matrices), one that is not ``down(relu(up x)**2)`` (two, no ``wg``)
    gated: bool = True
    # the chosen experts' renormalised weights are multiplied by this
    route_scale: float = 1.0

    def capacity(self, n_tokens: int) -> int:
        """Static per-expert token slots for an ``n_tokens`` group."""
        return max(
            self.top_k,
            int(math.ceil(self.capacity_factor * self.top_k * n_tokens / self.experts)),
        )


def init_moe_params(cfg: MoEConfig, seed: int = 0):
    """Scaled-normal init; expert weights stacked on a leading [E, ...] axis."""
    E, H, F = cfg.experts, cfg.hidden, cfg.intermediate
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)

    def norm_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)).astype(
            cfg.dtype
        )

    return {
        # routing is f32 end-to-end: init directly in f32, never rounded
        # through cfg.dtype
        "router": jax.random.normal(keys[0], (H, E), jnp.float32) / np.sqrt(H),
        "wg": norm_init(keys[1], (E, H, F), H),
        "wu": norm_init(keys[2], (E, H, F), H),
        "wd": norm_init(keys[3], (E, F, H), F),
    }


def ep_param_specs(axis: str = "expert"):
    """Expert-parallel PartitionSpecs: each chip owns ``E / |axis|`` experts'
    FFN weights; the router (tiny) is replicated."""
    return {
        "router": P(None, None),
        "wg": P(axis, None, None),
        "wu": P(axis, None, None),
        "wd": P(axis, None, None),
    }


def _routing(
    router_logits: jnp.ndarray,
    cfg: MoEConfig,
    capacity: int,
    valid: jnp.ndarray | None = None,
):
    """Top-k dispatch/combine tensors from router logits ``[T, E]`` (f32).

    Returns ``(dispatch [T,E,C] bool-ish, combine [T,E,C] f32, aux f32)``.
    Buffer positions are assigned rank-major (every token's first choice
    beats any token's second choice), token-major within a rank — the
    GShard priority order, so capacity overflow drops second opinions
    first.  ``valid`` masks padding tokens out of dispatch, capacity
    accounting, and the aux statistics.
    """
    T, E = router_logits.shape
    K = cfg.top_k
    probs = jax.nn.softmax(router_logits, axis=-1)  # [T, E] f32
    gate_k, idx_k = jax.lax.top_k(probs, K)  # [T, K]
    gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)

    sel = jax.nn.one_hot(idx_k.T, E, dtype=jnp.float32)  # [K, T, E]
    if valid is not None:
        sel = sel * valid.astype(jnp.float32)[None, :, None]
    flat = sel.reshape(K * T, E)
    pos = jnp.cumsum(flat, axis=0) - flat  # buffer slot per (rank, token)
    keep = (pos < capacity).astype(jnp.float32) * flat  # dropped past capacity
    cap_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
    disp_flat = keep[..., None] * cap_oh  # [K*T, E, C]
    gates_flat = gate_k.T.reshape(K * T)
    dispatch = disp_flat.reshape(K, T, E, capacity).sum(0)
    combine = (disp_flat * gates_flat[:, None, None]).reshape(
        K, T, E, capacity
    ).sum(0)

    # Switch load-balance loss over top-1 assignment (valid tokens only)
    top1 = jax.nn.one_hot(idx_k[:, 0], E, dtype=jnp.float32)
    if valid is not None:
        v = valid.astype(jnp.float32)[:, None]
        n = jnp.maximum(v.sum(), 1.0)
        frac_tokens = (top1 * v).sum(0) / n
        frac_probs = (probs * v).sum(0) / n
    else:
        frac_tokens = top1.mean(0)
        frac_probs = probs.mean(0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return dispatch, combine, aux


def _qeinsum(spec: str, x, w):
    """``einsum`` over a float weight or an int8 weight-only quant pair
    (``{"q", "s"}`` with per-output-channel scales over the contraction
    axis): the dot consumes int8→activation-dtype converts and the scale
    multiplies the OUTPUT (exact for per-output-channel scales)."""
    if isinstance(w, dict) and "q" in w:
        out = jnp.einsum(spec, x, w["q"].astype(x.dtype))
        # s keeps a singleton on the contraction axis, which lines up
        # against the batch-ish axis of the output under broadcasting
        return out * w["s"].astype(x.dtype)[None]
    return jnp.einsum(spec, x, w)


def moe_ffn(
    params,
    x: jnp.ndarray,
    cfg: MoEConfig,
    mesh: Mesh | None = None,
):
    """MoE feed-forward over tokens ``x [..., H]`` → ``(y [..., H], aux)``,
    the training form: static capacity, overflow dropped.

    Pure function of sharded inputs: under ``jit`` with ``ep_param_specs``
    placements, the ``gtec,gth->gech`` dispatch einsum (token-sharded ×
    expert-sharded) lowers to an ``all_to_all`` over the ``expert`` axis,
    and the combine einsum to its inverse.  ``mesh`` adds explicit
    sharding constraints on the expert-major intermediates so the
    placement is pinned rather than inferred.

    Tokens beyond the group size are chunked into GShard groups and
    dispatched group-locally (one ragged tail group padded and masked),
    keeping dispatch memory linear in the token count.  The capacity-
    factor drop policy is what makes routing learnable under a static
    budget; serving, where a drop would silently degrade a generation,
    goes through :func:`moe_serve`.
    """
    orig_shape = x.shape
    H = orig_shape[-1]
    xt = x.reshape(-1, H)
    T = xt.shape[0]
    group_size = cfg.group_size
    if not group_size or T <= group_size:
        G, Tg = 1, T
    else:
        G = -(-T // group_size)
        Tg = group_size
    pad = G * Tg - T
    if pad:
        xt = jnp.concatenate([xt, jnp.zeros((pad, H), xt.dtype)], axis=0)
    C = cfg.capacity(Tg)
    xg = xt.reshape(G, Tg, H)
    router_logits = xg.astype(jnp.float32) @ params["router"]  # [G, Tg, E]
    valid = (jnp.arange(G * Tg) < T).reshape(G, Tg)

    dispatch, combine, aux_g = jax.vmap(
        lambda lg, vg: _routing(lg, cfg, C, vg)
    )(router_logits, valid)
    dispatch = dispatch.astype(cfg.dtype)
    expert_in = jnp.einsum("gtec,gth->gech", dispatch, xg.astype(cfg.dtype))
    if mesh is not None and "expert" in mesh.axis_names:
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, NamedSharding(mesh, P(None, "expert", None, None))
        )
    h = jax.nn.silu(_qeinsum("gech,ehf->gecf", expert_in, params["wg"]))
    h = h * _qeinsum("gech,ehf->gecf", expert_in, params["wu"])
    expert_out = _qeinsum("gecf,efh->gech", h, params["wd"])
    if mesh is not None and "expert" in mesh.axis_names:
        expert_out = jax.lax.with_sharding_constraint(
            expert_out, NamedSharding(mesh, P(None, "expert", None, None))
        )
    y_g = jnp.einsum("gtec,gech->gth", combine.astype(cfg.dtype), expert_out)

    # aux: weighted mean over groups by their real-token counts
    w = valid.astype(jnp.float32).sum(axis=1)
    aux = (aux_g * w).sum() / jnp.maximum(w.sum(), 1.0)
    y = y_g.reshape(G * Tg, H)[:T]
    return y.reshape(orig_shape).astype(x.dtype), aux


def route(router_logits: jnp.ndarray, cfg: MoEConfig, bias=None):
    """The experts each token chooses and the weight each gets: ``(idx
    [T, K] int32, weights [T, K] f32)`` from f32 logits ``[T, E]`` over the
    whole router width.  Scores are a softmax over the experts or a sigmoid
    of each (``cfg.scoring``); the ``top_k`` largest of score + ``bias``
    (the ``noaux_tc`` correction, ``[E]``, where the model has one) are
    chosen, and the chosen experts' scores, without the bias, are
    renormalised to sum to 1."""
    if cfg.scoring == "sigmoid":
        scores = jax.nn.sigmoid(router_logits)
    else:
        scores = jax.nn.softmax(router_logits, axis=-1)
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(choice, cfg.top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, picked / jnp.maximum(picked.sum(-1, keepdims=True), 1e-9)


def _grouped_product(x, w, group_sizes):
    """``x [M, A]`` rows sorted by group times each group's ``w [G, A,
    B]``, in ``x``'s dtype: the Pallas kernel where the program is lowered
    for the TPU, ``jax.lax.ragged_dot`` for any other platform."""
    return jax.lax.platform_dependent(
        x, w, group_sizes, tpu=grouped_matmul, default=jax.lax.ragged_dot
    )


def _grouped(x, w, rows_expert, group_sizes):
    """``x [M, A]`` rows, sorted by expert, each times its own expert's
    ``w [E, A, B]`` (a float weight or an int8 weight-only pair)."""
    if isinstance(w, dict) and "q" in w:
        # an int8 pair keeps the compiler's product, over the stack cast
        # whole to the rows' dtype as before
        out = jax.lax.ragged_dot(x, w["q"].astype(x.dtype), group_sizes)
        return out * w["s"].astype(x.dtype)[rows_expert, 0]
    return _grouped_product(x, w, group_sizes)


def _activate(gate, up):
    """An expert's hidden activation: ``silu(gate) * up`` where it has a
    gate, ``relu(up) ** 2`` where it has none."""
    if gate is None:
        return jnp.square(jax.nn.relu(up))
    return jax.nn.silu(gate) * up


def _one_stack(w):
    """``[n, E, ...]`` (every layer's experts) as ``[n * E, ...]``: no copy."""
    return jax.tree_util.tree_map(lambda t: t.reshape((-1,) + t.shape[2:]), w)


# A ``[T, H] @ [H, F]`` product in bfloat16 reads H * F * 2 bytes and
# makes 2 * T * H * F operations: on a v5e (197 TFLOP/s, 819 GB/s) the
# read is the longer of the two up to T = 197e12 / 819e9 = 240 rows.  Well
# under that, multiplying EVERY row by an expert costs that expert's read
# and nothing more, so a program of at most this many rows loops over the
# experts it met; one of more rows sorts its pairs for the grouped product.
IN_PLACE_ROWS = 128


def serves_in_place(rows: int) -> bool:
    """Whether a serving program over ``rows`` tokens (a static fact of
    the program: slots x width) multiplies its held pairs by a loop over
    the experts they met (:func:`moe_serve`), and not by the grouped
    product."""
    return rows <= IN_PLACE_ROWS


def _expert(w, e):
    """Expert ``e``'s matrix of the stack ``w [E, A, B]`` (a float weight
    or an int8 weight-only pair), read where it lies."""
    return jax.tree_util.tree_map(
        lambda t: jax.lax.dynamic_index_in_dim(t, e, 0, keepdims=False), w
    )


def _times(x, w, out_dtype=None):
    """``x [T, A] @ w [A, B]``, ``w`` one expert's float weight or int8
    pair (``s [1, B]``), accumulated in float32."""
    if isinstance(w, dict) and "q" in w:
        out = jnp.matmul(x, w["q"].astype(x.dtype), preferred_element_type=out_dtype)
        return out * w["s"].astype(out.dtype)
    return jnp.matmul(x, w, preferred_element_type=out_dtype)


def _experts_in_place(xt, wg, wu, wd, layer, here, local, weights, group_sizes):
    """The held pairs' weighted sum ``[T, H]`` float32 by a loop whose
    trip count is the number of held experts that met a token: an
    iteration reads one such expert where it lies in the stacks (``[E,
    ...]``, or ``[n * E, ...]`` and this layer's from ``layer * E``),
    multiplies all ``T`` rows by it and adds the result under each row's
    weight for it, nought where the row did not choose it.  Each hit
    expert is read once whatever the rows that chose it; no sort, no
    kernel call."""
    T, H = xt.shape
    E = group_sizes.shape[0]
    first = 0 if layer is None else layer * E
    hit = group_sizes > 0
    nth = jnp.cumsum(hit) - 1  # a hit expert's place among the hit

    def one(i, acc):
        e = jnp.argmax(hit & (nth == i)).astype(jnp.int32)
        at = first + e
        gate = None if wg is None else _times(xt, _expert(wg, at))
        hidden = _activate(gate, _times(xt, _expert(wu, at)))
        out = _times(hidden, _expert(wd, at), jnp.float32)
        mine = here & (local == e)  # [T, K]
        weight = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1, keepdims=True)
        return acc + jnp.where(mine.any(-1, keepdims=True), out * weight, 0.0)

    return jax.lax.fori_loop(
        0, jnp.sum(hit, dtype=jnp.int32), one, jnp.zeros((T, H), jnp.float32)
    )


def _sorted_pairs(xt, flat, top_k: int):
    """The ``T x top_k`` pairs sorted by expert (``flat``: a pair's held
    expert, or E where it is held elsewhere: those sort behind every held
    expert's rows): ``(order, the sorted rows' experts, xs [T * top_k, H]``
    a token's row once per pair)."""
    order = jnp.argsort(flat)
    return order, flat[order], xt[order // top_k]


def _experts_grouped(sorted_pairs, wg, wu, wd, layer, here, weights, group_sizes):
    """The same sum of the pairs sorted by expert through one grouped
    product a matrix, over the whole stacks with every other layer's
    groups empty."""
    order, rows_expert, xs = sorted_pairs
    (T, K), E, H = here.shape, group_sizes.shape[0], xs.shape[-1]
    rows, sizes = rows_expert, group_sizes
    if layer is not None:
        layers = jax.tree_util.tree_leaves(wu)[0].shape[0] // E
        rows = layer * E + rows_expert
        sizes = (
            jnp.zeros((layers, E), jnp.int32).at[layer].set(group_sizes)
        ).reshape(-1)
    gate = None if wg is None else _grouped(xs, wg, rows, sizes)
    out = _grouped(_activate(gate, _grouped(xs, wu, rows, sizes)), wd, rows, sizes)
    # rows past the groups are pairs held elsewhere: the kernel never
    # writes them, and what they hold is masked out here
    out = jnp.where((rows_expert < E)[:, None], out, 0)
    back = jnp.argsort(order)  # undo the sort: row t*K + k again
    # the weighted sum in float32, as the weights are
    return jnp.einsum(
        "tkh,tk->th", out[back].reshape(T, K, H).astype(jnp.float32),
        jnp.where(here, weights, 0.0),
    )


def moe_serve(params, x: jnp.ndarray, cfg: MoEConfig, valid=None):
    """MoE feed-forward for serving: ``x [..., H]`` → ``(y [..., H],
    pairs, experts_hit, tile_rows)``.

    Every token is routed over the whole router width (``params["router"]``
    ``[H, router_width]`` f32, ``params["bias"]`` optional; the chosen
    weights times ``cfg.route_scale``).  An expert is gated (``wg``, ``wu``,
    ``wd``) or, with ``cfg.gated`` false, ``wd(relu(wu x) ** 2)``.  Where
    ``params`` holds ``shared_up`` / ``shared_down`` (and ``shared_gate``
    where experts are gated), that shared expert, of the experts' form, is
    added for every token, real or padding, on either path.  Of a token's
    ``top_k`` (token, expert) pairs those that fall on the experts held
    here (``cfg.first_expert`` and the ``cfg.experts`` after it, the
    leading axis of ``wg`` / ``wu`` / ``wd``) are multiplied by their
    experts and summed back per token, in float32, with the router's
    weights.  No capacity, no dropped pair; what the experts held
    elsewhere would add is left out (on one chip of an expert-parallel
    layer that partial sum is the layer's result here, and with every
    expert held it is the whole).  ``valid [...]`` marks real tokens:
    padding takes no expert.  ``pairs`` counts the pairs computed here,
    ``experts_hit`` the held experts that met at least one token and
    ``tile_rows`` the rows the grouped kernel multiplies (its row tiles of
    ``TILE_ROWS``, nought on the loop's path; a fact of the groups, counted
    on every platform): int32 scalars, for the scheduler's counters.

    Two paths share the router, the choice, the counts, the shared expert
    and the activation, and nothing else, because few rows and many want
    opposite things.  Which one a program takes is a fact of its shape
    (:func:`serves_in_place`: the rows ``T`` = the product of ``x``'s
    leading axes, at most ``IN_PLACE_ROWS``), no option and no model's name:

    * a decode step (``T`` = the scheduler's slots, 8) and any other
      program of few rows loops over the held experts that met a token
      (a dynamic trip count, ``experts_hit``), reads each where it lies in
      the stack and multiplies all ``T`` rows by it: the products are
      bound by the expert's read while ``T`` is small, so a step pays for
      the ≈ 1–3 experts it met and for no sort and no kernel call;
    * a prefill program (``[1, 512]``, ``[1, 256]``, ``[8, 32]``: ``T`` ≥
      256, thousands of pairs over every held expert) sorts its pairs by
      expert and sends them through one grouped product a matrix: no
      expert is multiplied by a token it was not given.  Lowered for the
      TPU that product is the Pallas kernel of ``ops/grouped_matmul.py``,
      which reads each held expert about once; for any other platform it
      is ``jax.lax.ragged_dot`` (the chip's own lowering of which ran at
      ≈ 10 x its experts' read at 15- and 21-lane widths: 7.5 ms a call at
      3,072 pairs over 64 experts of 2,688 x 1,920 on a v5e).

    Inside a scan over stacked layers ``wg`` / ``wu`` / ``wd`` may be the
    whole stacks ``[n, E, ...]`` with ``params["layer"]`` the layer's index
    in them: the loop reads expert ``layer * E + e``, and the grouped
    product runs over all ``n * E`` experts with every other layer's
    groups empty (the kernel takes no step for an empty group).  Neither
    reads a layer's experts through the scan's own slice of the stack: the
    grouped product is a custom call on the chip, and XLA copies all of a
    layer's experts for it, hit or not, every step (three 537 MB copies a
    layer at MiMo-V2.5's widths).
    """
    orig_shape = x.shape
    H = orig_shape[-1]
    xt = x.reshape(-1, H)
    T, K, E = xt.shape[0], cfg.top_k, cfg.experts
    in_place = serves_in_place(T)
    with jax.named_scope("moe.route"):
        # float32 all the way: at the default precision the chip would
        # multiply in bfloat16, and a choice between two experts whose
        # scores nearly tie would turn on that rounding
        logits = jnp.matmul(
            xt.astype(jnp.float32), params["router"],
            precision=jax.lax.Precision.HIGHEST,
        )
        idx, weights = route(logits, cfg, params.get("bias"))
        if cfg.route_scale != 1.0:
            weights = weights * cfg.route_scale
        local = idx - cfg.first_expert
        here = (local >= 0) & (local < E)
        if valid is not None:
            here = here & valid.reshape(-1)[:, None]
        flat = jnp.where(here, local, E).reshape(-1)
        group_sizes = jnp.sum(
            flat[:, None] == jnp.arange(E, dtype=flat.dtype)[None, :], axis=0,
            dtype=jnp.int32,
        )
        sorted_pairs = None if in_place else _sorted_pairs(xt, flat, K)
    with jax.named_scope("moe.experts"):
        wg, wu, wd = params.get("wg") if cfg.gated else None, params["wu"], params["wd"]
        layer = params.get("layer")
        if layer is not None:
            wg, wu, wd = _one_stack(wg), _one_stack(wu), _one_stack(wd)
        if in_place:
            y = _experts_in_place(
                xt, wg, wu, wd, layer, here, local, weights, group_sizes
            )
        else:
            y = _experts_grouped(
                sorted_pairs, wg, wu, wd, layer, here, weights, group_sizes
            )
    if "shared_up" in params:
        # the shared expert: every token takes it, whatever its routing,
        # and every share of the layer computes it alike
        with jax.named_scope("moe.shared"):
            gate = xt @ params["shared_gate"] if cfg.gated else None
            hidden = _activate(gate, xt @ params["shared_up"])
            y = y + (hidden @ params["shared_down"]).astype(jnp.float32)
    pairs = jnp.sum(here, dtype=jnp.int32)
    experts_hit = jnp.sum(group_sizes > 0, dtype=jnp.int32)
    tile_rows = jnp.int32(0) if in_place else row_tiles(group_sizes) * TILE_ROWS
    return y.reshape(orig_shape).astype(x.dtype), pairs, experts_hit, tile_rows


def make_ep_mesh(n_devices: int, expert_parallel: int | None = None) -> Mesh:
    """A ``("data", "expert")`` mesh: expert axis as large as divides both
    the device count and nothing else — callers pass ``expert_parallel``
    to pin it (defaults to all devices on the expert axis)."""
    devices = jax.devices()[:n_devices]
    ep = expert_parallel or len(devices)
    assert len(devices) % ep == 0, (len(devices), ep)
    grid = np.asarray(devices).reshape(len(devices) // ep, ep)
    return Mesh(grid, ("data", "expert"))


def make_moe_train_step(
    cfg: MoEConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    aux_weight: float = 0.01,
) -> tuple[Callable, Callable]:
    """Expert-parallel training: tokens sharded over ``data``, expert
    weights over ``expert``; the objective is denoising regression (fit
    the layer to a fixed random target map), enough to drive gradients
    through routing, dispatch and both collectives.

    Returns ``(init_fn, step_fn)`` where ``step_fn(params, opt_state, x,
    target) -> (params, opt_state, loss)`` is jitted SPMD.
    """
    from pathway_tpu.parallel.mesh import put_global

    specs = ep_param_specs()

    def init_fn(seed: int = 0):
        params = init_moe_params(cfg, seed)
        params = jax.tree_util.tree_map(
            lambda t, s: jax.device_put(t, NamedSharding(mesh, s)), params, specs
        )
        return params, optimizer.init(params)

    def loss_fn(params, x, target):
        y, aux = moe_ffn(params, x, cfg, mesh)
        mse = jnp.mean(jnp.square(y.astype(jnp.float32) - target.astype(jnp.float32)))
        return mse + aux_weight * aux

    @jax.jit
    def _step(params, opt_state, x, target):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, target)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    data_sharding = NamedSharding(mesh, P("data"))

    def step_fn(params, opt_state, x, target):
        x = put_global(np.asarray(x), data_sharding)
        target = put_global(np.asarray(target), data_sharding)
        return _step(params, opt_state, x, target)

    return init_fn, step_fn
