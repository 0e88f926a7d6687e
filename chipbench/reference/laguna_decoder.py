"""Plain reference of the Laguna language model (``model_type: laguna``;
poolside's Laguna S 2.1) as one full forward pass in ``jax.numpy`` float32
at ``highest`` matmul precision: no cache, no paging, no ring, no chunks,
no grouped product.

The equations (each departure from the published ``config.json`` is
under ``assumed`` in the configuration's file).  For
layer l, ``t = layer_types[l]``; RMS norm with a learned scale, no biases:

1. ``h = rms(x)``; ``[q | k | v] = h . W_qkv``, one fused matrix: q is
   ``NH_t = num_attention_heads_per_layer[l]`` heads x D, k and v KH heads
   x D each.
2. Rotary, halves rotated within the rotated dims: ``sliding_attention``
   the default rope on all D dims; ``full_attention`` YaRN on the first
   ``partial_rotary_factor x D`` dims, the rest untouched: ``f_i =
   base^(-2i/d)``, ``lo = floor(d ln(M / (beta_fast 2 pi)) / (2 ln
   base))``, ``hi = ceil(d ln(M / (beta_slow 2 pi)) / (2 ln base))``,
   clipped to ``[0, d - 1]``, ``r_i = clip((i - lo) / (hi - lo), 0, 1)``,
   ``inv_i = f_i / factor * r_i + f_i (1 - r_i)``; cos and sin times
   ``attention_factor``.
3. ``s_ij = q_i . k_j / sqrt(D)``, grouped queries, causal, and in a
   window layer ``j > i - sliding_window``.  Float32 softmax.
4. ``g = sigmoid(h . W_gate)`` (``[H, NH_t]``); ``o_h = g_h sum_j p_ij
   v_j``.
5. ``x <- x + concat_h(o_h) . W_o``.
6. ``h2 = rms(x)``.  Dense (``mlp_layer_types`` ``dense``): ``x <- x +
   W_d(silu(W_g h2) * W_u h2)``.  Sparse: ``p = softmax(h2 . W_r)`` in
   float32 over all the published experts; the ``top_k`` largest are
   chosen; ``w_e = route_scale p_e / sum_chosen p``; ``x <- x + sum w_e
   E_e(h2) + S(h2)`` over the chosen experts HELD here, ``[first, first +
   held)`` (what the experts held on other chips would add is left out,
   as in the program), ``E_e`` and the shared ``S`` SwiGLU.
7. After the last layer ``rms``, then the untied head over the vocabulary
   rows held.

It imports nothing of the program and takes nothing the program made.  Its
weights are drawn anew from the seed by the recipe the program states.  So
that a chip's share at the published widths and 48 answers of ~2.9 k
tokens fit beside nothing else on a 16 GB chip and inside the check's
time, the layers are walked one at a time, each upcast to float32; the
attention is computed a block of queries at a time (a window layer's block
against the keys its window reaches); and each held expert runs over the
rows that chose it, gathered by index, one expert at a time.

``weight_bits=8`` is the control: the same forward with every matmul
weight rounded to int8 with one scale per output channel (the nearest
precision below the bfloat16 the configuration states; the router, which
decides in float32 in the program too, is left as it is).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EXPERTS = ("wg", "wu", "wd")
QUERY_BLOCK = 256
GATHER_ROWS = 512


def sizes(config: dict) -> dict:
    """The sizes the forward needs, from the model's ``config.json`` keys
    (the share's keys beside them: ``num_experts`` held of
    ``num_experts_published``, from ``expert_shard_index`` x held)."""
    L, D = config["num_hidden_layers"], config["head_dim"]
    held = config["num_experts"]
    kinds = []
    for layer_type, mlp, heads in zip(
        config["layer_types"][:L], config["mlp_layer_types"][:L],
        config["num_attention_heads_per_layer"][:L],
    ):
        rope = config["rope_parameters"][layer_type]
        yarn = None
        if rope.get("rope_type", "default") == "yarn":
            yarn = (float(rope["factor"]), int(rope["original_max_position_embeddings"]),
                    float(rope["beta_fast"]), float(rope["beta_slow"]),
                    float(rope["attention_factor"]))
        sparse = mlp == "sparse"
        kinds.append((
            heads,
            config["sliding_window"] if layer_type == "sliding_attention" else None,
            float(rope["rope_theta"]),
            int(rope.get("partial_rotary_factor", 1.0) * D) // 2 * 2,
            yarn, sparse,
            config["moe_intermediate_size" if sparse else "intermediate_size"],
        ))
    runs: list[list] = []  # runs of like layers: the stacked leaves' unit
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return {
        "vocab": config["vocab_size"], "hidden": config["hidden_size"], "layers": L,
        "kv_heads": config["num_key_value_heads"], "head": D,
        "eps": float(config.get("rms_norm_eps", 1e-6)),
        "dtype": jnp.dtype(config.get("torch_dtype", "bfloat16")),
        "top_k": config["num_experts_per_tok"],
        "route_scale": float(config.get("moe_routed_scaling_factor") or 1.0),
        "shared": config["shared_expert_intermediate_size"],
        "held": held, "published": config.get("num_experts_published", held),
        "first": config.get("expert_shard_index", 0) * held,
        "runs": [(kind, n) for kind, n in runs],
    }


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, divisor, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / divisor).astype(dtype)


def init_weights(config: dict, seed: int = 0) -> dict:
    """Scaled-normal weights from ``seed`` by the program's stated recipe:
    eleven keys split from it (embedding, head, and the third for the
    layers); run ``r`` of like layers folds ``r`` into that third and
    splits eight: ``wqkv`` (key 0), ``wo`` (1), the gate ``attn_gate``
    (key 2 folded with 1), the dense SwiGLU (3, 4, 5) or, in a routed
    run, the held experts' ``wg`` / ``wu`` / ``wd`` (keys 3, 4, 5, each
    folded with the first held expert's index), the router (key 6,
    float32, published width) and the shared expert (its up and down
    split from key 6 folded with 1, its gate key 6 folded with 2).
    Matrices are normal / sqrt(fan_in) in the configuration's dtype, norms
    ones."""
    s = sizes(config)
    H, V, KH, D, dtype = s["hidden"], s["vocab"], s["kv_heads"], s["head"], s["dtype"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 11)

    def draw(key, shape, fan_in):
        return _draw(key, np.float32(np.sqrt(fan_in)), shape, dtype)

    runs = []
    for r, ((NH, _window, _theta, _rot, _yarn, sparse, F), n) in enumerate(s["runs"]):
        rk = jax.random.split(jax.random.fold_in(keys[2], r), 8)
        run = {
            "ln0": jnp.ones((n, H), dtype),
            "ln1": jnp.ones((n, H), dtype),
            "wqkv": draw(rk[0], (n, H, (NH + 2 * KH) * D), H),
            "wo": draw(rk[1], (n, NH * D, H), NH * D),
            "attn_gate": draw(jax.random.fold_in(rk[2], 1), (n, H, NH), H),
        }
        if sparse:
            E, width, Fs = s["held"], s["published"], s["shared"]
            ek = [jax.random.fold_in(k, s["first"]) for k in rk[3:6]]
            up, down = jax.random.split(jax.random.fold_in(rk[6], 1))
            run.update({
                "router": jax.random.normal(rk[6], (n, H, width), jnp.float32) / np.sqrt(H),
                "wg": draw(ek[0], (n, E, H, F), H),
                "wu": draw(ek[1], (n, E, H, F), H),
                "wd": draw(ek[2], (n, E, F, H), F),
                "shared_gate": draw(jax.random.fold_in(rk[6], 2), (n, H, Fs), H),
                "shared_up": draw(up, (n, H, Fs), H),
                "shared_down": draw(down, (n, Fs, H), Fs),
            })
        else:
            run.update({
                "wg": draw(rk[3], (n, H, F), H),
                "wu": draw(rk[4], (n, H, F), H),
                "wd": draw(rk[5], (n, F, H), F),
            })
        runs.append(run)
    return {
        "embed": draw(keys[0], (V, H), H),
        "lm_head": draw(keys[1], (H, V), H),
        "final_norm": jnp.ones((H,), dtype),
        "runs": runs,
    }


def _round_weight(w, bits: int | None):
    """``w`` in float32, or as weight-only int-``bits`` would hold it: a
    symmetric scale per output channel over the contraction axis."""
    w = w.astype(jnp.float32)
    if bits is None:
        return w
    top = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True) / top, 1e-12)
    return jnp.clip(jnp.round(w / scale), -top, top) * scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def yarn_inv_frequencies(d: int, theta: float, yarn) -> np.ndarray:
    """Equation 2's ``inv_i`` (float64) for ``d`` rotated dims."""
    factor, original, beta_fast, beta_slow, _scale = yarn
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    lo = np.floor(d * np.log(original / (beta_fast * 2 * np.pi)) / (2 * np.log(theta)))
    hi = np.ceil(d * np.log(original / (beta_slow * 2 * np.pi)) / (2 * np.log(theta)))
    lo, hi = max(lo, 0.0), min(hi, d - 1.0)
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo if hi > lo else 1e-3), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def _rope(x, positions, theta, rotary, yarn):
    """Rotary embedding on the first ``rotary`` dims of each head, halves
    rotated, under YaRN where ``yarn`` is given; ``x`` is [B, S, heads, D]."""
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary))
        scale = 1.0
    else:
        inv = jnp.asarray(yarn_inv_frequencies(rotary, theta, yarn), jnp.float32)
        scale = yarn[4]
    angle = positions[..., None].astype(jnp.float32) * inv
    cos, sin = scale * jnp.cos(angle)[..., None, :], scale * jnp.sin(angle)[..., None, :]
    x1, x2 = jnp.split(x[..., :rotary], 2, axis=-1)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([turned, x[..., rotary:]], axis=-1)


def attention(q, k, v, lengths, window):
    """Equation 3's softmax weights times the values, a block of queries at
    a time: q [B, S, KH, G, D], k and v [B, S, KH, D]; returns [B, S, KH,
    G, D].  A window layer's block reads the keys its window reaches."""
    B, S, KH, G, D = q.shape
    Q = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S
    reach = 0 if window is None else min(window, S)
    if reach:
        # the keys [q0 - reach, q0 + Q): noughts before the sequence, masked
        k = jnp.pad(k, ((0, 0), (reach, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (reach, 0), (0, 0), (0, 0)))

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, Q, axis=1)
        q_pos = q0 + jnp.arange(Q)
        if reach:
            kb = jax.lax.dynamic_slice_in_dim(k, q0, Q + reach, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, q0, Q + reach, axis=1)
            k_pos = q0 - reach + jnp.arange(Q + reach)
        else:
            kb, vb, k_pos = k, v, jnp.arange(S)
        seen = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] >= 0)
        if window is not None:
            seen = seen & (k_pos[None, :] > q_pos[:, None] - window)
        mask = seen[None] & (k_pos[None, None, :] < lengths[:, None, None])  # [B, Q, C]
        scores = jnp.einsum("bskgd,bckd->bkgsc", qb, kb) / np.sqrt(D)
        probs = jax.nn.softmax(jnp.where(mask[:, None, None], scores, -1e9), axis=-1)
        return jnp.einsum("bkgsc,bckd->bskgd", probs, vb)

    out = jax.lax.map(block, jnp.arange(0, S, Q))  # [S / Q, B, Q, KH, G, D]
    return jnp.moveaxis(out, 0, 1).reshape(B, S, KH, G, D)


def _mixer(run, index, x, lengths, kind, dims, bits):
    """Equations 1 to 5 of one layer of ``kind`` over the whole sequences
    ``x`` [B, S, H]; returns the residual stream after the attention."""
    NH, window, theta, rotary, yarn, _sparse, _F = kind
    KH, D, eps = dims
    w = {name: _round_weight(run[name][index], bits) for name in ("wqkv", "wo", "attn_gate")}
    B, S, _H = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    h = _rms(x, run["ln0"][index].astype(jnp.float32), eps)
    qkv = h @ w["wqkv"]
    nq, nk = NH * D, KH * D
    q = _rope(qkv[..., :nq].reshape(B, S, NH, D), positions, theta, rotary, yarn)
    k = _rope(qkv[..., nq:nq + nk].reshape(B, S, KH, D), positions, theta, rotary, yarn)
    v = qkv[..., nq + nk:].reshape(B, S, KH, D)
    ctx = attention(q.reshape(B, S, KH, NH // KH, D), k, v, lengths, window)
    gate = jax.nn.sigmoid(h @ w["attn_gate"])  # [B, S, NH]
    ctx = ctx.reshape(B, S, NH, D) * gate[..., None]
    return x + ctx.reshape(B, S, NH * D) @ w["wo"]


def _swiglu(h, g, u, d):
    return (jax.nn.silu(h @ g) * (h @ u)) @ d


def routed_ffn(h2, router, wg, wu, wd, *, top_k: int, first: int, route_scale: float,
               layer=0, valid=None, bits=None):
    """Equation 6's routed sum over the tokens ``h2`` [T, H] (``valid``
    marks those that take experts, all by default): the router's choice
    over all of ``router``'s experts, and the sum over the chosen among the
    held experts of ``wg`` / ``wu`` / ``wd`` ``[n, E, ...]`` at ``layer``
    (experts ``first`` onwards), one expert at a time, each over the rows
    that chose it, gathered by index ``GATHER_ROWS`` at a time.  The
    shared expert is the caller's."""
    T, E = h2.shape[0], wg.shape[1]
    valid = jnp.ones((T,), bool) if valid is None else valid
    probs = jax.nn.softmax(h2 @ router, axis=-1)  # [T, published]
    picked, chosen = jax.lax.top_k(probs, top_k)
    weights = route_scale * picked / jnp.sum(picked, axis=-1, keepdims=True)
    local = chosen - first
    mine = (local >= 0) & (local < E) & valid[:, None]
    # the pairs sorted by held expert; a padding token's, and those on
    # experts held elsewhere, behind every held expert's
    expert_of = jnp.where(mine, local, E).reshape(-1)
    order = jnp.argsort(expert_of, stable=True)
    token, weight = order // top_k, weights.reshape(-1)[order]
    counts = jnp.bincount(expert_of, length=E + 1)[:E]
    starts = jnp.cumsum(counts) - counts
    rows = min(GATHER_ROWS, T * top_k)

    def expert(e, total):
        g, u, d = (_round_weight(w[layer, e], bits) for w in (wg, wu, wd))

        def part(p, total):
            nth = p * rows + jnp.arange(rows)
            at = jnp.minimum(starts[e] + nth, token.shape[0] - 1)
            tok, wt = token[at], jnp.where(nth < counts[e], weight[at], 0.0)
            return total.at[tok].add(wt[:, None] * _swiglu(h2[tok], g, u, d))

        return jax.lax.fori_loop(0, (counts[e] + rows - 1) // rows, part, total)

    return jax.lax.fori_loop(0, E, expert, jnp.zeros_like(h2))


@functools.partial(jax.jit, static_argnames=("kind", "dims", "bits"))
def _layer(run, index, x, lengths, valid, kind, dims, bits):
    """One layer of ``kind`` over the whole sequences ``x`` [B, S, H]."""
    *_attention, sparse, _F = kind
    KH, D, eps, top_k, first, route_scale = dims
    x = _mixer(run, index, x, lengths, kind, (KH, D, eps), bits)
    h2 = _rms(x, run["ln1"][index].astype(jnp.float32), eps)
    if not sparse:
        return x + _swiglu(h2, *(_round_weight(run[n][index], bits) for n in EXPERTS))
    B, S, H = x.shape
    flat = h2.reshape(B * S, H)
    routed = routed_ffn(
        flat, run["router"][index], run["wg"], run["wu"], run["wd"], top_k=top_k,
        first=first, route_scale=route_scale, layer=index, valid=valid, bits=bits,
    )
    shared = _swiglu(flat, *(_round_weight(run[n][index], bits)
                             for n in ("shared_gate", "shared_up", "shared_down")))
    return x + (routed + shared).reshape(B, S, H)


@functools.partial(jax.jit, static_argnames=("eps", "bits"))
def _head(x, final_norm, lm_head, eps, bits):
    return _rms(x, final_norm.astype(jnp.float32), eps) @ _round_weight(lm_head, bits)


def logits_at(
    weights: dict, config: dict, ids: np.ndarray, lengths: np.ndarray,
    positions: np.ndarray, *, weight_bits: int | None = None,
) -> np.ndarray:
    """Next-token logits [B, P, vocab] of the full forward over ``ids``
    [B, S] (rows padded past ``lengths``) at ``positions`` [B, P]."""
    s = sizes(config)
    B, S = np.shape(ids)
    valid = jnp.asarray((np.arange(S)[None, :] < np.asarray(lengths)[:, None]).reshape(-1))
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
        lengths = jnp.asarray(lengths, jnp.int32)
        dims = (s["kv_heads"], s["head"], s["eps"], s["top_k"], s["first"], s["route_scale"])
        for (kind, n), run in zip(s["runs"], weights["runs"]):
            for index in range(n):
                x = _layer(run, index, x, lengths, valid, kind, dims, weight_bits)
        picked = jnp.take_along_axis(x, jnp.asarray(positions)[:, :, None], axis=1)
        out = _head(picked, weights["final_norm"], weights["lm_head"], s["eps"], weight_bits)
    return np.asarray(out)
