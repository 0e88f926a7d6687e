"""Plain reference of the MiMo-V2.5 language model (``model_type:
mimo_v2``; the MiMo-V2-Flash family) as one full forward pass in
``jax.numpy`` float32 at ``highest`` matmul precision: no cache, no
paging, no ring, no chunks, no grouped product.

The equations (ISSUE 29 lists them; each departure from the published
``config.json`` is under ``assumed`` in the configuration's file).  For
layer l, ``a = hybrid_layer_pattern[l]`` (0 global, 1 window) and ``m =
moe_layer_freq[l]`` (0 dense, 1 routed); RMS norm with a learned scale, no
biases:

1. ``h = rms(x)``; ``[q | k | v] = h . W_qkv``, one fused matrix: q is 64
   heads x 192, k is KH x 192, v is KH x 128; KH = 4 global, 8 window.
2. Rotary on the first 64 dims of every q and k head (halves rotated),
   the other 128 untouched; theta 1e7 global, 1e4 window.
3. ``v <- 0.707 v``.
4. ``s_ij = q_i . k_j / sqrt(192)``, grouped queries, causal, and in a
   window layer ``j > i - 128``.
5. Window layers: a learned logit ``b_h`` per query head joins the
   softmax and carries no value, ``p_ij = exp(s_ij) / (exp(b_h) + sum_j'
   exp(s_ij'))``; global layers: the plain softmax.  Float32.
6. ``x <- x + concat_h(sum_j p_ij v_j) . W_o``.
7. ``h2 = rms(x)``.  Dense: ``x <- x + W_d(silu(W_g h2) * W_u h2)``.
8. Routed: ``s = sigmoid(h2 . W_r)`` in float32 over all the published
   experts; the 8 with the largest ``s_e + c_e`` are chosen (``c`` the
   ``noaux_tc`` correction bias); ``w_e = s_e / sum_chosen s``; ``x <- x +
   sum w_e E_e(h2)`` over the chosen experts that are HELD here,
   ``[first, first + held)``: what the experts held on other chips would
   add is left out, as in the program (the share of one chip of an
   expert-parallel layer).  Each held expert runs over every token and is
   masked by the choice.
9. After the last layer ``rms``, then the untied head over the vocabulary
   rows held.

It imports nothing of the program and takes nothing the program made.  Its
weights are drawn anew from the seed by the recipe the program states; the
layers are walked one at a time, each upcast to float32, and a routed
layer's experts one at a time, so that a chip's share at the published
widths fits beside nothing else on a 16 GB chip.

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

MATMUL_WEIGHTS = ("wqkv", "wo", "wg", "wu", "wd")


def sizes(config: dict) -> dict:
    """The sizes the forward needs, from the model's ``config.json`` keys
    (the share's keys beside them: ``n_routed_experts`` held of
    ``n_routed_experts_published``, from ``expert_shard_index`` x held)."""
    L = config["num_hidden_layers"]
    held = config["n_routed_experts"]
    head = config["head_dim"]
    kinds = []
    for window, routed in zip(config["hybrid_layer_pattern"][:L], config["moe_layer_freq"][:L]):
        kinds.append((
            config["swa_num_key_value_heads" if window else "num_key_value_heads"],
            config["sliding_window"] if window else None,
            float(config["swa_rope_theta" if window else "rope_theta"]),
            bool(config.get(
                "add_swa_attention_sink_bias" if window else "add_full_attention_sink_bias",
                False,
            )),
            bool(routed),
            config["moe_intermediate_size" if routed else "intermediate_size"],
        ))
    runs: list[list] = []  # runs of like layers: the stacked leaves' unit
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return {
        "vocab": config["vocab_size"], "hidden": config["hidden_size"], "layers": L,
        "heads": config["num_attention_heads"], "head": head,
        "v_head": config.get("v_head_dim", head),
        "rotary": int(config.get("partial_rotary_factor", 1.0) * head) // 2 * 2,
        "value_scale": float(config.get("attention_value_scale") or 1.0),
        "eps": float(config.get("layernorm_epsilon", 1e-5)),
        "dtype": jnp.dtype(config.get("torch_dtype", "bfloat16")),
        "top_k": config["num_experts_per_tok"],
        "held": held, "published": config.get("n_routed_experts_published", held),
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
    splits eight: ``wqkv``, ``wo``, the sink logits (normal x 0.5), the
    three FFN matrices (for held experts each key folded with the first
    held expert's index), the router (float32, published width) and its
    correction bias (normal x 0.02).  Matrices are divided by
    sqrt(fan_in) and stored in the configuration's dtype; norms are ones."""
    s = sizes(config)
    H, V, NH, D, Dv = s["hidden"], s["vocab"], s["heads"], s["head"], s["v_head"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 11)

    def draw(key, shape, fan_in):
        return _draw(key, np.float32(np.sqrt(fan_in)), shape, s["dtype"])

    runs = []
    for r, ((kv_heads, _window, _theta, sink, routed, F), n) in enumerate(s["runs"]):
        rk = jax.random.split(jax.random.fold_in(keys[2], r), 8)
        run = {
            "ln0": jnp.ones((n, H), s["dtype"]),
            "ln1": jnp.ones((n, H), s["dtype"]),
            "wqkv": draw(rk[0], (n, H, NH * D + kv_heads * (D + Dv)), H),
            "wo": draw(rk[1], (n, NH * Dv, H), NH * Dv),
        }
        if sink:
            run["sink"] = 0.5 * jax.random.normal(rk[2], (n, NH), jnp.float32)
        if routed:
            E, width = s["held"], s["published"]
            ek = [jax.random.fold_in(k, s["first"]) for k in rk[3:6]]
            run["router"] = jax.random.normal(rk[6], (n, H, width), jnp.float32) / np.sqrt(H)
            run["bias"] = 0.02 * jax.random.normal(rk[7], (n, width), jnp.float32)
            run["wg"] = draw(ek[0], (n, E, H, F), H)
            run["wu"] = draw(ek[1], (n, E, H, F), H)
            run["wd"] = draw(ek[2], (n, E, F, H), F)
        else:
            run["wg"] = draw(rk[3], (n, H, F), H)
            run["wu"] = draw(rk[4], (n, H, F), H)
            run["wd"] = draw(rk[5], (n, F, H), F)
        runs.append(run)
    return {
        "embed": draw(keys[0], (V, H), H),
        "lm_head": draw(keys[1], (H, V), H),
        "final_norm": jnp.ones((H,), s["dtype"]),
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


def _rope(x, positions, theta, rotary):
    """Rotary embedding on the first ``rotary`` dims of each head, halves
    rotated; ``x`` is [B, S, heads, D]."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary))
    angle = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(angle)[..., None, :], jnp.sin(angle)[..., None, :]
    x1, x2 = jnp.split(x[..., :rotary], 2, axis=-1)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([turned, x[..., rotary:]], axis=-1)


def routed_ffn(h2, router, bias, wg, wu, wd, *, top_k: int, first: int, bits=None):
    """Equation 8 over tokens ``h2 [T, H]``: the router and its choice over
    all of ``router``'s experts, the sum over the chosen experts among the
    held ``wg`` / ``wu`` / ``wd`` ``[E, ...]`` (experts ``first`` onwards).
    Returns the sum ``[T, H]``."""
    scores = jax.nn.sigmoid(h2 @ router)  # [T, published] f32
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)

    def expert(e, total):
        gate = h2 @ _round_weight(wg[e], bits)
        up = h2 @ _round_weight(wu[e], bits)
        out = (jax.nn.silu(gate) * up) @ _round_weight(wd[e], bits)
        share = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        return total + share[:, None] * out

    return jax.lax.fori_loop(0, wg.shape[0], expert, jnp.zeros_like(h2))


@functools.partial(jax.jit, static_argnames=("kind", "dims", "bits"))
def _layer(run, index, x, lengths, kind, dims, bits):
    """One block of ``kind`` over the whole sequences ``x`` [B, S, H]."""
    kv_heads, window, theta, sink, routed, _F = kind
    heads, D, Dv, rotary, value_scale, eps, top_k, first = dims
    w = {
        name: (leaf[index] if name in ("wg", "wu", "wd") and routed  # an expert at a time
               else _round_weight(leaf[index], bits if name in MATMUL_WEIGHTS else None))
        for name, leaf in run.items()
    }
    B, S, H = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    mask = (positions[:, None, :] <= positions[:, :, None]) & (
        positions[:, None, :] < lengths[:, None, None]
    )
    if window is not None:
        mask = mask & (positions[:, None, :] > positions[:, :, None] - window)
    h = _rms(x, w["ln0"], eps)
    qkv = h @ w["wqkv"]
    nq, nk = heads * D, kv_heads * D
    q = _rope(qkv[..., :nq].reshape(B, S, heads, D), positions, theta, rotary)
    k = _rope(qkv[..., nq:nq + nk].reshape(B, S, kv_heads, D), positions, theta, rotary)
    v = value_scale * qkv[..., nq + nk:].reshape(B, S, kv_heads, Dv)
    group = heads // kv_heads
    qg = q.reshape(B, S, kv_heads, group, D)
    scores = jnp.einsum("bskgd,bckd->bkgsc", qg, k) / np.sqrt(D)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e9)
    if sink:
        top = jnp.maximum(scores.max(-1, keepdims=True), w["sink"].reshape(1, kv_heads, group, 1, 1))
        e = jnp.exp(scores - top)
        probs = e / (jnp.exp(w["sink"].reshape(1, kv_heads, group, 1, 1) - top) + e.sum(-1, keepdims=True))
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgsc,bckd->bskgd", probs, v).reshape(B, S, heads * Dv)
    x = x + ctx @ w["wo"]
    h2 = _rms(x, w["ln1"], eps)
    if not routed:
        return x + (jax.nn.silu(h2 @ w["wg"]) * (h2 @ w["wu"])) @ w["wd"]
    out = routed_ffn(
        h2.reshape(B * S, H), w["router"], w["bias"], w["wg"], w["wu"], w["wd"],
        top_k=top_k, first=first, bits=bits,
    )
    return x + out.reshape(B, S, H)


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
    dims = (s["heads"], s["head"], s["v_head"], s["rotary"], s["value_scale"],
            s["eps"], s["top_k"], s["first"])
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
        lengths = jnp.asarray(lengths, jnp.int32)
        for (kind, n), run in zip(s["runs"], weights["runs"]):
            for index in range(n):
                x = _layer(run, index, x, lengths, kind, dims, weight_bits)
        picked = jnp.take_along_axis(x, jnp.asarray(positions)[:, :, None], axis=1)
        out = _head(picked, weights["final_norm"], weights["lm_head"], s["eps"], weight_bits)
    return np.asarray(out)
