"""Plain reference of the Nemotron-H language model (``model_type:
nemotron_h``; Nemotron 3 Nano) as one full forward pass in ``jax.numpy``
float32 at ``highest`` matmul precision: no cache, no paging, no chunked
scan, no grouped product.

The equations (ISSUE 34 lists them; each departure from the published
``config.json`` is under ``assumed`` in the configuration's file).  Every
layer is ``x <- x + part(rms(x))`` with ONE part, by its letter of
``hybrid_override_pattern``; RMS norm with a learned scale, eps 1e-5, no
biases but the convolution's:

``M``, a Mamba-2 mixer (NH heads of width P, G groups, state size N):

1. ``[z | xBC | dt] = h . W_in``, widths NH P | NH P + 2 G N | NH.
2. ``xBC <- silu(conv1d(xBC) + b)``: causal, depthwise, K = 4 taps, zeros
   before the sequence.  ``[x | B | C] = xBC``, widths NH P | G N | G N.
3. ``dt <- softplus(dt + dt_bias)`` (no clamp: the source states no
   ``time_step_limit``); ``A = -exp(A_log)``, a head.
4. **Token by token** (a ``lax.scan`` over positions, not the chunked
   form the program's prefill takes): for head h in group g = h // (NH/G),
   ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t[g]`` (``S`` is P x N,
   float32, noughts before the sequence), ``y_t = S_t C_t[g] + D x_t``.
5. ``y <- rms_grouped(y * silu(z))``: over each of the G groups of NH P / G
   with its weight.  ``x <- x + y . W_out``.

``*``, attention: grouped queries (NH_a heads, KH KV heads, width D), no
bias, **no rotary embedding**, causal, scale D^-1/2, softmax in float32,
``x <- x + ctx . W_o``.

``E``, experts: ``s = sigmoid(h . W_r)`` in float32 over all the published
experts; the ``top_k`` with the largest ``s_e + c_e`` are chosen (``c`` the
correction bias); ``w_e = 2.5 s_e / sum_chosen s``; an expert is ``W_down
relu(W_up h)^2``; ``x <- x + sum w_e E_e(h) + Shared(h)`` over the chosen
experts that are HELD here, ``[first, first + held)``: what the experts
held on other chips would add is left out, as in the program; the shared
expert (the same form, wider) is added whole.  Each held expert runs over
every token and is masked by the choice.

After the last layer ``rms``, then the untied head over the vocabulary
rows held.

It imports nothing of the program and takes nothing the program made.  Its
weights are drawn anew from the seed by the recipe the program states; the
layers are walked one at a time, each upcast to float32, and a routed
layer's experts one at a time, so that a chip's share at the published
widths fits beside nothing else on a 16 GB chip.

Two controls, each the same forward with one thing held in the nearest
precision below what the configuration states: ``weight_bits=8`` rounds
every matmul weight to int8 with one scale per output channel (the router,
which decides in float32 in the program too, is left as it is);
``state_dtype="bfloat16"`` rounds the recurrent state ``S`` to bfloat16
after every token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PARTS = {"M": "mamba", "*": "attention", "E": "experts"}
MATMUL_WEIGHTS = ("in_proj", "out_proj", "wqkv", "wo", "wu", "wd", "shared_up", "shared_down")


def sizes(config: dict) -> dict:
    """The sizes the forward needs, from the model's ``config.json`` keys
    (the share's keys beside them: ``n_routed_experts`` held of
    ``n_routed_experts_published``, from ``expert_shard_index`` x held)."""
    L = config["num_hidden_layers"]
    held = config["n_routed_experts"]
    runs: list[list] = []  # runs of like layers: the stacked leaves' unit
    for letter in config["hybrid_override_pattern"][:L]:
        if runs and runs[-1][0] == PARTS[letter]:
            runs[-1][1] += 1
        else:
            runs.append([PARTS[letter], 1])
    NH, P, G, N = (config["mamba_num_heads"], config["mamba_head_dim"],
                   config["n_groups"], config["ssm_state_size"])
    return {
        "vocab": config["vocab_size"], "hidden": config["hidden_size"], "layers": L,
        "heads": config["num_attention_heads"], "kv_heads": config["num_key_value_heads"],
        "head": config["head_dim"],
        "ssm_heads": NH, "ssm_head": P, "groups": G, "state": N,
        "inner": NH * P, "columns": NH * P + 2 * G * N, "taps": config["conv_kernel"],
        "dt_init": (config.get("time_step_min", 0.001), config.get("time_step_max", 0.1),
                    config.get("time_step_floor", 1e-4)),
        "eps": float(config.get("layer_norm_epsilon", 1e-5)),
        "dtype": jnp.dtype(config.get("torch_dtype", "bfloat16")),
        "top_k": config["num_experts_per_tok"],
        "route_scale": float(config.get("routed_scaling_factor") or 1.0),
        "expert": config["moe_intermediate_size"],
        "shared": config["moe_shared_expert_intermediate_size"],
        "held": held, "published": config.get("n_routed_experts_published", held),
        "first": config.get("expert_shard_index", 0) * held,
        "runs": [(part, n) for part, n in runs],
    }


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, divisor, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / divisor).astype(dtype)


def init_weights(config: dict, seed: int = 0) -> dict:
    """Scaled-normal weights from ``seed`` by the program's stated recipe:
    eleven keys split from it (embedding, head, and the third for the
    layers); run ``r`` of like layers folds ``r`` into that third and
    splits eight.  Matrices are normal / sqrt(fan_in) in the
    configuration's dtype, norms ones.

    A Mamba-2 run: ``in_proj``, ``out_proj``, the convolution's taps
    (normal / sqrt(K)) and bias (normal x 0.1), the time step (log-uniform
    in ``time_step_min`` .. ``time_step_max``, floored, ``dt_bias`` its
    inverse softplus), ``A`` (uniform [1, 16), ``A_log`` its logarithm),
    ``D`` (1 + normal x 0.5): keys 0 to 6.  An attention run: ``wqkv``,
    ``wo``: keys 0, 1.  An expert run: ``wu`` and ``wd`` of the held experts
    (keys 4 and 5, each folded with the first held expert's index), the
    router (key 6, float32, published width), the correction bias (key 7,
    normal x 0.02), the shared expert (key 6 folded with 1, split in two)."""
    s = sizes(config)
    H, V, dtype = s["hidden"], s["vocab"], s["dtype"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 11)

    def draw(key, shape, fan_in):
        return _draw(key, np.float32(np.sqrt(fan_in)), shape, dtype)

    runs = []
    for r, (part, n) in enumerate(s["runs"]):
        rk = jax.random.split(jax.random.fold_in(keys[2], r), 8)
        run = {"ln0": jnp.ones((n, H), dtype)}
        if part == "mamba":
            NH, K, inner, columns = s["ssm_heads"], s["taps"], s["inner"], s["columns"]
            lo, hi, floor = s["dt_init"]
            step = jnp.maximum(
                jnp.exp(
                    jax.random.uniform(rk[4], (n, NH), jnp.float32)
                    * (np.log(hi) - np.log(lo)) + np.log(lo)
                ),
                floor,
            )
            run.update({
                "in_proj": draw(rk[0], (n, H, inner + columns + NH), H),
                "out_proj": draw(rk[1], (n, inner, H), inner),
                "conv_w": draw(rk[2], (n, K, columns), K),
                "conv_b": (0.1 * jax.random.normal(rk[3], (n, columns), jnp.float32)).astype(dtype),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(jax.random.uniform(rk[5], (n, NH), jnp.float32, 1.0, 16.0)),
                "D": 1.0 + 0.5 * jax.random.normal(rk[6], (n, NH), jnp.float32),
                "gate_norm": jnp.ones((n, inner), dtype),
            })
        elif part == "attention":
            NHa, KH, D = s["heads"], s["kv_heads"], s["head"]
            run["wqkv"] = draw(rk[0], (n, H, (NHa + 2 * KH) * D), H)
            run["wo"] = draw(rk[1], (n, NHa * D, H), NHa * D)
        else:
            E, width, F, Fs = s["held"], s["published"], s["expert"], s["shared"]
            up, down = jax.random.split(jax.random.fold_in(rk[6], 1))
            run.update({
                "router": jax.random.normal(rk[6], (n, H, width), jnp.float32) / np.sqrt(H),
                "bias": 0.02 * jax.random.normal(rk[7], (n, width), jnp.float32),
                "wu": draw(jax.random.fold_in(rk[4], s["first"]), (n, E, H, F), H),
                "wd": draw(jax.random.fold_in(rk[5], s["first"]), (n, E, F, H), F),
                "shared_up": draw(up, (n, H, Fs), H),
                "shared_down": draw(down, (n, Fs, H), Fs),
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


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def mamba_mixer(h, w, *, heads: int, head: int, groups: int, state: int, eps: float,
                state_dtype=None):
    """Equations M1 to M5 over whole sequences ``h [B, S, H]`` (normed);
    ``w`` one layer's leaves in float32.  Returns the mixer's output."""
    B_, S, _H = h.shape
    inner, GN = heads * head, groups * state
    proj = h @ w["in_proj"]
    z, xbc, dt = proj[..., :inner], proj[..., inner:2 * inner + 2 * GN], proj[..., 2 * inner + 2 * GN:]
    taps = w["conv_w"].shape[0]
    before = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = w["conv_b"] + sum(w["conv_w"][k] * before[:, k:k + S] for k in range(taps))
    xbc = jax.nn.silu(conv)
    x = xbc[..., :inner].reshape(B_, S, heads, head)
    per_head = lambda t: jnp.repeat(t.reshape(B_, S, groups, state), heads // groups, axis=2)
    Bm, Cm = per_head(xbc[..., inner:inner + GN]), per_head(xbc[..., inner + GN:])
    dt = jax.nn.softplus(dt + w["dt_bias"])  # [B, S, NH]
    A = -jnp.exp(w["A_log"])

    def token(held, at):
        x_t, B_t, C_t, dt_t = at  # [B, NH, P], [B, NH, N], [B, NH, N], [B, NH]
        held = jnp.exp(dt_t * A)[..., None, None] * held + (
            (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :]
        )
        if state_dtype is not None:
            # rounded as that dtype would hold it; a cast there and back is
            # one the compiler may take out (it allows excess precision)
            kept = jnp.finfo(state_dtype)
            held = jax.lax.reduce_precision(held, kept.nexp, kept.nmant)
        y_t = jnp.sum(held * C_t[:, :, None, :], axis=-1) + w["D"][:, None] * x_t
        return held, y_t

    first = jnp.zeros((B_, heads, head, state), jnp.float32)
    by_position = lambda t: jnp.moveaxis(t, 1, 0)
    _last, y = jax.lax.scan(
        token, first, (by_position(x), by_position(Bm), by_position(Cm), by_position(dt))
    )
    y = jnp.moveaxis(y, 0, 1).reshape(B_, S, inner) * jax.nn.silu(z)
    y = y.reshape(B_, S, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return (y.reshape(B_, S, inner) * w["gate_norm"]) @ w["out_proj"]


def attention(h, w, lengths, *, heads: int, kv_heads: int, head: int):
    """Grouped-query causal attention over ``h [B, S, H]``, no rotary."""
    B_, S, _H = h.shape
    positions = jnp.arange(S)
    mask = (positions[None, None, :] <= positions[None, :, None]) & (
        positions[None, None, :] < lengths[:, None, None]
    )
    qkv = h @ w["wqkv"]
    nq, nk = heads * head, kv_heads * head
    q = qkv[..., :nq].reshape(B_, S, kv_heads, heads // kv_heads, head)
    k = qkv[..., nq:nq + nk].reshape(B_, S, kv_heads, head)
    v = qkv[..., nq + nk:].reshape(B_, S, kv_heads, head)
    scores = jnp.einsum("bskgd,bckd->bkgsc", q, k) / np.sqrt(head)
    probs = jax.nn.softmax(jnp.where(mask[:, None, None, :, :], scores, -1e9), axis=-1)
    return jnp.einsum("bkgsc,bckd->bskgd", probs, v).reshape(B_, S, heads * head) @ w["wo"]


def routed_ffn(h, router, bias, wu, wd, *, top_k: int, first: int, route_scale: float,
               bits=None):
    """The routed sum over tokens ``h [T, H]``: the router and its choice
    over all of ``router``'s experts, the sum over the chosen experts among
    the held ``wu`` / ``wd`` ``[E, ...]`` (experts ``first`` onwards), the
    shared expert left to the caller.  Returns ``[T, H]``."""
    scores = jax.nn.sigmoid(h @ router)  # [T, published] f32
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = route_scale * picked / jnp.sum(picked, axis=-1, keepdims=True)

    def expert(e, total):
        out = _relu2(h @ _round_weight(wu[e], bits)) @ _round_weight(wd[e], bits)
        share = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        return total + share[:, None] * out

    return jax.lax.fori_loop(0, wu.shape[0], expert, jnp.zeros_like(h))


@functools.partial(jax.jit, static_argnames=("part", "dims", "bits", "state_dtype"))
def _layer(run, index, x, lengths, part, dims, bits, state_dtype):
    """One layer of ``part`` over the whole sequences ``x`` [B, S, H]."""
    (heads, kv_heads, head, ssm_heads, ssm_head, groups, state, eps, top_k, first,
     route_scale) = dims
    w = {
        name: (leaf[index] if name in ("wu", "wd")  # an expert at a time
               else _round_weight(leaf[index], bits if name in MATMUL_WEIGHTS else None))
        for name, leaf in run.items()
    }
    h = _rms(x, w["ln0"], eps)
    if part == "mamba":
        return x + mamba_mixer(
            h, w, heads=ssm_heads, head=ssm_head, groups=groups, state=state, eps=eps,
            state_dtype=state_dtype,
        )
    if part == "attention":
        return x + attention(h, w, lengths, heads=heads, kv_heads=kv_heads, head=head)
    B_, S, H = x.shape
    flat = h.reshape(B_ * S, H)
    out = routed_ffn(
        flat, w["router"], w["bias"], w["wu"], w["wd"], top_k=top_k, first=first,
        route_scale=route_scale, bits=bits,
    ) + _relu2(flat @ w["shared_up"]) @ w["shared_down"]
    return x + out.reshape(B_, S, H)


@functools.partial(jax.jit, static_argnames=("eps", "bits"))
def _head(x, final_norm, lm_head, eps, bits):
    return _rms(x, final_norm.astype(jnp.float32), eps) @ _round_weight(lm_head, bits)


def logits_at(
    weights: dict, config: dict, ids: np.ndarray, lengths: np.ndarray,
    positions: np.ndarray, *, weight_bits: int | None = None, state_dtype: str | None = None,
) -> np.ndarray:
    """Next-token logits [B, P, vocab] of the full forward over ``ids``
    [B, S] (rows padded past ``lengths``) at ``positions`` [B, P]."""
    s = sizes(config)
    dims = (s["heads"], s["kv_heads"], s["head"], s["ssm_heads"], s["ssm_head"],
            s["groups"], s["state"], s["eps"], s["top_k"], s["first"], s["route_scale"])
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
        lengths = jnp.asarray(lengths, jnp.int32)
        for (part, n), run in zip(s["runs"], weights["runs"]):
            for index in range(n):
                x = _layer(run, index, x, lengths, part, dims, weight_bits, state_dtype)
        picked = jnp.take_along_axis(x, jnp.asarray(positions)[:, :, None], axis=1)
        out = _head(picked, weights["final_norm"], weights["lm_head"], s["eps"], weight_bits)
    return np.asarray(out)
