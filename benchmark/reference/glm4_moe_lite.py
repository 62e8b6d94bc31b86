"""The `glm4_moe_lite` family, plain: the full causal forward pass of a stack
whose every layer is latent attention (MLA) and then a feed-forward (a dense
gated MLP in the first `first_k_dense_replace` layers, a sparse-expert block
with a shared expert in the rest), in straightforward float32 `jax.numpy` at
`highest` matmul precision, written from the public `glm4_moe_lite` modelling
code (the DeepSeek-V2/V3 attention) and the keys of
`zai-org/GLM-4.7-Flash`'s config.json. The NON-absorbed form only: a head's
keys and values are made from the latent of every token. No cache, no
chunks, no kernel, no batching of experts. Imports nothing of the program.

    Norm(x) = x / sqrt(mean(x^2) + eps) * w                  (a plain weight)
    x <- x + MLA(Norm_1(x));  x <- x + FFN(Norm_2(x))
    logits = Norm_f(x) W_head^T                               (untied)

MLA, u the normed input, H heads: `c_q = Norm_q(u W_dq)`;
`[q_nope_h | q_rope_h] = c_q W_uq` a head; `[c_kv | k_r] = u W_dkv`;
`c_kv <- Norm_kv(c_kv)`; `q_rope_h`, and `k_r` (ONE for all heads), turn by
rotary positions: all `qk_rope_head_dim` of them, `inv_freq_i =
theta^(-2i/r)`, no scaling (`rope_scaling` null); `[k_nope_h | v_h] = c_kv
W_ukv` a head; `score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) .
k_r(s)) / sqrt(nope + rope)`, causal softmax; `out = [o_1 .. o_H] W_o`.

Dense MLP: `(silu(u W_gate) * (u W_up)) W_down`. Expert block:
`s = sigmoid(u W_r)` over all experts; the choice is the top k of
`s + b_corr` (`noaux_tc`; `n_group` 1 and `topk_group` 1, so no group limits
it); their weights `s` over their sum (`norm_topk_prob`) times
`routed_scaling_factor`; an expert is of the dense MLP's form; the shared
expert (`n_shared_experts` of the experts' width side by side), ungated, is
added.

Departures from the public modelling code, each a layout that seeded
weights do not need: rotary turns the pair (i, i + r/2) where the checkpoint
interleaves (2i, 2i + 1), a fixed permutation of `W_uq`'s and `W_dkv`'s
rope columns; `W_ukv`'s columns are a head's `[k_nope | v]`, head after
head, as the public `kv_b_proj`'s are; the multi-token-prediction block
(`num_nextn_predict_layers`) is no part of the forward pass that gives the
logits and is left out (the public code drops its weights on load).

The chip's share (`cfg["expert_share"]`: first, count, of): the router keeps
all `of` outputs and its experts per token, the weights normalise over all
chosen, and the result holds the held experts' part and the shared expert.
What the absent experts would add is left out. The vocabulary is the slice
`vocab_size` the configuration states.

Weights: bfloat16 for every matrix and the embeddings; float32 for the
router, its correction bias and the norms. They are kept as such and widened
to float32 one matrix at a time where they are used: the reference in
blocks. Leaf i of layer l is
`mean + std * normal(fold_in(fold_in(key(seed), l), i))`, rounded to its
dtype; the template repeats the recipe in the program's layout (an MLP's
and an expert's `W_gate` and `W_up` lie side by side). The recipe is
benchmark/reference/qwen3_next.py's (normal by fan-in into a layer, by
fan-in and the residual's additions out of it, those with zero sums over
their inputs, the routed experts' an eighth of that) but for three things:
the norms are plain and drawn 1 +- 0.1; the correction bias is drawn
0 +- 0.02 as nemotron_h.py's; and `W_uq` is drawn `QUERY_SCALE` times its
fan-in's scale. With unit queries the scores of random weights are N(0, 1),
a query at 8,000 tokens spreads over thousands of rows, its output is the
mean of their values, a fiftieth of a value, and the layer under test adds
a hundredth of what the feed-forward adds: a row written wrong would not
move a token. At 2.5 a query reads about fifteen rows of 8,000
(`L exp(-sigma^2)`) and the layer adds a third of the stream, as trained
attention does.

Controls, put in the program's place: `int8w` rounds every bfloat16 matrix
to 8 bits by output channel as it is widened; `bf16` rounds the left
operand of every product to bfloat16 (what the chip's default precision
does to the program's).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

BF16, F32 = "bfloat16", "float32"
ROUTED_DOWN = 8.0   # as qwen3_next.py's and nemotron_h.py's, for their reason
QUERY_SCALE = 2.5   # the scores' standard deviation (the docstring says why)
QUERY_ROWS = 512    # queries a block of the attention's scores


# -- sizes ---------------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    share = cfg["expert_share"]
    return {
        "dim": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"], "layers": cfg["num_hidden_layers"],
        "dense_layers": cfg["first_k_dense_replace"],
        "heads": cfg["num_attention_heads"], "q_rank": cfg["q_lora_rank"],
        "kv_rank": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": int(cfg["qk_rope_head_dim"] * cfg["partial_rotary_factor"]),
        "v": cfg["v_head_dim"], "theta": float(cfg["rope_theta"]),
        "dense_ffn": cfg["intermediate_size"],
        "experts": share["of"], "held_first": share["first"],
        "held": share["count"], "top_k": cfg["num_experts_per_tok"],
        "ffn": cfg["moe_intermediate_size"],
        "shared_ffn": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        "scale": cfg["routed_scaling_factor"],
    }


def kind_of(l: int, z: dict) -> str:
    return "F" if l < z["dense_layers"] else "E"


def layer_spec(kind: str, z: dict) -> list:
    """(name, shape, dtype, mean, std) of one published layer's leaves (the
    attention's, then the feed-forward's), in the order their keys are
    folded."""
    d, h = z["dim"], z["heads"]
    by = lambda fan_in: 1.0 / math.sqrt(fan_in)
    # by fan-in and by the residual's additions, two a layer
    out = lambda fan_in: 1.0 / math.sqrt(fan_in * 2 * z["layers"])
    attention = [
        ("norm1", (d,), F32, 1.0, 0.1),
        ("w_dq", (d, z["q_rank"]), BF16, 0.0, by(d)),
        ("q_norm", (z["q_rank"],), F32, 1.0, 0.1),
        ("w_uq", (z["q_rank"], h * (z["nope"] + z["rope"])), BF16, 0.0,
         QUERY_SCALE * by(z["q_rank"])),
        ("w_dkv", (d, z["kv_rank"] + z["rope"]), BF16, 0.0, by(d)),
        ("kv_norm", (z["kv_rank"],), F32, 1.0, 0.1),
        ("w_ukv", (z["kv_rank"], h * (z["nope"] + z["v"])), BF16, 0.0,
         by(z["kv_rank"])),
        ("wo", (h * z["v"], d), BF16, 0.0, out(h * z["v"])),
        ("norm2", (d,), F32, 1.0, 0.1)]
    if kind == "F":
        f = z["dense_ffn"]
        return attention + [("w_gate", (d, f), BF16, 0.0, by(d)),
                            ("w_up", (d, f), BF16, 0.0, by(d)),
                            ("w_down", (f, d), BF16, 0.0, out(f))]
    if kind != "E":
        raise ValueError(f"unknown layer kind {kind!r}")
    held, f, sf = z["held"], z["ffn"], z["shared_ffn"]
    return attention + [
        ("router", (d, z["experts"]), F32, 0.0, by(d)),
        ("b_corr", (z["experts"],), F32, 0.0, 0.02),
        ("w_gate", (held, d, f), BF16, 0.0, by(d)),
        ("w_up", (held, d, f), BF16, 0.0, by(d)),
        ("w_down", (held, f, d), BF16, 0.0, out(f) / ROUTED_DOWN),
        ("s_gate", (d, sf), BF16, 0.0, by(d)),
        ("s_up", (d, sf), BF16, 0.0, by(d)),
        ("s_down", (sf, d), BF16, 0.0, out(sf))]


def top_spec(z: dict) -> list:
    return [("embed", (z["vocab"], z["dim"]), BF16, 0.0, 0.02),
            ("head", (z["vocab"], z["dim"]), BF16, 0.0,
             1.0 / math.sqrt(z["dim"])),
            ("norm_f", (z["dim"],), F32, 1.0, 0.1)]


# Projections out of a layer are drawn with zero sums over their inputs, as
# the other references' are: a layer's hidden units have positive means, a
# plain draw adds one vector to every token alike, the routers see it and a
# round's tokens choose alike. A dense layer's `w_down` is one of them.
CENTRED = ("wo", "w_down", "s_down")


def leaf(key, i: int, shape, dtype: str, mean: float, std: float,
         centred: bool = False):
    """One leaf of the recipe. Jitted by the caller, so that the float32
    draw of a large leaf is rounded as it is made."""
    if std == 0.0:
        return jnp.full(shape, mean, dtype)
    draw = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
    if centred:
        draw = draw - jnp.mean(draw, axis=-2, keepdims=True)
    return (mean + std * draw).astype(dtype)


def make_weights(seed: int, cfg: dict) -> dict:
    """{"top": {...}, "layers": [{...}, ...], "precision": "f32"}, on the
    device, each leaf from its own jitted call."""
    z = sizes(cfg)
    make = jax.jit(leaf, static_argnums=(1, 2, 3, 4, 5, 6))
    key = jax.random.key(seed)
    top_key = jax.random.fold_in(key, z["layers"])
    top = {name: make(top_key, i, shape, dtype, mean, std)
           for i, (name, shape, dtype, mean, std) in enumerate(top_spec(z))}
    layers = []
    for l in range(z["layers"]):
        k = jax.random.fold_in(key, l)
        layers.append({name: make(k, i, shape, dtype, mean, std,
                                  name in CENTRED)
                       for i, (name, shape, dtype, mean, std)
                       in enumerate(layer_spec(kind_of(l, z), z))})
    return {"top": top, "layers": layers, "precision": "f32"}


def at_precision(w: dict, precision: str) -> dict:
    """The weights as a control holds them: the same leaves, rounded where
    they are widened (`_wide`), since no second copy fits."""
    if precision not in ("f32", "bf16", "int8w"):
        raise ValueError(f"unknown precision {precision!r}")
    return {**w, "precision": precision}


# -- the arithmetic ---------------------------------------------------------------

def _wide(a, precision: str):
    """A stored matrix in float32, as `precision` holds it. The input axis
    is the one before last, so an output channel's scale is taken over it."""
    a = a.astype(jnp.float32)
    if precision == "int8w" and a.ndim >= 2:
        peak = jnp.maximum(jnp.max(jnp.abs(a), axis=-2, keepdims=True), 1e-30)
        return jnp.round(a / peak * 127.0) * (peak / 127.0)
    return a


def _mm(x, a, precision: str):
    if precision == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.dot(x, _wide(a, precision))


def norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rotary(x, z: dict):
    """x (B, T, H, rope) at positions 0 .. T-1: dimension i turns with
    i + rope/2."""
    r = z["rope"]
    inv_freq = z["theta"] ** (-2.0 * jnp.arange(r // 2) / r)
    angle = jnp.arange(x.shape[1])[:, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def latent_attention(p: dict, u, z: dict, precision: str = "f32"):
    """u (B, T, D) -> (B, T, D): causal attention with keys and values made
    from the latent of every token (the non-absorbed form). The scores are
    made `QUERY_ROWS` queries at a time, so that a request of 16,384 tokens
    fits."""
    b, t, _ = u.shape
    h, nope, rope, v = z["heads"], z["nope"], z["rope"], z["v"]
    c_q = norm(_mm(u, p["w_dq"], precision), p["q_norm"], z["eps"])
    q = _mm(c_q, p["w_uq"], precision).reshape(b, t, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], z)], axis=-1)
    ckr = _mm(u, p["w_dkv"], precision)
    c_kv = norm(ckr[..., :z["kv_rank"]], p["kv_norm"], z["eps"])
    k_r = rotary(ckr[..., z["kv_rank"]:][:, :, None, :], z)
    kv = _mm(c_kv, p["w_ukv"], precision).reshape(b, t, h, nope + v)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (b, t, h, rope))], axis=-1)
    if precision == "bf16":  # the left operands of the two products
        q = q.astype(jnp.bfloat16).astype(jnp.float32)
    rows = min(QUERY_ROWS, t)
    if t % rows:
        raise ValueError(f"{t} tokens are no multiple of {rows}")
    at = jnp.arange(t)

    def block(q_rows):
        q_blk, first = q_rows
        s = jnp.einsum("bqhk,blhk->bhql", q_blk, k) / math.sqrt(nope + rope)
        causal = at[None, :] <= (first + jnp.arange(rows))[:, None]
        a = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
        if precision == "bf16":
            a = a.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.einsum("bhql,blhv->bqhv", a, kv[..., nope:])

    o = jax.lax.map(block, (
        jnp.moveaxis(q.reshape(b, t // rows, rows, h, nope + rope), 1, 0),
        jnp.arange(0, t, rows)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, h * v)
    return _mm(o, p["wo"], precision)


def _ffn(x, gate, up, down, precision: str):
    hidden = jax.nn.silu(_mm(x, gate, precision)) * _mm(x, up, precision)
    return _mm(hidden, down, precision)


def dense_mlp(p: dict, u, z: dict, precision: str = "f32"):
    return _ffn(u, p["w_gate"], p["w_up"], p["w_down"], precision)


def route(p: dict, u, z: dict):
    """(N, D) -> the chosen experts (N, k) and their weights (N, k)."""
    scores = jax.nn.sigmoid(jnp.dot(u, p["router"]))
    _, chosen = jax.lax.top_k(scores + p["b_corr"], z["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, picked / (jnp.sum(picked, axis=-1, keepdims=True)
                             + 1e-20) * z["scale"]


def moe_parts(p: dict, u, z: dict, precision: str = "f32"):
    """(the held experts' part, the shared expert's), each (B, T, D). The
    held experts are `p["w_up"]`'s, ids `held_first ..`; one at a time."""
    shape = u.shape
    flat = u.reshape(-1, shape[-1])
    chosen, weights = route(p, flat, z)
    first = z["held_first"]

    def one(acc, expert):
        e, gate, up, down = expert
        w = jnp.sum(jnp.where(chosen == e + first, weights, 0.0), axis=-1)
        return acc + w[:, None] * _ffn(flat, gate, up, down, precision), None

    held = p["w_up"].shape[0]
    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(flat),
        (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]))
    shared = _ffn(flat, p["s_gate"], p["s_up"], p["s_down"], precision)
    return routed.reshape(shape), shared.reshape(shape)


def moe_layer(p: dict, u, z: dict, precision: str = "f32"):
    routed, shared = moe_parts(p, u, z, precision)
    return routed + shared


FEED_FORWARDS = {"F": dense_mlp, "E": moe_layer}


def _frozen(z: dict) -> tuple:
    return tuple(sorted(z.items()))


_JITS: dict = {}


def _block(kind: str, precision: str, zt: tuple):
    """One residual block, jitted: the attention (`L`) under `norm1`, or a
    feed-forward (`F`, `E`) under `norm2`. One call a block, so that one
    block's widened matrices are all that is held beside the weights."""
    if (kind, precision, zt) not in _JITS:
        z = dict(zt)
        if kind == "L":
            fn = lambda p, x: x + latent_attention(
                p, norm(x, p["norm1"], z["eps"]), z, precision)
        else:
            fn = lambda p, x: x + FEED_FORWARDS[kind](
                p, norm(x, p["norm2"], z["eps"]), z, precision)
        _JITS[kind, precision, zt] = jax.jit(fn)
    return _JITS[kind, precision, zt]


def hidden_states(w: dict, ids, cfg: dict):
    """ids (B, T) -> the stack's output before the last norm, (B, T, D)."""
    z = sizes(cfg)
    zt, precision = _frozen(z), w["precision"]
    x = jnp.take(w["top"]["embed"], ids, axis=0).astype(jnp.float32)
    for l, p in enumerate(w["layers"]):
        x = _block("L", precision, zt)(p, x)
        x = _block(kind_of(l, z), precision, zt)(p, x)
    return x


def logits_at(w: dict, ids, positions, cfg: dict):
    """ids (B, T) int32, positions (B, P) int32 -> the next-token logits
    (B, P, vocab) float32 after each of those positions. Every layer is
    causal, so padding after a row's end cannot reach a position before it."""
    x = hidden_states(w, ids, cfg)
    x = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    x = norm(x, w["top"]["norm_f"], cfg["rms_norm_eps"])
    return _head(w["precision"])(x, w["top"]["head"])


def _head(precision: str):
    if ("head", precision) not in _JITS:
        _JITS["head", precision] = jax.jit(
            lambda x, head: _mm(x, head.T, precision))
    return _JITS["head", precision]


def served_logits(w: dict, cfg: dict, requests: list, pad_to: int = 1024):
    """The reference's logits behind every served token. `requests` is a list
    of (prompt ids, served tokens); gives, for each, an array
    (len(tokens), vocab): row i is the distribution the token i was drawn
    from, given the prompt and the served tokens before it. One request at a
    time, padded to a multiple of `pad_to` tokens (a multiple of
    `QUERY_ROWS`, or less than it): few shapes, and a request of 16,384
    tokens fits beside the weights."""
    out = []
    with jax.default_matmul_precision("highest"):
        for prompt, toks in requests:
            seq = list(prompt) + list(toks[:-1])
            step = pad_to if len(seq) > QUERY_ROWS else 128
            ids = np.zeros((1, -(-len(seq) // step) * step), np.int32)
            ids[0, :len(seq)] = seq
            pos = (len(prompt) - 1 + np.arange(len(toks)))[None]
            out.append(np.asarray(logits_at(
                w, jnp.asarray(ids), jnp.asarray(pos, jnp.int32), cfg))[0])
    return out


def token_gaps(ref_logits: list, tokens: list) -> np.ndarray:
    """For every served token, how far its reference logit lies below the
    reference's best at that position (0 where it is the best)."""
    gaps = []
    for logits, toks in zip(ref_logits, tokens):
        toks = np.asarray(toks, np.int64)
        gaps.append(logits.max(axis=-1)
                    - logits[np.arange(len(toks)), toks])
    return np.concatenate(gaps)


def routed_choices(w: dict, ids, cfg: dict) -> list:
    """For each expert block, the experts chosen at every position of `ids`
    (B, T, k): what a comparison of routing reads."""
    z = sizes(cfg)
    zt, precision = _frozen(z), w["precision"]
    x = jnp.take(w["top"]["embed"], ids, axis=0).astype(jnp.float32)
    out = []
    with jax.default_matmul_precision("highest"):
        for l, p in enumerate(w["layers"]):
            x = _block("L", precision, zt)(p, x)
            kind = kind_of(l, z)
            if kind == "E":
                u = norm(x, p["norm2"], z["eps"])
                chosen, _ = route(p, u.reshape(-1, u.shape[-1]), z)
                out.append(np.asarray(chosen).reshape(ids.shape + (-1,)))
            x = _block(kind, precision, zt)(p, x)
    return out
