"""The `qwen3_next` family, plain: the full causal forward pass of a stack
whose layers are a mixer (the gated delta rule, or every
`full_attention_interval`-th layer gated rotary attention) and then a
sparse-expert block, in straightforward float32 `jax.numpy` at `highest`
matmul precision, written from the public `qwen3_next` modelling code and the
keys of `Qwen/Qwen3-Next-80B-A3B-Instruct`'s config.json. No cache, no
chunked rule, no kernel, no batching of experts: the delta rule is the
recurrence itself, one step a token. Imports nothing of the program.

    Norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)          (zero-centred)
    x <- x + Mixer(Norm_1(x));  x <- x + Experts(Norm_2(x))
    logits = Norm_f(x) W_head^T

Gated delta layer: `[q | k | v | z] = u W_qkvz`, `[b | a] = u W_ba`;
`[q | k | v] <- silu(conv1d_causal_depthwise([q | k | v]))` (no bias);
`beta = sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)` a value head;
q, k L2-normalised a head, q times key_dim^-1/2, each key head repeated for
its value heads; a head's state S (key by value), a token:
`S <- exp(g) S; d = beta (v - S^T k); S <- S + k d^T; o = S^T q`; out
`(w_n * o / sqrt(mean_head(o^2) + eps)) * silu(z)` (w_n plain), then W_out.

Gated attention layer: `[q | gate] = u W_q` (a head's query, then its gate),
`k = u W_k`, `v = u W_v`; q, k <- Norm over the head; rotary on the first
`partial_rotary_factor * head_dim` of a head, `inv_freq_i = theta^(-2i/r)`,
the pair (i, i + r/2); causal softmax attention, scale head_dim^-1/2, a
key/value head for `heads / kv_heads` query heads; `o * sigmoid(gate)`, W_o.

Expert block: `p = softmax(u W_r)` over all experts; the top k; their
weights over their sum (`norm_topk_prob`); an expert is
`(silu(u W_gate) * (u W_up)) W_down`; the shared expert of the same form
times `sigmoid(u w_s)` is added.

The chip's share (`cfg["expert_share"]`: first, count, of): the router keeps
all `of` outputs and its experts per token, the weights normalise over all
chosen, and the result holds the held experts' part and the shared expert.
What the absent experts would add is left out. The vocabulary is the slice
`vocab_size` the configuration states.

Weights: bfloat16 for every matrix and the embeddings; float32 for `A_log`,
`dt_bias`, the convolution, the router and the norms. They are kept as such
and widened to float32 one matrix at a time where they are used: the
reference in blocks. Leaf i of layer l is
`mean + std * normal(fold_in(fold_in(key(seed), l), i))`, rounded to its
dtype; the template repeats the recipe in the program's layout (its norms
hold `1 + w`, an expert's `W_gate` and `W_up` lie side by side).

Controls, put in the program's place: `int8w` rounds every bfloat16 matrix
to 8 bits by output channel as it is widened; `fp8` rounds it to
float8_e4m3 by output channel; `bf16` rounds the left operand of every
product to bfloat16 (what the chip's default precision does to the
program's).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

BF16, F32 = "bfloat16", "float32"
# The routed experts' down-projections are drawn this many times smaller than
# their fan-in says, as benchmark/reference/nemotron_h.py's are and for its
# reason: a near tie of the router, which rounding decides, then moves a
# fiftieth of a layer's output and dies out. With ten choices of 512 the
# tenth and the eleventh score lie closer than with six of 128.
ROUTED_DOWN = 8.0


# -- sizes ---------------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    kh, kd = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    vh, vd = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    share = cfg["expert_share"]
    n = cfg["num_hidden_layers"]
    every = cfg["full_attention_interval"]
    return {
        "dim": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"],
        "kinds": "".join("G" if (i + 1) % every == 0 else "D"
                         for i in range(n)),
        "kh": kh, "kd": kd, "vh": vh, "vd": vd, "keys": kh * kd,
        "values": vh * vd, "conv_k": cfg["linear_conv_kernel_dim"],
        "conv_dim": 2 * kh * kd + vh * vd,
        "q_heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "rotary": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        "theta": float(cfg["rope_theta"]),
        "experts": share["of"], "held_first": share["first"],
        "held": share["count"], "top_k": cfg["num_experts_per_tok"],
        "ffn": cfg["moe_intermediate_size"],
        "shared_ffn": cfg["shared_expert_intermediate_size"],
    }


def layer_spec(kind: str, z: dict) -> list:
    """(name, shape, dtype, mean, std) of one published layer's leaves (the
    mixer's, then the expert block's), in the order their keys are folded."""
    d = z["dim"]
    into = 1.0 / math.sqrt(d)  # by fan-in: 0.0221 at 2048
    # by fan-in and by the residual's additions, two a layer
    out = lambda fan_in: 1.0 / math.sqrt(fan_in * 2 * len(z["kinds"]))
    if kind == "D":
        mixer = [
            ("w_qkvz", (d, z["conv_dim"] + z["values"]), BF16, 0.0, into),
            ("w_ba", (d, 2 * z["vh"]), BF16, 0.0, into),
            ("conv_w", (z["conv_k"], z["conv_dim"]), F32, 0.0, 0.4),
            ("dt_bias", (z["vh"],), F32, -3.0, 1.0),
            ("A_log", (z["vh"],), F32, 0.0, 0.7),
            ("onorm", (z["vd"],), F32, 1.0, 0.1),
            ("w_out", (z["values"], d), BF16, 0.0, out(z["values"]))]
    elif kind == "G":
        q, kv = z["q_heads"] * z["hd"], z["kv_heads"] * z["hd"]
        mixer = [("wq", (d, 2 * q), BF16, 0.0, into),
                 ("wk", (d, kv), BF16, 0.0, into),
                 ("wv", (d, kv), BF16, 0.0, into),
                 ("q_norm", (z["hd"],), F32, 0.0, 0.1),
                 ("k_norm", (z["hd"],), F32, 0.0, 0.1),
                 ("wo", (q, d), BF16, 0.0, out(q))]
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    held, f, sf = z["held"], z["ffn"], z["shared_ffn"]
    return [("norm1", (d,), F32, 0.0, 0.1)] + mixer + [
        ("norm2", (d,), F32, 0.0, 0.1),
        ("router", (d, z["experts"]), F32, 0.0, into),
        ("w_gate", (held, d, f), BF16, 0.0, into),
        ("w_up", (held, d, f), BF16, 0.0, into),
        ("w_down", (held, f, d), BF16, 0.0, out(f) / ROUTED_DOWN),
        ("s_gate", (d, sf), BF16, 0.0, into),
        ("s_up", (d, sf), BF16, 0.0, into),
        ("s_down", (sf, d), BF16, 0.0, out(sf)),
        ("s_w", (d, 1), BF16, 0.0, into)]


def top_spec(z: dict) -> list:
    return [("embed", (z["vocab"], z["dim"]), BF16, 0.0, 0.02),
            ("head", (z["vocab"], z["dim"]), BF16, 0.0,
             1.0 / math.sqrt(z["dim"])),
            ("norm_f", (z["dim"],), F32, 0.0, 0.1)]


# Projections out of a layer are drawn with zero sums over their inputs, as
# benchmark/reference/nemotron_h.py's are: a layer's hidden units have
# positive means, a plain draw adds one vector to every token alike, the
# routers see it and a round's tokens choose alike.
CENTRED = ("w_out", "wo", "w_down", "s_down")


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
    top_key = jax.random.fold_in(key, len(z["kinds"]))
    top = {name: make(top_key, i, shape, dtype, mean, std)
           for i, (name, shape, dtype, mean, std) in enumerate(top_spec(z))}
    layers = []
    for l, kind in enumerate(z["kinds"]):
        k = jax.random.fold_in(key, l)
        layers.append({name: make(k, i, shape, dtype, mean, std,
                                  name in CENTRED)
                       for i, (name, shape, dtype, mean, std)
                       in enumerate(layer_spec(kind, z))})
    return {"top": top, "layers": layers, "precision": "f32"}


def at_precision(w: dict, precision: str) -> dict:
    """The weights as a control holds them: the same leaves, rounded where
    they are widened (`_wide`), since no second copy fits."""
    if precision not in ("f32", "bf16", "int8w", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    return {**w, "precision": precision}


# -- the arithmetic ---------------------------------------------------------------

def _wide(a, precision: str):
    """A stored matrix in float32, as `precision` holds it. The input axis
    is the one before last, so an output channel's scale is taken over it."""
    a = a.astype(jnp.float32)
    if precision in ("int8w", "fp8") and a.ndim >= 2:
        peak = jnp.maximum(jnp.max(jnp.abs(a), axis=-2, keepdims=True), 1e-30)
        if precision == "int8w":
            return jnp.round(a / peak * 127.0) * (peak / 127.0)
        return (a / peak * 448.0).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * (peak / 448.0)
    return a


def _mm(x, a, precision: str):
    if precision == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.dot(x, _wide(a, precision))


def norm(x, w, eps: float):
    """Zero-centred RMSNorm: the stored weight is the scale less one."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_layer(p: dict, u, z: dict, precision: str = "f32"):
    """u (B, T, D) -> (B, T, D): the gated delta rule, one step a token,
    from a zero state."""
    b, t, _ = u.shape
    kh, kd, vh, vd, k = z["kh"], z["kd"], z["vh"], z["vd"], z["conv_k"]
    qkvz = _mm(u, p["w_qkvz"], precision)
    ba = _mm(u, p["w_ba"], precision)
    qkv, gate = jnp.split(qkvz, [z["conv_dim"]], axis=-1)
    padded = jnp.pad(qkv, ((0, 0), (k - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + t] * p["conv_w"][j]
                          for j in range(k)))
    q, key, v = jnp.split(qkv, [z["keys"], 2 * z["keys"]], axis=-1)
    per = vh // kh  # value head h reads key head h // per
    q = jnp.repeat(_l2norm(q.reshape(b, t, kh, kd)) * kd ** -0.5, per, axis=2)
    key = jnp.repeat(_l2norm(key.reshape(b, t, kh, kd)), per, axis=2)
    v = v.reshape(b, t, vh, vd)
    beta = jax.nn.sigmoid(ba[..., :vh])                       # (B, T, Hv)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., vh:] + p["dt_bias"])

    def step(s, at):
        q_t, k_t, v_t, g_t, beta_t = at
        s = s * jnp.exp(g_t)[..., None, None]
        d = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., None] * d[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    s0 = jnp.zeros((b, vh, kd, vd), jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, key, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)                                # (B, T, Hv, Dv)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + z["eps"])
    o = (o * p["onorm"]).reshape(b, t, vh * vd) * jax.nn.silu(gate)
    return _mm(o, p["w_out"], precision)


def rotary(x, z: dict):
    """x (B, T, H, Dh) at positions 0 .. T-1: the first `rotary` of a head
    turn, dimension i with i + rotary/2; the rest pass."""
    r = z["rotary"]
    inv_freq = z["theta"] ** (-2.0 * jnp.arange(r // 2) / r)
    angle = jnp.arange(x.shape[1])[:, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def attention_layer(p: dict, u, z: dict, precision: str = "f32"):
    """Causal softmax attention, `q_heads` over `kv_heads`, with head norms,
    partial rotary positions and an output gate."""
    b, t, _ = u.shape
    qh, kvh, hd = z["q_heads"], z["kv_heads"], z["hd"]
    qg = _mm(u, p["wq"], precision).reshape(b, t, qh, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = _mm(u, p["wk"], precision).reshape(b, t, kvh, hd)
    v = _mm(u, p["wv"], precision).reshape(b, t, kvh, hd)
    q = rotary(norm(q, p["q_norm"], z["eps"]), z)
    k = rotary(norm(k, p["k_norm"], z["eps"]), z)
    q = q.reshape(b, t, kvh, qh // kvh, hd)
    s = jnp.einsum("bqgrk,blgk->bgrql", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    o = jnp.einsum("bgrql,blgk->bqgrk", a, v).reshape(b, t, qh * hd)
    o = o * jax.nn.sigmoid(gate.reshape(b, t, qh * hd))
    return _mm(o, p["wo"], precision)


def route(p: dict, u, z: dict):
    """(N, D) -> the chosen experts (N, k) and their weights (N, k)."""
    scores = jax.nn.softmax(jnp.dot(u, p["router"]), axis=-1)
    picked, chosen = jax.lax.top_k(scores, z["top_k"])
    return chosen, picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def _ffn(x, gate, up, down, precision: str):
    hidden = jax.nn.silu(_mm(x, gate, precision)) * _mm(x, up, precision)
    return _mm(hidden, down, precision)


def moe_parts(p: dict, u, z: dict, precision: str = "f32"):
    """(the held experts' part, the gated shared expert's), each (B, T, D).
    The held experts are `p["w_up"]`'s, ids `held_first ..`; one at a
    time."""
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
    shared = _ffn(flat, p["s_gate"], p["s_up"], p["s_down"], precision) \
        * jax.nn.sigmoid(_mm(flat, p["s_w"], precision))
    return routed.reshape(shape), shared.reshape(shape)


def moe_layer(p: dict, u, z: dict, precision: str = "f32"):
    routed, shared = moe_parts(p, u, z, precision)
    return routed + shared


MIXERS = {"D": delta_layer, "G": attention_layer}


def _frozen(z: dict) -> tuple:
    return tuple(sorted(z.items()))


_JITS: dict = {}


def _block(kind: str, precision: str, zt: tuple):
    """One residual block, jitted: a mixer (`D`, `G`) under `norm1` or the
    expert block (`E`) under `norm2`. One call a block, so that one block's
    widened matrices are all that is held beside the weights."""
    if (kind, precision, zt) not in _JITS:
        z = dict(zt)
        if kind == "E":
            fn = lambda p, x: x + moe_layer(
                p, norm(x, p["norm2"], z["eps"]), z, precision)
        else:
            fn = lambda p, x: x + MIXERS[kind](
                p, norm(x, p["norm1"], z["eps"]), z, precision)
        _JITS[kind, precision, zt] = jax.jit(fn)
    return _JITS[kind, precision, zt]


def hidden_states(w: dict, ids, cfg: dict):
    """ids (B, T) -> the stack's output before the last norm, (B, T, D)."""
    z = sizes(cfg)
    zt, precision = _frozen(z), w["precision"]
    x = jnp.take(w["top"]["embed"], ids, axis=0).astype(jnp.float32)
    for kind, p in zip(z["kinds"], w["layers"]):
        x = _block(kind, precision, zt)(p, x)
        x = _block("E", precision, zt)(p, x)
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


def served_logits(w: dict, cfg: dict, requests: list, rows: int = 4,
                  pad_to: int | None = None):
    """The reference's logits behind every served token. `requests` is a list
    of (prompt ids, served tokens); gives, for each, an array
    (len(tokens), vocab): row i is the distribution the token i was drawn
    from, given the prompt and the served tokens before it. Runs `rows`
    requests at a time, padded to a multiple of 128 tokens."""
    longest = max(len(p) + len(toks) for p, toks in requests)
    pad_to = pad_to or -(-longest // 128) * 128
    most = max(len(toks) for _, toks in requests)
    out = []
    with jax.default_matmul_precision("highest"):
        for at in range(0, len(requests), rows):
            block = requests[at:at + rows]
            ids = np.zeros((rows, pad_to), np.int32)
            pos = np.zeros((rows, most), np.int32)
            for r, (prompt, toks) in enumerate(block):
                seq = list(prompt) + list(toks[:-1])
                ids[r, :len(seq)] = seq
                pos[r, :len(toks)] = len(prompt) - 1 + np.arange(len(toks))
            logits = np.asarray(logits_at(w, jnp.asarray(ids),
                                          jnp.asarray(pos), cfg))
            out += [logits[r, :len(toks)]
                    for r, (_, toks) in enumerate(block)]
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
        for kind, p in zip(z["kinds"], w["layers"]):
            x = _block(kind, precision, zt)(p, x)
            u = norm(x, p["norm2"], z["eps"])
            chosen, _ = route(p, u.reshape(-1, u.shape[-1]), z)
            out.append(np.asarray(chosen).reshape(ids.shape + (-1,)))
            x = _block("E", precision, zt)(p, x)
    return out
