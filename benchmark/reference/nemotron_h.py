"""The `nemotron_h` family, plain: the full causal forward pass of a hybrid
stack (Mamba-2 `M`, sparse experts `E`, attention `*`, one mixer a layer)
in straightforward float32 `jax.numpy` at `highest` matmul precision,
written from the public `nemotron_h` modelling code and the keys of
`nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`'s config.json. No cache, no
chunked scan, no kernel, no batching of experts: the state-space layer is
the recurrence itself, one step a token. Imports nothing of the program.

Every layer: `x <- x + mixer(RMSNorm(x))`; then RMSNorm and the untied head.
No position table and no rotary: the public attention layer applies none.

The chip's share (`cfg["expert_share"]`: first, count, of): the router keeps
all `of` outputs and its experts per token, the weights normalise over all
chosen, and the result holds the held experts' part and the shared expert.
What the absent experts would add is left out. The vocabulary is the slice
`vocab_size` the configuration states.

Weights: what the checkpoint's name says, bfloat16 for every matrix and the
embeddings; float32 for `A_log`, `dt_bias`, `D`, the router, its correction
bias and the norms. They are kept as such (in float32 the cell's share is
18 GB) and widened to float32 one matrix at a time where they are used: the
reference in blocks. Leaf i of layer l is
`mean + std * normal(fold_in(fold_in(key(seed), l), i))`, rounded to its
dtype; the template repeats the recipe in the program's layout.

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

EPS = 1e-5
BF16, F32 = "bfloat16", "float32"
# The routed experts' down-projections are drawn this many times smaller than
# their fan-in says. At fan-in scale one expert is 15 % of a layer's output,
# a near tie of the router (which rounding decides) moves that much of the
# stream, later tokens inherit it through state and keys, their routers tie
# more often, and program and reference part on every stream: 29 % of the
# served tokens were off the reference's best (my chip run, PR 27). At an
# eighth, a moved expert is 2 % and dies out.
ROUTED_DOWN = 8.0


# -- sizes ---------------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    inner = heads * hd
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    share = cfg["expert_share"]
    return {
        "dim": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "pattern": cfg["hybrid_override_pattern"],
        "m_heads": heads, "m_hd": hd, "inner": inner, "groups": groups,
        "state": state, "conv_k": cfg["conv_kernel"],
        "conv_dim": inner + 2 * groups * state,
        "in_cols": 2 * inner + 2 * groups * state + heads,
        "q_heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "experts": share["of"], "held_first": share["first"],
        "held": share["count"], "top_k": cfg["num_experts_per_tok"],
        "ffn": cfg["moe_intermediate_size"],
        "shared_ffn": cfg["moe_shared_expert_intermediate_size"],
        "scale": cfg["routed_scaling_factor"],
    }


def layer_spec(kind: str, z: dict, n_layers: int) -> list:
    """(name, shape, dtype, mean, std) of one layer's leaves, in the order
    their keys are folded."""
    d = z["dim"]
    into = 1.0 / math.sqrt(d)  # by fan-in: 0.0193 at 2688
    # by fan-in, and rescale_prenorm_residual's 1/sqrt(layers)
    out = lambda fan_in: 1.0 / math.sqrt(fan_in * n_layers)
    norm = ("norm", (d,), F32, 1.0, 0.0)
    if kind == "M":
        return [norm,
                ("w_in", (d, z["in_cols"]), BF16, 0.0, into),
                ("conv_w", (z["conv_k"], z["conv_dim"]), F32, 0.0, 0.4),
                ("conv_b", (z["conv_dim"],), F32, 0.0, 0.1),
                ("dt_bias", (z["m_heads"],), F32, -3.0, 1.0),
                ("A_log", (z["m_heads"],), F32, 0.0, 0.7),
                ("D", (z["m_heads"],), F32, 1.0, 0.0),
                ("gnorm", (z["inner"],), F32, 1.0, 0.0),
                ("w_out", (z["inner"], d), BF16, 0.0, out(z["inner"]))]
    if kind == "*":
        q, kv = z["q_heads"] * z["hd"], z["kv_heads"] * z["hd"]
        return [norm,
                ("wq", (d, q), BF16, 0.0, into),
                ("wk", (d, kv), BF16, 0.0, into),
                ("wv", (d, kv), BF16, 0.0, into),
                ("wo", (q, d), BF16, 0.0, out(q))]
    if kind == "E":
        return [norm,
                ("router", (d, z["experts"]), F32, 0.0, into),
                ("b_corr", (z["experts"],), F32, 0.0, 0.02),
                ("w_up", (z["held"], d, z["ffn"]), BF16, 0.0, into),
                ("w_down", (z["held"], z["ffn"], d), BF16, 0.0,
                 out(z["ffn"]) / ROUTED_DOWN),
                ("s_up", (d, z["shared_ffn"]), BF16, 0.0, into),
                ("s_down", (z["shared_ffn"], d), BF16, 0.0,
                 out(z["shared_ffn"]))]
    raise ValueError(f"unknown layer kind {kind!r}")


def top_spec(z: dict) -> list:
    return [("embed", (z["vocab"], z["dim"]), BF16, 0.0, 0.02),
            ("head", (z["vocab"], z["dim"]), BF16, 0.0,
             1.0 / math.sqrt(z["dim"])),
            ("norm_f", (z["dim"],), F32, 1.0, 0.0)]


# Projections out of a layer are drawn with zero sums over their inputs. A
# layer's hidden units have positive means (squared ReLU, silu), so a plain
# draw gives every token the same added vector, the routers see it, and a
# round's tokens choose alike: 26.5-28.3 of 64 experts hit a layer, by the
# seed, where independent choices hit 34.3 (my chip run, PR 27). A balanced
# checkpoint routes evenly; centred columns are this recipe's way there.
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
    n = len(z["pattern"])
    top_key = jax.random.fold_in(key, n)
    top = {name: make(top_key, i, shape, dtype, mean, std)
           for i, (name, shape, dtype, mean, std) in enumerate(top_spec(z))}
    layers = []
    for l, kind in enumerate(z["pattern"]):
        k = jax.random.fold_in(key, l)
        layers.append({name: make(k, i, shape, dtype, mean, std,
                                  name in CENTRED)
                       for i, (name, shape, dtype, mean, std)
                       in enumerate(layer_spec(kind, z, n))})
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
    is the one before last, for a stack of experts too, so an output
    channel's scale is taken over it."""
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


def rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + EPS) * scale


def mamba_layer(p: dict, u, z: dict, precision: str = "f32"):
    """u (B, T, D) -> (B, T, D): the recurrence, one step a token, from a
    zero state."""
    b, t, _ = u.shape
    heads, hd, groups, state = z["m_heads"], z["m_hd"], z["groups"], z["state"]
    inner, k = z["inner"], z["conv_k"]
    proj = _mm(u, p["w_in"], precision)
    gate, xbc, dt = jnp.split(proj, [inner, inner + z["conv_dim"]], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + t] * p["conv_w"][j] for j in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x, bmat, cmat = jnp.split(xbc, [inner, inner + groups * state], axis=-1)
    x = x.reshape(b, t, heads, hd)
    per = heads // groups  # head h uses group h // per
    bmat = jnp.repeat(bmat.reshape(b, t, groups, state), per, axis=2)
    cmat = jnp.repeat(cmat.reshape(b, t, groups, state), per, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])            # (B, T, H)
    decay = jnp.exp(dt * -jnp.exp(p["A_log"]))

    def step(h, at):
        x_t, b_t, c_t, dt_t, decay_t = at
        h = decay_t[..., None, None] * h + (
            (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    h0 = jnp.zeros((b, heads, hd, state), jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, bmat, cmat, dt, decay)))
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * x     # (B, T, H, P)
    y = y.reshape(b, t, inner) * jax.nn.silu(gate)
    y = y.reshape(b, t, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + EPS)
    y = y.reshape(b, t, inner) * p["gnorm"]
    return _mm(y, p["w_out"], precision)


def attention_layer(p: dict, u, z: dict, precision: str = "f32"):
    """Causal softmax attention, `q_heads` over `kv_heads`, no positions."""
    b, t, _ = u.shape
    qh, kvh, hd = z["q_heads"], z["kv_heads"], z["hd"]
    q = _mm(u, p["wq"], precision).reshape(b, t, kvh, qh // kvh, hd)
    k = _mm(u, p["wk"], precision).reshape(b, t, kvh, hd)
    v = _mm(u, p["wv"], precision).reshape(b, t, kvh, hd)
    s = jnp.einsum("bqgrk,blgk->bgrql", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    o = jnp.einsum("bgrql,blgk->bqgrk", a, v).reshape(b, t, qh * hd)
    return _mm(o, p["wo"], precision)


def route(p: dict, u, z: dict):
    """(N, D) -> the chosen experts (N, k) and their weights (N, k)."""
    s = jax.nn.sigmoid(jnp.dot(u, p["router"]))
    _, chosen = jax.lax.top_k(s + p["b_corr"], z["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * z["scale"]


def _ffn(x, up, down, precision: str):
    return _mm(jnp.square(jax.nn.relu(_mm(x, up, precision))), down,
               precision)


def moe_parts(p: dict, u, z: dict, precision: str = "f32"):
    """(the held experts' part, the shared expert's), each (B, T, D). The
    held experts are `p["w_up"]`'s, ids `held_first ..`; one at a time."""
    shape = u.shape
    flat = u.reshape(-1, shape[-1])
    chosen, weights = route(p, flat, z)
    first = z["held_first"]

    def one(acc, expert):
        e, up, down = expert
        gate = jnp.sum(jnp.where(chosen == e + first, weights, 0.0), axis=-1)
        return acc + gate[:, None] * _ffn(flat, up, down, precision), None

    held = p["w_up"].shape[0]
    routed, _ = jax.lax.scan(one, jnp.zeros_like(flat),
                             (jnp.arange(held), p["w_up"], p["w_down"]))
    shared = _ffn(flat, p["s_up"], p["s_down"], precision)
    return routed.reshape(shape), shared.reshape(shape)


def moe_layer(p: dict, u, z: dict, precision: str = "f32"):
    routed, shared = moe_parts(p, u, z, precision)
    return routed + shared


MIXERS = {"M": mamba_layer, "*": attention_layer, "E": moe_layer}


def hidden_states(w: dict, ids, cfg: dict):
    """ids (B, T) -> the stack's output before the last norm, (B, T, D).
    One jitted call a layer, so that one layer's widened matrices are all
    that is held beside the weights."""
    z = sizes(cfg)
    precision = w["precision"]
    x = jnp.take(w["top"]["embed"], ids, axis=0).astype(jnp.float32)
    for kind, p in zip(z["pattern"], w["layers"]):
        x = _layer(kind, precision, _frozen(z))(p, x)
    return x


def _frozen(z: dict) -> tuple:
    return tuple(sorted(z.items()))


_LAYERS: dict = {}


def _layer(kind: str, precision: str, zt: tuple):
    if (kind, precision, zt) not in _LAYERS:
        z = dict(zt)
        _LAYERS[kind, precision, zt] = jax.jit(
            lambda p, x: x + MIXERS[kind](p, rmsnorm(x, p["norm"]), z,
                                          precision))
    return _LAYERS[kind, precision, zt]


def logits_at(w: dict, ids, positions, cfg: dict):
    """ids (B, T) int32, positions (B, P) int32 -> the next-token logits
    (B, P, vocab) float32 after each of those positions. Every layer is
    causal, so padding after a row's end cannot reach a position before it."""
    x = hidden_states(w, ids, cfg)
    x = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    x = rmsnorm(x, w["top"]["norm_f"])
    return _head(w["precision"])(x, w["top"]["head"])


def _head(precision: str):
    if ("head", precision) not in _LAYERS:
        _LAYERS["head", precision] = jax.jit(
            lambda x, head: _mm(x, head.T, precision))
    return _LAYERS["head", precision]


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
    """For each expert layer, the experts chosen at every position of `ids`
    (B, T, k): what a comparison of routing reads."""
    z = sizes(cfg)
    precision = w["precision"]
    x = jnp.take(w["top"]["embed"], ids, axis=0).astype(jnp.float32)
    out = []
    with jax.default_matmul_precision("highest"):
        for kind, p in zip(z["pattern"], w["layers"]):
            if kind == "E":
                u = rmsnorm(x, p["norm"])
                chosen, _ = route(p, u.reshape(-1, u.shape[-1]), z)
                out.append(np.asarray(chosen).reshape(ids.shape + (-1,)))
            x = _layer(kind, precision, _frozen(z))(p, x)
    return out
