"""GPT-2 large, plain: the full causal forward pass in straightforward
float32 `jax.numpy` at `highest` matmul precision, written from the published
description (Radford et al. 2019; `openai-community/gpt2-large` config.json:
n_embd 1280, n_layer 36, n_head 20, n_positions 1024, vocab 50257, learned
positions, pre-LayerNorm blocks, tanh GELU, head tied to the embedding).
No cache, no batching tricks, no kernel. Imports nothing of the program and
takes nothing the program made: the weights come from the seed by the recipe
below, which the template repeats.

Departures from the published model, all of them `models/lm.py`'s own and
kept here because the reference has to compute what the program claims to:
no bias on the query, key and value projections (GPT-2's c_attn has one);
LayerNorm epsilon 1e-6 (published: 1e-5). Weights are normal draws at
GPT-2's initial scales (0.02; 0.01 for positions; residual projections
divided by sqrt(2 * n_layer)), not a trained checkpoint.

Controls (the precision a later PR would be tempted by, put in the program's
place): `precision="bf16"` holds weights and activations in bfloat16;
`precision="int8w"` rounds each weight matrix to 8 bits by output channel
and keeps the rest in float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import weights


def weight_spec(cfg: dict) -> list:
    d, h, n = cfg["n_embd"], cfg["n_head"], cfg["n_layer"]
    dh, f = d // h, 4 * cfg["n_embd"]
    res = 0.02 / math.sqrt(2 * n)
    return [
        ("wte", (cfg["vocab_size"], d), 0.02),
        ("wpe", (cfg["n_positions"], d), 0.01),
        ("ln1.scale", (n, d), "ones"), ("ln1.bias", (n, d), "zeros"),
        ("wq", (n, d, h, dh), 0.02), ("wk", (n, d, h, dh), 0.02),
        ("wv", (n, d, h, dh), 0.02), ("wo", (n, h, dh, d), res),
        ("bo", (n, d), "zeros"),
        ("ln2.scale", (n, d), "ones"), ("ln2.bias", (n, d), "zeros"),
        ("w1", (n, d, f), 0.02), ("b1", (n, f), "zeros"),
        ("w2", (n, f, d), res), ("b2", (n, d), "zeros"),
        ("ln_f.scale", (d,), "ones"), ("ln_f.bias", (d,), "zeros"),
    ]


def make_weights(seed: int, cfg: dict) -> dict:
    return weights.make(seed, weight_spec(cfg))


def _layernorm(x, scale, bias, eps=1e-6):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps) * scale + bias
            ).astype(x.dtype)


def _q8_by_channel(w, reduce_axes):
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=reduce_axes, keepdims=True),
                        1e-30) / 127.0
    return jnp.round(w / scale) * scale


_MATRICES = {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2), "w1": (1,),
             "w2": (1,), "wte": (1,)}


def at_precision(w: dict, precision: str) -> dict:
    """The weights as a control holds them."""
    if precision == "f32":
        return w
    if precision == "bf16":
        return {k: a.astype(jnp.bfloat16) for k, a in w.items()}
    if precision == "int8w":
        return {k: _q8_by_channel(a, _MATRICES[k]) if k in _MATRICES else a
                for k, a in w.items()}
    raise ValueError(f"unknown precision {precision!r}")


def logits_at(w: dict, ids, positions, cfg: dict):
    """ids (B, T) int32, positions (B, P) int32 -> the next-token logits
    (B, P, vocab) float32 after each of those positions. Full causal
    attention over the whole of each row; padding after a row's end cannot
    reach a position before it."""
    dt = w["wte"].dtype
    t = ids.shape[1]
    dh = cfg["n_embd"] // cfg["n_head"]
    x = w["wte"][ids] + w["wpe"][:t][None]
    causal = jnp.tril(jnp.ones((t, t), bool))
    names = ("ln1.scale", "ln1.bias", "wq", "wk", "wv", "wo", "bo",
             "ln2.scale", "ln2.bias", "w1", "b1", "w2", "b2")

    def block(x, layer):
        (g1, c1, wq, wk, wv, wo, bo, g2, c2, w1, b1, w2, b2) = layer
        h = _layernorm(x, g1, c1)
        q = jnp.einsum("btd,dhk->bhtk", h, wq)
        k = jnp.einsum("btd,dhk->bhtk", h, wk)
        v = jnp.einsum("btd,dhk->bhtk", h, wv)
        s = jnp.einsum("bhqk,bhlk->bhql", q, k,
                       preferred_element_type=jnp.float32) / math.sqrt(dh)
        a = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1).astype(dt)
        o = jnp.einsum("bhql,bhlk->bhqk", a, v)
        x = x + jnp.einsum("bhtk,hkd->btd", o, wo) + bo.astype(dt)
        h = _layernorm(x, g2, c2)
        h = jax.nn.gelu(jnp.dot(h, w1) + b1.astype(dt), approximate=True)
        return x + jnp.dot(h, w2) + b2.astype(dt), None

    x, _ = jax.lax.scan(block, x, tuple(w[n] for n in names))
    x = _layernorm(x, w["ln_f.scale"], w["ln_f.bias"])
    x = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    return jnp.einsum("bpd,vd->bpv", x, w["wte"],
                      preferred_element_type=jnp.float32)


def served_logits(w: dict, cfg: dict, requests: list, rows: int = 4,
                  pad_to: int | None = None):
    """The reference's logits behind every served token. `requests` is a list
    of (prompt ids, served tokens); yields, for each, an array
    (len(tokens), vocab): row i is the distribution the token i was drawn
    from, given the prompt and the served tokens before it. Runs `rows`
    requests at a time, so that it fits beside nothing else."""
    pad_to = pad_to or cfg["n_positions"]
    most = max(len(toks) for _, toks in requests)
    fn = jax.jit(lambda w_, ids, pos: logits_at(w_, ids, pos, cfg))
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
            logits = np.asarray(fn(w, jnp.asarray(ids), jnp.asarray(pos)))
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
