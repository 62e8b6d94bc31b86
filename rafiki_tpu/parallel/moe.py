"""Mixture-of-experts feed-forward layer, drop-free, with expert parallelism.

One layer for every use: the router scores all experts (softmax, or
sigmoid with a correction bias that moves the choice and not the weight),
each token takes its top ``k``, the weights are renormalised and scaled as
the model says, and **no token is dropped**: there is no capacity. A layer
is told which experts it holds, ``held = (first, count)``: it routes over
all of them and computes the held ones' part of the result (this chip's
share of an expert-parallel deployment; what the absent experts would add
is left out, and no code stands in for their exchange). A shared expert,
where the model has one, is every chip's alike and is added by the caller.
An expert is ``act(x W_up) W_down`` or, ``gated``, ``(act(x W_gate) * (x
W_up)) W_down`` with the two up-matrices side by side in one leaf
``[W_gate | W_up]`` (D, 2F): the loop over the experts hit then reads one
matrix and makes one product where two leaves would cost it two of each.

The experts' products come in two forms with one result. ``dense``: every
held expert over every token, weighted by the gates, as one batched product
(static shapes; what GSPMD partitions over the ``expert`` mesh axis for
training). ``gather``: a loop over the experts that a token chose, reading
only those experts' weights in place, which is what serving wants: a decode
round of 16 tokens at 6 of 128 hits about half of 64 held experts, and the
weights are most of the round's bytes (a prefill chunk hits them all and
still reads each once). Many tokens (four groups of ``GROUP_ROWS`` and
more: a prefill chunk of 512) are first laid out expert by expert, so that
the loop runs a token through the experts it chose alone (``_grouped``).

``moe_apply`` is the ``k = 1`` softmax case with the Switch load-balancing
loss (Fedus et al. 2021, eq. 4) for the trainer.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from rafiki_tpu.models.core import normal_init, relu2

Params = Dict[str, Any]
HIGHEST = jax.lax.Precision.HIGHEST
GROUP_ROWS = 128  # tokens of one expert taken at once: a tile's sublanes x 16


def route(x: jax.Array, router: jax.Array, k: int, *,
          bias: Optional[jax.Array] = None, score: str = "sigmoid",
          renorm: bool = True, scale: float = 1.0
          ) -> Tuple[jax.Array, jax.Array]:
    """x (N, D) -> (gates (N, E), scores (N, E)), f32. ``gates[n, e]`` is
    the weight of expert e for token n, 0 where the token did not choose it.
    Scores in f32 at ``highest`` (a near tie decides an expert); the choice
    is the top k of ``scores + bias``, the weight the score itself."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=HIGHEST)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router score {score!r}")
    _, chosen = jax.lax.top_k(scores if bias is None else scores + bias, k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)       # (N, k)
    if renorm:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    hot = jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)
    return jnp.sum(hot * (picked * scale)[..., None], axis=1), scores


def _gated(act: Callable) -> Callable:
    """The hidden layer of a gated expert from its two halves side by side."""
    def hidden(h):
        gate, up = jnp.split(h, 2, axis=-1)
        return act(gate) * up
    return hidden


def expert_products(x: jax.Array, gates: jax.Array, w_up: jax.Array,
                    w_down: jax.Array, *, b_up: Optional[jax.Array] = None,
                    b_down: Optional[jax.Array] = None,
                    act: Callable = relu2, gated: bool = False,
                    gather: bool = False, top: Optional[int] = None,
                    rows: int = GROUP_ROWS) -> jax.Array:
    """sum_e gates[:, e] * (act(x W_up[e] + b_up[e]) W_down[e] + b_down[e])
    over the E' experts of ``gates``: x (N, D), gates (N, E'), w_up
    (E', D, F), w_down (E', F, D) -> (N, D) f32. ``gated``: w_up is
    (E', D, 2F), ``[W_gate | W_up]``, and the hidden layer is
    ``act(x W_gate) * (x W_up)``. Operands in the weights' dtype,
    accumulation in f32. ``top``: the most experts a token chose, where the
    caller knows it; the gathered form then takes many tokens (four times
    ``rows`` and more) expert by expert in groups of ``rows``, each token
    through its own experts only."""
    xw = x.astype(w_up.dtype)
    hidden = _gated(act) if gated else act
    if not gather:
        h = jnp.einsum("nd,edf->enf", xw, w_up,
                       preferred_element_type=jnp.float32)
        if b_up is not None:
            h = h + b_up[:, None, :]
        h = hidden(h) * gates.T[..., None]
        y = jnp.einsum("enf,efd->nd", h.astype(w_down.dtype), w_down,
                       preferred_element_type=jnp.float32)
        if b_down is not None:
            y = y + jnp.dot(gates, b_down.astype(jnp.float32))
        return y
    if b_up is not None or b_down is not None:
        raise ValueError("the gathered form has no biases")
    if top is not None and x.shape[0] >= 4 * rows:
        return _grouped(xw, gates, w_up, w_down, hidden,
                        min(top, gates.shape[1]), rows)
    hit = jnp.any(gates > 0.0, axis=0)                        # (E',)
    order = jnp.argsort(~hit, stable=True)                    # hit ones first

    def one(i, acc):
        e = order[i]
        up = jax.lax.dynamic_index_in_dim(w_up, e, keepdims=False)
        down = jax.lax.dynamic_index_in_dim(w_down, e, keepdims=False)
        g = jax.lax.dynamic_index_in_dim(gates, e, axis=1, keepdims=False)
        h = hidden(jnp.dot(xw, up, preferred_element_type=jnp.float32))
        h = h * g[:, None]
        return acc + jnp.dot(h.astype(down.dtype), down,
                             preferred_element_type=jnp.float32)

    return jax.lax.fori_loop(0, jnp.sum(hit), one,
                             jnp.zeros(x.shape, jnp.float32))


def _grouped(xw: jax.Array, gates: jax.Array, w_up: jax.Array,
             w_down: jax.Array, hidden: Callable, top: int, rows: int
             ) -> jax.Array:
    """The gathered form for many tokens: a token goes through the experts
    it chose and no other. The (token, expert) choices are laid out expert
    after expert in a buffer, each expert's padded to a multiple of ``rows``
    (at most ``N * top / rows + E'`` groups, a static bound; none dropped:
    an expert with more tokens takes more groups), the loop runs over the
    groups that hold a token, reading each expert's weights in place once a
    group, and a token's rows are summed back. With 512 tokens at 4 of 64
    the loop over the experts hit ran all 512 through each of 32 held
    experts, sixteen times the products (PERF.md, PR 34)."""
    n, held = gates.shape
    weight, expert = jax.lax.top_k(gates, top)                # (N, top)
    chosen = weight > 0.0
    chose = gates > 0.0
    groups = -(-jnp.sum(chose, axis=0, dtype=jnp.int32) // rows)      # (E',)
    ends = jnp.cumsum(groups)
    # a token's place among its expert's
    rank = jnp.cumsum(chose, axis=0, dtype=jnp.int32) - 1
    n_rows = (-(-n * top // rows) + held) * rows
    place = jnp.take_along_axis(
        ((ends - groups) * rows)[None, :] + rank, expert, axis=1)
    place = jnp.where(chosen, place, n_rows).reshape(-1)      # (N * top,)
    token = jnp.full((n_rows,), n, jnp.int32).at[place].set(
        jnp.repeat(jnp.arange(n, dtype=jnp.int32), top), mode="drop",
        unique_indices=True)
    scale = jnp.zeros((n_rows,), jnp.float32).at[place].set(
        weight.reshape(-1), mode="drop", unique_indices=True)
    xs = jnp.take(xw, token, axis=0, mode="fill", fill_value=0)

    def one(i, out):
        e = jnp.searchsorted(ends, i, side="right").astype(jnp.int32)
        up = jax.lax.dynamic_index_in_dim(w_up, e, keepdims=False)
        down = jax.lax.dynamic_index_in_dim(w_down, e, keepdims=False)
        at = i * rows
        h = hidden(jnp.dot(jax.lax.dynamic_slice_in_dim(xs, at, rows), up,
                           preferred_element_type=jnp.float32))
        h = h * jax.lax.dynamic_slice_in_dim(scale, at, rows)[:, None]
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.dot(h.astype(down.dtype), down,
                         preferred_element_type=jnp.float32), at, axis=0)

    out = jax.lax.fori_loop(0, ends[-1], one,
                            jnp.zeros((n_rows, xw.shape[1]), jnp.float32))
    back = jnp.take(out, place.reshape(n, top), axis=0, mode="fill",
                    fill_value=0)                             # (N, top, D)
    return jnp.sum(back, axis=1)


def expert_layer(p: Params, x: jax.Array, k: int, *,
                 held: Optional[Tuple[int, int]] = None,
                 score: str = "sigmoid", renorm: bool = True,
                 scale: float = 1.0, act: Callable = relu2,
                 gated: bool = False, live: Optional[jax.Array] = None,
                 gather: bool = False
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The routed part of the layer for the experts held here. ``p``:
    ``router`` (D, E), ``b_corr`` (E,) or absent, ``w_up`` (count, D, F; or
    2F, ``gated``), ``w_down`` (count, F, D), the held experts' own.
    x (N, D) -> (y (N, D) f32, counts): ``expert_tokens`` the (token, held
    expert) choices and ``experts_hit`` the held experts with a token.
    ``live`` (N,) bool takes tokens out of the routing (an idle decode slot
    reads no expert)."""
    n_experts = p["router"].shape[-1]
    first, count = held if held is not None else (0, n_experts)
    gates, _ = route(x, p["router"], k, bias=p.get("b_corr"), score=score,
                     renorm=renorm, scale=scale)
    if live is not None:
        gates = jnp.where(live[:, None], gates, 0.0)
    gates = jax.lax.dynamic_slice_in_dim(gates, first, count, axis=1)
    y = expert_products(x, gates, p["w_up"], p["w_down"], act=act,
                        gated=gated, gather=gather, top=k)
    chosen = gates > 0.0
    return y, {"expert_tokens": jnp.sum(chosen, dtype=jnp.int32),
               "experts_hit": jnp.sum(jnp.any(chosen, axis=0),
                                      dtype=jnp.int32)}


def ffn(x: jax.Array, w_up: jax.Array, w_down: jax.Array,
        act: Callable = relu2, gated: bool = False) -> jax.Array:
    """A plain feed-forward (a shared expert): (N, D) -> f32. ``gated``:
    w_up is ``[W_gate | W_up]`` (D, 2F), as an expert's."""
    h = (_gated(act) if gated else act)(
        jnp.dot(x.astype(w_up.dtype), w_up,
                preferred_element_type=jnp.float32))
    return jnp.dot(h.astype(w_down.dtype), w_down,
                   preferred_element_type=jnp.float32)


# -- the trainer's layer: k = 1, softmax, GELU with biases -------------------

def moe_init(rng: jax.Array, dim: int, hidden: int, n_experts: int) -> Params:
    kr, k1, k2 = jax.random.split(rng, 3)
    std1 = math.sqrt(2.0 / dim)
    std2 = math.sqrt(2.0 / hidden)
    return {
        "router": normal_init(kr, (dim, n_experts), std=0.02),
        "w1": normal_init(k1, (n_experts, dim, hidden), std=std1),
        "b1": jnp.zeros((n_experts, hidden), jnp.float32),
        "w2": normal_init(k2, (n_experts, hidden, dim), std=std2),
        "b2": jnp.zeros((n_experts, dim), jnp.float32),
    }


def moe_partition_specs() -> Params:
    return {
        "router": P(None, None),
        "w1": P("expert", None, "model"),
        "b1": P("expert", "model"),
        "w2": P("expert", "model", None),
        "b2": P("expert", None),
    }


def moe_apply(params: Params, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss): each token through its top-1 expert,
    weighted by the router's softmax probability; none dropped."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d).astype(jnp.float32)
    n_exp = params["router"].shape[-1]
    gates, probs = route(xt, params["router"], 1, score="softmax",
                         renorm=False)
    y = expert_products(xt, gates, params["w1"], params["w2"],
                        b_up=params["b1"], b_down=params["b2"],
                        act=jax.nn.gelu)
    # Switch load-balancing loss: E * sum_e f_e * p_e
    frac_tokens = jnp.mean((gates > 0.0).astype(jnp.float32), axis=0)
    aux = n_exp * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
    return y.reshape(b, s, d).astype(x.dtype), aux
