"""ViT-B/16, plain: the forward pass, the loss, its gradient and AdamW in
straightforward float32 `jax.numpy` at `highest` matmul precision, written
from the published description (Dosovitskiy et al. 2020, table 1 and section
3.1; `google/vit-base-patch16-224` config.json). Imports nothing of the
program and takes nothing the program made: the weights come from the seed
by the recipe below, which the template repeats.

Departures from the published model, all of them `models/vit.py`'s own and
kept here because the reference has to compute what the program claims to:
global average pooling over the patch tokens in place of a class token; no
bias on the query, key and value projections; tanh-approximated GELU;
LayerNorm epsilon 1e-6 (published: 1e-12). Weights are normal draws with the
usual fan-in/fan-out variances, not a trained checkpoint.

`quant="fp8"` is the control, the step below the bfloat16 the configuration
states: whatever the program holds in bfloat16 (both operands of every
matrix product, and the activations a block hands on) is rounded to float8
on a per-tensor scale, e4m3 on the way forward and its gradient e5m2 on the
way back, as float8 training does.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import weights

ADAM_B1, ADAM_B2, ADAM_EPS, WEIGHT_DECAY = 0.9, 0.999, 1e-8, 1e-4


def weight_spec(cfg: dict) -> list:
    """(name, shape, std) in the order the recipe draws them; std is a
    number, or 'ones' / 'zeros'."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dh, f, n = d // h, cfg["intermediate_size"], cfg["num_hidden_layers"]
    p, c, k = cfg["patch_size"], cfg["num_channels"], cfg["num_labels"]
    s = (cfg["image_size"] // p) ** 2
    xav = lambda a, b: math.sqrt(2.0 / (a + b))
    return [
        ("patch.kernel", (p, p, c, d), math.sqrt(2.0 / (p * p * c))),
        ("patch.bias", (d,), "zeros"),
        ("pos", (1, s, d), 0.02),
        ("ln1.scale", (n, d), "ones"), ("ln1.bias", (n, d), "zeros"),
        ("wq", (n, d, h, dh), xav(d, d)), ("wk", (n, d, h, dh), xav(d, d)),
        ("wv", (n, d, h, dh), xav(d, d)), ("wo", (n, h, dh, d), xav(d, d)),
        ("bo", (n, d), "zeros"),
        ("ln2.scale", (n, d), "ones"), ("ln2.bias", (n, d), "zeros"),
        ("w1.kernel", (n, d, f), xav(d, f)), ("w1.bias", (n, f), "zeros"),
        ("w2.kernel", (n, f, d), xav(f, d)), ("w2.bias", (n, d), "zeros"),
        ("ln_f.scale", (d,), "ones"), ("ln_f.bias", (d,), "zeros"),
        ("head.kernel", (d, k), xav(d, k)), ("head.bias", (k,), "zeros"),
    ]


def make_weights(seed: int, cfg: dict) -> dict:
    return weights.make(seed, weight_spec(cfg))


def _round_to(x, dtype, largest):
    """Round to a float8 format with the tensor's largest value at the
    format's largest."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / largest
    return (x / scale).astype(dtype).astype(x.dtype) * scale


@jax.custom_vjp
def _fp8(x):
    """What float8 training holds: the value in e4m3 on the way forward, its
    gradient in e5m2 on the way back, each on a per-tensor scale."""
    return _round_to(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round_to(g, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _layernorm(x, scale, bias, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def forward(w: dict, images, cfg: dict, quant: str | None = None):
    """images (B, H, W, C) float32 -> logits (B, num_labels)."""
    q = held = _fp8 if quant == "fp8" else (lambda a: a)
    p = cfg["patch_size"]
    dh = cfg["hidden_size"] // cfg["num_attention_heads"]
    x = jax.lax.conv_general_dilated(
        q(images), q(w["patch.kernel"]), (p, p), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + w["patch.bias"]
    x = x.reshape(x.shape[0], -1, cfg["hidden_size"]) + w["pos"]
    names = ("ln1.scale", "ln1.bias", "wq", "wk", "wv", "wo", "bo",
             "ln2.scale", "ln2.bias", "w1.kernel", "w1.bias", "w2.kernel",
             "w2.bias")

    @jax.checkpoint  # keep only each block's input: float32 has to fit
    def block(x, layer):
        (g1, b1, wq, wk, wv, wo, bo, g2, b2, k1, c1, k2, c2) = layer
        h = q(_layernorm(x, g1, b1))
        qh = jnp.einsum("bsd,dhk->bhsk", h, q(wq))
        kh = jnp.einsum("bsd,dhk->bhsk", h, q(wk))
        vh = jnp.einsum("bsd,dhk->bhsk", h, q(wv))
        s = jnp.einsum("bhqk,bhlk->bhql", q(qh), q(kh)) / math.sqrt(dh)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhql,bhlk->bhqk", q(a), q(vh))
        x = held(x + jnp.einsum("bhsk,hkd->bsd", q(o), q(wo)) + bo)
        h = q(_layernorm(x, g2, b2))
        h = jax.nn.gelu(jnp.dot(h, q(k1)) + c1, approximate=True)
        return held(x + jnp.dot(q(h), q(k2)) + c2), None

    x, _ = jax.lax.scan(block, x, tuple(w[n] for n in names))
    x = _layernorm(x, w["ln_f.scale"], w["ln_f.bias"])
    x = jnp.mean(x, axis=1)
    return jnp.dot(q(x), q(w["head.kernel"])) + w["head.bias"]


def loss_fn(w, x, y, cfg, quant=None, rows=None):
    """Mean softmax cross-entropy over the batch; `rows` keeps only the
    first so many rows (the half-batch fault)."""
    if rows is not None:
        x, y = x[:rows], y[:rows]
    logits = forward(w, x, cfg, quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def adamw_step(w, m, v, t, g, lr):
    """optax.adamw's update with its defaults, written out."""
    t = t + 1
    m = jax.tree.map(lambda m_, g_: ADAM_B1 * m_ + (1 - ADAM_B1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: ADAM_B2 * v_ + (1 - ADAM_B2) * g_ * g_,
                     v, g)
    c1 = 1 - ADAM_B1 ** t.astype(jnp.float32)
    c2 = 1 - ADAM_B2 ** t.astype(jnp.float32)
    w = jax.tree.map(
        lambda w_, m_, v_: w_ - lr * (
            (m_ / c1) / (jnp.sqrt(v_ / c2) + ADAM_EPS) + WEIGHT_DECAY * w_),
        w, m, v)
    return w, m, v, t


def epoch_order(data_seed: int, epoch: int, n: int, batch: int):
    """The trainer's batches of one epoch: a permutation drawn from
    `default_rng([seed, epoch])`, cut into whole batches."""
    perm = np.random.default_rng([data_seed, epoch]).permutation(n)
    return perm[:(n // batch) * batch].reshape(-1, batch)


def train(seed: int, cfg: dict, x, y, lr: float, batch: int, epochs: int,
          quant: str | None = None, fault: str | None = None) -> dict:
    """Carry the trial the program ran: the weights from `seed`, the
    trainer's data order, `epochs` epochs of AdamW at `lr`. Returns each
    epoch's mean loss, every step's loss, the first gradient's norm by leaf
    and the change of each leaf over the trial (its norm)."""
    rows = batch // 2 if fault == "half_batch" else None
    with jax.default_matmul_precision("highest"):
        w0 = make_weights(seed, cfg)
        x_dev = jnp.asarray(x, jnp.float32)
        y_dev = jnp.asarray(y, jnp.int32)
        grad = jax.value_and_grad(
            lambda w, xb, yb: loss_fn(w, xb, yb, cfg, quant, rows))

        @jax.jit
        def run_epoch(w, m, v, t, x_dev, y_dev, idx, lr):
            def body(carry, ix):
                w, m, v, t = carry
                value, g = grad(w, jnp.take(x_dev, ix, axis=0),
                                jnp.take(y_dev, ix, axis=0))
                gn = jax.tree.map(lambda a: jnp.sqrt(jnp.sum(a * a)), g)
                if fault != "frozen":
                    w, m, v, t = adamw_step(w, m, v, t, g, lr)
                return (w, m, v, t), (value, gn)

            (w, m, v, t), (losses, gns) = jax.lax.scan(
                body, (w, m, v, t), idx)
            return w, m, v, t, losses, jax.tree.map(lambda a: a[0], gns)

        w = w0
        m = jax.tree.map(jnp.zeros_like, w)
        v = jax.tree.map(jnp.zeros_like, w)
        t = jnp.zeros((), jnp.int32)
        step_losses, first_grad = [], None
        for epoch in range(epochs):
            idx = jnp.asarray(epoch_order(seed, epoch, len(x), batch),
                              jnp.int32)
            w, m, v, t, losses, gn = run_epoch(w, m, v, t, x_dev, y_dev, idx,
                                               jnp.float32(lr))
            step_losses.append(np.asarray(losses))
            if first_grad is None:
                first_grad = {k: float(a) for k, a in gn.items()}
        change = {k: float(jnp.sqrt(jnp.sum((w[k] - w0[k]) ** 2)))
                  for k in w}
    step_losses = np.stack(step_losses)
    return {"epoch_losses": [float(a) for a in step_losses.mean(axis=1)],
            "step_losses": step_losses, "first_grad_norm": first_grad,
            "change_norm": change, "params": w, "params0": w0}
