"""Parallelism stack on the 8-fake-device mesh: GSPMD trainer, ring
attention, pipeline, MoE — the distributed-simulation tests the reference
never had (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from rafiki_tpu.models import core, vit
from rafiki_tpu.ops.attention import mha_reference
from rafiki_tpu.parallel.moe import moe_apply, moe_init
from rafiki_tpu.parallel.pipeline import gpipe_apply
from rafiki_tpu.parallel.ring import ring_attention
from rafiki_tpu.parallel.sharding import (
    GspmdTrainer,
    filter_pspec,
    make_train_mesh,
)


def test_filter_pspec():
    mesh = make_train_mesh(dp=4, tp=2)
    assert filter_pspec(P("data", "model"), mesh) == P("data", "model")
    assert filter_pspec(P("bogus", "model"), mesh) == P(None, "model")
    assert filter_pspec(P(("data", "bogus"), None), mesh) == P(("data",), None)


def test_make_train_mesh_axes():
    mesh = make_train_mesh(dp=2, tp=2, sp=2)
    assert mesh.shape["data"] == 2 and mesh.shape["model"] == 2
    assert mesh.shape["seq"] == 2 and mesh.shape["pipe"] == 1
    with pytest.raises(ValueError):
        make_train_mesh(dp=3, tp=3)


def test_gspmd_vit_step_dp_tp_sp():
    cfg = vit.tiny()
    mesh = make_train_mesh(dp=2, tp=2, sp=2)

    def loss_fn(params, batch, rng):
        x, y = batch
        logits = vit.apply(params, x, cfg, rng, deterministic=False)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        acc = (jnp.argmax(logits, -1) == y).mean()
        return loss, {"acc": acc}

    trainer = GspmdTrainer(
        loss_fn, optax.adamw(1e-3), vit.partition_specs(cfg),
        (vit.batch_spec(), P("data")), mesh)
    params, opt_state = trainer.init(lambda rng: vit.init(rng, cfg))

    # TP sharding really landed on the heads axis
    wq = params["blocks"]["attn"]["wq"]
    assert "model" in wq.sharding.spec

    x = np.random.default_rng(0).normal(size=(8, 32, 32, 3)).astype(np.float32)
    y = np.zeros((8,), np.int32)
    losses = []
    for i in range(3):
        params, opt_state, loss, aux = trainer.step(
            params, opt_state, (x, y), jax.random.key(i))
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]  # it's learning the constant label


def test_ring_attention_matches_reference():
    devs = np.array(jax.devices()).reshape(2, 4)
    mesh = Mesh(devs, ("data", "seq"))
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    shape = (2, 2, 32, 16)  # S=32 over 4 seq shards
    q = jax.random.normal(k1, shape)
    k = jax.random.normal(k2, shape)
    v = jax.random.normal(k3, shape)
    for causal in (False, True):
        out = ring_attention(q, k, v, mesh, causal=causal)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_ring_attention_differentiable():
    devs = np.array(jax.devices()).reshape(1, 8)
    mesh = Mesh(devs, ("data", "seq"))
    q = jax.random.normal(jax.random.key(0), (1, 1, 16, 8))

    def loss(q):
        return jnp.sum(ring_attention(q, q, q, mesh, causal=True) ** 2)

    def loss_ref(q):
        return jnp.sum(mha_reference(q, q, q, causal=True) ** 2)

    g = jax.grad(loss)(q)
    g_ref = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)


def test_gpipe_matches_sequential():
    mesh = Mesh(np.array(jax.devices()), ("pipe",))  # 8 stages
    depth, dim, batch = 8, 16, 8
    keys = jax.random.split(jax.random.key(0), depth)
    stacked = core.stack_layers(
        [core.dense_init(k, dim, dim) for k in keys])

    def block_fn(layer, x):
        return jnp.tanh(core.dense(layer, x))

    x = jax.random.normal(jax.random.key(1), (batch, dim))
    y_pipe = gpipe_apply(block_fn, stacked, x, mesh, n_microbatches=4)

    def seq_apply(x):
        def body(h, layer):
            return block_fn(layer, h), None
        h, _ = jax.lax.scan(body, x, stacked)
        return h

    np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(seq_apply(x)),
                               atol=1e-5, rtol=1e-5)


def test_gpipe_differentiable():
    mesh = Mesh(np.array(jax.devices()[:4]), ("pipe",))
    depth, dim = 4, 8
    keys = jax.random.split(jax.random.key(0), depth)
    stacked = core.stack_layers([core.dense_init(k, dim, dim) for k in keys])

    def block_fn(layer, x):
        return jnp.tanh(core.dense(layer, x))

    x = jax.random.normal(jax.random.key(1), (4, dim))

    def loss(p):
        return jnp.sum(gpipe_apply(block_fn, p, x, mesh, 2) ** 2)

    g = jax.grad(loss)(stacked)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))
    assert max(np.abs(np.asarray(l)).max() for l in jax.tree.leaves(g)) > 0


def test_moe_single_expert_equals_dense():
    dim, hidden = 8, 16
    params = moe_init(jax.random.key(0), dim, hidden, n_experts=1)
    x = jax.random.normal(jax.random.key(1), (2, 4, dim))
    y, aux = moe_apply(params, x)
    # with one expert the gate is 1 and MoE reduces to its dense FFN
    xt = x.reshape(-1, dim).astype(jnp.float32)
    href = jax.nn.gelu(xt @ params["w1"][0] + params["b1"][0])
    yref = (href @ params["w2"][0] + params["b2"][0]).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(aux), 1.0, atol=1e-5)


def test_moe_top1_drops_no_token():
    """The k = 1 case of the drop-free layer: with every token sent to one
    expert (which a capacity of 2 of 8 used to cut), every token comes out
    as that expert's feed-forward weighted by its gate."""
    dim, hidden, n_exp = 4, 8, 2
    params = moe_init(jax.random.key(0), dim, hidden, n_exp)
    # positive inputs + this router force every token to expert 0
    params["router"] = jnp.array([[10.0, -10.0]] * dim)
    x = jnp.abs(jax.random.normal(jax.random.key(1), (1, 8, dim))) + 0.1
    y, aux = moe_apply(params, x)
    n_nonzero = int(jnp.sum(jnp.any(jnp.abs(y[0]) > 1e-9, axis=-1)))
    assert n_nonzero == 8
    href = jax.nn.gelu(x[0] @ params["w1"][0] + params["b1"][0])
    yref = href @ params["w2"][0] + params["b2"][0]
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(yref),
                               atol=1e-4, rtol=1e-4)
    # all tokens on one of two experts: the balance loss is at its worst
    np.testing.assert_allclose(float(aux), 2.0, atol=1e-3)


def test_gpipe_streamed_input_matches_sequential():
    # M % n_stages == 0 takes the sharded-input streaming path (O(B/n)
    # input HBM per stage); must agree with the sequential reference and
    # stay differentiable
    mesh = Mesh(np.array(jax.devices()[:4]), ("pipe",))
    depth, dim, batch = 4, 16, 16
    keys = jax.random.split(jax.random.key(0), depth)
    stacked = core.stack_layers([core.dense_init(k, dim, dim) for k in keys])

    def block_fn(layer, x):
        return jnp.tanh(core.dense(layer, x))

    x = jax.random.normal(jax.random.key(1), (batch, dim))
    y_pipe = gpipe_apply(block_fn, stacked, x, mesh, n_microbatches=8)

    def seq_apply(x):
        def body(h, layer):
            return block_fn(layer, h), None
        h, _ = jax.lax.scan(body, x, stacked)
        return h

    np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(seq_apply(x)),
                               atol=1e-5, rtol=1e-5)

    def loss(p):
        return jnp.sum(gpipe_apply(block_fn, p, x, mesh, 8) ** 2)

    g = jax.grad(loss)(stacked)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))

def test_gpipe_nondividing_microbatches_pad_and_stream():
    # M not a multiple of the stage count: the queue pads up to M' but the
    # schedule stays M + n - 1 — outputs and grads must match sequential
    # exactly (VERDICT r3: the replicated-input fallback is gone; padding
    # keeps input HBM at O(B/n) for every M)
    mesh = Mesh(np.array(jax.devices()[:4]), ("pipe",))
    depth, dim = 4, 16
    keys = jax.random.split(jax.random.key(0), depth)
    stacked = core.stack_layers([core.dense_init(k, dim, dim) for k in keys])

    def block_fn(layer, x):
        return jnp.tanh(core.dense(layer, x))

    def seq_apply(x):
        def body(h, layer):
            return block_fn(layer, h), None
        h, _ = jax.lax.scan(body, x, stacked)
        return h

    for m in (3, 5, 7):  # none divide 4
        batch = 2 * m
        x = jax.random.normal(jax.random.key(m), (batch, dim))
        y_pipe = gpipe_apply(block_fn, stacked, x, mesh, n_microbatches=m)
        np.testing.assert_allclose(
            np.asarray(y_pipe), np.asarray(seq_apply(x)),
            atol=1e-5, rtol=1e-5)

    x = jax.random.normal(jax.random.key(9), (6, dim))

    def loss(p):
        return jnp.sum(gpipe_apply(block_fn, p, x, mesh, 3) ** 2)

    def loss_seq(p):
        def body(h, layer):
            return block_fn(layer, h), None
        h, _ = jax.lax.scan(body, x, p)
        return jnp.sum(h ** 2)

    g = jax.grad(loss)(stacked)
    g_ref = jax.grad(loss_seq)(stacked)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
