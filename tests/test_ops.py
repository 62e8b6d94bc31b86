"""Flash-attention kernel vs XLA reference (runs interpreted on the CPU
test mesh, compiled on real TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rafiki_tpu.ops import flash_attention, mha_reference


def _qkv(rng, b=2, h=2, s=48, dh=16):
    ks = jax.random.split(jax.random.key(rng), 3)
    shape = (b, h, s, dh)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv(0)
    out = flash_attention(q, k, v, causal, None, 16, 16, True)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_padded_seq():
    # S=40 not a multiple of the 16-block: exercises the kv_len mask
    q, k, v = _qkv(1, s=40)
    out = flash_attention(q, k, v, False, None, 16, 16, True)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_causal_cross_length():
    # decode shape: sq != skv must use the end-aligned mask (tril k=skv-sq),
    # i.e. a single trailing query attends ALL keys
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (1, 2, 4, 16))
    k = jax.random.normal(ks[1], (1, 2, 32, 16))
    v = jax.random.normal(ks[2], (1, 2, 32, 16))
    out = flash_attention(q, k, v, True, None, 16, 16, True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_gradients_match_reference():
    q, k, v = _qkv(2, b=1, h=1, s=32, dh=8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 16, 16, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_flash_causal_dead_rows():
    """Causal with kv_len < q_len: rows attending zero keys must output
    exactly 0 and contribute nothing to dk/dv (regression: fully-masked
    rows inside a partially-live q block once got p = exp(0) = 1)."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 32, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 16, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 16, 16))
    out = flash_attention(q, k, v, True, None, 32, 16, True)
    ref = mha_reference(q, k, v, causal=True)
    # rows 0..15 see no keys (end-aligned causal): ours are exactly zero
    assert float(jnp.abs(out[:, :, :16]).max()) == 0.0
    assert float(jnp.abs(out[:, :, 16:] - ref[:, :, 16:]).max()) < 2e-2
    g = jax.grad(lambda a, b, c: flash_attention(
        a, b, c, True, None, 32, 16, True)[:, :, 16:].sum())(q, k, v)
    gr = jax.grad(lambda a, b, c: mha_reference(
        a, b, c, causal=True)[:, :, 16:].sum())(q, k, v)
    for x, y in zip(g, gr):
        assert float(jnp.abs(x - y).max()) < 5e-2
