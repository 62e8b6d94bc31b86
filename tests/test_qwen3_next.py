"""The gated delta rule (ops/gated_delta.py), gated rotary attention
(ops/attention.py) and gated softmax-routed experts with a gated shared
expert (parallel/moe.py) as layer kinds of models/lm.py's hybrid stack,
against their plain reference (benchmark/reference/qwen3_next.py) at a tiny
size: every published layer is two entries of the program's pattern, a
mixer (`D` or `G`) and then `E`.

The program's weights here are float32 (the reference's bfloat16-rounded
values, widened), so that program and reference differ by summation order
alone and no near tie of the router separates them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as ref
from rafiki_tpu.models import lm
from rafiki_tpu.ops.attention import rotary
from rafiki_tpu.ops.gated_delta import (GatedDeltaConfig, gated_delta_init,
                                        gated_delta_mixer,
                                        gated_delta_state_init)
from rafiki_tpu.parallel import moe


def _cfg(layers=4, every=4, share=(0, 4, 16)):
    return {"hidden_size": 64, "vocab_size": 256, "rms_norm_eps": 1e-6,
            "num_hidden_layers": layers, "full_attention_interval": every,
            "linear_num_key_heads": 2, "linear_key_head_dim": 16,
            "linear_num_value_heads": 4, "linear_value_head_dim": 16,
            "linear_conv_kernel_dim": 4, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 32,
            "partial_rotary_factor": 0.25, "rope_theta": 1e7,
            "expert_share": dict(zip(("first", "count", "of"), share)),
            "num_experts_per_tok": 3, "moe_intermediate_size": 32,
            "shared_expert_intermediate_size": 32}


def _program(cfg, chunk=8):
    z = ref.sizes(cfg)
    return lm.HybridConfig(
        vocab=z["vocab"], max_len=128, dim=z["dim"],
        pattern="".join(kind + "E" for kind in z["kinds"]),
        delta=GatedDeltaConfig(dim=z["dim"], key_heads=z["kh"],
                               value_heads=z["vh"], key_dim=z["kd"],
                               value_dim=z["vd"], conv_kernel=z["conv_k"],
                               chunk_size=chunk, eps=z["eps"]),
        q_heads=z["q_heads"], kv_heads=z["kv_heads"], head_dim=z["hd"],
        rotary_dim=z["rotary"], rope_theta=z["theta"],
        n_experts=z["experts"], top_k=z["top_k"], ffn=z["ffn"],
        shared_ffn=z["shared_ffn"], route_score="softmax", route_bias=False,
        route_scale=1.0, expert_act="silu", expert_gated=True,
        shared_gate=True, held=(z["held_first"], z["held"]), eps=z["eps"])


def _expert_params(p):
    """A reference layer's expert block as the program's leaves: the norm
    holds `1 + w`, `W_gate` and `W_up` lie side by side."""
    beside = lambda a, b: jnp.concatenate([p[a], p[b]], axis=-1)
    return {"norm": {"scale": 1.0 + p["norm2"]}, "router": p["router"],
            "w_up": beside("w_gate", "w_up"), "w_down": p["w_down"],
            "s_up": beside("s_gate", "s_up"), "s_down": p["s_down"],
            "s_gate": p["s_w"]}


def _params(w):
    """The reference's weights as the program's tree, widened to float32."""
    wide = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    layers = []
    for p in w["layers"]:
        p = wide(p)
        mixer = {k: p[k] for k in p if k in (
            "w_qkvz", "w_ba", "conv_w", "dt_bias", "A_log", "onorm", "w_out",
            "wq", "wk", "wv", "wo")}
        mixer.update({k: {"scale": 1.0 + p[k]} for k in ("q_norm", "k_norm")
                      if k in p})
        layers += [{"norm": {"scale": 1.0 + p["norm1"]}, **mixer},
                   _expert_params(p)]
    top = wide(w["top"])
    return {"embed": {"table": top["embed"]}, "head": top["head"],
            "norm_f": {"scale": 1.0 + top["norm_f"]},
            "layers": lm.hybrid_layers(layers)}


def _reference(w, ids, cfg):
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits_at(w, jnp.asarray(ids), pos, cfg))


@pytest.mark.parametrize("layers,every", [(1, 4), (1, 1), (4, 4)],
                         ids=["DE", "GE", "DEDEDEGE"])
def test_each_new_layer_kind_against_the_reference(layers, every):
    cfg = _cfg(layers, every)
    hc = _program(cfg)
    w = ref.make_weights(3, cfg)
    # the recipe draws the zero-centred norms off zero: `1 + w` is tested
    assert float(jnp.abs(w["layers"][0]["norm1"]).max()) > 0.05
    ids = np.random.default_rng(0).integers(0, 256, size=(2, 40))
    got = np.asarray(lm.hybrid_apply(_params(w), ids, hc))
    want = _reference(w, ids, cfg)
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < 2e-3


def test_prefill_then_decode_against_the_full_forward():
    """Chunks of 16 (two chunks of the rule each) into the paged pool and
    the slot's state, then decode rounds with idle rows beside the live
    one, against the reference's full forward pass: logits, not tokens."""
    cfg = _cfg()
    hc = _program(cfg)
    w = ref.make_weights(5, cfg)
    params = _params(w)
    ids = np.random.default_rng(1).integers(0, 256, size=(1, 40))
    want = _reference(w, ids, cfg)[0]
    cache = lm.init_hybrid_cache(hc, 16, 8, 3, kv_dtype=jnp.float32)
    assert set(cache) == {"k", "v", "delta_conv", "delta_s"}
    assert cache["k"].shape[0] == 1 and cache["delta_s"].shape[:2] == (3, 3)
    assert lm.hybrid_state_bytes(cache) == (
        cache["delta_conv"].nbytes + cache["delta_s"].nbytes) > 0
    table = np.arange(16, dtype=np.int32)
    for start in (0, 16):
        logits, cache = lm.hybrid_paged_prefill(
            params, cache, table, ids[0, start:start + 16], start, 16, 1, hc)
    assert np.abs(np.asarray(logits) - want[31]).max() < 2e-3
    idle = np.full(16, 16, np.int32)
    tables = np.stack([idle, table, idle])
    before = np.asarray(cache["delta_s"][:, 0])
    for t in range(32, 38):
        logits, cache, counts = lm.hybrid_paged_decode_step(
            params, cache, np.array([9, ids[0, t], 9]), np.array([0, t, 0]),
            tables, hc)
        assert np.abs(np.asarray(logits[1]) - want[t]).max() < 2e-3
        assert int(counts["expert_layers"]) == 4
        assert int(counts["expert_tokens"]) <= 3 * 4  # the live row alone
    assert np.array_equal(np.asarray(cache["delta_s"][:, 0]), before)


def test_a_pattern_may_hold_every_kind_and_the_cache_a_group_each():
    """Mamba and the delta rule side by side, both attentions in one pool:
    prefill in two chunks then decode track the full forward."""
    from rafiki_tpu.ops.mamba2 import Mamba2Config

    hc = lm.HybridConfig(
        vocab=64, max_len=64, dim=32, pattern="MED*GE",
        mamba=Mamba2Config(dim=32, heads=4, head_dim=8, groups=2, state=8,
                           chunk_size=4),
        delta=GatedDeltaConfig(dim=32, key_heads=2, value_heads=4, key_dim=8,
                               value_dim=8, chunk_size=4),
        q_heads=4, kv_heads=2, head_dim=8, rotary_dim=4, n_experts=8,
        top_k=2, ffn=16, shared_ffn=16, held=(0, 8))
    params = lm.hybrid_init(jax.random.key(0), hc, dtype=jnp.float32)
    ids = np.random.default_rng(2).integers(0, 64, size=(1, 20))
    full = np.asarray(lm.hybrid_apply(params, ids, hc))[0]
    cache = lm.init_hybrid_cache(hc, 8, 8, 2, kv_dtype=jnp.float32)
    assert set(cache) == {"k", "v", "conv", "h", "delta_conv", "delta_s"}
    assert cache["k"].shape[0] == 2  # `*` and `G` share the pool
    table = np.arange(8, dtype=np.int32)
    for start in (0, 8):
        logits, cache = lm.hybrid_paged_prefill(
            params, cache, table, ids[0, start:start + 8], start, 8, 0, hc)
    np.testing.assert_allclose(np.asarray(logits), full[15], atol=2e-4)
    logits, cache, _ = lm.hybrid_paged_decode_step(
        params, cache, np.array([ids[0, 16], 0]), np.array([16, 0]),
        np.stack([table, np.full(8, 8, np.int32)]), hc)
    np.testing.assert_allclose(np.asarray(logits[0]), full[16], atol=2e-4)


def test_the_delta_rule_in_chunks_and_in_single_steps_is_one_recurrence():
    """64 tokens at once (chunks of 8), in two calls of 32 with the state
    handed over, and one token at a time: the same outputs and final state;
    ragged lengths, among them one whose last chunk ends inside the pad,
    move no state past their end."""
    cfg = GatedDeltaConfig(dim=32, key_heads=2, value_heads=4, key_dim=8,
                           value_dim=8, chunk_size=8)
    p = gated_delta_init(jax.random.key(0), cfg)
    u = jax.random.normal(jax.random.key(1), (2, 64, 32))
    zero = lambda: gated_delta_state_init(cfg, 2)
    full = jnp.full((2,), 64, jnp.int32)
    y, st = gated_delta_mixer(p, u, zero(), full, cfg)
    half = jnp.full((2,), 32, jnp.int32)
    y1, s1 = gated_delta_mixer(p, u[:, :32], zero(), half, cfg)
    y2, s2 = gated_delta_mixer(p, u[:, 32:], s1, half, cfg)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jnp.concatenate([y1, y2], 1)),
                               atol=2e-5)
    state, ys = zero(), []
    for t in range(64):
        yt, state = gated_delta_mixer(p, u[:, t:t + 1], state,
                                      jnp.ones((2,), jnp.int32), cfg)
        ys.append(yt)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jnp.concatenate(ys, 1)), atol=2e-5)
    assert float(jnp.abs(st["s"]).max()) > 0.01
    for name in ("conv", "s"):
        np.testing.assert_allclose(np.asarray(st[name]),
                                   np.asarray(s2[name]), atol=2e-5)
        np.testing.assert_allclose(np.asarray(st[name]),
                                   np.asarray(state[name]), atol=2e-5)
    # 43 and 16 real tokens of 64: the states after 43 and after 16, the
    # first ending three tokens into a chunk, and the outputs up to there
    ragged = jnp.array([43, 16], jnp.int32)
    yr, padded = gated_delta_mixer(p, u, zero(), ragged, cfg)
    for row, n in enumerate((43, 16)):
        yn, sn = gated_delta_mixer(p, u[row:row + 1, :n],
                                   gated_delta_state_init(cfg, 1),
                                   jnp.array([n], jnp.int32), cfg)
        np.testing.assert_allclose(np.asarray(yr[row, :n]),
                                   np.asarray(yn[0]), atol=2e-5)
        for name in ("conv", "s"):
            np.testing.assert_allclose(np.asarray(padded[name][row]),
                                       np.asarray(sn[name][0]), atol=2e-5)
    # a sequence that sits a call out (length 0) keeps its state
    _, kept = gated_delta_mixer(p, u[:, :8], st, jnp.array([0, 8]), cfg)
    assert np.array_equal(np.asarray(kept["s"][0]), np.asarray(st["s"][0]))
    assert not np.array_equal(np.asarray(kept["s"][1]),
                              np.asarray(st["s"][1]))


def test_partial_rotary_turns_the_first_of_a_head_by_relative_position():
    """The last 24 of a head of 32 pass; the turned 8 keep their length and
    their products depend on the distance between positions alone; and the
    model reads positions nowhere else: with no rotary dimension the
    attention layer's answer at a position is the same wherever in the
    table the sequence begins."""
    x = jax.random.normal(jax.random.key(0), (2, 5, 3, 32))
    pos = jnp.array([[0, 1, 2, 3, 4], [7, 8, 9, 10, 4000]])
    y = rotary(x, pos, 8, 1e7)
    assert np.array_equal(np.asarray(y[..., 8:]), np.asarray(x[..., 8:]))
    assert np.array_equal(np.asarray(y[0, 0]), np.asarray(x[0, 0]))  # at 0
    assert float(jnp.abs(y[1, :, :, :8] - x[1, :, :, :8]).max()) > 0.1
    np.testing.assert_allclose(
        np.asarray(jnp.sum(y[..., :8] ** 2, -1)),
        np.asarray(jnp.sum(x[..., :8] ** 2, -1)), rtol=1e-5)
    q, k = x[:1, :1], x[1:, :1]
    dot = lambda m, n: float(jnp.sum(
        rotary(q, jnp.array([[m]]), 8, 1e7)
        * rotary(k, jnp.array([[n]]), 8, 1e7)))
    assert dot(7, 3) == pytest.approx(dot(3004, 3000), abs=1e-4)
    assert abs(dot(7, 3) - dot(7, 5)) > 1e-3
    # the pair is (i, i + 4) of the first 8, at theta^(-2i/8)
    one = jnp.zeros((1, 1, 1, 32)).at[..., 1].set(1.0)
    turned = np.asarray(rotary(one, jnp.array([[2]]), 8, 1e7))[0, 0, 0]
    angle = 2 * 1e7 ** (-2 / 8)
    np.testing.assert_allclose(turned[[1, 5]], [np.cos(angle), np.sin(angle)],
                               rtol=1e-5)
    assert np.count_nonzero(turned) == 2


@pytest.mark.parametrize("rotary_dim", [0, 8])
def test_positions_enter_through_the_rotary_dimensions_alone(rotary_dim):
    """One attention layer over a sequence and over the same sequence with
    its earlier tokens in another order: attention is a sum over the rows
    before, so with no rotary dimension the last token's logits are the
    same, and with some they are not. Positions enter nowhere else."""
    hc = lm.HybridConfig(vocab=64, max_len=32, dim=32, pattern="G",
                         q_heads=4, kv_heads=2, head_dim=16,
                         rotary_dim=rotary_dim, eps=1e-6)
    params = lm.hybrid_init(jax.random.key(1), hc, dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(0, 64, size=12)
    other = np.concatenate([ids[:11][::-1], ids[11:]])
    last = lambda seq: np.asarray(
        lm.hybrid_apply(params, seq[None], hc))[0, -1]
    gap = np.abs(last(ids) - last(other)).max()
    assert gap < 1e-5 if rotary_dim == 0 else gap > 1e-3


@pytest.mark.parametrize("count", [4, 16])
def test_the_shares_and_the_gated_shared_expert_once_are_the_uncut_layer(
        count):
    """Each of the chips that divide a layer routes over all 16 experts and
    computes its own; their routed parts and the gated shared expert
    counted once are the reference's uncut layer. Reference and program
    alike (the gathered loop and the dense product in turn)."""
    whole = _cfg(1, 4, share=(0, 16, 16))
    z = ref.sizes(whole)
    w = ref.make_weights(7, whole)["layers"][0]
    u = jax.random.normal(jax.random.key(1), (3, 10, 64))
    with jax.default_matmul_precision("highest"):
        uncut = ref.moe_layer(w, u, z)
        parts, shared = [], None
        for first in range(0, 16, count):
            zs = {**z, "held_first": first, "held": count}
            cut = {**w, **{name: w[name][first:first + count]
                           for name in ("w_gate", "w_up", "w_down")}}
            routed, shared = ref.moe_parts(cut, u, zs)
            parts.append(routed)
            p32 = jax.tree.map(lambda a: a.astype(jnp.float32),
                               _expert_params(cut))
            got, counts = moe.expert_layer(
                p32, u.reshape(30, 64), 3, held=(first, count),
                score="softmax", act=jax.nn.silu, gated=True,
                gather=bool(first % 8))
            assert np.abs(np.asarray(got).reshape(3, 10, 64)
                          - np.asarray(routed)).max() < 1e-4
            assert 0 < int(counts["experts_hit"]) <= count
        assert np.abs(np.asarray(sum(parts) + shared)
                      - np.asarray(uncut)).max() < 1e-4
        # the program's shared expert, its own gate on it
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), _expert_params(w))
        flat = u.reshape(30, 64)
        mine = moe.ffn(flat, p32["s_up"], p32["s_down"], jax.nn.silu, True) \
            * jax.nn.sigmoid(flat @ p32["s_gate"])
        assert np.abs(np.asarray(mine).reshape(3, 10, 64)
                      - np.asarray(shared)).max() < 1e-4
    assert all(np.abs(np.asarray(part)).max() > 1e-3 for part in parts)


def test_the_expert_layers_variant_comes_from_the_config():
    """One layer, two settings: sigmoid scores with a correction bias,
    scaled, squared ReLU, ungated; softmax scores with none, silu, gated,
    the shared expert under its own gate. The leaves follow the config."""
    base = dict(vocab=64, max_len=32, dim=32, pattern="E", n_experts=8,
                top_k=2, ffn=16, shared_ffn=24, held=(0, 8))
    plain = lm.hybrid_layer_init(jax.random.key(0), "E",
                                 lm.HybridConfig(**base))
    gated = lm.hybrid_layer_init(jax.random.key(0), "E", lm.HybridConfig(
        **base, route_score="softmax", route_bias=False, route_scale=1.0,
        expert_act="silu", expert_gated=True, shared_gate=True))
    assert set(gated) - set(plain) == {"s_gate"}
    assert set(plain) - set(gated) == {"b_corr"}
    assert plain["w_up"].shape == (8, 32, 16)
    assert gated["w_up"].shape == (8, 32, 32)
    assert gated["s_up"].shape == (32, 48) and gated["s_gate"].shape == (32, 1)
    with pytest.raises(ValueError, match="unknown layer kind"):
        lm.hybrid_layer_init(jax.random.key(0), "Q", lm.HybridConfig(**base))
