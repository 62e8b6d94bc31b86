"""The `glm4_moe_lite` kinds of the hybrid stack (models/lm.py: `L` latent
attention over a paged pool of latent rows, ops/mla.py; `F` a dense gated
MLP alone; sigmoid-routed gated experts with an ungated shared one) against
their plain reference (benchmark/reference/glm4_moe_lite.py, the
non-absorbed form) at a tiny size, and the stateless half of the hybrid
stack's generation contract through the worker
(tests/fixtures/hybrid_gen_model.py `TinyLatentLM`): no slot, no slot count,
and a prefix cache that serves its prompts.

The program's weights here are float32 (the reference's bfloat16-rounded
values, widened), so that program and reference differ by summation order
alone and no near tie of the router separates them.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import glm4_moe_lite as ref
from rafiki_tpu.models import lm
from rafiki_tpu.ops import mla
from rafiki_tpu.parallel import moe
from tests.test_hybrid_lm import (_drain, _model, _start_worker, _stream,
                                  _total)


def _cfg(layers=3, share=(0, 4, 8), dense=1):
    return {"hidden_size": 64, "vocab_size": 256, "rms_norm_eps": 1e-5,
            "num_hidden_layers": layers, "first_k_dense_replace": dense,
            "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
            "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
            "partial_rotary_factor": 1, "v_head_dim": 16, "rope_theta": 1e6,
            "intermediate_size": 96,
            "expert_share": dict(zip(("first", "count", "of"), share)),
            "num_experts_per_tok": 2, "moe_intermediate_size": 32,
            "n_shared_experts": 1, "routed_scaling_factor": 1.8}


def _mla_cfg(z):
    return mla.MLAConfig(
        dim=z["dim"], heads=z["heads"], q_rank=z["q_rank"],
        kv_rank=z["kv_rank"], nope_dim=z["nope"], rope_dim=z["rope"],
        v_dim=z["v"], rope_theta=z["theta"], eps=z["eps"])


def _program(cfg, pattern=None):
    z = ref.sizes(cfg)
    return lm.HybridConfig(
        vocab=z["vocab"], max_len=128, dim=z["dim"],
        pattern=pattern or "".join(
            "L" + ref.kind_of(l, z) for l in range(z["layers"])),
        mla=_mla_cfg(z), n_experts=z["experts"], top_k=z["top_k"],
        ffn=z["ffn"], shared_ffn=z["shared_ffn"], dense_ffn=z["dense_ffn"],
        route_score="sigmoid", route_bias=True, route_scale=z["scale"],
        expert_act="silu", expert_gated=True,
        held=(z["held_first"], z["held"]), eps=z["eps"])


def _wide(a):
    return a.astype(jnp.float32)


def _beside(a, b):
    return jnp.concatenate([_wide(a), _wide(b)], axis=-1)


def _latent_params(p):
    return {"norm": {"scale": p["norm1"]}, "w_dq": _wide(p["w_dq"]),
            "q_norm": {"scale": p["q_norm"]}, "w_uq": _wide(p["w_uq"]),
            "w_dkv": _wide(p["w_dkv"]), "kv_norm": {"scale": p["kv_norm"]},
            "w_ukv": _wide(p["w_ukv"]), "wo": _wide(p["wo"])}


def _forward_params(p, kind):
    out = {"norm": {"scale": p["norm2"]},
           "w_up": _beside(p["w_gate"], p["w_up"]),
           "w_down": _wide(p["w_down"])}
    if kind == "E":
        out.update(router=p["router"], b_corr=p["b_corr"],
                   s_up=_beside(p["s_gate"], p["s_up"]),
                   s_down=_wide(p["s_down"]))
    return out


def _params(w, cfg):
    """The reference's weights as the program's tree, widened to float32:
    two entries of the pattern a published layer."""
    z = ref.sizes(cfg)
    layers = []
    for l, p in enumerate(w["layers"]):
        layers += [_latent_params(p), _forward_params(p, ref.kind_of(l, z))]
    top = w["top"]
    return {"embed": {"table": _wide(top["embed"])},
            "head": _wide(top["head"]), "norm_f": {"scale": top["norm_f"]},
            "layers": lm.hybrid_layers(layers)}


def _row_bytes(cache):
    """Bytes a token leaves in the pool over all paged groups and layers."""
    blocks, tokens = lm.hybrid_pool_shape(cache)
    return sum(cache[name].nbytes for name in lm.PAGED_GROUPS
               if name in cache) // (blocks * tokens)


def _reference(w, ids, cfg):
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits_at(w, jnp.asarray(ids), pos, cfg))


@pytest.mark.parametrize("form", ["absorbed", "expanded"])
def test_both_forms_of_the_latent_layer_are_the_references(form):
    """ops/mla.py over a view that holds the sequence's own rows, in each
    form, against the reference's non-absorbed layer."""
    cfg = _cfg()
    z = ref.sizes(cfg)
    mc = _mla_cfg(z)
    p = ref.make_weights(4, cfg)["layers"][1]
    u = jax.random.normal(jax.random.key(1), (2, 24, 64), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.latent_attention(p, u, z))
        mine = _latent_params(p)
        q_nope, q_rope, rows = mla.mla_project(mine, u, pos, mc)
        assert rows.shape == (2, 24, mc.row) and mc.row == 16 + 8
        view = jnp.zeros((2, 32, mc.row)).at[:, :24].set(rows)
        got = np.asarray(mla.mla_attend(mine, q_nope, q_rope, view, pos, mc,
                                        form))
    assert np.abs(want).max() > 0.05
    assert np.abs(got - want).max() < 2e-5


def test_the_cheaper_form_is_read_off_the_shapes():
    """At the published widths one query a sequence is absorbed and a chunk
    of 512 expanded; the two cost the same near 398 queries."""
    mc = mla.MLAConfig(dim=2048, heads=20, q_rank=768, kv_rank=512,
                       nope_dim=192, rope_dim=64, v_dim=256)
    assert mc.row == 576
    assert mla.cheaper_form(1, mc) == mla.cheaper_form(8, mc) == "absorbed"
    assert mla.cheaper_form(512, mc) == "expanded"
    assert mla.cheaper_form(398, mc) == "absorbed"
    assert mla.cheaper_form(399, mc) == "expanded"
    with pytest.raises(ValueError):
        mla.mla_attend({"w_ukv": jnp.zeros((512, 20 * 448))},
                       jnp.zeros((1, 1, 20, 192)), jnp.zeros((1, 1, 20, 64)),
                       jnp.zeros((1, 8, 576)), jnp.zeros((1, 1), jnp.int32),
                       mc, form="folded")


@pytest.mark.parametrize("layers,dense", [(1, 1), (1, 0), (3, 1)],
                         ids=["LF", "LE", "LFLELE"])
def test_each_new_layer_kind_against_the_reference(layers, dense):
    cfg = _cfg(layers, dense=dense)
    hc = _program(cfg)
    assert hc.pattern == {(1, 1): "LF", (1, 0): "LE", (3, 1): "LFLELE"}[
        layers, dense]
    w = ref.make_weights(3, cfg)
    # the recipe draws the plain norms off one
    assert float(jnp.abs(w["layers"][0]["kv_norm"] - 1.0).max()) > 0.05
    ids = np.random.default_rng(0).integers(0, 256, size=(2, 40))
    got = np.asarray(lm.hybrid_apply(_params(w, cfg), ids, hc))
    want = _reference(w, ids, cfg)
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < 2e-3


def test_three_chunks_then_decode_through_the_pool_are_the_full_forward():
    """A prompt prefilled in three chunks (the last one short and padded)
    into the latent pool, then decode rounds with idle rows beside the live
    one, against the reference's full forward pass at every served
    position: logits, not tokens. No slot and no slot count anywhere."""
    cfg = _cfg()
    hc = _program(cfg)
    w = ref.make_weights(5, cfg)
    params = _params(w, cfg)
    ids = np.random.default_rng(1).integers(0, 256, size=(1, 48))
    want = _reference(w, ids, cfg)[0]
    cache = lm.init_hybrid_cache(hc, 16, 8, kv_dtype=jnp.float32)
    assert set(cache) == {"latent"}
    assert cache["latent"].shape == (3, 16, 8, 128)  # 24, up to the lanes
    assert lm.hybrid_pool_shape(cache) == (16, 8)
    assert lm.hybrid_state_bytes(cache) == 0
    table = np.arange(16, dtype=np.int32)
    for start, n in ((0, 16), (16, 16), (32, 8)):
        chunk = np.zeros(16, np.int64)
        chunk[:n] = ids[0, start:start + n]
        logits, cache = lm.hybrid_paged_prefill(
            params, cache, table, chunk, start, n, None, hc)
        assert np.abs(np.asarray(logits) - want[start + n - 1]).max() < 2e-3
    idle = np.full(16, 16, np.int32)
    tables = np.stack([idle, table, idle])
    for t in range(40, 48):
        logits, cache, counts = lm.hybrid_paged_decode_step(
            params, cache, np.array([9, ids[0, t], 9]), np.array([0, t, 0]),
            tables, hc)
        assert np.abs(np.asarray(logits[1]) - want[t]).max() < 2e-3
        assert int(counts["expert_layers"]) == 2
        assert int(counts["expert_tokens"]) <= 2 * 2  # the live row alone
    # a narrower table that still covers the live row gives the same bits
    narrow, _, _ = lm.hybrid_paged_decode_step(
        params, cache, np.array([9, ids[0, 47], 9]), np.array([0, 47, 0]),
        tables[:, :8], hc)
    assert np.array_equal(np.asarray(narrow[1]), np.asarray(logits[1]))


def test_a_row_written_without_its_turn_moves_the_logits():
    """The fault the benchmark's rehearsal plants: prefill writes the rotary
    key unturned; a decode round over those rows is off by far more than
    rounding."""
    cfg = _cfg()
    hc = _program(cfg)
    w = ref.make_weights(5, cfg)
    params = _params(w, cfg)
    ids = np.random.default_rng(2).integers(0, 256, size=(1, 33))
    want = _reference(w, ids, cfg)[0]
    table = np.arange(16, dtype=np.int32)
    off = []
    for turn in (True, False):
        cache = lm.init_hybrid_cache(hc, 16, 8, kv_dtype=jnp.float32)
        _, cache = lm.hybrid_paged_prefill(
            params, cache, table, ids[0, :32], 0, 32, None, hc,
            turn_rows=turn)
        logits, _, _ = lm.hybrid_paged_decode_step(
            params, cache, ids[0, 32:], np.array([32]), table[None], hc)
        off.append(np.abs(np.asarray(logits[0]) - want[32]).max())
    assert off[0] < 2e-3 and off[1] > 0.05


@pytest.mark.parametrize("count", [4, 8])
def test_the_shares_and_the_shared_expert_once_are_the_uncut_layer(count):
    """Shares of `count` of 8 experts, each through the program's layer,
    with the ungated shared expert counted once, add up to the uncut
    reference's layer."""
    whole = _cfg(share=(0, 8, 8))
    z = ref.sizes(whole)
    p = ref.make_weights(7, whole)["layers"][1]
    u = jax.random.normal(jax.random.key(2), (1, 24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.moe_layer(p, u, z))
        flat = u.reshape(24, 64)
        shared = moe.ffn(flat, _beside(p["s_gate"], p["s_up"]),
                         _wide(p["s_down"]), jax.nn.silu, gated=True)
        total = np.asarray(shared)
        for first in range(0, 8, count):
            mine = {"router": p["router"], "b_corr": p["b_corr"],
                    "w_up": _beside(p["w_gate"], p["w_up"])[
                        first:first + count],
                    "w_down": _wide(p["w_down"])[first:first + count]}
            for gather in (False, True):
                part, counts = moe.expert_layer(
                    mine, flat, 2, held=(first, count), score="sigmoid",
                    scale=1.8, act=jax.nn.silu, gated=True, gather=gather)
                if gather:
                    assert np.abs(np.asarray(part) - last).max() < 1e-5
                last = np.asarray(part)
            total = total + last
            # the same share through the reference
            cut = {**p, **{k: p[k][first:first + count]
                           for k in ("w_gate", "w_up", "w_down")}}
            routed, _ = ref.moe_parts(cut, u, {
                **z, "held_first": first, "held": count})
            assert np.abs(np.asarray(routed)[0] - last).max() < 1e-4
    assert np.abs(want).max() > 0.05
    assert np.abs(total - want[0]).max() < 1e-4


def test_the_pools_bytes_a_token_and_copy_on_write_of_the_latent_group():
    """576 numbers a token a layer at the published widths, in 640 lanes of
    bfloat16 (a multiple of the chip's 128), whatever the layers'
    feed-forwards; a copied block carries its rows."""
    mc = mla.MLAConfig(dim=64, heads=20, q_rank=32, kv_rank=512,
                       nope_dim=8, rope_dim=64, v_dim=8)
    hc = lm.HybridConfig(dim=64, pattern="LF" + "LE" * 11, mla=mc)
    cache = lm.init_hybrid_cache(hc, 6, 4)
    assert mc.row == 576 and mc.cache_row == 640
    assert cache["latent"].shape == (12, 6, 4, 640)
    assert _row_bytes(cache) == 640 * 2 * 12 == 15360
    assert lm.hybrid_state_bytes(cache) == 0
    filled = {"latent": jax.random.normal(
        jax.random.key(0), cache["latent"].shape).astype(jnp.bfloat16)}
    new = lm.copy_hybrid_kv_blocks(filled, np.array([1, 2]),
                                   np.array([4, 5]))
    assert set(new) == {"latent"}
    assert np.array_equal(np.asarray(new["latent"][:, 4:6], np.float32),
                          np.asarray(filled["latent"][:, 1:3], np.float32))
    assert np.array_equal(np.asarray(new["latent"][:, :4], np.float32),
                          np.asarray(filled["latent"][:, :4], np.float32))


def test_a_pattern_may_hold_full_heads_and_latent_rows_and_state():
    """`*`, `G` and `L` behind one block table, a Mamba layer's state
    beside them: the cache has a group each, the state's bytes are the
    state's alone, copy-on-write copies every paged group, and prefill in
    two chunks then decode track the full forward."""
    from rafiki_tpu.ops.mamba2 import Mamba2Config

    hc = lm.HybridConfig(
        vocab=64, max_len=64, dim=32, pattern="LF*EMGL",
        mla=mla.MLAConfig(dim=32, heads=4, q_rank=16, kv_rank=8, nope_dim=4,
                          rope_dim=4, v_dim=8),
        mamba=Mamba2Config(dim=32, heads=4, head_dim=8, groups=2, state=8,
                           conv_kernel=4, chunk_size=4),
        q_heads=4, kv_heads=2, head_dim=8, rotary_dim=4, n_experts=4,
        top_k=2, ffn=16, shared_ffn=16, dense_ffn=48, expert_act="silu",
        expert_gated=True, held=(0, 4))
    params = lm.hybrid_init(jax.random.key(0), hc, dtype=jnp.float32)
    ids = np.random.default_rng(3).integers(0, 64, size=(1, 24))
    want = np.asarray(lm.hybrid_apply(params, ids, hc))[0]
    with pytest.raises(ValueError, match="number of slots"):
        lm.init_hybrid_cache(hc, 8, 4)
    cache = lm.init_hybrid_cache(hc, 8, 4, 2, kv_dtype=jnp.float32)
    assert set(cache) == {"k", "v", "latent", "conv", "h"}
    assert cache["k"].shape[0] == 2 and cache["latent"].shape[0] == 2
    assert lm.hybrid_state_bytes(cache) == (
        cache["conv"].nbytes + cache["h"].nbytes) > 0
    assert _row_bytes(cache) == 4 * (2 * 2 * 16 + 2 * 128)
    table = np.array([3, 1, 4, 6, 0, 2, 7, 5], np.int32)
    with pytest.raises(ValueError, match="needs the slot"):
        lm.hybrid_paged_prefill(params, cache, table, ids[0, :8], 0, 8,
                                None, hc)
    for start in (0, 8):
        logits, cache = lm.hybrid_paged_prefill(
            params, cache, table, ids[0, start:start + 8], start, 8, 1, hc)
    assert np.abs(np.asarray(logits) - want[15]).max() < 2e-4
    tables = np.stack([np.full(8, 8, np.int32), table])
    for t in range(16, 24):
        logits, cache, _ = lm.hybrid_paged_decode_step(
            params, cache, np.array([5, ids[0, t]]), np.array([0, t]),
            tables, hc)
        assert np.abs(np.asarray(logits[1]) - want[t]).max() < 2e-4
    new = lm.copy_hybrid_kv_blocks(cache, np.array([3]), np.array([7]))
    for name in ("k", "v", "latent"):
        assert np.array_equal(np.asarray(new[name][:, 7]),
                              np.asarray(cache[name][:, 3]))
        assert float(jnp.abs(cache[name][:, 3]).max()) > 0
    assert new["h"] is cache["h"]


def test_the_worker_serves_a_stateless_hybrid_model_its_prefix_hits(
        monkeypatch):
    """The first model of the hybrid stack with no recurrent state: the
    worker hands it no slot, the prefix cache is on, a second request with
    the same prompt is served its blocks (a hit) and returns the same
    tokens, as a fresh run does; the chunks and the pool's row are
    counted."""
    from rafiki_tpu.cache.queue import InProcessBroker

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("RAFIKI_GEN_PREFILL_CHUNK", "8")
    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1")
    monkeypatch.setenv("RAFIKI_GEN_PREFIX_CACHE", "1")
    model = _model("TinyLatentLM")
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 64, size=21).tolist()
    other = rng.integers(0, 64, size=13).tolist()
    from tests.test_hybrid_lm import _solo

    want = _solo(model, prompt, 10)
    broker = InProcessBroker()
    worker, ctx, t = _start_worker(broker, model, job="TinyLatentLM")
    q = list(broker.get_worker_queues("TinyLatentLM").values())[0]
    names = ("rafiki_gen_prefix_hits_total", "rafiki_gen_prefix_misses_total",
             "rafiki_gen_prefix_tokens_total", "rafiki_gen_state_resets_total",
             "rafiki_gen_prefill_chunks_total",
             "rafiki_gen_prefill_chunk_tokens_total",
             "rafiki_gen_kv_cow_copies_total")
    before = {n: _total(n) for n in names}
    model.prefills.clear()
    try:
        deadline = time.monotonic() + 10
        while getattr(worker, "_alloc", None) is None \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert worker._alloc.prefix_cache is True
        first = _drain(_stream(q, prompt, 10))[0]
        mid = _drain(_stream(q, other, 6))[0]
        again = _drain(_stream(q, prompt, 10))[0]
        assert first == again == want and len(mid) == 6
        time.sleep(0.3)  # the serve loop mirrors the allocator's counters
        delta = {n: _total(n) - before[n] for n in names}
        assert delta["rafiki_gen_prefix_misses_total"] == 2
        assert delta["rafiki_gen_prefix_hits_total"] == 1
        # all but the prompt's last token come from the cache: two chain
        # blocks and the copied tail
        assert delta["rafiki_gen_prefix_tokens_total"] == 20
        assert delta["rafiki_gen_kv_cow_copies_total"] >= 1
        assert delta["rafiki_gen_state_resets_total"] == 0
        # chunks of 8: 21 -> 3, 13 -> 2, and the hit prefills one token
        assert delta["rafiki_gen_prefill_chunks_total"] == 6
        assert delta["rafiki_gen_prefill_chunk_tokens_total"] == 21 + 13 + 1
        assert [s for s, _ in model.prefills] == [0, 8, 16, 0, 8, 20]
        assert all(slot is None for _, slot in model.prefills)
        from rafiki_tpu.utils.metrics import REGISTRY

        row = REGISTRY.get("rafiki_gen_kv_row_bytes")
        mc = model.cfg.mla
        assert [c.value() for c in row.children().values()] == [
            2 * mc.cache_row * 4]  # two latent layers, float32 here
    finally:
        ctx.stopping = True
        t.join(timeout=10)


def test_a_model_without_state_sets_no_state_gauge(monkeypatch):
    """`rafiki_gen_state_bytes` is the recurrent models' alone; the pool's
    row bytes are every paged model's."""
    import threading

    from rafiki_tpu.cache.queue import InProcessBroker
    from rafiki_tpu.utils.metrics import REGISTRY
    from rafiki_tpu.worker.generation import GenerationWorker
    from tests.test_hybrid_lm import _Ctx

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "1")
    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1")
    served = {}
    for name in ("TinyLatentLM", "TinyDeltaLM"):
        broker = InProcessBroker()
        worker = GenerationWorker(name, "trial1", db=None, broker=broker)
        model = _model(name)
        worker._load_model = lambda sid, model=model: model
        ctx = _Ctx(f"gauge-{name}")
        t = threading.Thread(target=worker.start, args=(ctx,), daemon=True)
        t.start()
        try:
            deadline = time.monotonic() + 10
            while not broker.get_worker_queues(name) \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            q = list(broker.get_worker_queues(name).values())[0]
            assert len(_drain(_stream(q, [1, 2, 3], 3))[0]) == 3
        finally:
            ctx.stopping = True
            t.join(timeout=10)
        for gauge in ("rafiki_gen_state_bytes", "rafiki_gen_kv_row_bytes"):
            served[name, gauge] = [
                c.value() for k, c in REGISTRY.get(gauge).children().items()
                if f"gauge-{name}" in str(k)]
    assert served["TinyLatentLM", "rafiki_gen_state_bytes"] == []
    assert served["TinyDeltaLM", "rafiki_gen_state_bytes"][0] > 0
    # TinyDeltaLM: one attention layer, keys and values of 2 heads of 8, f32
    assert served["TinyDeltaLM", "rafiki_gen_kv_row_bytes"] == [2 * 16 * 4]
    assert served["TinyLatentLM", "rafiki_gen_kv_row_bytes"] == [2 * 128 * 4]


def test_the_door_hears_a_long_prompt_prefill_and_shows_no_empty_delta(
        monkeypatch):
    """A prompt of four chunks whose every chunk takes longer than half the
    door's stall window: the worker's sign of life after each chunk that is
    not the last keeps the stream open, the client sees tokens alone, and
    the stream ends as asked. (Without the sign the door answers `decode
    stalled` before the first token.)"""
    import json

    import requests

    from rafiki_tpu.cache.queue import InProcessBroker
    from rafiki_tpu.predictor.predictor import Predictor
    from rafiki_tpu.predictor.server import PredictorServer

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("RAFIKI_GEN_PREFILL_CHUNK", "8")
    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1")
    monkeypatch.setenv("RAFIKI_GEN_STREAM_TIMEOUT_S", "0.6")
    model = _model("TinyLatentLM")
    prompt = np.random.default_rng(5).integers(0, 64, size=30).tolist()
    model.paged_prefill(model.init_paged_kv_cache(8, 8),
                        np.arange(8, dtype=np.int32), prompt[:8], 0)  # warm
    plain = model.paged_prefill

    def slow(*args):
        time.sleep(0.4)
        return plain(*args)

    model.paged_prefill = slow
    broker = InProcessBroker()
    worker, ctx, t = _start_worker(broker, model, job="longprompt")
    predictor = Predictor("longprompt", broker, task=None)
    server = PredictorServer(predictor, "longapp", auth=False).start()
    try:
        lines = []
        with requests.post(
                f"http://127.0.0.1:{server.port}/generate",
                json={"prompt_ids": prompt, "max_tokens": 5},
                stream=True, timeout=60) as resp:
            assert resp.status_code == 200
            lines = [json.loads(raw) for raw in resp.iter_lines() if raw]
        assert all(d["tokens"] or d["finished"] for d in lines)
        assert not any(d.get("error") for d in lines)
        assert len([t for d in lines for t in d["tokens"]]) == 5
        assert lines[-1]["finished"] and lines[-1]["reason"] == "max_tokens"
    finally:
        server.stop()
        ctx.stopping = True
        t.join(timeout=10)


# -- the prefill chunk's two kernels: rows in VMEM, tokens expert by expert --

def _chunk_inputs(first, real, t=128, rows=1024):
    """A chunk of `t` queries at `first ..`, `real` of them real, over a
    view of `rows` rows that holds the chunk's own; sizes the kernel's
    tiles hold."""
    mc = mla.MLAConfig(dim=256, heads=2, q_rank=64, kv_rank=128, nope_dim=64,
                       rope_dim=64, v_dim=128)
    p = mla.mla_init(jax.random.key(0), mc, jnp.bfloat16)
    rng = np.random.default_rng(first + real)
    u = jnp.asarray(rng.normal(size=(1, t, mc.dim)), jnp.float32)
    pos = (first + jnp.arange(t))[None]
    q_nope, q_rope, new = mla.mla_project(p, u, pos, mc)
    view = jnp.asarray(rng.normal(size=(1, rows, mc.cache_row)),
                       jnp.bfloat16).at[..., mc.row:].set(0)
    view = view.at[0, pos[0]].set(jnp.pad(
        new[0].astype(jnp.bfloat16), ((0, 0), (0, mc.cache_row - mc.row))))
    return mc, p, q_nope, q_rope, view, pos, jnp.int32(first + real - 1)


@pytest.mark.parametrize("first,real", [(0, 128), (384, 128), (512, 100),
                                        (896, 128)],
                         ids=["first-chunk", "inside-a-block",
                              "short-last-chunk", "last-block"])
def test_the_prefill_kernel_is_the_expanded_form(first, real):
    """ops/mla.py `attend_rows` (the Pallas interpreter here; Mosaic on the
    chip, tests/test_chip_compile.py) against the plain expanded form on
    the chunk's real queries: whole blocks before the chunk unmasked, the
    chunk's own block masked, rows past the last real query never read."""
    mc, p, q_nope, q_rope, view, pos, last = _chunk_inputs(first, real)
    plain = np.asarray(mla.mla_attend(p, q_nope, q_rope, view, pos, mc,
                                      "expanded", kernel=False))
    assert not mla.kernel_takes(1, 128, 1024)  # no TPU here
    # rows past the last real query hold what must not matter
    spoiled = view.at[0, (int(last) // mla.BLOCK_ROWS + 1)
                      * mla.BLOCK_ROWS:].set(jnp.nan)
    got = np.asarray(mla.mla_attend(p, q_nope, q_rope, spoiled, pos, mc,
                                    "expanded", last=last, kernel=True))
    assert np.isfinite(got).all()
    absorbed = np.asarray(mla.mla_attend(p, q_nope, q_rope, view, pos, mc,
                                         "absorbed"))
    # bfloat16 operands: as near the plain form as the plain forms are to
    # each other
    near = max(2 * np.abs(absorbed - plain)[:, :real].max(), 2e-3)
    assert np.abs(plain).max() > 0.2
    assert np.abs(got - plain)[:, :real].max() < near


@pytest.mark.parametrize("gated,dtype,tokens,experts,k,rows", [
    (True, jnp.float32, 64, 8, 2, 4), (False, jnp.float32, 96, 6, 3, 8),
    (True, jnp.bfloat16, 64, 8, 2, 4), (True, jnp.float32, 512, 8, 2, 128)],
    ids=["gated", "plain-top3", "bfloat16", "the-default-group"])
def test_many_tokens_go_expert_by_expert_and_none_is_dropped(
        gated, dtype, tokens, experts, k, rows):
    """`expert_products(gather=True, top=k)` with four groups of tokens and
    more: every token through the experts it chose alone, an expert with
    more tokens than a group taking several groups; the same sums as the
    loop over the experts hit and as the dense form."""
    rng = np.random.default_rng(tokens + experts)
    x = jnp.asarray(rng.normal(size=(tokens, 16)), jnp.float32)
    w_up = jnp.asarray(rng.normal(size=(experts, 16, 48 if gated else 24))
                       * 0.3, dtype)
    w_down = jnp.asarray(rng.normal(size=(experts, 24, 16)) * 0.3, dtype)
    router = jnp.asarray(rng.normal(size=(16, 2 * experts)), jnp.float32)
    router = router.at[:, 0].add(3.0 * jnp.sign(router[:, 0]))  # a crowd
    gates = moe.route(x, router, k)[0][:, :experts]  # half are held
    crowd = int(jnp.sum(gates[:, 0] > 0))
    assert crowd > rows or rows == 128
    how = dict(gated=gated, act=jax.nn.silu)
    loop = moe.expert_products(x, gates, w_up, w_down, gather=True, **how)
    dense = moe.expert_products(x, gates, w_up, w_down, **how)
    grouped = jax.jit(lambda x, g: moe.expert_products(
        x, g, w_up, w_down, gather=True, top=k, rows=rows, **how))(x, gates)
    assert float(jnp.abs(loop).max()) > 1.0
    assert float(jnp.abs(grouped - loop).max()) < 1e-5
    assert float(jnp.abs(grouped - dense).max()) < 1e-5
    # fewer than four groups of tokens keep the loop (a decode round, a
    # chunk of 64): the same program as before
    few = jax.make_jaxpr(lambda x, g: moe.expert_products(
        x, g, w_up, w_down, gather=True, top=k, rows=tokens, **how))(x, gates)
    assert "scatter" not in str(few)

