"""The hybrid language model (Mamba-2, sparse experts, grouped-query
attention in one stack: models/lm.py, ops/mamba2.py, parallel/moe.py) against
its plain reference (benchmark/reference/nemotron_h.py) at a tiny size, and
the recurrent-state half of the generation contract through the worker
(tests/fixtures/hybrid_gen_model.py): a slot's state starts from zero at
admission, continues across prefill chunks, survives a round it sits out,
and is rebuilt from position 0 after a preemption.

The program's weights here are float32 (the reference's bfloat16-rounded
values, widened), so that program and reference differ by summation order
alone and no near tie of the router separates them.
"""

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as ref
from rafiki_tpu.models import core, lm
from rafiki_tpu.ops.attention import gqa_cached
from rafiki_tpu.ops.mamba2 import (Mamba2Config, mamba2_init, mamba2_mixer,
                                   mamba2_state_init)
from rafiki_tpu.parallel import moe

HERE = os.path.dirname(__file__)


def _cfg(pattern, share=(0, 4, 8)):
    return {"hidden_size": 64, "vocab_size": 256,
            "hybrid_override_pattern": pattern, "mamba_num_heads": 8,
            "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
            "conv_kernel": 4, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16,
            "expert_share": dict(zip(("first", "count", "of"), share)),
            "num_experts_per_tok": 2, "moe_intermediate_size": 32,
            "moe_shared_expert_intermediate_size": 64,
            "routed_scaling_factor": 2.5}


def _program(cfg, chunk=8):
    share = cfg["expert_share"]
    return lm.HybridConfig(
        vocab=cfg["vocab_size"], max_len=128, dim=64,
        pattern=cfg["hybrid_override_pattern"],
        mamba=Mamba2Config(dim=64, heads=8, head_dim=8, groups=2, state=16,
                           conv_kernel=4, chunk_size=chunk),
        q_heads=4, kv_heads=2, head_dim=16, n_experts=share["of"], top_k=2,
        ffn=32, shared_ffn=64, route_scale=2.5,
        held=(share["first"], share["count"]))


def _params(w, hc):
    """The reference's weights as the program's tree, widened to float32."""
    wide = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    layers = [wide({**p, "norm": {"scale": p["norm"]}}) for p in w["layers"]]
    top = w["top"]
    return {"embed": {"table": wide(top["embed"])}, "head": wide(top["head"]),
            "norm_f": {"scale": top["norm_f"]},
            "layers": lm.hybrid_layers(layers)}


def _reference(w, ids, cfg):
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits_at(w, jnp.asarray(ids), pos, cfg))


@pytest.mark.parametrize("pattern", ["M", "E", "*", "MEM*EMEM*E"])
def test_each_layer_kind_against_the_reference(pattern):
    cfg = _cfg(pattern)
    hc = _program(cfg)
    w = ref.make_weights(3, cfg)
    ids = np.random.default_rng(0).integers(0, 256, size=(2, 40))
    got = np.asarray(lm.hybrid_apply(_params(w, hc), ids, hc))
    want = _reference(w, ids, cfg)
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < 2e-3


def test_prefill_then_decode_against_the_full_forward():
    """Chunks of 16 into a paged pool and the slot's state, then decode
    rounds with idle rows beside the live one, against the reference's full
    forward pass over the same tokens."""
    cfg = _cfg("MEM*EMEM*E")
    hc = _program(cfg)
    w = ref.make_weights(5, cfg)
    params = _params(w, hc)
    ids = np.random.default_rng(1).integers(0, 256, size=(1, 40))
    want = _reference(w, ids, cfg)[0]
    cache = lm.init_hybrid_cache(hc, 16, 8, 3, kv_dtype=jnp.float32)
    table = np.arange(16, dtype=np.int32)
    for start in (0, 16):
        logits, cache = lm.hybrid_paged_prefill(
            params, cache, table, ids[0, start:start + 16], start, 16, 1, hc)
    assert np.abs(np.asarray(logits) - want[31]).max() < 2e-3
    idle = np.full(16, 16, np.int32)
    tables = np.stack([idle, table, idle])
    before = np.asarray(cache["h"][:, 0])
    for t in range(32, 38):
        logits, cache, counts = lm.hybrid_paged_decode_step(
            params, cache, np.array([9, ids[0, t], 9]), np.array([0, t, 0]),
            tables, hc)
        assert np.abs(np.asarray(logits[1]) - want[t]).max() < 2e-3
        assert int(counts["expert_layers"]) == 4
        assert int(counts["expert_tokens"]) <= 2 * 4  # the live row alone
    assert np.array_equal(np.asarray(cache["h"][:, 0]), before)  # idle rows


def test_the_two_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Each chip of the pair routes over all 8 experts and computes its
    own 4; the two routed parts and the shared expert counted once are the
    reference's uncut layer. Reference and program alike."""
    whole = _cfg("E", share=(0, 8, 8))
    z = ref.sizes(whole)
    w = ref.make_weights(7, whole)["layers"][0]
    u = jax.random.normal(jax.random.key(1), (3, 10, 64))
    with jax.default_matmul_precision("highest"):
        uncut = ref.moe_layer(w, u, z)
        parts, shared = [], None
        for first in (0, 4):
            zs = {**z, "held_first": first, "held": 4}
            half = {**w, "w_up": w["w_up"][first:first + 4],
                    "w_down": w["w_down"][first:first + 4]}
            routed, shared = ref.moe_parts(half, u, zs)
            parts.append(routed)
            p32 = jax.tree.map(lambda a: a.astype(jnp.float32), half)
            got, counts = moe.expert_layer(
                p32, u.reshape(30, 64), 2, held=(first, 4), scale=2.5,
                gather=bool(first))
            assert np.abs(np.asarray(got).reshape(3, 10, 64)
                          - np.asarray(routed)).max() < 1e-4
            assert 0 < int(counts["experts_hit"]) <= 4
        assert np.abs(np.asarray(parts[0] + parts[1] + shared)
                      - np.asarray(uncut)).max() < 1e-4
    # 30 tokens x 2 choices fall on the two halves together
    assert np.abs(np.asarray(parts[0])).max() > 0.01


@pytest.mark.parametrize("k,score,gated", [
    (1, "softmax", False), (2, "sigmoid", False), (6, "sigmoid", False),
    (10, "softmax", True)])
def test_expert_products_gathered_and_dense_agree_and_drop_no_token(
        k, score, gated):
    rng = jax.random.key(k)
    x = jax.random.normal(rng, (12, 16))
    n, up = (16, 48) if gated else (8, 24)  # [W_gate | W_up] side by side
    p = {"router": jax.random.normal(jax.random.fold_in(rng, 1), (16, n)),
         "w_up": jax.random.normal(jax.random.fold_in(rng, 2), (n, 16, up)),
         "w_down": jax.random.normal(jax.random.fold_in(rng, 3), (n, 24, 16))}
    gates, _ = moe.route(x, p["router"], k, score=score)
    assert np.all(np.sum(np.asarray(gates) > 0, axis=1) == k)  # none dropped
    how = dict(score=score, gated=gated,
               act=jax.nn.silu if gated else core.relu2)
    dense, cd = moe.expert_layer(p, x, k, **how, gather=False)
    gathered, cg = moe.expert_layer(p, x, k, **how, gather=True)
    if gated:  # the hidden layer is act(x W_gate) * (x W_up), by hand
        h = jnp.einsum("nd,edf->enf", x, p["w_up"])
        by_hand = jnp.einsum(
            "enf,efd,ne->nd", jax.nn.silu(h[..., :24]) * h[..., 24:],
            p["w_down"], gates)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(by_hand),
                                   atol=1e-3, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(gathered),
                               atol=1e-4, rtol=1e-4)
    assert int(cd["expert_tokens"]) == int(cg["expert_tokens"]) == 12 * k
    # a token taken out of the routing reads no expert
    none, c0 = moe.expert_layer(p, x, k, **how, gather=True,
                                live=jnp.zeros(12, bool))
    assert float(jnp.abs(none).max()) == 0.0 and int(c0["experts_hit"]) == 0


def test_mamba2_chunks_and_single_steps_are_one_recurrence():
    """64 tokens at once (chunks of 8), in two calls of 32 with the state
    handed over, and one token at a time: the same outputs and final state;
    padding after a sequence's end moves no state."""
    cfg = Mamba2Config(dim=32, heads=4, head_dim=8, groups=2, state=8,
                       chunk_size=8)
    p = mamba2_init(jax.random.key(0), cfg)
    u = jax.random.normal(jax.random.key(1), (2, 64, 32))
    full = jnp.full((2,), 64, jnp.int32)
    y, st = mamba2_mixer(p, u, mamba2_state_init(cfg, 2), full, cfg)
    half = jnp.full((2,), 32, jnp.int32)
    y1, s1 = mamba2_mixer(p, u[:, :32], mamba2_state_init(cfg, 2), half, cfg)
    y2, s2 = mamba2_mixer(p, u[:, 32:], s1, half, cfg)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jnp.concatenate([y1, y2], 1)),
                               atol=2e-5)
    state, ys = mamba2_state_init(cfg, 2), []
    for t in range(64):
        yt, state = mamba2_mixer(p, u[:, t:t + 1], state,
                                 jnp.ones((2,), jnp.int32), cfg)
        ys.append(yt)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jnp.concatenate(ys, 1)), atol=2e-5)
    for name in ("conv", "h"):
        np.testing.assert_allclose(np.asarray(st[name]),
                                   np.asarray(s2[name]), atol=2e-5)
        np.testing.assert_allclose(np.asarray(st[name]),
                                   np.asarray(state[name]), atol=2e-5)
    # 40 real tokens of 64: the state is that after 40
    _, s40 = mamba2_mixer(p, u[:, :40], mamba2_state_init(cfg, 2),
                          jnp.full((2,), 40, jnp.int32), cfg)
    _, padded = mamba2_mixer(p, u, mamba2_state_init(cfg, 2),
                             jnp.full((2,), 40, jnp.int32), cfg)
    for name in ("conv", "h"):
        np.testing.assert_allclose(np.asarray(padded[name]),
                                   np.asarray(s40[name]), atol=2e-5)


def test_gqa_cached_is_causal_attention_over_shared_heads():
    b, t, hq, hkv, dh = 2, 6, 4, 2, 8
    q = jax.random.normal(jax.random.key(0), (b, t, hq, dh))
    k = jax.random.normal(jax.random.key(1), (b, t, hkv, dh))
    v = jax.random.normal(jax.random.key(2), (b, t, hkv, dh))
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    got = gqa_cached(q, k, v, pos).reshape(b, t, hq, dh)
    for h in range(hq):
        s = jnp.einsum("btk,blk->btl", q[:, :, h], k[:, :, h // 2]) / 8 ** .5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
        want = jnp.einsum("btl,blk->btk", jax.nn.softmax(s, -1),
                          v[:, :, h // 2])
        np.testing.assert_allclose(np.asarray(got[:, :, h]),
                                   np.asarray(want), atol=1e-5)


def test_rmsnorm_and_relu2():
    x = jax.random.normal(jax.random.key(0), (3, 16)) * 4
    y = core.rmsnorm({"scale": jnp.full((16,), 2.0)}, x)
    np.testing.assert_allclose(
        np.asarray(jnp.sqrt(jnp.mean(y * y, -1))), 2.0, rtol=1e-4)
    g = core.group_rmsnorm(jnp.ones(16), x, 4)
    np.testing.assert_allclose(np.asarray(jnp.sqrt(jnp.mean(
        g.reshape(3, 4, 4) ** 2, -1))), 1.0, rtol=1e-3)
    assert np.array_equal(np.asarray(core.relu2(jnp.array([-2., 0., 3.]))),
                          [0., 0., 9.])


def test_bfloat16_leaves_round_trip_params_and_artifact(tmp_path):
    """bfloat16 weights through dump_parameters -> sdk/params.py ->
    sdk/artifact.py -> load_parameters, bit for bit, beside float32 ones."""
    from rafiki_tpu.sdk import artifact, params

    hc = _program(_cfg("ME"))
    tree = jax.tree.map(np.asarray, lm.hybrid_init(jax.random.key(0), hc))
    assert tree["layers"]["01"]["w_up"].dtype == jnp.bfloat16
    path = str(tmp_path / "trial.params")
    artifact.write_artifact(path, params.dump_params(tree))
    back = params.load_params(artifact.read_artifact(path))
    flat, back_flat = jax.tree.leaves(tree), jax.tree.leaves(back)
    assert len(flat) == len(back_flat)
    for a, b in zip(flat, back_flat):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == np.asarray(b).tobytes()
    ids = np.arange(12).reshape(1, 12)
    assert np.array_equal(np.asarray(lm.hybrid_apply(tree, ids, hc)),
                          np.asarray(lm.hybrid_apply(back, ids, hc)))


def test_the_transformer_lm_with_expert_blocks_decodes_through_the_cache():
    """The two refusals went: a top-1 drop-free expert block routes each
    token on its own, so prefill and decode track the full forward."""
    cfg = lm.tiny(vocab=64, max_len=32, dim=16, depth=2, heads=2,
                  moe_experts=4)
    params = lm.init(jax.random.key(0), cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, size=12))
    full, _ = lm.apply(params, ids[None], cfg)
    cache = lm.init_kv_cache(cfg, max_slots=1, max_len=32)
    logits, cache = lm.prefill(params, cache, 0, ids[:8], 8, cfg)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[0, 7]),
                               atol=0.05)
    pool = lm.init_paged_kv_cache(cfg, 4, 8)
    table = np.arange(4, dtype=np.int32)
    lp, pool = lm.paged_prefill(params, pool, table, ids[:8], 0, 8, cfg)
    assert np.array_equal(np.asarray(lp), np.asarray(logits))
    for t in range(8, 11):
        lr, cache = lm.decode_step(params, cache, ids[t:t + 1],
                                   jnp.array([t]), cfg)
        lp, pool = lm.paged_decode_step(params, pool, ids[t:t + 1],
                                        np.array([t]), table[None], cfg)
        assert np.array_equal(np.asarray(lr), np.asarray(lp))
        np.testing.assert_allclose(np.asarray(lr[0]),
                                   np.asarray(full[0, t]), atol=0.05)


# -- through the worker -----------------------------------------------------------

class _Ctx:
    def __init__(self, service_id="w1"):
        self.service_id = service_id
        self.chips = None
        self.stopping = False

    def ready(self):
        pass


def _fixture():
    sys.path.insert(0, HERE)
    try:
        from fixtures import hybrid_gen_model
    finally:
        sys.path.pop(0)
    return hybrid_gen_model


def _model(name="TinyHybridLM"):
    m = getattr(_fixture(), name)()
    m.train(None)
    return m


def _start_worker(broker, model, job):
    from rafiki_tpu.worker.generation import GenerationWorker

    worker = GenerationWorker(job, "trial1", db=None, broker=broker)
    worker._load_model = lambda sid: model
    ctx = _Ctx()
    t = threading.Thread(target=worker.start, args=(ctx,), daemon=True)
    t.start()
    deadline = time.monotonic() + 10
    while not broker.get_worker_queues(job) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert broker.get_worker_queues(job), "worker never registered"
    return worker, ctx, t


def _stream(q, prompt, max_tokens, timeout_s=60.0, **more):
    fut = q.submit_many([{"prompt_ids": list(prompt),
                          "max_tokens": max_tokens, **more}],
                        deadline=time.monotonic() + timeout_s)[0]
    return fut.result(timeout_s)


def _drain(stream, timeout_s=60.0):
    toks, reason = [], None
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            d = stream.next_delta(1.0)
        except TimeoutError:
            continue
        except StopIteration:
            break
        toks.extend(d.tokens)
        if d.finished:
            reason = d.reason
            break
    return toks, reason


def _solo(model, prompt, n):
    """A fresh run of one prompt outside the worker: whole-prompt prefill
    into a new cache, then greedy decode."""
    cache = model.init_kv_cache(1)
    tok, cache = model.prefill(cache, 0, list(prompt))
    toks = [tok]
    while len(toks) < n:
        nxt, cache = model.decode_step(
            cache, np.array([toks[-1]], np.int32),
            np.array([len(prompt) + len(toks) - 1], np.int32))
        toks.append(int(np.asarray(nxt)[0]))
    return toks


def _total(name):
    from rafiki_tpu.utils.metrics import REGISTRY

    metric = REGISTRY.get(name)
    return 0.0 if metric is None else float(
        sum(c.value() for c in metric.children().values()))


@pytest.mark.parametrize("template", ["TinyHybridLM", "TinyDeltaLM"])
def test_worker_serves_a_recurrent_model_as_fresh_runs(monkeypatch, template):
    """Chunked prefill across chunk boundaries (while siblings decode), a
    slot reused after another stream, and the same prompt sent again with
    the prefix cache on: every stream is the fresh run of its prompt, every
    admission prefills from position 0 of its own slot and counts as a
    prefix miss, and the program's expert counts reach the counters."""
    from rafiki_tpu.cache.queue import InProcessBroker

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("RAFIKI_GEN_PREFILL_CHUNK", "8")
    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1")
    monkeypatch.setenv("RAFIKI_GEN_PREFIX_CACHE", "1")
    model = _model(template)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, size=n).tolist() for n in (21, 5, 13, 30)]
    prompts.append(prompts[0])  # a whole-prompt prefix hit, were it served
    want = [_solo(model, p, 10) for p in prompts]
    broker = InProcessBroker()
    worker, ctx, t = _start_worker(broker, model, job=template)
    q = list(broker.get_worker_queues(template).values())[0]
    before = {n: _total(n) for n in (
        "rafiki_gen_state_resets_total", "rafiki_gen_prefix_hits_total",
        "rafiki_gen_prefix_misses_total", "rafiki_gen_experts_hit_total",
        "rafiki_gen_expert_tokens_total",
        "rafiki_gen_expert_layer_rounds_total")}
    model.prefills.clear()
    try:
        deadline = time.monotonic() + 10  # the queue registers first
        while getattr(worker, "_alloc", None) is None \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert worker._alloc.prefix_cache is False
        streams = [_stream(q, p, 10) for p in prompts[:2]]
        got = [_drain(s)[0] for s in streams]
        # five streams over two slots: slots are reused after other streams
        got += [_drain(_stream(q, p, 10))[0] for p in prompts[2:]]
        assert got == want
        time.sleep(0.3)  # the serve loop mirrors the allocator's counters
        after = {n: _total(n) for n in before}
        delta = {n: after[n] - before[n] for n in before}
        assert delta["rafiki_gen_state_resets_total"] == 5
        assert delta["rafiki_gen_prefix_hits_total"] == 0
        assert delta["rafiki_gen_prefix_misses_total"] == 5
        rounds = delta["rafiki_gen_expert_layer_rounds_total"]
        assert rounds > 0 and rounds % 2 == 0  # two expert layers a round
        assert 0 < delta["rafiki_gen_experts_hit_total"] \
            <= delta["rafiki_gen_expert_tokens_total"]
        assert _total("rafiki_gen_state_bytes") > 0
        firsts = [(s, slot) for s, slot in model.prefills if s == 0]
        assert len(firsts) == 5 and {slot for _, slot in firsts} == {0, 1}
        # chunks of 8: the prompts of 21, 5, 13, 30 and 21 tokens
        assert sorted(s for s, _ in model.prefills) == (
            [0] * 5 + [8] * 4 + [16] * 3 + [24])
    finally:
        ctx.stopping = True
        t.join(timeout=10)


@pytest.mark.chaos
def test_worker_preempts_and_resumes_a_recurrent_stream_from_zero(
        monkeypatch):
    """Three long streams through a pool that holds one and a half: the
    youngest is preempted, later re-prefilled from position 0 (its state
    rebuilt from its whole history), and still streams the fresh run."""
    from rafiki_tpu.cache.queue import InProcessBroker

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "3")
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("RAFIKI_GEN_KV_POOL_BLOCKS", "6")
    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1")
    monkeypatch.setenv("RAFIKI_GEN_PREFILL_CHUNK", "8")
    model = _model()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 64, size=16).tolist() for _ in range(3)]
    want = [_solo(model, p, 16) for p in prompts]
    broker = InProcessBroker()
    worker, ctx, t = _start_worker(broker, model, job="hybridflood")
    q = list(broker.get_worker_queues("hybridflood").values())[0]
    preempts0 = _total("rafiki_gen_preemptions_total")
    resets0 = _total("rafiki_gen_state_resets_total")
    try:
        streams = [_stream(q, p, 16) for p in prompts]
        got = [_drain(s)[0] for s in streams]
        assert got == want
        preempts = _total("rafiki_gen_preemptions_total") - preempts0
        assert preempts >= 1
        # every resume that reached a prefill started its slot's state
        # anew (a stream can be preempted again while it still waits)
        resets = _total("rafiki_gen_state_resets_total") - resets0
        assert 3 + 1 <= resets <= 3 + preempts
    finally:
        ctx.stopping = True
        t.join(timeout=10)


@pytest.mark.parametrize("wired", ["sampling", "verify"])
def test_a_recurrent_template_that_wires_sampling_is_refused_at_deploy(
        monkeypatch, wired):
    from rafiki_tpu.cache.queue import InProcessBroker
    from rafiki_tpu.worker.generation import (GenerationUnsupportedError,
                                              GenerationWorker)

    base = _fixture().TinyHybridLM
    methods = {"decode_step_sampled": lambda self, *a: None,
               "paged_decode_step_sampled": lambda self, *a: None}
    if wired == "verify":
        methods["paged_verify_step"] = lambda self, *a: None
    model = type("Wired", (base,), methods)()
    model.train(None)
    worker = GenerationWorker("refused", "trial1", db=None,
                              broker=InProcessBroker())
    worker._load_model = lambda sid: model
    with pytest.raises(GenerationUnsupportedError,
                       match="recurrent_state"):
        worker.start(_Ctx())


def test_a_sampled_request_to_a_recurrent_model_is_refused_typed(monkeypatch):
    from rafiki_tpu.cache.queue import InProcessBroker
    from rafiki_tpu.worker.generation import GenerationRequestError

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")
    broker = InProcessBroker()
    worker, ctx, t = _start_worker(broker, _model(), job="hybridsampled")
    q = list(broker.get_worker_queues("hybridsampled").values())[0]
    try:
        with pytest.raises(GenerationRequestError, match="sampling-capable"):
            _stream(q, [1, 2, 3], 4, temperature=0.8)
        assert len(_drain(_stream(q, [1, 2, 3], 4))[0]) == 4
    finally:
        ctx.stopping = True
        t.join(timeout=10)


def test_the_static_check_knows_the_slot_arguments():
    from rafiki_tpu.analysis.template import verify_template_source

    with open(os.path.join(HERE, "fixtures", "hybrid_gen_model.py")) as f:
        source = f.read()
    report = verify_template_source(source, "TinyHybridLM")
    assert report.capabilities["generation_spec"]["recurrent_state"] is True
    assert not [f for f in report.findings if f.code == "GEN002"]
    # without the declaration the same signatures are one argument too many
    plain = source.replace("recurrent_state=True", "recurrent_state=False")
    codes = [f.code for f in verify_template_source(
        plain, "TinyHybridLM").findings]
    assert codes.count("GEN002") == 2
