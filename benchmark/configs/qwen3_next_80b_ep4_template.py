"""The model template a `qwen3_next_80b_ep4` cell uploads through
`Client.create_model`: the generation contract over models/lm.py's hybrid
stack at the `qwen3_next` family's keys: every published layer is two
entries of the pattern, a mixer (`D` the gated delta rule, every
`ATTN_EVERY`-th `G` gated rotary attention) and then `E` the expert block
(softmax router without a bias, gated silu experts, a gated shared expert),
as one chip's share of four that divide each layer: the experts
`HELD_FIRST .. HELD_FIRST + HELD - 1` of `EXPERTS` and a slice of the
vocabulary. `train()` makes the weights on the device from the seed by the
benchmark's own recipe (benchmark/reference/qwen3_next.py makes the same
ones without importing the program) in the program's layout: a norm holds
`1 + w` of the reference's zero-centred `w`, an expert's `W_gate` and `W_up`
lie side by side in one leaf. No optimizer step is taken.

The spec declares `recurrent_state`: the worker hands the paged methods the
slot, and a slot's delta-rule state starts from zero at `start == 0`. The
jitted programs take the weights as an argument and donate the cache.

The `# @cell` lines are set by the harness from the configuration; as they
stand they are the tiny size the CPU rehearsal runs.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from rafiki_tpu.models import lm
from rafiki_tpu.ops.gated_delta import GatedDeltaConfig
from rafiki_tpu.sdk import BaseModel, FixedKnob, GenerationSpec

SEED = 0  # @cell
VOCAB = 512  # @cell
MAX_CONTEXT = 128  # @cell
DIM = 64  # @cell
LAYERS = 4  # @cell
ATTN_EVERY = 4  # @cell
EPS = 1e-06  # @cell
K_HEADS = 2  # @cell
K_DIM = 16  # @cell
V_HEADS = 4  # @cell
V_DIM = 16  # @cell
CONV = 4  # @cell
CHUNK = 8  # @cell
Q_HEADS = 4  # @cell
KV_HEADS = 2  # @cell
HEAD_DIM = 16  # @cell
ROTARY_FACTOR = 0.25  # @cell
THETA = 10000000  # @cell
EXPERTS = 16  # @cell
HELD_FIRST = 0  # @cell
HELD = 4  # @cell
TOP_K = 3  # @cell
FFN = 32  # @cell
SHARED_FFN = 32  # @cell
FAULT = ""  # @cell

# a published layer: its mixer, then its expert block
KINDS = "".join("G" if (i + 1) % ATTN_EVERY == 0 else "D"
                for i in range(LAYERS))
CFG = lm.HybridConfig(
    vocab=VOCAB, max_len=MAX_CONTEXT, dim=DIM,
    pattern="".join(kind + "E" for kind in KINDS),
    delta=GatedDeltaConfig(dim=DIM, key_heads=K_HEADS, value_heads=V_HEADS,
                           key_dim=K_DIM, value_dim=V_DIM, conv_kernel=CONV,
                           chunk_size=CHUNK, eps=EPS),
    q_heads=Q_HEADS, kv_heads=KV_HEADS, head_dim=HEAD_DIM,
    rotary_dim=int(HEAD_DIM * ROTARY_FACTOR), rope_theta=float(THETA),
    n_experts=EXPERTS, top_k=TOP_K, ffn=FFN, shared_ffn=SHARED_FFN,
    route_score="softmax", route_bias=False, route_scale=1.0,
    expert_act="silu", expert_gated=True, shared_gate=True,
    held=(HELD_FIRST, HELD), eps=EPS)
# one compiled prefill program for each bucket a chunk is padded to; at the
# program's default chunk of 64 tokens only the first is ever used
PREFILL_BUCKETS = tuple(b for b in (64, 128, 256, 512, 1024, 2048)
                        if b < MAX_CONTEXT) + (MAX_CONTEXT,)
RING_BLOCK = 16  # tokens a block of the ring contract's fixed tables
COUNTS = ("expert_tokens", "experts_hit", "expert_layers")
BF16, F32 = jnp.bfloat16, jnp.float32


def layer_spec(kind):
    """(name, shape, dtype, mean, std) of one published layer's leaves (the
    mixer's, then the expert block's): the benchmark's recipe, in the order
    the keys are folded."""
    d = CFG.delta
    into = 1.0 / math.sqrt(DIM)
    out = lambda fan_in: 1.0 / math.sqrt(fan_in * 2 * LAYERS)
    if kind == "D":
        mixer = [("w_qkvz", (DIM, d.in_cols), BF16, 0.0, into),
                 ("w_ba", (DIM, 2 * V_HEADS), BF16, 0.0, into),
                 ("conv_w", (CONV, d.conv_dim), F32, 0.0, 0.4),
                 ("dt_bias", (V_HEADS,), F32, -3.0, 1.0),
                 ("A_log", (V_HEADS,), F32, 0.0, 0.7),
                 ("onorm", (V_DIM,), F32, 1.0, 0.1),
                 ("w_out", (d.values, DIM), BF16, 0.0, out(d.values))]
    else:
        q, kv = Q_HEADS * HEAD_DIM, KV_HEADS * HEAD_DIM
        mixer = [("wq", (DIM, 2 * q), BF16, 0.0, into),
                 ("wk", (DIM, kv), BF16, 0.0, into),
                 ("wv", (DIM, kv), BF16, 0.0, into),
                 ("q_norm", (HEAD_DIM,), F32, 0.0, 0.1),
                 ("k_norm", (HEAD_DIM,), F32, 0.0, 0.1),
                 ("wo", (q, DIM), BF16, 0.0, out(q))]
    return [("norm1", (DIM,), F32, 0.0, 0.1)] + mixer + [
        ("norm2", (DIM,), F32, 0.0, 0.1),
        ("router", (DIM, EXPERTS), F32, 0.0, into),
        ("w_gate", (HELD, DIM, FFN), BF16, 0.0, into),
        ("w_up", (HELD, DIM, FFN), BF16, 0.0, into),
        ("w_down", (HELD, FFN, DIM), BF16, 0.0, out(FFN) / 8.0),
        ("s_gate", (DIM, SHARED_FFN), BF16, 0.0, into),
        ("s_up", (DIM, SHARED_FFN), BF16, 0.0, into),
        ("s_down", (SHARED_FFN, DIM), BF16, 0.0, out(SHARED_FFN)),
        ("s_w", (DIM, 1), BF16, 0.0, into)]


TOP_SPEC = [("embed", (VOCAB, DIM), BF16, 0.0, 0.02),
            ("head", (VOCAB, DIM), BF16, 0.0, 1.0 / math.sqrt(DIM)),
            ("norm_f", (DIM,), F32, 0.0, 0.1)]


CENTRED = ("w_out", "wo", "w_down", "s_down")  # zero sums over the inputs
ZERO_CENTRED = ("norm1", "norm2", "q_norm", "k_norm")  # the scale less one


def _leaf(key, i, shape, dtype, mean, std, centred=False):
    if std == 0.0:
        return jnp.full(shape, mean, dtype)
    draw = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
    if centred:
        draw = draw - jnp.mean(draw, axis=-2, keepdims=True)
    return (mean + std * draw).astype(dtype)


def make_params(key):
    """Leaf i of published layer l is `mean + std * normal(fold_in(fold_in(
    key, l), i))`, rounded to its dtype, laid out as models/lm.py reads it:
    two entries of the pattern a published layer. Each leaf is drawn by its
    own jitted call, so that the float32 draw of a large one (a layer's
    W_up is 0.27 GB in bfloat16) is rounded as it is made and nothing is
    held twice."""
    make = jax.jit(_leaf, static_argnums=(1, 2, 3, 4, 5, 6))
    scale = jax.jit(lambda w: {"scale": 1.0 + w})
    beside = jax.jit(lambda a, b: jnp.concatenate([a, b], axis=-1))
    layers = []
    for l, kind in enumerate(KINDS):
        k = jax.random.fold_in(key, l)
        leaf = {name: make(k, i, shape, dtype, mean, std, name in CENTRED)
                for i, (name, shape, dtype, mean, std)
                in enumerate(layer_spec(kind))}
        leaf.update({name: scale(leaf[name]) for name in ZERO_CENTRED
                     if name in leaf})
        experts = {"norm": leaf.pop("norm2"), "router": leaf.pop("router"),
                   "w_up": beside(leaf.pop("w_gate"), leaf.pop("w_up")),
                   "w_down": leaf.pop("w_down"),
                   "s_up": beside(leaf.pop("s_gate"), leaf.pop("s_up")),
                   "s_down": leaf.pop("s_down"), "s_gate": leaf.pop("s_w")}
        layers += [{"norm": leaf.pop("norm1"), **leaf}, experts]
    top_key = jax.random.fold_in(key, LAYERS)
    embed, head, norm_f = (make(top_key, i, *spec[1:])
                           for i, spec in enumerate(TOP_SPEC))
    return {"embed": {"table": embed}, "head": head, "norm_f": scale(norm_f),
            "layers": lm.hybrid_layers(layers)}


def _pad(prompt_ids):
    n = len(prompt_ids)
    ids = np.zeros(next(b for b in PREFILL_BUCKETS if b >= n), np.int32)
    ids[:n] = prompt_ids
    return ids, n


class BenchDeltaLM(BaseModel):
    dependencies = {"jax": None}
    generation_spec = GenerationSpec(eos_token_id=None,
                                     max_context=MAX_CONTEXT,
                                     recurrent_state=True)

    @staticmethod
    def get_knob_config():
        return {"dim": FixedKnob(DIM)}

    def __init__(self, **knobs):
        super().__init__(**knobs)
        self._params = None
        self._jits = {}
        self._ring_tables = None

    def train(self, dataset_uri):
        self._params = make_params(jax.random.key(SEED))

    def evaluate(self, dataset_uri):
        return 0.0  # nothing is trained: the cells of this model serve

    def dump_parameters(self):
        return jax.tree.map(np.asarray, self._params)

    def load_parameters(self, params):
        self._params = params
        self._jits = {}

    def destroy(self):
        self._params = None  # the trial's copy has to leave the device
        self._jits = {}

    def _device_params(self):
        self._params = jax.tree.map(jnp.asarray, self._params)
        return self._params

    def _jit(self, name, fn, donate=None):
        if name not in self._jits:
            self._jits[name] = jax.jit(
                fn, donate_argnums=() if donate is None else (donate,))
        return self._jits[name]

    def predict(self, queries):
        out = []
        for q in queries:
            cache = self.init_kv_cache(1)
            tok, cache = self.prefill(cache, 0, list(q))
            toks = [tok]
            for _ in range(7):
                nxt, cache = self.decode_step(
                    cache, np.array([tok], np.int32),
                    np.array([len(q) + len(toks) - 1], np.int32))
                tok = int(np.asarray(nxt)[0])
                toks.append(tok)
            out.append(toks)
        return out

    # -- generation contract, contiguous ring (required; not on the path):
    # the paged programs behind tables that give each slot its own blocks --

    def init_kv_cache(self, max_slots):
        per_slot = -(-MAX_CONTEXT // RING_BLOCK)
        self._ring_tables = np.arange(max_slots * per_slot,
                                      dtype=np.int32).reshape(max_slots, -1)
        return self.init_paged_kv_cache(max_slots * per_slot, RING_BLOCK,
                                        max_slots)

    def prefill(self, cache, slot, prompt_ids):
        return self.paged_prefill(cache, self._ring_tables[slot], prompt_ids,
                                  0, slot)

    def decode_step(self, cache, ids, positions):
        toks, cache, _ = self.paged_decode_step(cache, ids, positions,
                                                self._ring_tables)
        return toks, cache

    # -- paged decode memory (worker/kv_paging.py drives these) --------------

    def init_paged_kv_cache(self, pool_blocks, block_tokens, max_slots):
        self._device_params()
        return lm.init_hybrid_cache(CFG, pool_blocks, block_tokens, max_slots)

    def recurrent_state_bytes(self, cache):
        return lm.hybrid_state_bytes(cache)

    def paged_prefill(self, cache, block_table, prompt_ids, start, slot):
        ids, n = _pad(prompt_ids)

        def paged_prefill_chunk(p, c, bt, i, st, m, sl):
            # tests only: a slot admitted without its state reset
            reset = False if FAULT == "stale_state" else None
            logits, c = lm.hybrid_paged_prefill(p, c, bt, i, st, m, sl, CFG,
                                                reset=reset)
            return lm.greedy_token(logits), c

        tok, cache = self._jit("paged_prefill", paged_prefill_chunk,
                               donate=1)(
            self._params, cache, np.asarray(block_table, np.int32), ids,
            np.int32(start), np.int32(n), np.int32(slot))
        tok = int(tok)
        if FAULT == "wrong_token":  # tests only: an answer altered where
            tok = (tok + 1) % VOCAB  # it is produced
        return tok, cache

    def paged_decode_step(self, cache, ids, positions, block_tables):
        def paged_decode_round(p, c, i, q, bts):
            logits, c, counts = lm.hybrid_paged_decode_step(p, c, i, q, bts,
                                                            CFG)
            # the tokens and what the program counted, in one array: one
            # fetch a round
            return jnp.concatenate([lm.greedy_token(logits), jnp.stack(
                [counts[name] for name in COUNTS])]), c

        out, cache = self._jit("paged_decode", paged_decode_round,
                               donate=1)(
            self._params, cache, np.asarray(ids, np.int32),
            np.asarray(positions, np.int32),
            np.asarray(block_tables, np.int32))
        out = np.asarray(out)
        toks, counts = out[:len(ids)], out[len(ids):]
        if FAULT == "wrong_token":
            toks = (toks + 1) % VOCAB
        return toks, cache, dict(zip(COUNTS, counts))

    def kv_copy_blocks(self, cache, src, dst):
        return self._jit("copy", lm.copy_hybrid_kv_blocks, donate=0)(
            cache, np.asarray(src, np.int32), np.asarray(dst, np.int32))
