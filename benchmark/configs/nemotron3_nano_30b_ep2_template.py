"""The model template a `nemotron3_nano_30b_ep2` cell uploads through
`Client.create_model`: the generation contract over models/lm.py's hybrid
stack (Mamba-2, sparse experts, grouped-query attention, one mixer a layer)
at the `nemotron_h` family's keys, as one chip's share of an expert-parallel
pair: the experts `HELD_FIRST .. HELD_FIRST + HELD - 1` of `EXPERTS` and a
slice of the vocabulary. `train()` makes the weights on the device from the
seed by the benchmark's own recipe (benchmark/reference/nemotron_h.py makes
the same ones without importing the program) and takes no optimizer step.

The spec declares `recurrent_state`: the worker hands the paged methods the
slot, and a slot's Mamba state starts from zero at `start == 0`. The jitted
programs take the weights as an argument and donate the cache.

The `# @cell` lines are set by the harness from the configuration; as they
stand they are the tiny size the CPU rehearsal runs.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from rafiki_tpu.models import lm
from rafiki_tpu.ops.mamba2 import Mamba2Config
from rafiki_tpu.sdk import BaseModel, FixedKnob, GenerationSpec

SEED = 0  # @cell
VOCAB = 512  # @cell
MAX_CONTEXT = 128  # @cell
DIM = 64  # @cell
PATTERN = "MEM*E"  # @cell
M_HEADS = 8  # @cell
M_HEAD_DIM = 8  # @cell
GROUPS = 2  # @cell
STATE = 16  # @cell
CONV = 4  # @cell
CHUNK = 8  # @cell
Q_HEADS = 4  # @cell
KV_HEADS = 2  # @cell
HEAD_DIM = 16  # @cell
EXPERTS = 8  # @cell
HELD_FIRST = 0  # @cell
HELD = 4  # @cell
TOP_K = 2  # @cell
FFN = 32  # @cell
SHARED_FFN = 64  # @cell
SCALE = 2.5  # @cell
FAULT = ""  # @cell

CFG = lm.HybridConfig(
    vocab=VOCAB, max_len=MAX_CONTEXT, dim=DIM, pattern=PATTERN,
    mamba=Mamba2Config(dim=DIM, heads=M_HEADS, head_dim=M_HEAD_DIM,
                       groups=GROUPS, state=STATE, conv_kernel=CONV,
                       chunk_size=CHUNK),
    q_heads=Q_HEADS, kv_heads=KV_HEADS, head_dim=HEAD_DIM, n_experts=EXPERTS,
    top_k=TOP_K, ffn=FFN, shared_ffn=SHARED_FFN, route_scale=SCALE,
    held=(HELD_FIRST, HELD))
# one compiled prefill program for each bucket a chunk is padded to; at the
# program's default chunk of 64 tokens only the first is ever used
PREFILL_BUCKETS = tuple(b for b in (64, 128, 256, 512, 1024, 2048)
                        if b < MAX_CONTEXT) + (MAX_CONTEXT,)
RING_BLOCK = 16  # tokens a block of the ring contract's fixed tables
COUNTS = ("expert_tokens", "experts_hit", "expert_layers")
BF16, F32 = jnp.bfloat16, jnp.float32


def layer_spec(kind):
    """(name, shape, dtype, mean, std) of one layer's leaves: the
    benchmark's recipe, in the order the keys are folded."""
    m = CFG.mamba
    into = 1.0 / math.sqrt(DIM)
    out = lambda fan_in: 1.0 / math.sqrt(fan_in * len(PATTERN))
    if kind == "M":
        return [("norm", (DIM,), F32, 1.0, 0.0),
                ("w_in", (DIM, m.in_cols), BF16, 0.0, into),
                ("conv_w", (CONV, m.conv_dim), F32, 0.0, 0.4),
                ("conv_b", (m.conv_dim,), F32, 0.0, 0.1),
                ("dt_bias", (M_HEADS,), F32, -3.0, 1.0),
                ("A_log", (M_HEADS,), F32, 0.0, 0.7),
                ("D", (M_HEADS,), F32, 1.0, 0.0),
                ("gnorm", (m.inner,), F32, 1.0, 0.0),
                ("w_out", (m.inner, DIM), BF16, 0.0, out(m.inner))]
    if kind == "*":
        q, kv = Q_HEADS * HEAD_DIM, KV_HEADS * HEAD_DIM
        return [("norm", (DIM,), F32, 1.0, 0.0),
                ("wq", (DIM, q), BF16, 0.0, into),
                ("wk", (DIM, kv), BF16, 0.0, into),
                ("wv", (DIM, kv), BF16, 0.0, into),
                ("wo", (q, DIM), BF16, 0.0, out(q))]
    return [("norm", (DIM,), F32, 1.0, 0.0),
            ("router", (DIM, EXPERTS), F32, 0.0, into),
            ("b_corr", (EXPERTS,), F32, 0.0, 0.02),
            ("w_up", (HELD, DIM, FFN), BF16, 0.0, into),
            ("w_down", (HELD, FFN, DIM), BF16, 0.0, out(FFN) / 8.0),
            ("s_up", (DIM, SHARED_FFN), BF16, 0.0, into),
            ("s_down", (SHARED_FFN, DIM), BF16, 0.0, out(SHARED_FFN))]


TOP_SPEC = [("embed", (VOCAB, DIM), BF16, 0.0, 0.02),
            ("head", (VOCAB, DIM), BF16, 0.0, 1.0 / math.sqrt(DIM)),
            ("norm_f", (DIM,), F32, 1.0, 0.0)]


CENTRED = ("w_out", "wo", "w_down", "s_down")  # zero sums over the inputs


def _leaf(key, i, shape, dtype, mean, std, centred=False):
    if std == 0.0:
        return jnp.full(shape, mean, dtype)
    draw = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
    if centred:
        draw = draw - jnp.mean(draw, axis=-2, keepdims=True)
    return (mean + std * draw).astype(dtype)


def make_params(key):
    """Leaf i of layer l is `mean + std * normal(fold_in(fold_in(key, l),
    i))`, rounded to its dtype, laid out as models/lm.py reads it. Each leaf
    is drawn by its own jitted call, so that the float32 draw of a large one
    (an expert layer's W_up is 0.64 GB in bfloat16) is rounded as it is made
    and nothing is held twice."""
    make = jax.jit(_leaf, static_argnums=(1, 2, 3, 4, 5, 6))
    layers = []
    for l, kind in enumerate(PATTERN):
        k = jax.random.fold_in(key, l)
        layer = {name: make(k, i, shape, dtype, mean, std, name in CENTRED)
                 for i, (name, shape, dtype, mean, std)
                 in enumerate(layer_spec(kind))}
        layers.append({**layer, "norm": {"scale": layer["norm"]}})
    top_key = jax.random.fold_in(key, len(PATTERN))
    embed, head, norm_f = (make(top_key, i, *spec[1:])
                           for i, spec in enumerate(TOP_SPEC))
    return {"embed": {"table": embed}, "head": head,
            "norm_f": {"scale": norm_f}, "layers": lm.hybrid_layers(layers)}


def _pad(prompt_ids):
    n = len(prompt_ids)
    ids = np.zeros(next(b for b in PREFILL_BUCKETS if b >= n), np.int32)
    ids[:n] = prompt_ids
    return ids, n


class BenchHybridLM(BaseModel):
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
