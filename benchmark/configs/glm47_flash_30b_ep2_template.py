"""The model template a `glm47_flash_30b_ep2` cell uploads through
`Client.create_model`: the generation contract over models/lm.py's hybrid
stack at the `glm4_moe_lite` family's keys: every published layer is two
entries of the pattern, `L` latent attention (ops/mla.py) and then its
feed-forward, `F` a dense gated MLP in the first `DENSE_LAYERS` layers and
`E` the expert block in the rest (sigmoid router with a correction bias,
gated silu experts, an ungated shared expert), as one chip's share of two
that divide each layer: the experts `HELD_FIRST .. HELD_FIRST + HELD - 1`
of `EXPERTS` and a slice of the vocabulary. `train()` makes the weights on
the device from the seed by the benchmark's own recipe
(benchmark/reference/glm4_moe_lite.py makes the same ones without importing
the program) in the program's layout: an MLP's and an expert's `W_gate` and
`W_up` lie side by side in one leaf. No optimizer step is taken.

The model holds no recurrent state: the worker hands the paged methods no
slot, the cache is the latent pool alone (`(layers, blocks, tokens, 576)`,
ONE array), and the prefix cache publishes its prompts. The jitted programs
take the weights as an argument and donate the cache.

The `# @cell` lines are set by the harness from the configuration; as they
stand they are the tiny size the CPU rehearsal runs.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from rafiki_tpu.models import lm
from rafiki_tpu.ops.mla import MLAConfig
from rafiki_tpu.sdk import BaseModel, FixedKnob, GenerationSpec

SEED = 0  # @cell
VOCAB = 512  # @cell
MAX_CONTEXT = 128  # @cell
DIM = 64  # @cell
LAYERS = 3  # @cell
DENSE_LAYERS = 1  # @cell
EPS = 1e-05  # @cell
HEADS = 4  # @cell
Q_RANK = 32  # @cell
KV_RANK = 16  # @cell
NOPE_DIM = 8  # @cell
ROPE_DIM = 8  # @cell
ROTARY_FACTOR = 1  # @cell
V_DIM = 16  # @cell
THETA = 1000000  # @cell
DENSE_FFN = 96  # @cell
EXPERTS = 8  # @cell
HELD_FIRST = 0  # @cell
HELD = 4  # @cell
TOP_K = 2  # @cell
FFN = 32  # @cell
SHARED_EXPERTS = 1  # @cell
ROUTE_SCALE = 1.8  # @cell
FAULT = ""  # @cell

# a published layer: its attention, then its feed-forward
KINDS = "".join("F" if l < DENSE_LAYERS else "E" for l in range(LAYERS))
SHARED_FFN = FFN * SHARED_EXPERTS
CFG = lm.HybridConfig(
    vocab=VOCAB, max_len=MAX_CONTEXT, dim=DIM,
    pattern="".join("L" + kind for kind in KINDS),
    mla=MLAConfig(dim=DIM, heads=HEADS, q_rank=Q_RANK, kv_rank=KV_RANK,
                  nope_dim=NOPE_DIM, rope_dim=int(ROPE_DIM * ROTARY_FACTOR),
                  v_dim=V_DIM, rope_theta=float(THETA), eps=EPS),
    n_experts=EXPERTS, top_k=TOP_K, ffn=FFN, shared_ffn=SHARED_FFN,
    dense_ffn=DENSE_FFN, route_score="sigmoid", route_bias=True,
    route_scale=ROUTE_SCALE, expert_act="silu", expert_gated=True,
    shared_gate=False, held=(HELD_FIRST, HELD), eps=EPS)
# ONE compiled prefill program: every chunk, the last of a prompt too, is
# padded to the chunk the long-prompt traffic prefills in (512), so that no
# shorter bucket is first met, and compiled, inside a window; a prompt's
# last chunk computes 256 rows of padding in the mean, a thirtieth of a
# prompt of 7,590
PREFILL_BUCKETS = tuple(b for b in (512, 1024, 2048, 4096, 8192)
                        if b < MAX_CONTEXT) + (MAX_CONTEXT,)
RING_BLOCK = 16  # tokens a block of the ring contract's fixed tables
COUNTS = ("expert_tokens", "experts_hit", "expert_layers")
BF16, F32 = jnp.bfloat16, jnp.float32
QUERY_SCALE = 2.5  # the reference's: the scores' standard deviation
ROUTED_DOWN = 8.0


def layer_spec(kind):
    """(name, shape, dtype, mean, std) of one published layer's leaves (the
    attention's, then the feed-forward's): the benchmark's recipe, in the
    order the keys are folded."""
    m = CFG.mla
    by = lambda fan_in: 1.0 / math.sqrt(fan_in)
    out = lambda fan_in: 1.0 / math.sqrt(fan_in * 2 * LAYERS)
    attention = [
        ("norm1", (DIM,), F32, 1.0, 0.1),
        ("w_dq", (DIM, Q_RANK), BF16, 0.0, by(DIM)),
        ("q_norm", (Q_RANK,), F32, 1.0, 0.1),
        ("w_uq", (Q_RANK, HEADS * (NOPE_DIM + m.rope_dim)), BF16, 0.0,
         QUERY_SCALE * by(Q_RANK)),
        ("w_dkv", (DIM, m.row), BF16, 0.0, by(DIM)),
        ("kv_norm", (KV_RANK,), F32, 1.0, 0.1),
        ("w_ukv", (KV_RANK, HEADS * (NOPE_DIM + V_DIM)), BF16, 0.0,
         by(KV_RANK)),
        ("wo", (HEADS * V_DIM, DIM), BF16, 0.0, out(HEADS * V_DIM)),
        ("norm2", (DIM,), F32, 1.0, 0.1)]
    if kind == "F":
        return attention + [
            ("w_gate", (DIM, DENSE_FFN), BF16, 0.0, by(DIM)),
            ("w_up", (DIM, DENSE_FFN), BF16, 0.0, by(DIM)),
            ("w_down", (DENSE_FFN, DIM), BF16, 0.0, out(DENSE_FFN))]
    return attention + [
        ("router", (DIM, EXPERTS), F32, 0.0, by(DIM)),
        ("b_corr", (EXPERTS,), F32, 0.0, 0.02),
        ("w_gate", (HELD, DIM, FFN), BF16, 0.0, by(DIM)),
        ("w_up", (HELD, DIM, FFN), BF16, 0.0, by(DIM)),
        ("w_down", (HELD, FFN, DIM), BF16, 0.0, out(FFN) / ROUTED_DOWN),
        ("s_gate", (DIM, SHARED_FFN), BF16, 0.0, by(DIM)),
        ("s_up", (DIM, SHARED_FFN), BF16, 0.0, by(DIM)),
        ("s_down", (SHARED_FFN, DIM), BF16, 0.0, out(SHARED_FFN))]


TOP_SPEC = [("embed", (VOCAB, DIM), BF16, 0.0, 0.02),
            ("head", (VOCAB, DIM), BF16, 0.0, 1.0 / math.sqrt(DIM)),
            ("norm_f", (DIM,), F32, 1.0, 0.1)]


CENTRED = ("wo", "w_down", "s_down")  # zero sums over the inputs
NORMS = ("norm1", "norm2", "q_norm", "kv_norm")


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
    W_gate is 0.2 GB in bfloat16) is rounded as it is made and nothing is
    held twice."""
    make = jax.jit(_leaf, static_argnums=(1, 2, 3, 4, 5, 6))
    beside = jax.jit(lambda a, b: jnp.concatenate([a, b], axis=-1))
    layers = []
    for l, kind in enumerate(KINDS):
        k = jax.random.fold_in(key, l)
        leaf = {name: make(k, i, shape, dtype, mean, std, name in CENTRED)
                for i, (name, shape, dtype, mean, std)
                in enumerate(layer_spec(kind))}
        leaf.update({name: {"scale": leaf[name]} for name in NORMS})
        forward = {"norm": leaf.pop("norm2"),
                   "w_up": beside(leaf.pop("w_gate"), leaf.pop("w_up")),
                   "w_down": leaf.pop("w_down")}
        if kind == "E":
            forward.update(
                router=leaf.pop("router"), b_corr=leaf.pop("b_corr"),
                s_up=beside(leaf.pop("s_gate"), leaf.pop("s_up")),
                s_down=leaf.pop("s_down"))
        layers += [{"norm": leaf.pop("norm1"), **leaf}, forward]
    top_key = jax.random.fold_in(key, LAYERS)
    embed, head, norm_f = (make(top_key, i, *spec[1:])
                           for i, spec in enumerate(TOP_SPEC))
    return {"embed": {"table": embed}, "head": head,
            "norm_f": {"scale": norm_f}, "layers": lm.hybrid_layers(layers)}


def _pad(prompt_ids):
    n = len(prompt_ids)
    ids = np.zeros(next(b for b in PREFILL_BUCKETS if b >= n), np.int32)
    ids[:n] = prompt_ids
    return ids, n


class BenchLatentLM(BaseModel):
    dependencies = {"jax": None}
    generation_spec = GenerationSpec(eos_token_id=None,
                                     max_context=MAX_CONTEXT)

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
        return self.init_paged_kv_cache(max_slots * per_slot, RING_BLOCK)

    def prefill(self, cache, slot, prompt_ids):
        return self.paged_prefill(cache, self._ring_tables[slot], prompt_ids,
                                  0)

    def decode_step(self, cache, ids, positions):
        toks, cache, _ = self.paged_decode_step(cache, ids, positions,
                                                self._ring_tables)
        return toks, cache

    # -- paged decode memory (worker/kv_paging.py drives these) --------------

    def init_paged_kv_cache(self, pool_blocks, block_tokens):
        self._device_params()
        return lm.init_hybrid_cache(CFG, pool_blocks, block_tokens)

    def paged_prefill(self, cache, block_table, prompt_ids, start):
        ids, n = _pad(prompt_ids)

        def paged_prefill_chunk(p, c, bt, i, st, m):
            # tests only: rows written to the pool without their rotary turn
            logits, c = lm.hybrid_paged_prefill(
                p, c, bt, i, st, m, None, CFG,
                turn_rows=FAULT != "unturned_row")
            return lm.greedy_token(logits), c

        tok, cache = self._jit("paged_prefill", paged_prefill_chunk,
                               donate=1)(
            self._params, cache, np.asarray(block_table, np.int32), ids,
            np.int32(start), np.int32(n))
        if FAULT == "wrong_token":  # tests only: an answer altered where
            tok = (int(tok) + 1) % VOCAB  # it is produced
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
