"""The model template a `gpt2_large` cell uploads through
`Client.create_model`: the generation contract of tests/fixtures/gen_model.py
over models/lm.py at GPT-2's block shape. `train()` makes the weights on the
device from the seed by the benchmark's own recipe (so that the plain
reference can make the same ones without importing the program) and takes no
optimizer step: this configuration's cells measure serving.

Unlike the fixture, the jitted programs take the weights as an argument (a
3.1 GB constant folded into a program would not compile) and donate the pool.

The `# @cell` lines are set by the harness from the configuration; as they
stand they are the tiny size the CPU rehearsal runs.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from rafiki_tpu.models import lm
from rafiki_tpu.models.transformer import TransformerConfig
from rafiki_tpu.sdk import BaseModel, FixedKnob, GenerationSpec

SEED = 0  # @cell
VOCAB = 512  # @cell
MAX_CONTEXT = 128  # @cell
DIM = 64  # @cell
DEPTH = 2  # @cell
HEADS = 4  # @cell
FAULT = ""  # @cell

CFG = lm.LMConfig(vocab=VOCAB, max_len=MAX_CONTEXT,
                  encoder=TransformerConfig(dim=DIM, depth=DEPTH, heads=HEADS,
                                            causal=True))
# one compiled prefill program for each bucket a chunk is padded to; at the
# program's default chunk of 64 tokens only the first is ever used
PREFILL_BUCKETS = tuple(b for b in (64, 128, 256, 512, 1024, 2048)
                        if b < MAX_CONTEXT) + (MAX_CONTEXT,)


def make_params(key):
    """The benchmark's weight recipe, laid out as models/lm.py's tree: leaf i
    is `normal(fold_in(key, i)) * std` in the order below."""
    d, h, n, f = DIM, HEADS, DEPTH, 4 * DIM
    dh = d // h
    res = 0.02 / math.sqrt(2 * n)
    spec = [
        ((VOCAB, d), 0.02), ((MAX_CONTEXT, d), 0.01),
        ((n, d), 1.0), ((n, d), 0.0),
        ((n, d, h, dh), 0.02), ((n, d, h, dh), 0.02), ((n, d, h, dh), 0.02),
        ((n, h, dh, d), res), ((n, d), 0.0),
        ((n, d), 1.0), ((n, d), 0.0),
        ((n, d, f), 0.02), ((n, f), 0.0), ((n, f, d), res), ((n, d), 0.0),
        ((d,), 1.0), ((d,), 0.0),
    ]
    leaves = []
    for i, (shape, std) in enumerate(spec):
        if std == 1.0:
            leaves.append(jnp.ones(shape, jnp.float32))
        elif std == 0.0:
            leaves.append(jnp.zeros(shape, jnp.float32))
        else:
            leaves.append(jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32) * std)
    (wte, wpe, g1, b1, wq, wk, wv, wo, bo, g2, b2, k1, c1, k2, c2,
     gf, bf) = leaves
    return {
        "embed": {"table": wte},
        "pos": wpe[None],
        "blocks": {
            "ln1": {"scale": g1, "bias": b1},
            "attn": {"wq": wq, "wk": wk, "wv": wv, "wo": wo, "bo": bo},
            "ln2": {"scale": g2, "bias": b2},
            "mlp": {"w1": {"kernel": k1, "bias": c1},
                    "w2": {"kernel": k2, "bias": c2}},
        },
        "ln_f": {"scale": gf, "bias": bf},
    }


def _pad(prompt_ids):
    n = len(prompt_ids)
    ids = np.zeros(next(b for b in PREFILL_BUCKETS if b >= n), np.int32)
    ids[:n] = prompt_ids
    return ids, n


class BenchLM(BaseModel):
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

    def train(self, dataset_uri):
        self._params = jax.jit(make_params)(jax.random.key(SEED))

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

    # -- generation contract, contiguous ring (required; not on the path) ----

    def init_kv_cache(self, max_slots):
        self._device_params()
        return lm.init_kv_cache(CFG, max_slots, max_len=MAX_CONTEXT)

    def prefill(self, cache, slot, prompt_ids):
        ids, n = _pad(prompt_ids)
        fn = self._jit("prefill", lambda p, c, s, i, m: lm.prefill(
            p, c, s, i, m, CFG))
        logits, cache = fn(self._params, cache, slot, ids, n)
        return int(lm.greedy_token(logits)), cache

    def decode_step(self, cache, ids, positions):
        fn = self._jit("decode", lambda p, c, i, q: lm.decode_step(
            p, c, i, q, CFG))
        logits, cache = fn(self._params, cache, ids, positions)
        return lm.greedy_token(logits), cache

    # -- paged decode memory (worker/kv_paging.py drives these) --------------

    def init_paged_kv_cache(self, pool_blocks, block_tokens):
        self._device_params()
        return lm.init_paged_kv_cache(CFG, pool_blocks, block_tokens)

    def paged_prefill(self, cache, block_table, prompt_ids, start):
        ids, n = _pad(prompt_ids)

        def paged_prefill_chunk(p, c, bt, i, st, m):
            logits, c = lm.paged_prefill(p, c, bt, i, st, m, CFG)
            return lm.greedy_token(logits), c

        tok, cache = self._jit("paged_prefill", paged_prefill_chunk,
                               donate=1)(
            self._params, cache, np.asarray(block_table, np.int32), ids,
            np.int32(start), np.int32(n))
        tok = int(tok)
        if FAULT == "wrong_token":  # tests only: an answer altered where
            tok = (tok + 1) % VOCAB  # it is produced
        return tok, cache

    def paged_decode_step(self, cache, ids, positions, block_tables):
        def paged_decode_round(p, c, i, q, bts):
            logits, c = lm.paged_decode_step(p, c, i, q, bts, CFG)
            return lm.greedy_token(logits), c

        toks, cache = self._jit("paged_decode", paged_decode_round,
                                donate=1)(
            self._params, cache, np.asarray(ids, np.int32),
            np.asarray(positions, np.int32),
            np.asarray(block_tables, np.int32))
        if FAULT == "wrong_token":
            toks = (toks + 1) % VOCAB
        return toks, cache

    def kv_copy_blocks(self, cache, src, dst):
        return self._jit("copy", lm.copy_kv_blocks, donate=0)(
            cache, np.asarray(src, np.int32), np.asarray(dst, np.int32))
