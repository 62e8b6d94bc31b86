"""A tiny generation-capable template over models/lm.py's hybrid stack
(Mamba-2, sparse experts, grouped-query attention): the fixture of the
recurrent-state half of the generation contract. Its spec declares
``recurrent_state``, so the worker hands ``init_paged_kv_cache`` the slot
count and ``paged_prefill`` the slot. float32 weights from a fixed key, so
that greedy decode is exact and two runs of one prompt agree to the token.
``TinyDeltaLM`` is the same template over the stack's other kinds: the gated
delta rule, gated rotary attention, gated softmax-routed experts.
``TinyLatentLM`` is the stack without a stateful kind (latent attention, a
dense MLP, sigmoid-routed experts): its spec declares no state, so the worker
hands it no slot and no slot count, and the prefix cache serves its prompts.
"""

import jax
import jax.numpy as jnp
import numpy as np

from rafiki_tpu.models import lm
from rafiki_tpu.ops.gated_delta import GatedDeltaConfig
from rafiki_tpu.ops.mamba2 import Mamba2Config
from rafiki_tpu.ops.mla import MLAConfig
from rafiki_tpu.sdk import BaseModel, FixedKnob, GenerationSpec

VOCAB = 64
MAX_CONTEXT = 64
DIM = 32
CFG = lm.HybridConfig(
    vocab=VOCAB, max_len=MAX_CONTEXT, dim=DIM, pattern="MEM*E",
    mamba=Mamba2Config(dim=DIM, heads=4, head_dim=8, groups=2, state=8,
                       conv_kernel=4, chunk_size=4),
    q_heads=4, kv_heads=2, head_dim=8, n_experts=8, top_k=2, ffn=16,
    shared_ffn=32, route_scale=2.5, held=(0, 4))
DELTA_CFG = lm.HybridConfig(
    vocab=VOCAB, max_len=MAX_CONTEXT, dim=DIM, pattern="DEGE",
    delta=GatedDeltaConfig(dim=DIM, key_heads=2, value_heads=4, key_dim=8,
                           value_dim=8, conv_kernel=4, chunk_size=4),
    q_heads=4, kv_heads=2, head_dim=8, rotary_dim=4, rope_theta=1e7,
    n_experts=8, top_k=3, ffn=16, shared_ffn=16, route_score="softmax",
    route_bias=False, route_scale=1.0, expert_act="silu", expert_gated=True,
    shared_gate=True, held=(0, 4), eps=1e-6)
LATENT_CFG = lm.HybridConfig(
    vocab=VOCAB, max_len=MAX_CONTEXT, dim=DIM, pattern="LFLE",
    mla=MLAConfig(dim=DIM, heads=4, q_rank=16, kv_rank=8, nope_dim=4,
                  rope_dim=4, v_dim=8),
    n_experts=8, top_k=2, ffn=16, shared_ffn=16, dense_ffn=48,
    route_scale=1.8, expert_act="silu", expert_gated=True, held=(0, 4))
BUCKETS = (8, 16, 32, MAX_CONTEXT)
RING_BLOCK = 8


def _pad(prompt_ids):
    n = len(prompt_ids)
    ids = np.zeros(next(b for b in BUCKETS if b >= n), np.int32)
    ids[:n] = prompt_ids
    return ids, n


class TinyHybridLM(BaseModel):
    dependencies = {"numpy": None}
    generation_spec = GenerationSpec(eos_token_id=None,
                                     max_context=MAX_CONTEXT,
                                     recurrent_state=True)
    cfg = CFG

    @staticmethod
    def get_knob_config():
        return {"dim": FixedKnob(DIM)}

    def __init__(self, **knobs):
        super().__init__(**knobs)
        self._params = None
        self._ring_tables = None
        self._prefill = jax.jit(
            lambda p, c, bt, i, st, m, sl: lm.hybrid_paged_prefill(
                p, c, bt, i, st, m, sl, self.cfg))
        self._decode = jax.jit(
            lambda p, c, i, q, bts: lm.hybrid_paged_decode_step(
                p, c, i, q, bts, self.cfg))
        self._copy = jax.jit(lm.copy_hybrid_kv_blocks)
        #: (start, slot) of every paged_prefill call, for the tests
        self.prefills = []

    def train(self, dataset_uri):
        self._params = lm.hybrid_init(jax.random.key(0), self.cfg,
                                      dtype=jnp.float32)

    def evaluate(self, dataset_uri):
        return 0.0

    def predict(self, queries):
        return [[0] for _ in queries]

    def dump_parameters(self):
        return jax.tree.map(np.asarray, self._params)

    def load_parameters(self, params):
        self._params = jax.tree.map(jnp.asarray, params)

    # -- the ring contract, through the paged programs ----------------------

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

    # -- the paged contract, with the slot -----------------------------------

    def init_paged_kv_cache(self, pool_blocks, block_tokens, max_slots):
        return lm.init_hybrid_cache(self.cfg, pool_blocks, block_tokens,
                                    max_slots, kv_dtype=jnp.float32)

    def recurrent_state_bytes(self, cache):
        return lm.hybrid_state_bytes(cache)

    def paged_prefill(self, cache, block_table, prompt_ids, start, slot):
        self.prefills.append((int(start), int(slot)))
        ids, n = _pad(prompt_ids)
        logits, cache = self._prefill(
            self._params, cache, np.asarray(block_table, np.int32), ids,
            np.int32(start), np.int32(n), np.int32(slot))
        return int(lm.greedy_token(logits)), cache

    def paged_decode_step(self, cache, ids, positions, block_tables):
        logits, cache, counts = self._decode(
            self._params, cache, np.asarray(ids, np.int32),
            np.asarray(positions, np.int32),
            np.asarray(block_tables, np.int32))
        return lm.greedy_token(logits), cache, counts

    def kv_copy_blocks(self, cache, src, dst):
        return self._copy(cache, np.asarray(src, np.int32),
                          np.asarray(dst, np.int32))


class TinyDeltaLM(TinyHybridLM):
    cfg = DELTA_CFG


class TinyLatentLM(TinyHybridLM):
    """No recurrent state: the paged methods take no slot."""
    generation_spec = GenerationSpec(eos_token_id=None,
                                     max_context=MAX_CONTEXT)
    cfg = LATENT_CFG

    def init_kv_cache(self, max_slots):
        per_slot = -(-MAX_CONTEXT // RING_BLOCK)
        self._ring_tables = np.arange(max_slots * per_slot,
                                      dtype=np.int32).reshape(max_slots, -1)
        return self.init_paged_kv_cache(max_slots * per_slot, RING_BLOCK)

    def prefill(self, cache, slot, prompt_ids):
        return self.paged_prefill(cache, self._ring_tables[slot], prompt_ids,
                                  0)

    def init_paged_kv_cache(self, pool_blocks, block_tokens):
        return lm.init_hybrid_cache(self.cfg, pool_blocks, block_tokens,
                                    kv_dtype=jnp.float32)

    def __init__(self, **knobs):
        super().__init__(**knobs)
        self._prefill = jax.jit(
            lambda p, c, bt, i, st, m: lm.hybrid_paged_prefill(
                p, c, bt, i, st, m, None, self.cfg))

    def paged_prefill(self, cache, block_table, prompt_ids, start):
        self.prefills.append((int(start), None))
        ids, n = _pad(prompt_ids)
        logits, cache = self._prefill(
            self._params, cache, np.asarray(block_table, np.int32), ids,
            np.int32(start), np.int32(n))
        return int(lm.greedy_token(logits)), cache
