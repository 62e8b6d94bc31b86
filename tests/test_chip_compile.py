"""The Pallas kernels of the main path compile for the real chip.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (a ``v5e:2x2`` topology). Nothing runs — this says
nothing about results or times — but what Mosaic refuses on the chip it
refuses here, at no chip time: a slice not aligned to the tiling, too much
VMEM, a scratch shape it cannot lay out.

The topology is described inside a module-scoped fixture, never at import
(only one process may load libtpu; every xdist worker imports this file),
and these tests stay in this ONE file so they land on one worker.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from rafiki_tpu.ops import flash_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled.as_text()


# ViT-B/16's sequence (197 with a class token; padded to the 128 block),
# the smoke's comparison shape, and the long shape ops/attention.py quotes
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("seq", [197, 2048, 8192])
def test_flash_attention_compiles_for_v5e(one_chip, seq, causal, dtype):
    """Head width 64 as the minor dimension, (block_q, 1) f32 scratch,
    k.T inside the kernels: forward alone is one Mosaic kernel, forward +
    backward three (fwd with logsumexp, dQ, dK/dV)."""
    shape = jax.ShapeDtypeStruct((4, 12, seq, 64), dtype, sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal)  # interpret=False: Mosaic

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda q, k, v: flash_attention(q, k, v, causal)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    assert _compile(fwd, shape, shape, shape).count("tpu_custom_call") == 1
    assert _compile(fwd_bwd, shape, shape, shape).count(
        "tpu_custom_call") == 3


def test_flash_attention_cross_length_compiles_for_v5e(one_chip):
    """Decode-shaped call: a short query block against a long key range
    (the end-aligned causal mask)."""
    q = jax.ShapeDtypeStruct((4, 12, 128, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((4, 12, 2048, 64), jnp.bfloat16,
                              sharding=one_chip)
    text = _compile(lambda q, k, v: flash_attention(q, k, v, True), q, kv, kv)
    assert text.count("tpu_custom_call") == 1


# GPT-2 large as `gpt2_large.chat_saturated` serves it: 5 slots, a pool of 320
# blocks of 16 rows; the decode round at the narrowest and the widest rung
# of its table ladder, and the prefill chunk of 64 tokens
@pytest.mark.parametrize("program, width", [
    ("decode", 8), ("decode", 64), ("prefill", 64)])
def test_dense_serving_program_reads_its_weights_in_place(one_chip, program,
                                                          width):
    """The serving forward's layer scan (`lm._dense_layers`) keeps the
    rounding of a product's operand with the product. Before PR 33 the TPU
    compiler hoisted it out of the loop: six `convert`s to `bf16[36,...]`
    ahead of the `while`, 1,416 MB of temporaries and 6.5 of a decode
    round's 10.6 ms on the chip. Now no `convert` yields a stack in bf16,
    and the temporaries are a view's: 0.5 MB at 8 blocks, 63 MB at 64."""
    import re

    from rafiki_tpu.models import lm

    cfg = lm.tiny(vocab=50257, max_len=1024, dim=1280, depth=36, heads=20)
    slots = 5

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = on_chip(jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: lm.init_paged_kv_cache(cfg, 320, 16)))

    def paged_decode_round(p, c, i, q, bts):
        logits, c = lm.paged_decode_step(p, c, i, q, bts, cfg)
        return lm.greedy_token(logits), c

    def paged_prefill_chunk(p, c, bt, i, st, m):
        logits, c = lm.paged_prefill(p, c, bt, i, st, m, cfg)
        return lm.greedy_token(logits), c

    if program == "decode":
        fn, args = paged_decode_round, (
            i32(slots), i32(slots), i32(slots, width))
    else:
        fn, args = paged_prefill_chunk, (i32(64), i32(width), i32(), i32())
    compiled = jax.jit(fn, donate_argnums=1).lower(
        params, cache, *args).compile()
    stacked = re.findall(r"= bf16\[36,[^\n]* convert\(", compiled.as_text())
    assert not stacked, stacked
    assert compiled.memory_analysis().temp_size_in_bytes < 100e6


@pytest.mark.parametrize("rows", [1024, 16384])
def test_latent_prefill_kernel_compiles_for_v5e(one_chip, rows):
    """ops/mla.py `attend_rows` at GLM-4.7-Flash's widths: 20 heads, a
    chunk of 512 queries 256 wide, values 256 wide, blocks of 512 rows, the
    chunk's position prefetched as scalars: one Mosaic kernel."""
    from rafiki_tpu.ops import mla

    def on(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compile(
        lambda q, kv, a, b: mla.attend_rows(q, kv, a, b, 1 / 16),
        on((20, 512, 256)), on((20, 512, rows)),
        on((), jnp.int32), on((), jnp.int32))
    assert text.count("tpu_custom_call") == 1


def test_latent_prefill_chunk_writes_no_score_for_v5e(one_chip, monkeypatch):
    """A latent layer and an expert layer at GLM-4.7-Flash's widths, a
    chunk of 512 over a table of 1,024 blocks: every width of the chunk's
    ladder takes the kernel (five Mosaic calls), no (heads, 512, rows) f32
    score takes memory, and the experts take their tokens in
    groups of 128 (no product of all 512 with an expert's matrix)."""
    import re

    from rafiki_tpu.models import lm
    from rafiki_tpu.ops import mla

    monkeypatch.setattr(mla, "_on_tpu", lambda: True)
    cfg = lm.HybridConfig(
        vocab=1024, max_len=16384, dim=2048, pattern="LE",
        mla=mla.MLAConfig(dim=2048, heads=20, q_rank=768, kv_rank=512,
                          nope_dim=192, rope_dim=64, v_dim=256),
        n_experts=64, top_k=4, ffn=1536,
        shared_ffn=1024,  # not the model's 1536: told from an expert's
        route_score="sigmoid", route_bias=True, route_scale=1.8,
        expert_act="silu", expert_gated=True, held=(0, 32))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = on_chip(jax.eval_shape(
        lambda: lm.hybrid_init(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: lm.init_hybrid_cache(cfg, 2048, 16)))
    compiled = jax.jit(
        lambda p, c, bt, i, st, m: lm.hybrid_paged_prefill(
            p, c, bt, i, st, m, None, cfg), donate_argnums=1).lower(
        params, cache, i32(1024), i32(512), i32(), i32()).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 5
    # a head's scores at the widest view are 671 MB in f32; the keys and
    # values, transposed, as the kernel reads them, 336 MB in bf16
    assert re.findall(r"bf16\[20,512,16384\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 400e6
    assert not re.findall(r"f32\[512,3072\]", text)
    assert re.findall(r"f32\[128,3072\]", text)


def test_latent_decode_kernel_compiles_for_v5e(one_chip):
    """ops/mla.py `attend_pool` at the long-prompt cell's widths: 16 slots,
    20 heads, rows of 640 lanes, 12 layers of 16,384 blocks of 16 rows in
    HBM, a table of 1,024 blocks and the layer, the tables and the lengths
    prefetched as scalars: one Mosaic kernel, no temporaries (the pool is
    read where it lies)."""
    from rafiki_tpu.ops import mla

    def on(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, pool, l, tb, n: mla.attend_pool(q, pool, l, tb, n, 1 / 16)
    ).lower(on((16, 20, 640)), on((12, 16384, 16, 640)), on((), jnp.int32),
            on((16, 1024), jnp.int32), on((16,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6


def test_latent_decode_round_gathers_no_view_for_v5e(one_chip, monkeypatch):
    """Two latent layers, each with its expert layer, at GLM-4.7-Flash's
    widths: a decode round of 16 slots at a table of 1,024 blocks reads
    the pool through the kernel, lowered ONCE for both layers (one kernel
    in the module, one call a layer in the compiled program), and holds no
    (16, 16384, 640) view of the rows: its temporaries are a few MB where
    the gathered view alone was 335 MB a layer."""
    import re

    from rafiki_tpu.models import lm
    from rafiki_tpu.ops import mla

    monkeypatch.setattr(mla, "_on_tpu", lambda: True)
    cfg = lm.HybridConfig(
        vocab=1024, max_len=16384, dim=2048, pattern="LELE",
        mla=mla.MLAConfig(dim=2048, heads=20, q_rank=768, kv_rank=512,
                          nope_dim=192, rope_dim=64, v_dim=256),
        n_experts=64, top_k=4, ffn=1536, shared_ffn=1536,
        route_score="sigmoid", route_bias=True, route_scale=1.8,
        expert_act="silu", expert_gated=True, held=(0, 32))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = on_chip(jax.eval_shape(
        lambda: lm.hybrid_init(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: lm.init_hybrid_cache(cfg, 16384, 16)))
    lowered = jax.jit(
        lambda p, c, i, q, bts: lm.hybrid_paged_decode_step(
            p, c, i, q, bts, cfg)[:2], donate_argnums=1).lower(
        params, cache, i32(16), i32(16), i32(16, 1024))
    assert lowered.as_text().count("tpu_custom_call") == 1
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert not re.findall(r"\[16,16384,640\]", text)
    assert not re.findall(r"\[16,1024,16,640\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6
