"""Blockwise (flash) attention as pallas TPU kernels — forward and backward.

Computes softmax(q k^T * scale [+ causal mask]) v without ever materializing
an (S, S) score matrix in HBM or holding more than one kv block in VMEM:

- **forward**: grid (batch*heads, q-blocks, kv-blocks); the kv axis is the
  innermost (sequential on TPU) grid dimension, so each program sees one
  (block_q, dh) q tile and one (block_k, dh) k/v tile while online-softmax
  statistics (acc, row-max m, row-sum l) live in VMEM scratch that persists
  across the kv iteration. Per-row logsumexp is saved for the backward.
- **backward**: the standard two-kernel flash backward. With
  delta = rowsum(dO * O) precomputed, dQ streams kv blocks
  (dq += scale * [p * (dO v^T - delta)] k) and dK/dV streams q blocks
  (dv += p^T dO; dk += scale * [p * (dO v^T - delta)]^T q), where
  p = exp(s - lse) is recomputed from the saved logsumexp — O(S) residuals,
  O(S^2) flops, never an (S, S) tensor in memory.

This is the memory-bound op where HBM traffic — not FLOPs — sets the
ceiling, hence hand kernels rather than trusting XLA fusion. The reference
system has no analogue (its deepest compute is a TF1 GAN, reference
pg_gans.py); this exists for the transformer model zoo (ViT/BERT) and the
long-context path (parallel/ring.py composes blockwise attention across
chips; this kernel is the within-chip block).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
LANES = 128  # TPU lane width: minor dim of any Mosaic-lowered block tile


def _band_mask(q_start, j, block_q, block_k, kv_len, causal, causal_off):
    """(block_q, block_k) validity mask for kv block j against q block at
    q_start. Causal is end-aligned, matching mha_reference's
    tril(k=skv-sq): query i attends keys j <= i + (kv_len - q_len)."""
    k_idx = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_idx < kv_len
    if causal:
        q_idx = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = jnp.logical_and(mask, q_idx + causal_off >= k_idx)
    return mask


def _when_live(causal, cond_fn):
    """Run the decorated body only when the block intersects the causal band
    (unconditionally for non-causal attention — a static python branch)."""
    def deco(fn):
        if causal:
            pl.when(cond_fn())(fn)
        else:
            fn()
    return deco


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, sm_scale: float,
                causal: bool, q_len: int, kv_len: int, block_q: int,
                block_k: int, n_kv: int, save_lse: bool):
    # the lse output exists only when the forward runs under the VJP — the
    # primal-only path never writes row statistics to HBM
    if save_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        lse_ref, (acc_ref, m_ref, l_ref) = None, rest
    j = pl.program_id(2)
    q_start = pl.program_id(1) * block_q
    causal_off = kv_len - q_len

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @_when_live(causal, lambda: j * block_k <= q_start + block_q - 1 + causal_off)
    def _update():
        q = q_ref[0].astype(jnp.float32) * sm_scale           # (Bq, Dh)
        k = k_ref[0].astype(jnp.float32)                      # (Bk, Dh)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        mask = _band_mask(q_start, j, block_q, block_k, kv_len, causal,
                          causal_off)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # explicit re-mask: rows with no visible keys have m_new == NEG_INF
        # and would otherwise get p = exp(0) = 1 on every masked column
        # (possible when causal and q block only partially intersects the
        # band), polluting l, o, and the backward's dk/dv
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if save_lse:
            # rows that saw no keys (causal with kv_len < q_len) get
            # lse=+inf so the backward's exp(s - lse) underflows to 0
            lse = jnp.where(l > 0,
                            m_ref[...] + jnp.log(jnp.maximum(l, 1e-30)),
                            jnp.inf)
            # broadcast across the 128 lanes: row statistics live in a
            # (block_q, 128) tile because Mosaic requires the minor block
            # dim to be a lane multiple — (1, block_q) is not lowerable
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _flash_forward(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
                   sm_scale: Optional[float], block_q: int, block_k: int,
                   save_lse: bool, interpret: bool):
    """q,k,v: (B, H, S, Dh) -> out (B, H, Sq, Dh), lse (B*H, Sq_padded) or
    None. lse is only computed (and written to HBM) under the VJP."""
    b, h, sq, dh = q.shape
    skv = k.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(dh)
    qf = _pad_to(q.reshape(b * h, sq, dh), 1, block_q)
    kf = _pad_to(k.reshape(b * h, skv, dh), 1, block_k)
    vf = _pad_to(v.reshape(b * h, skv, dh), 1, block_k)
    n_q = qf.shape[1] // block_q
    n_kv = kf.shape[1] // block_k

    kernel = functools.partial(
        _fwd_kernel, sm_scale=scale, causal=causal, q_len=sq, kv_len=skv,
        block_q=block_q, block_k=block_k, n_kv=n_kv, save_lse=save_lse)
    out_specs = [
        pl.BlockSpec((1, block_q, dh), lambda bh, i, j: (bh, i, 0),
                     memory_space=pltpu.VMEM),
    ]
    out_shape = [jax.ShapeDtypeStruct((b * h, qf.shape[1], dh), q.dtype)]
    if save_lse:
        # (bh, S, 128): row statistics broadcast across lanes so every
        # block tile is (block_q, 128) — the minimum Mosaic f32 tile
        out_specs.append(
            pl.BlockSpec((1, block_q, LANES), lambda bh, i, j: (bh, i, 0),
                         memory_space=pltpu.VMEM))
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, qf.shape[1], LANES), jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, i, j: (bh, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dh), lambda bh, i, j: (bh, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dh), lambda bh, i, j: (bh, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    if save_lse:
        out, lse = res
        # keep only lane 0 as the residual — the broadcast costs 128x the
        # O(S) statistics memory flash attention exists to save
        lse = lse[:, :, 0]
    else:
        (out,), lse = res, None
    return out[:, :sq, :].reshape(b, h, sq, dh), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, sm_scale: float, causal: bool, q_len: int,
               kv_len: int, block_q: int, block_k: int, n_kv: int):
    j = pl.program_id(2)
    q_start = pl.program_id(1) * block_q
    causal_off = kv_len - q_len

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @_when_live(causal, lambda: j * block_k <= q_start + block_q - 1 + causal_off)
    def _update():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        s = sm_scale * jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        mask = _band_mask(q_start, j, block_q, block_k, kv_len, causal,
                          causal_off)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])                       # (Bq, Bk)
        dp = jnp.dot(g, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        acc_ref[...] += sm_scale * jnp.dot(
            ds, k, preferred_element_type=jnp.float32)

    @pl.when(j == n_kv - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, sm_scale: float, causal: bool,
                q_len: int, kv_len: int, block_q: int, block_k: int,
                n_q: int):
    i = pl.program_id(2)
    jblk = pl.program_id(1)  # hoisted: program_id inside pl.when bodies is
    k_start = jblk * block_k  # not rewritten by the interpret-mode lowering
    q_start = i * block_q
    causal_off = kv_len - q_len

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # the q block contributes iff the causal band reaches this kv block
    @_when_live(causal, lambda: q_start + block_q - 1 + causal_off >= k_start)
    def _update():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        s = sm_scale * jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        mask = _band_mask(q_start, jblk, block_q, block_k,
                          kv_len, causal, causal_off)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])                       # (Bq, Bk)
        dv_acc[...] += jnp.dot(p.T, g, preferred_element_type=jnp.float32)
        dp = jnp.dot(g, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        dk_acc[...] += sm_scale * jnp.dot(
            ds.T, q, preferred_element_type=jnp.float32)

    @pl.when(i == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k,
                    interpret):
    b, h, sq, dh = q.shape
    skv = k.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(dh)
    qf = _pad_to(q.reshape(b * h, sq, dh), 1, block_q)
    kf = _pad_to(k.reshape(b * h, skv, dh), 1, block_k)
    vf = _pad_to(v.reshape(b * h, skv, dh), 1, block_k)
    gf = _pad_to(g.reshape(b * h, sq, dh), 1, block_q)   # zero-padded: padded
    of = _pad_to(out.reshape(b * h, sq, dh), 1, block_q)  # rows contribute 0
    n_q = qf.shape[1] // block_q
    n_kv = kf.shape[1] // block_k
    # delta_i = sum_d dO_i O_i — the rowwise correction term of the flash
    # backward (d(softmax) along its normalization); both row statistics
    # are lanes-broadcast to (bh, S, 128) here, transiently (the saved
    # residual is the compact (bh, S) lse)
    delta = jnp.broadcast_to(
        jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                axis=-1)[:, :, None],
        (qf.shape[0], qf.shape[1], LANES))
    lse = jnp.broadcast_to(lse[:, :, None],
                           (qf.shape[0], qf.shape[1], LANES))

    common = dict(sm_scale=scale, causal=causal, q_len=sq, kv_len=skv,
                  block_q=block_q, block_k=block_k)
    q_spec = pl.BlockSpec((1, block_q, dh), lambda bh, i, j: (bh, i, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, block_k, dh), lambda bh, i, j: (bh, j, 0),
                           memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, block_q, LANES), lambda bh, i, j: (bh, i, 0),
                            memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_kv=n_kv, **common),
        grid=(b * h, n_q, n_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, gf, lse, delta)

    # dk/dv: kv blocks are the parallel axis, q blocks stream innermost
    q_spec2 = pl.BlockSpec((1, block_q, dh), lambda bh, j, i: (bh, i, 0),
                           memory_space=pltpu.VMEM)
    kv_spec2 = pl.BlockSpec((1, block_k, dh), lambda bh, j, i: (bh, j, 0),
                            memory_space=pltpu.VMEM)
    row_spec2 = pl.BlockSpec((1, block_q, LANES), lambda bh, j, i: (bh, i, 0),
                             memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_q=n_q, **common),
        grid=(b * h, n_kv, n_q),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=[kv_spec2, kv_spec2],
        out_shape=[jax.ShapeDtypeStruct(kf.shape, k.dtype),
                   jax.ShapeDtypeStruct(vf.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, dh), jnp.float32),
                        pltpu.VMEM((block_k, dh), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, gf, lse, delta)

    dq = dq[:, :sq, :].reshape(b, h, sq, dh)
    dk = dk[:, :skv, :].reshape(b, h, skv, dh)
    dv = dv[:, :skv, :].reshape(b, h, skv, dh)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, sm_scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """Flash attention over (B, H, S, Dh) tensors. The kernels compile
    for the TPU (Mosaic) or raise; ``interpret=True`` is for a caller that
    WANTS the Pallas interpreter (CPU tests, a CPU rehearsal) — the kernel
    never picks it by itself."""
    out, _ = _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                            save_lse=False, interpret=interpret)
    return out


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                              save_lse=True, interpret=interpret)
    return out, (q, k, v, out, lse)


def _bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, sm_scale,
                           block_q, block_k, interpret)


flash_attention.defvjp(_fwd, _bwd)
